"""Device time by the program's own named scopes: what the per-layer
``scope.*`` readers share.

The trace names each op by its HLO instruction (``%fusion.4025 = ...``)
and carries no scope. The program's ledger record of the step it ran
(``tensor2robot_tpu.observability.programs.get('train/step')``, taken at
the first dispatch from that very executable) maps every instruction to
the scope that issued it and its direction (``ProgramRecord.op_scopes``).
This module joins the two:

* the step program's ops inside the traced window, clipped to its
  events, as ``_lm_ops.window_ops`` takes them;
* each interval to the innermost op: a ``%while`` or a ``%conditional``
  keeps only the time its body's ops leave, so a loop is not counted on
  top of its body, and the families add up to the step's busy time;
* each op to a family through :data:`TABLE`, the one table of scope
  patterns. A fourth trunk adds rows to it, not a reader.

A program without the record (a parent before PR 37) gives None, and so
does every ``scope.*`` reader. The join is made once a run and logged to
stderr: every family's ms a step, forward / backward / recomputed, and
the five largest ops that no scope names.
"""

from __future__ import annotations

import re
import sys
import time
from typing import Dict, Optional

from benchmark.metrics import _traced

FAMILIES = ('attention_kernel', 'attention_mix', 'route', 'experts', 'dense',
            'head', 'optimizer', 'layer_other', 'unnamed')
DIRECTIONS = ('forward', 'backward', 'recomputed')

# Scope path (``OpScope.path``: module names and named scopes, the
# primitive last) -> family; the first row that matches wins. Names are
# whole components: ``afmoe/moe/route`` matches ``.../afmoe/moe/route/...``.
TABLE = (
    # The attention kernels (pallas_call names, ops/flash_attention.py).
    ('attention_kernel', r'flash_attention_(fwd|bwd|dq|dkv)'),
    # Adam's update and the EMA (train/trainer.py). A fusion of the update
    # into a gradient's product is read by the product's scope.
    ('optimizer', r'train/optimizer'),
    # The vocabulary loss, both passes in GLM (the MTP module's included).
    ('head', r'afmoe/head_loss|zaya/head|glm/head'),
    ('attention_mix', r'zaya/cca/mix|glm/mla/mix'),
    # The expert layer's dispatch and combine, and ZAYA's router MLP: the
    # other trunks' routers score inside ``afmoe/moe/route``.
    ('route', r'afmoe/moe/route|zaya/router'),
    # XLA names a grouped product's custom calls itself, with no scope.
    ('experts', r'afmoe/moe/experts|ragged-dot-[a-z]+'),
    # Projections, dense MLPs, shared experts, the MTP projection.
    ('dense', r'afmoe/attn/project|afmoe/dense_mlp|afmoe/moe/shared|'
              r'zaya/cca/project|glm/mla/project|glm/dense_mlp|'
              r'glm/mtp/project'),
    # Anything else under the model: norms, residuals, rotary, the kernel's
    # layouts, the embedding. ``Trunk``: the token trunks' top module;
    # ``Embedding``: the frames model's towers.
    ('layer_other', r'Trunk|Embedding'),
)
_COMPILED = tuple((family, re.compile(r'(^|/)(' + pattern + r')(/|$)'))
                  for family, pattern in TABLE)


def family_of(path: str) -> str:
  """The family of one scope path; 'unnamed' where no row matches."""
  for family, pattern in _COMPILED:
    if pattern.search(path):
      return family
  return 'unnamed'


def _record():
  """(op map, what the record cost) of the step's ledger record, or
  (None, '') where the program keeps none (a parent)."""
  try:
    from tensor2robot_tpu.observability import metrics, programs

    record = programs.get('train/step')
    scopes = record.op_scopes() if record is not None else None
    gauges = metrics.snapshot('trainer/program_record_')
  except Exception as e:  # pylint: disable=broad-except
    return None, f'scopes: the record has no op map ({e!r})'
  if not scopes:
    return None, ''
  return scopes, (
      f'record of the step: {len(record.hlo_text) / 1e6:.1f} MB of HLO, '
      f'{len(scopes)} ops; taken at the first dispatch in '
      f'{gauges.get("trainer/program_record_seconds", float("nan")):.3f} s '
      f'(lower + compile {record.compile_seconds:.3f} s) with '
      f'{gauges.get("trainer/program_record_backend_compiles", "?")} backend '
      'compiles')


def _exclusive(ops):
  """(name, start, end) -> [(name, ns)], each op's time less what the
  ops inside it took: the innermost op gets each instant."""
  ops = sorted(ops, key=lambda o: (o[1], -o[2]))
  own = [b - a for _, a, b in ops]
  stack = []
  for i, (_, a, b) in enumerate(ops):
    while stack and ops[stack[-1]][2] <= a:
      stack.pop()
    if stack:
      j = stack[-1]
      own[j] -= min(b, ops[j][2]) - a
    stack.append(i)
  return [(name, ns) for (name, _, _), ns in zip(ops, own)]


def join(ctx: Dict) -> Optional[Dict]:
  """{'ms': {family: ms a step}, 'by_direction': {family: {direction:
  ms}}, 'unnamed_share': %, 'unnamed_top': [(op, ms)], 'steps': n}, or
  None where the program keeps no record of its step."""
  if 'scopes' in ctx['cache']:
    return ctx['cache']['scopes']
  t0 = time.perf_counter()
  scopes, facts = _record()
  out = None
  if scopes is not None:
    out = _join(ctx, scopes)
    facts += '\n' + _table(out, time.perf_counter() - t0)
  if facts:
    print(facts, file=sys.stderr, flush=True)
  ctx['cache']['scopes'] = out
  return out


def _join(ctx: Dict, scopes: Dict) -> Optional[Dict]:
  traced = _traced.traced(ctx)
  steps = traced['dispatches'] * ctx.get('steps_per_dispatch', 1)
  step_name = traced['step_name']
  from benchmark.lib import trace

  dev = trace.reduce(ctx['profile'])['devices'][0]
  events = sorted((a, b) for n, a, b in dev['modules'] if n == step_name)
  inside, i = [], 0
  for name, a, b in sorted(dev['ops_in_window'], key=lambda o: o[1]):
    while i < len(events) and events[i][1] <= a:
      i += 1
    if i < len(events) and b > events[i][0]:
      inside.append((name, max(a, events[i][0]), min(b, events[i][1])))
  if not steps or not inside:
    return None
  loops: Dict[str, float] = {}
  for name, a, b in inside:
    if name.startswith('%while'):
      key = name.partition(' = ')[0]
      loops[key] = loops.get(key, 0.0) + (b - a)
  ns = {f: {d: 0.0 for d in DIRECTIONS + ('',)} for f in FAMILIES}
  unnamed: Dict[str, float] = {}
  looked_up: Dict[str, tuple] = {}
  missing = 0
  for name, own in _exclusive(inside):
    if name not in looked_up:
      scope = scopes.get(name.partition(' = ')[0].lstrip('%'))
      missing += scope is None
      looked_up[name] = ((family_of(scope.path), scope.direction)
                         if scope is not None else ('unnamed', ''))
    family, direction = looked_up[name]
    ns[family][direction] += own
    if family == 'unnamed':
      unnamed[name] = unnamed.get(name, 0.0) + own
  total = sum(sum(d.values()) for d in ns.values())
  per_step = 1e6 * steps
  largest = sorted(unnamed.items(), key=lambda kv: -kv[1])[:5]
  return {
      'steps': steps,
      'ms': {f: sum(d.values()) / per_step for f, d in ns.items()},
      'by_direction': {f: {d: v / per_step for d, v in by.items() if v}
                       for f, by in ns.items()},
      'unnamed_share': 100.0 * sum(ns['unnamed'].values()) / total
                       if total else None,
      'unnamed_top': [(_traced._short(n), v / per_step)  # pylint: disable=protected-access
                      for n, v in largest],
      'step_busy_ms': traced['step_busy_s'] * 1e3 / steps,
      'ops_not_in_record': missing, 'ops': len(looked_up),
      # Whole loops, bodies included: what a reader of op names sees.
      'loops_ms': {n: v / per_step for n, v in sorted(loops.items())},
  }


def _table(out: Optional[Dict], seconds: float) -> str:
  if out is None:
    return f'scopes: nothing to join ({seconds:.1f} s)'
  lines = [f'scopes: device time by family, ms a step over {out["steps"]} '
           f'steps (the step\'s ops: {out["step_busy_ms"]:.2f}); '
           f'{out["ops"]} distinct ops, {out["ops_not_in_record"]} not in '
           f'the record; joined in {seconds:.1f} s',
           f'  {"family":18s} {"all":>9s} ' +
           ' '.join(f'{d:>10s}' for d in DIRECTIONS)]
  for family in FAMILIES:
    by = out['by_direction'][family]
    lines.append(f'  {family:18s} {out["ms"][family]:9.3f} ' + ' '.join(
        f'{by.get(d, 0.0):10.3f}' for d in DIRECTIONS))
  lines.append(f'  unnamed share {out["unnamed_share"]:.3f}%; largest '
               'unnamed ops, ms a step:')
  lines.extend(f'    {ms:8.3f} {name}' for name, ms in out['unnamed_top'])
  lines.append('  loops (%while, bodies included), ms a step: ' + ', '.join(
      f'{name} {ms:.3f}' for name, ms in out['loops_ms'].items()))
  return '\n'.join(lines)


def family_ms(ctx: Dict, family: str) -> Optional[float]:
  """ms a step of one family, or None (no record, or nothing there)."""
  out = join(ctx)
  if out is None or not out['ms'][family]:
    return None
  return out['ms'][family]
