"""What the token cell's readers share: the device's ops inside the
traced window, by the name the compiler gave each.

The trace is taken with HLO protos off and carries no scope names: an
event of the line ``XLA Ops`` is named by its HLO instruction's text,
``%<name>.<n> = <result type> <opcode>(...)``. So ops are attributed by
what that text holds:

* attention: ``%flash_attention_fwd``, ``_dq``, ``_dkv``: the names the
  program gives its three attention kernels (``ops/flash_attention.py``);
* the grouped product: ``%ragged-dot``: every ``jax.lax.ragged_dot`` of
  the expert layer, forward, recomputed and both gradients, and the
  small op that makes its tile table;
* routing: every other op whose result has the routed-row buffer's
  leading dimension (``[room, ...]``), the (token, choice) pairs'
  (``[tokens, k, ...]``) or the router's (``[tokens, experts]``): scores,
  top-k, the sorts, the gathers in both directions, the weighted
  combine, and the elementwise part of the experts (silu x up) that is no
  matrix product. Shapes come from the run's context, not from a table.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, List, Tuple

from benchmark.lib import trace
from benchmark.metrics import _traced

ATTENTION = '%flash_attention_'
GROUPED = '%ragged-dot'
_SHAPE = re.compile(r'[a-z0-9]+\[([0-9,]*)\]')


def window_ops(ctx: Dict) -> List[Tuple[str, float]]:
  """(name, nanoseconds inside the traced window) of device 0's ops."""
  if 'lm_ops' in ctx['cache']:
    return ctx['cache']['lm_ops']
  step_name = _traced.traced(ctx)['step_name']
  dev = trace.reduce(ctx['profile'])['devices'][0]
  events = [(a, b) for n, a, b in dev['modules'] if n == step_name]
  lo, hi = min(a for a, _ in events), max(b for _, b in events)
  ops = [(n, min(b, hi) - max(a, lo)) for n, a, b in dev['ops_in_window']
         if b > lo and a < hi]
  ctx['cache']['lm_ops'] = ops
  return ops


def seconds_of(ctx: Dict, wanted: Callable[[str], bool]) -> float:
  return sum(ns for name, ns in window_ops(ctx) if wanted(name)) / 1e9


def result_dims(name: str) -> List[Tuple[int, ...]]:
  """Dimensions of each array in an op's result type."""
  _, sep, rhs = name.partition(' = ')
  if not sep:
    return []
  if rhs.startswith('('):
    head = rhs[:rhs.index(')') + 1] if ')' in rhs else rhs
  else:
    head = rhs.split(' ', 1)[0]
  return [tuple(int(d) for d in m.group(1).split(',') if d)
          for m in _SHAPE.finditer(head)]


def is_routing(ctx: Dict) -> Callable[[str], bool]:
  shapes = ctx['route_shapes']
  room, tokens = shapes['room'], shapes['tokens']
  k, experts = shapes['k'], shapes['experts']

  def wanted(name: str) -> bool:
    if name.startswith(GROUPED) or name.startswith(ATTENTION):
      return False
    for dims in result_dims(name):
      if dims[:1] == (room,) or dims[:2] in ((tokens, k),
                                             (tokens, experts)):
        return True
    return False

  return wanted


def dispatches_traced(ctx: Dict) -> int:
  return _traced.traced(ctx)['dispatches']


def steps_traced(ctx: Dict) -> int:
  return dispatches_traced(ctx) * ctx['steps_per_dispatch']


def rows_routed_per_step(ctx: Dict):
  """From the program's counters over the window (they are published one
  dispatch behind, so rows and tokens are read as a ratio)."""
  moved = ctx.get('moe_counters') or {}
  tokens = moved.get('moe/tokens')
  if not tokens:
    return None
  per_step = (ctx['tokens_per_example'] * ctx['examples_per_dispatch'] //
              ctx['steps_per_dispatch'] * ctx['expert_layers'])
  return moved['moe/rows_routed'] * per_step / tokens
