"""Compiled-program ledger: per-executable FLOPs/bytes/MFU telemetry.

The observability plane (metrics/tracing/flight/postmortem) watches the
*host* — queue depths, dispatch walls, checkpoint latencies. The XLA
executables the framework compiles were invisible: a BENCH headline
could claim "kernel_policy=auto cut step time 1.3×" with no evidence the
program's bytes-accessed actually shrank, and the MFU campaign
(ROADMAP direction 4) had no denominator on-box. This module is the
missing surface: a process-global **ledger of every executable the
framework compiles** — the trainer step (``train/trainer.py``), serving
buckets (``serving/batching.py``), bench/roofline programs — recording,
ONCE at compile time (zero per-dispatch cost):

* ``cost_analysis()`` — FLOPs, bytes accessed, transcendentals: the
  roofline numerators;
* ``memory_analysis()`` — argument/output/temp/alias bytes: where the
  HBM went, per executable rather than per allocator high-water mark;
* the **program fingerprint** — sha256 of the location-stripped
  StableHLO, the same digest scheme ``export/exporters.py`` uses for
  serving artifacts (PR 7), so a trainer program and its exported twin
  are comparable;
* compile wall time (the restart-goodput denominator, next to
  ``compile/cache_hits|misses`` from ``utils/compilation_cache.py``);
* the **donation map** — which donated arguments XLA actually aliased
  (parsed from the executable's ``input_output_alias`` header) vs. how
  many leaves the caller donated, plus any captured unused-donation
  warnings: a silently-undonated buffer doubles the program's working
  set and this is the first place it shows;
* input/output shardings, truncated to a report-safe repr;
* the compiled HLO's text, kept beside the record (not in its dict):
  :meth:`ProgramRecord.op_scopes` maps each instruction — the name an
  op carries in a device trace — to the named scope that issued it and
  its direction (forward, backward, recomputed), which is how
  ``benchmark/metrics/_scopes.py`` splits a traced step's device time
  by layer.

From a record + measured device seconds, :func:`utilization` derives
**MFU / HBM-bandwidth / fraction-of-roofline** gauges
(``serving/model/<name>/mfu``), published by the serving plane. A
**steady-state recompile sentinel**
(:class:`RecompileSentinel`) is the runtime twin of the static
``recompile-hazard`` rule: after warmup, any growth of a jitted
function's executable cache — or a re-record under the same name with a
new fingerprint — increments ``programs/steady_state_recompiles``,
lands a ``'program'`` flight event within the same dispatch, and fires
the optional escalation hook.

Discipline matches the rest of ``observability/``: no jax import at
module scope (the records are duck-typed off jax's ``Compiled`` /
``Lowered`` objects, so the module itself stays importable on stdlib-
only hosts), bounded memory (one small record per distinct program
name; the step's HLO text is its largest part), every shared field
lock-guarded. Surfaces: ``/programz`` (``observability/metricsz.py``),
the ``programs`` section of ``metrics.report()``, and
``tools/program_report.py`` (render/diff two dumps).
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import os
import re
import threading
import time
import warnings as warnings_mod
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

from tensor2robot_tpu.observability import flight
from tensor2robot_tpu.observability import metrics as metrics_lib

__all__ = [
    'ProgramRecord', 'ProgramLedger', 'RecompileSentinel', 'ledger',
    'record_compiled', 'record_jitted', 'get', 'names', 'document', 'dump',
    'utilization', 'flag_recompile', 'OpScope', 'op_scope', 'hlo_op_scopes',
    'set_recompile_escalation', 'set_device_peaks', 'set_enabled', 'enabled',
    'program_fingerprint', 'clear', 'ENV_PEAK_FLOPS', 'ENV_PEAK_HBM_GBPS',
]

# Peak device numbers for the MFU/roofline denominators: bf16 matmul
# FLOPs/s and HBM GB/s by ``Device.device_kind`` (same table bench.py
# uses for its headline MFU). CPU and unknown backends resolve to None
# — utilization then publishes only what needs no peak (hbm_gbps is
# measured bytes over measured seconds) unless the env vars or
# :func:`set_device_peaks` supply the denominators (how the tier-1 CPU
# drills pin the MFU math).
_TABLE_PEAK_FLOPS = {
    'TPU v4': 275e12,
    'TPU v5 lite': 197e12,
    'TPU v5p': 459e12,
    'TPU v6e': 918e12,
}
_TABLE_PEAK_HBM_GBPS = {
    'TPU v4': 1228.0,
    'TPU v5 lite': 819.0,
    'TPU v5p': 2765.0,
    'TPU v6e': 1640.0,
}

ENV_PEAK_FLOPS = 'T2R_PEAK_FLOPS'
ENV_PEAK_HBM_GBPS = 'T2R_PEAK_HBM_GBPS'

_MAX_SHARDING_CHARS = 512


def program_fingerprint(text: str) -> str:
  """PR-7 digest scheme over any MLIR/HLO module text.

  MLIR ``loc(...)`` debug locations carry call-site file:line that
  drifts between otherwise identical programs; stripping them first
  makes equal fingerprints <=> same compute program (the property the
  recompile sentinel and ``program_report.py --diff`` both need).
  """
  text = re.sub(r'(?m)^#loc.*$', '', text)
  text = re.sub(r'loc\([^)]*\)', '', text)
  return hashlib.sha256(text.encode()).hexdigest()


@dataclasses.dataclass
class ProgramRecord:
  """One compiled executable's compile-time facts (JSON-ready)."""

  name: str
  fingerprint: str = ''
  fingerprint_source: str = ''  # 'stablehlo' (lowered) | 'hlo' (compiled)
  flops: float = 0.0
  bytes_accessed: float = 0.0
  transcendentals: float = 0.0
  argument_bytes: int = 0
  output_bytes: int = 0
  temp_bytes: int = 0
  alias_bytes: int = 0
  generated_code_bytes: int = 0
  peak_bytes: int = 0  # argument + output + temp - alias: live footprint
  compile_seconds: float = 0.0
  donate_argnums: Tuple[int, ...] = ()
  donated_params: Optional[int] = None  # flattened leaves requested
  aliased_params: Optional[int] = None  # params XLA actually aliased
  undonated_params: Optional[int] = None  # requested but silently elided
  donation_warnings: Tuple[str, ...] = ()
  input_shardings: str = ''
  output_shardings: str = ''
  device_kind: str = ''
  source: str = ''  # which compile point recorded it
  recorded_unix: float = 0.0
  recompiles: int = 0  # re-records under this name with a NEW fingerprint
  # Hand-written (Pallas/Mosaic) kernels in the compiled text: how a run
  # on the chip shows that a kernel it asked for is really in the
  # program, and that a default program has none (chip_smoke.py).
  custom_calls: int = 0
  # Collective ops XLA put in the compiled text, by kind (async starts
  # count once): how a multi-chip run shows that the mesh's gradient
  # all-reduce / fsdp all-gather are really in the program.
  collectives: Dict[str, int] = dataclasses.field(default_factory=dict)
  # Train steps folded into ONE execution of this program (the trainer's
  # steps_per_dispatch scan). cost_analysis counts the WHOLE K-step
  # executable; utilization() divides by this so its gauges stay
  # per-step quantities a device-feed run can't inflate by K.
  steps_per_execution: int = 1
  # The compiled (optimized) HLO module's text: its instruction names
  # are the op names of a device trace of this executable, and each
  # instruction's ``metadata={op_name=...}`` the scope that issued it.
  # Kept for :meth:`op_scopes`, never put in :meth:`to_dict`.
  hlo_text: str = dataclasses.field(default='', repr=False, compare=False)

  def to_dict(self) -> Dict[str, Any]:
    out = dataclasses.asdict(self)
    del out['hlo_text']
    out['donate_argnums'] = list(self.donate_argnums)
    out['donation_warnings'] = list(self.donation_warnings)
    return out

  def op_scopes(self) -> Dict[str, 'OpScope']:
    """Instruction name -> :class:`OpScope`, parsed from
    :attr:`hlo_text` on first read and kept ({} without the text); see
    :func:`hlo_op_scopes`."""
    scopes = self.__dict__.get('_op_scopes')
    if scopes is None:
      scopes = self.__dict__['_op_scopes'] = hlo_op_scopes(self.hlo_text)
    return scopes


# ------------------------------------------------------ extraction helpers
#
# All duck-typed off jax's Compiled/Lowered: a missing method or a
# backend that cannot answer degrades that field to its default rather
# than losing the record (the CPU backend answers all of them, which is
# what makes the tier-1 drills possible).


def _cost_analysis(compiled) -> Dict[str, float]:
  try:
    cost = compiled.cost_analysis()
  except Exception:  # pylint: disable=broad-except
    return {}
  return cost if isinstance(cost, dict) else {}


def _memory_analysis(compiled):
  try:
    return compiled.memory_analysis()
  except Exception:  # pylint: disable=broad-except
    return None


def _compiled_text(compiled) -> str:
  try:
    return compiled.as_text() or ''
  except Exception:  # pylint: disable=broad-except
    return ''


_COLLECTIVE_RE = re.compile(
    r'\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)'
    r'(?:-start)?\(')


def _collective_counts(text: str) -> Dict[str, int]:
  return dict(collections.Counter(_COLLECTIVE_RE.findall(text)))


def _aliased_param_numbers(text: str) -> Optional[Tuple[int, ...]]:
  """Parameter numbers XLA aliased to outputs, from the HLO header.

  The optimized module's first line carries the truth about donation:
  ``input_output_alias={ {0}: (0, {}, may-alias), ... }`` — each tuple's
  first element is an aliased parameter number. A requested donation
  missing here was silently elided (the buffer is copied, not reused).
  None when the executable text is unavailable.
  """
  if not text:
    return None
  header = text[:text.find('\n')] if '\n' in text else text
  start = header.find('input_output_alias={')
  if start < 0:
    return ()
  # Scan to the matching close brace (the value nests one brace level
  # per output index, so a regex alone would stop short).
  i = header.find('{', start)
  depth, end = 0, -1
  for j in range(i, len(header)):
    if header[j] == '{':
      depth += 1
    elif header[j] == '}':
      depth -= 1
      if depth == 0:
        end = j
        break
  if end < 0:
    return ()
  block = header[i:end + 1]
  return tuple(sorted({int(m) for m in re.findall(r'\(\s*(\d+)\s*,', block)}))


class OpScope(NamedTuple):
  """Where one instruction of a compiled program came from.

  ``path`` is its ``op_name`` with the transformations unwrapped: a
  ``jvp(...)``, ``transpose(...)`` or ``vmap(...)`` gives way to the
  names it holds, a ``jit(...)`` (a function's name, not a scope) and
  the remat's ``checkpoint`` / ``rematted_computation`` go, and a name
  repeated next to itself is kept once. What is left are the module
  names and named scopes that issued the instruction, the primitive
  last, the same on the way forward and back
  (``Trunk/layer0/attn/afmoe/attn/project/dot_general``). ``direction``
  is :data:`FORWARD`, :data:`BACKWARD` (under a ``transpose``) or
  :data:`RECOMPUTED` (a remat's second forward pass); both are '' for an
  instruction with no ``op_name``.
  """

  path: str
  direction: str


FORWARD, BACKWARD, RECOMPUTED = 'forward', 'backward', 'recomputed'

_COMPUTATION_RE = re.compile(r'^(?:ENTRY )?%?([\w.\-]+) \(.* -> .*\{\s*$')
_INSTRUCTION_RE = re.compile(r'^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$')
_OP_NAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS_RE = re.compile(r'\bcalls=%?([\w.\-]+)')
_TO_APPLY_RE = re.compile(r'\bto_apply=%?([\w.\-]+)')
_WRAPPED_RE = re.compile(r'^(\w+)\((.*)\)$')
_OPERAND_RE = re.compile(r'[^%),]*%([\w.\-]+)')
_REMAT_PARTS = frozenset(('checkpoint', 'rematted_computation'))
# What a fusion that holds one of these does is the product's; the rest
# of it (an optimizer's update, a bias, a cast) rides along.
_PRODUCTS = frozenset(('dot', 'convolution', 'custom-call', 'ragged-dot'))


def _opcode(rhs: str) -> Tuple[str, str]:
  """'f32[8,16]{1,0} dot(%a, %b), ...' -> ('dot', 'a'): the opcode and
  the first operand ('' for none); tuple results too."""
  if rhs.startswith('('):
    depth = 0
    for i, ch in enumerate(rhs):
      depth += (ch == '(') - (ch == ')')
      if depth == 0:
        rhs = rhs[i + 1:]
        break
  else:
    rhs = rhs.partition(' ')[2]
  op, _, rest = rhs.lstrip().partition('(')
  operand = _OPERAND_RE.match(rest)
  return op, operand.group(1) if operand else ''


def _name_parts(name: str) -> List[str]:
  """The components of a name stack, transformations unwrapped and
  ``jit(...)`` dropped: 'jit(f)/transpose(jvp(a/b))/c' -> [a, b, c]."""
  parts, depth, start = [], 0, 0
  for i, ch in enumerate(name + '/'):
    if ch == '(':
      depth += 1
    elif ch == ')':
      depth -= 1
    elif ch == '/' and depth == 0:
      part = name[start:i]
      start = i + 1
      wrapped = _WRAPPED_RE.match(part)
      if wrapped is None:
        if part:
          parts.append(part)
      elif wrapped.group(1) not in ('jit', 'pjit'):
        parts.extend(_name_parts(wrapped.group(2)))
  return parts


def op_scope(op_name: str) -> OpScope:
  """One ``op_name`` as an :class:`OpScope` (a fused op's several names,
  ``;``-separated, are read by the first)."""
  op_name = op_name.split(';', 1)[0]
  parts = _name_parts(op_name)
  if 'rematted_computation' in parts:
    direction = RECOMPUTED
  elif 'transpose(' in op_name:
    direction = BACKWARD
  else:
    direction = FORWARD
  kept: List[str] = []
  for part in parts:
    if part not in _REMAT_PARTS and (not kept or kept[-1] != part):
      kept.append(part)
  return OpScope('/'.join(kept), direction)


def hlo_op_scopes(text: str) -> Dict[str, OpScope]:
  """Every instruction of a compiled HLO module's text -> its scope.

  An instruction is read by its own ``op_name``, with two exceptions. A
  fusion is the scope of the first product (dot, convolution, custom
  call) it holds, nested fusions searched too, and otherwise of its
  root's: root-only would hand a gradient's product to the optimizer
  whose update XLA fused into it. An instruction with no ``op_name`` (a
  copy, slice or tuple that XLA made) is read by its first operand's,
  as far back as one has a name. Within a fusion a name with a scope
  beats a bare primitive's (``gather``: what XLA's expansions leave in
  the fused computation), which is kept only where nothing better is.
  Instruction names are unique in a
  module, so one map covers the entry, loop bodies and branches: every
  instruction that can be an op of a device trace, and no instruction
  inside a fusion or a reduction's region."""
  # name -> (opcode, op_name, fused computation, first operand)
  instrs: Dict[str, Tuple[str, str, str, str]] = {}
  comps: Dict[str, List[str]] = {}
  roots: Dict[str, str] = {}
  inner = set()   # fusions' and regions' computations: never trace ops
  current = None
  for line in text.splitlines():
    if current is None:
      m = _COMPUTATION_RE.match(line)
      if m is not None:
        current = m.group(1)
        comps[current] = []
      continue
    if line.startswith('}'):
      current = None
      continue
    m = _INSTRUCTION_RE.match(line)
    if m is None:
      continue
    name, rhs = m.group(1), m.group(2)
    op, operand = _opcode(rhs)
    named = _OP_NAME_RE.search(rhs)
    calls = _CALLS_RE.search(rhs) if op == 'fusion' else None
    region = _TO_APPLY_RE.search(rhs) if op != 'call' else None
    inner.update(ref.group(1) for ref in (calls, region) if ref is not None)
    instrs[name] = (op, named.group(1) if named else '',
                    calls.group(1) if calls else '', operand)
    comps[current].append(name)
    if line.lstrip().startswith('ROOT '):
      roots[current] = name

  def first(candidates) -> str:
    """The first name with a scope ('/'), else the first name at all:
    XLA's rewrites leave some instructions a bare primitive's name."""
    found = ''
    for candidate in candidates:
      if '/' in candidate:
        return candidate
      found = found or candidate
    return found

  products: Dict[str, str] = {}

  def product(comp: str) -> str:
    """The first product's ``op_name`` in ``comp``, nested fusions too."""
    if comp not in products:
      products[comp] = ''
      products[comp] = first(
          instrs[name][1] if instrs[name][0] in _PRODUCTS else
          product(instrs[name][2]) if instrs[name][2] else ''
          for name in comps.get(comp, ()))
    return products[comp]

  resolved: Dict[str, str] = {}

  def resolve(name: str) -> str:
    if name not in resolved:
      resolved[name] = ''   # a cycle reads as unnamed, never loops
      _, op_name, calls, operand = instrs.get(name, ('', '', '', ''))

      def candidates():
        if calls:
          yield product(calls)
          yield resolve(roots.get(calls, ''))
        yield op_name

      found = first(candidates())
      resolved[name] = found or (resolve(operand) if operand else '')
    return resolved[name]

  out: Dict[str, OpScope] = {}
  for comp, names in comps.items():
    if comp not in inner:
      for name in names:
        op_name = resolve(name)
        out[name] = op_scope(op_name) if op_name else OpScope('', '')
  return out


def _sharding_repr(value) -> str:
  try:
    text = repr(value)
  except Exception:  # pylint: disable=broad-except
    return ''
  if len(text) > _MAX_SHARDING_CHARS:
    text = text[:_MAX_SHARDING_CHARS - 1] + '…'
  return text


def _device_kind() -> str:
  try:
    import jax

    return str(jax.devices()[0].device_kind)
  except Exception:  # pylint: disable=broad-except
    return ''


# --------------------------------------------------------------- the ledger


class ProgramLedger:
  """Thread-safe map of program name → :class:`ProgramRecord`.

  Bounded by construction: one record per distinct program name, and
  the framework compiles a handful of programs (train step, K serving
  buckets, bench kernels) — not one per dispatch. Re-recording a name
  with a changed fingerprint counts a recompile and (by default) flags
  it, which is exactly the steady-state hazard the sentinel exists for.
  """

  def __init__(self):
    self._lock = threading.Lock()
    self._records: Dict[str, ProgramRecord] = {}  # GUARDED_BY(self._lock)
    self._provider_registered = False  # GUARDED_BY(self._lock)
    self._recorded = metrics_lib.counter('programs/recorded')
    self._recompiles = metrics_lib.counter('programs/recompiles')

  def record_compiled(
      self,
      name: str,
      compiled,
      *,
      lowered=None,
      compile_seconds: Optional[float] = None,
      donate_argnums: Sequence[int] = (),
      donated_params: Optional[int] = None,
      captured_warnings: Sequence[str] = (),
      device_kind: Optional[str] = None,
      source: str = '',
      flag_steady_state: bool = True,
      steps_per_execution: int = 1,
  ) -> Optional[ProgramRecord]:
    """Extracts and stores one executable's record; returns it.

    ``lowered`` (the pre-compile ``Lowered``) supplies the canonical
    StableHLO fingerprint; without it the optimized HLO text is hashed
    instead (still stable, but not comparable to export fingerprints).
    ``donated_params`` is the flattened leaf count the caller donated —
    compared against the executable's actual alias list to expose
    silently-undonated buffers. None on any total extraction failure;
    never raises (telemetry must not take down a train loop).
    """
    if not _enabled:
      return None
    try:
      record = self._extract(
          name, compiled, lowered, compile_seconds, donate_argnums,
          donated_params, captured_warnings, device_kind, source)
      record.steps_per_execution = max(1, int(steps_per_execution))
    except Exception:  # pylint: disable=broad-except
      return None
    recompiled = False
    with self._lock:
      prev = self._records.get(name)
      if prev is not None:
        record.recompiles = prev.recompiles
        if prev.fingerprint and record.fingerprint != prev.fingerprint:
          record.recompiles += 1
          recompiled = True
      self._records[name] = record
      register_provider = not self._provider_registered
      self._provider_registered = True
    self._recorded.inc()
    if register_provider:
      metrics_lib.register_report_provider('programs', self._report_section)
    if recompiled:
      self._recompiles.inc()
      if flag_steady_state:
        flag_recompile(name, f'fingerprint={record.fingerprint[:12]} '
                             f'recompiles={record.recompiles}')
    return record

  def _extract(self, name, compiled, lowered, compile_seconds,
               donate_argnums, donated_params, captured_warnings,
               device_kind, source) -> ProgramRecord:
    cost = _cost_analysis(compiled)
    mem = _memory_analysis(compiled)
    fingerprint, fp_source = '', ''
    if lowered is not None:
      try:
        fingerprint, fp_source = (
            program_fingerprint(lowered.as_text()), 'stablehlo')
      except Exception:  # pylint: disable=broad-except
        pass
    text = _compiled_text(compiled)
    if not fingerprint and text:
      fingerprint, fp_source = program_fingerprint(text), 'hlo'
    aliased = _aliased_param_numbers(text)
    aliased_n = None if aliased is None else len(aliased)
    undonated = None
    if donated_params is not None and aliased_n is not None:
      undonated = max(0, int(donated_params) - aliased_n)
    mem_get = lambda attr: int(getattr(mem, attr, 0) or 0)
    argument_bytes = mem_get('argument_size_in_bytes')
    output_bytes = mem_get('output_size_in_bytes')
    temp_bytes = mem_get('temp_size_in_bytes')
    alias_bytes = mem_get('alias_size_in_bytes')
    return ProgramRecord(
        name=name,
        fingerprint=fingerprint,
        fingerprint_source=fp_source,
        flops=float(cost.get('flops', 0.0) or 0.0),
        bytes_accessed=float(cost.get('bytes accessed', 0.0) or 0.0),
        transcendentals=float(cost.get('transcendentals', 0.0) or 0.0),
        argument_bytes=argument_bytes,
        output_bytes=output_bytes,
        temp_bytes=temp_bytes,
        alias_bytes=alias_bytes,
        generated_code_bytes=mem_get('generated_code_size_in_bytes'),
        peak_bytes=max(
            0, argument_bytes + output_bytes + temp_bytes - alias_bytes),
        compile_seconds=float(compile_seconds or 0.0),
        donate_argnums=tuple(int(i) for i in donate_argnums),
        donated_params=(None if donated_params is None
                        else int(donated_params)),
        aliased_params=aliased_n,
        undonated_params=undonated,
        donation_warnings=tuple(str(w)[:256] for w in captured_warnings),
        input_shardings=_sharding_repr(
            getattr(compiled, 'input_shardings', '')),
        output_shardings=_sharding_repr(
            getattr(compiled, 'output_shardings', '')),
        device_kind=(device_kind if device_kind is not None
                     else _device_kind()),
        source=source,
        recorded_unix=time.time(),
        custom_calls=text.count('tpu_custom_call'),
        collectives=_collective_counts(text),
        hlo_text=text,
    )

  def get(self, name: str) -> Optional[ProgramRecord]:
    with self._lock:
      return self._records.get(name)

  def names(self) -> List[str]:
    with self._lock:
      return sorted(self._records)

  def document(self) -> Dict[str, Any]:
    """The full JSON-ready ledger (``/programz``, dumps, bench line)."""
    with self._lock:
      records = [self._records[k].to_dict() for k in sorted(self._records)]
    return {
        'programs': records,
        'recorded': metrics_lib.counter('programs/recorded').value,
        'recompiles': metrics_lib.counter('programs/recompiles').value,
        'steady_state_recompiles':
            metrics_lib.counter('programs/steady_state_recompiles').value,
    }

  def _report_section(self) -> Dict[str, Any]:
    """Compact per-program summary for ``metrics.report()``."""
    with self._lock:
      records = list(self._records.values())
    return {
        rec.name: {
            'gflops': round(rec.flops / 1e9, 3),
            'mb_accessed': round(rec.bytes_accessed / 1e6, 3),
            'peak_mb': round(rec.peak_bytes / 1e6, 3),
            'compile_seconds': round(rec.compile_seconds, 3),
            'fingerprint': rec.fingerprint[:12],
            'donated': (None if rec.donated_params is None
                        else f'{rec.aliased_params}/{rec.donated_params}'),
            'recompiles': rec.recompiles,
            'custom_calls': rec.custom_calls,
        } for rec in records
    }

  def clear(self) -> None:
    with self._lock:
      self._records.clear()


_LEDGER = ProgramLedger()

# Module-global fast-path switch (flight.py idiom): a racing reader sees
# either value, both valid. Disabled, every record_* is one global read.
_enabled = True

# Optional escalation hook for steady-state recompiles (e.g. a live
# postmortem dump or an anomaly-watch poke). Called OUTSIDE any ledger
# lock with (name, detail); exceptions are swallowed.
_escalation: Optional[Callable[[str, str], None]] = None


def ledger() -> ProgramLedger:
  return _LEDGER


def set_enabled(on: bool) -> None:
  """Master switch; disabled, the ledger records and derives nothing."""
  global _enabled
  _enabled = bool(on)


def enabled() -> bool:
  return _enabled


def set_recompile_escalation(
    fn: Optional[Callable[[str, str], None]]) -> None:
  global _escalation
  _escalation = fn


def record_compiled(name: str, compiled, **kwargs) -> Optional[ProgramRecord]:
  """Records ``compiled`` into the process-global ledger."""
  return _LEDGER.record_compiled(name, compiled, **kwargs)


def record_jitted(name: str, jit_fn, args: Sequence[Any],
                  donate_argnums: Sequence[int] = (),
                  donated_params: Optional[int] = None,
                  source: str = '',
                  steps_per_execution: int = 1,
                  flag_steady_state: bool = True) -> Optional[ProgramRecord]:
  """Lowers and compiles ``jit_fn`` at ``args`` and records it.

  Given the very arguments of a call ``jit_fn`` has already made (the
  trainer's first dispatch), the trace, the lowering and the executable
  all come from jax's caches: no backend compile, the record is of the
  executable that runs. Avals rebuilt from those arguments
  (``ShapeDtypeStruct``) miss the caches and pay a second compile.
  Unused-donation warnings emitted during lower/compile are captured
  into the record. Never raises.
  """
  if not _enabled:
    return None
  try:
    t0 = time.perf_counter()
    with warnings_mod.catch_warnings(record=True) as caught:
      warnings_mod.simplefilter('always')
      lowered = jit_fn.lower(*args)
      compiled = lowered.compile()
    dt = time.perf_counter() - t0
    donation_warnings = tuple(
        str(w.message) for w in caught
        if 'donat' in str(w.message).lower())
  except Exception:  # pylint: disable=broad-except
    return None
  return _LEDGER.record_compiled(
      name, compiled, lowered=lowered, compile_seconds=dt,
      donate_argnums=donate_argnums, donated_params=donated_params,
      captured_warnings=donation_warnings, source=source,
      flag_steady_state=flag_steady_state,
      steps_per_execution=steps_per_execution)


def get(name: str) -> Optional[ProgramRecord]:
  return _LEDGER.get(name)


def names() -> List[str]:
  return _LEDGER.names()


def document() -> Dict[str, Any]:
  return _LEDGER.document()


def dump(path: str) -> str:
  """Writes the ledger document as JSON; returns ``path``."""
  doc = document()
  with open(path, 'w', encoding='utf-8') as f:
    json.dump(doc, f, indent=2, sort_keys=True)
  return path


def clear() -> None:
  """Drops all records (test isolation; counters keep their totals)."""
  _LEDGER.clear()


# ------------------------------------------------------------- utilization


def set_device_peaks(flops: Optional[float] = None,
                     hbm_gbps: Optional[float] = None) -> None:
  """Explicit peak overrides (tests, CPU runs, odd parts). None clears."""
  global _peak_flops_override, _peak_hbm_override
  _peak_flops_override = None if flops is None else float(flops)
  _peak_hbm_override = None if hbm_gbps is None else float(hbm_gbps)


_peak_flops_override: Optional[float] = None
_peak_hbm_override: Optional[float] = None


def _env_float(var: str) -> Optional[float]:
  raw = os.environ.get(var, '').strip()
  if not raw:
    return None
  try:
    return float(raw)
  except ValueError:
    return None


def _resolve_peaks(device_kind: str
                   ) -> Tuple[Optional[float], Optional[float]]:
  flops = (_peak_flops_override
           if _peak_flops_override is not None
           else _env_float(ENV_PEAK_FLOPS))
  hbm = (_peak_hbm_override
         if _peak_hbm_override is not None
         else _env_float(ENV_PEAK_HBM_GBPS))
  if flops is None:
    flops = _TABLE_PEAK_FLOPS.get(device_kind)
  if hbm is None:
    hbm = _TABLE_PEAK_HBM_GBPS.get(device_kind)
  return flops, hbm


def utilization(name: str, n_steps: int,
                device_seconds: float) -> Dict[str, float]:
  """Derived roofline gauges for ``n_steps`` train steps of ``name``.

  ``n_steps`` counts STEPS, not dispatches: a K-step scanned executable
  (``steps_per_dispatch`` with or without device feed) records
  ``steps_per_execution=K`` and its cost_analysis covers the whole
  K-step program, so per-step FLOPs/bytes are ``record / K`` — the
  normalization that keeps MFU honest when one dispatch trains K
  steps (and exact for ragged tail groups shorter than K, which a
  per-dispatch multiply would overcount). For K == 1 this is the
  historical dispatch-count math bit for bit.

  ``hbm_gbps`` (measured bytes-accessed over measured device seconds)
  needs no peak and is always present; ``mfu`` and ``roofline_fraction``
  appear when the matching peak is known (device table, env vars, or
  :func:`set_device_peaks`). {} when the program is unrecorded, the
  ledger is disabled, or no device time was measured.
  """
  if not _enabled or n_steps <= 0 or device_seconds <= 0:
    return {}
  record = _LEDGER.get(name)
  if record is None:
    return {}
  per_exec = max(1, int(record.steps_per_execution))
  flops = record.flops / per_exec * n_steps
  bytes_accessed = record.bytes_accessed / per_exec * n_steps
  out = {
      'hbm_gbps': bytes_accessed / device_seconds / 1e9,
      'tflops': flops / device_seconds / 1e12,
  }
  peak_flops, peak_hbm = _resolve_peaks(record.device_kind)
  roofline = []
  if peak_flops:
    out['mfu'] = flops / device_seconds / peak_flops
    roofline.append(out['mfu'])
  if peak_hbm:
    roofline.append(out['hbm_gbps'] / peak_hbm)
  if roofline:
    # Fraction of the binding roof: a program at 8% MFU but 92% of HBM
    # bandwidth is bandwidth-bound, not badly scheduled.
    out['roofline_fraction'] = max(roofline)
  return out


# ---------------------------------------------------- recompile sentinel


def flag_recompile(name: str, detail: str = '') -> None:
  """Counts + flight-records one steady-state recompile of ``name``."""
  metrics_lib.counter('programs/steady_state_recompiles').inc()
  flight.event('program', f'{name}/recompile', detail)
  escalation = _escalation
  if escalation is not None:
    try:
      escalation(name, detail)
    except Exception:  # pylint: disable=broad-except
      pass


class RecompileSentinel:
  """O(1)-per-dispatch steady-state recompile detector.

  Watches a jitted function's executable-cache size (jax's
  ``_cache_size()``, one C++ call) from the dispatch loop: growth after
  ``warmup`` observations means a NEW program was traced+compiled in
  steady state — the production incarnation of the static
  ``recompile-hazard`` rule, flagged within the dispatch that paid it.
  Single-consumer by design (lives on the trainer loop thread), so no
  lock: the three fields are only touched by :meth:`observe`.
  """

  def __init__(self, name: str, warmup: int = 2):
    self.name = name
    self._warmup = max(0, int(warmup))
    self._observations = 0
    self._baseline: Optional[int] = None

  def observe(self, cache_size: Optional[int]) -> bool:
    """Feeds one post-dispatch cache size; True iff a recompile flagged."""
    if cache_size is None:
      return False
    self._observations += 1
    if self._baseline is None or self._observations <= self._warmup:
      self._baseline = max(int(cache_size), self._baseline or 0)
      return False
    if cache_size > self._baseline:
      grown = cache_size - self._baseline
      self._baseline = int(cache_size)
      flag_recompile(
          self.name,
          f'jit_cache_size={cache_size} new_programs={grown} '
          f'after={self._observations}_dispatches')
      return True
    return False


def jit_cache_size(jit_fn) -> Optional[int]:
  """Best-effort executable-cache size of a jitted callable (else None)."""
  probe = getattr(jit_fn, '_cache_size', None)
  if probe is None:
    return None
  try:
    return int(probe())
  except Exception:  # pylint: disable=broad-except
    return None


def dispatch_probe(jit_fn, name: str, warmup: int = 2):
  """Builds the per-dispatch recompile probe for one jitted callable.

  The :class:`RecompileSentinel` logic with everything hoisted out of
  the dispatch loop: the ``_cache_size`` attribute lookup happens once
  here, and the steady-state path inside the returned closure is one
  C++ cache-size read, one int compare against the closed-over
  baseline, and a return — no method dispatch, no sentinel object.
  Returns a zero-arg closure reporting True iff the observation
  flagged a recompile; callables without a cache probe get a no-op
  closure, so call sites need no branching beyond the on/off gate.
  """
  raw = getattr(jit_fn, '_cache_size', None)
  if raw is None:
    return lambda: False
  observations = 0
  baseline: Optional[int] = None

  def probe() -> bool:
    nonlocal observations, baseline
    try:
      size = raw()
    except Exception:  # pylint: disable=broad-except
      return False
    observations += 1
    if baseline is None or observations <= warmup:
      baseline = size if baseline is None or size > baseline else baseline
      return False
    if size > baseline:
      grown = size - baseline
      baseline = size
      flag_recompile(
          name, f'jit_cache_size={size} new_programs={grown} '
          f'after={observations}_dispatches')
      return True
    return False

  return probe
