"""From a profiler trace (``.xplane.pb``) to what the metric readers use.

Read with ``jax.profiler.ProfileData`` alone. A TPU plane is named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event for every
operation that ran on the core, and ``XLA Modules`` one for every
program. The host's threads are lines of the plane ``/host:CPU``; the
benchmark's own ``TraceAnnotation`` spans are events there, on the same
clock, when the host tracer is on. It slows the host several times over
(PERF.md, Findings), so the cells trace with it off and place their own
spans, timed with ``time.time_ns()``, by :func:`profile_start_ns`.

``reduce`` returns, per device, the busy time (the union of the op
intervals, clipped to the window), the time by op name and by program,
and the idle gaps; and the host spans whose names were asked for.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE_PREFIX = '/device:TPU:'
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
HOST_PLANE = '/host:CPU'


def find_xplane(trace_dir: str) -> str:
  paths = sorted(glob.glob(
      os.path.join(trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
  if not paths:
    raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
  return paths[-1]


def load(path: str):
  from jax.profiler import ProfileData

  return ProfileData.from_file(path)


def profile_start_ns(profile) -> Optional[int]:
  """When the trace's clock reads 0, in nanoseconds since the epoch (the
  plane ``Task Environment`` says): places spans timed with
  ``time.time_ns()`` on the trace's clock."""
  for plane in profile.planes:
    if plane.name == 'Task Environment':
      for key, value in plane.stats:
        if key == 'profile_start_time':
          return int(value)
  return None


def _events(line) -> List[Tuple[str, float, float]]:
  """(name, start, end) in nanoseconds."""
  return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
          for e in line.events]


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
  total, end = 0.0, None
  for a, b in sorted(intervals):
    if end is None or a > end:
      total += b - a
      end = b
    elif b > end:
      total += b - end
      end = b
  return total


def gaps(intervals: Iterable[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
  """The stretches of [lo, hi] that no interval covers."""
  out, cursor = [], lo
  for a, b in sorted(intervals):
    if a > cursor:
      out.append((cursor, min(a, hi)))
    cursor = max(cursor, b)
    if cursor >= hi:
      break
  if cursor < hi:
    out.append((cursor, hi))
  return [(a, b) for a, b in out if b > a]


def summary(profile) -> List[str]:
  """One line a plane and line: what a first look at a trace needs."""
  out = []
  for plane in profile.planes:
    for line in plane.lines:
      events = list(line.events)
      names = sorted({e.name for e in events[:2000]})[:4]
      out.append(f'{plane.name} | {line.name} | {len(events)} events | '
                 f'{names}')
  return out


def reduce(profile, host_spans: Iterable[str] = (),
           window_ns: Optional[Tuple[float, float]] = None,
           own_spans: Iterable[Tuple[str, int, int]] = ()) -> Dict:
  """``window_ns`` clips everything; default: from the first device op or
  program to the end of the last.
  ``own_spans`` are (name, start, end) in epoch nanoseconds; they are
  used for a name the trace itself holds no span of."""
  devices = []
  for plane in profile.planes:
    if not plane.name.startswith(DEVICE_PLANE_PREFIX):
      continue
    ops, modules = [], []
    for line in plane.lines:
      if line.name == OPS_LINE:
        ops = _events(line)
      elif line.name == MODULES_LINE:
        modules = _events(line)
    if ops:
      devices.append({'name': plane.name, 'ops': ops, 'modules': modules})
  if not devices:
    return {'devices': [], 'host_spans': {}, 'window_ns': None}
  if window_ns is None:
    spans_of = [e for d in devices for e in d['ops'] + d['modules']]
    window_ns = (min(e[1] for e in spans_of), max(e[2] for e in spans_of))
  lo, hi = window_ns
  out_devices = []
  for d in devices:
    clipped = [(n, max(a, lo), min(b, hi)) for n, a, b in d['ops']
               if b > lo and a < hi]
    by_op: Dict[str, float] = {}
    for n, a, b in clipped:
      by_op[n] = by_op.get(n, 0.0) + (b - a)
    by_module: Dict[str, Dict[str, float]] = {}
    for n, a, b in d['modules']:
      if b > lo and a < hi:
        m = by_module.setdefault(n, {'ns': 0.0, 'count': 0, 'whole': 0})
        m['ns'] += min(b, hi) - max(a, lo)
        m['count'] += 1
        m['whole'] += int(a >= lo and b <= hi)
    intervals = [(a, b) for _, a, b in clipped]
    out_devices.append({
        'name': d['name'],
        'busy_ns': union_length(intervals),
        'by_op_ns': by_op,
        'by_module': by_module,
        'modules': [(n, a, b) for n, a, b in d['modules']
                    if a >= lo and b <= hi],
        'ops_in_window': clipped,
        'idle_gaps_ns': gaps(intervals, lo, hi),
    })
  spans: Dict[str, List[Tuple[float, float]]] = {n: [] for n in host_spans}
  if spans:
    for plane in profile.planes:
      if plane.name != HOST_PLANE:
        continue
      for line in plane.lines:
        for e in line.events:
          if e.name in spans:
            spans[e.name].append(
                (float(e.start_ns), float(e.start_ns + e.duration_ns)))
  start = profile_start_ns(profile)
  if start is not None:
    traced_names = {n for n, v in spans.items() if v}
    for name, a, b in own_spans:
      if name in spans and name not in traced_names:
        spans[name].append((float(a - start), float(b - start)))
  return {'devices': out_devices, 'host_spans': spans, 'window_ns': window_ns}


def attribute_gaps(gap_list: List[Tuple[float, float]],
                   spans: Dict[str, List[Tuple[float, float]]],
                   other: str = 'host: under neither span (unattributed)'
                   ) -> List[Tuple[str, float]]:
  """Seconds of idle gaps by the host span that covers most of each."""
  totals: Dict[str, float] = {}
  for a, b in gap_list:
    best, best_cover = other, 0.0
    for name, intervals in spans.items():
      cover = sum(max(0.0, min(b, y) - max(a, x)) for x, y in intervals
                  if y > a and x < b)
      if cover > best_cover and cover >= 0.5 * (b - a):
        best, best_cover = name, cover
    totals[best] = totals.get(best, 0.0) + (b - a) / 1e9
  return sorted(totals.items(), key=lambda kv: -kv[1])
