"""What every reader of the program's own spans shares: the spans of the
program's ring placed on the trace's clock and joined, by key, to the
train step's events on the device. Worked out once a run (kept in
``ctx['cache']``), logged to stderr as the tables the numbers come from.

The program (``tensor2robot_tpu/observability/tracing.py``) keeps the
last 65,536 finished spans as ``(name, start_ns, end_ns, thread, key)``
in ``perf_counter_ns``; ``clock_anchor()`` ties that clock to the wall
clock and the trace's ``profile_start_time`` ties the wall clock to the
trace. The trainer keys its spans by the batch ordinal, which is the
dispatch ordinal, so step event n on the device is dispatch n: aligned
from the end (the trace stops after the loop has drained, so the last
step event is the last ``trainer/dispatch`` span) and checked twice: the
count of step events equals the count of dispatches enqueued inside the
trace, and ``trainer/device_wait`` of dispatch n ends where step event n
ends (the host unblocks when the device finishes).

A program that keeps no ring (the parent of the PR that added it), a ring
that has wrapped over the window, or a join that fails its checks: every
reader returns None and the log says why.
"""

from __future__ import annotations

import bisect
import statistics
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.lib import trace
from benchmark.metrics import _traced

DISPATCH = 'trainer/dispatch'
DEVICE_WAIT = 'trainer/device_wait'
TRANSFER = 'trainer/place/transfer'
PUT = 'trainer/place/put'
PLACE = 'trainer/place_stage'
PARSE = 'data/engine/parse_decode'
# These four tile the loop thread's time between boundaries.
LOOP_SPANS = ('trainer/after_dispatch', 'trainer/wait_batch', DISPATCH,
              DEVICE_WAIT)
# A device_wait that really blocked ends when the device finishes; one
# that found the outputs ready says nothing about the clocks.
BLOCKED_NS = 1e6
# The join is refused if the host's and the device's clocks disagree by
# more than this in the median (a misalignment by one dispatch reads a
# whole step).
AGREE_NS = 5e6

Interval = Tuple[float, float]
Span = Tuple[str, float, float, str, object]


def say(message: str) -> None:
  print('program spans: ' + message, file=sys.stderr, flush=True)


def on_trace_clock(spans: Sequence[Span], anchor: Tuple[int, int],
                   profile_start_ns: int) -> List[Span]:
  """``anchor`` is ``(time_ns, perf_counter_ns)`` read together."""
  shift = anchor[0] - anchor[1] - profile_start_ns
  return [(name, a + shift, b + shift, thread, key)
          for name, a, b, thread, key in spans]


def _overlap(pieces: Sequence[Interval], lo: float, hi: float) -> float:
  """Length of sorted, disjoint ``pieces`` inside [lo, hi]."""
  return sum(min(b, hi) - max(a, lo) for a, b in pieces
             if min(b, hi) > max(a, lo))


def _clip(intervals: Sequence[Interval], starts: Sequence[float],
          lo: float, hi: float) -> List[Interval]:
  """The parts of sorted, disjoint ``intervals`` inside [lo, hi]."""
  out = []
  i = max(0, bisect.bisect_right(starts, lo) - 1)
  while i < len(intervals) and intervals[i][0] < hi:
    a, b = max(intervals[i][0], lo), min(intervals[i][1], hi)
    if b > a:
      out.append((a, b))
    i += 1
  return out


def _table(spans: Sequence[Span], lo: float, hi: float) -> Dict[str, Dict]:
  """Per span name, over the spans that touch [lo, hi]: count, total and
  median duration in ms, and the threads that recorded them."""
  rows: Dict[str, Dict] = {}
  for name, a, b, thread, _ in spans:
    if b > lo and a < hi:
      row = rows.setdefault(name, {'ms': [], 'threads': set()})
      row['ms'].append((b - a) / 1e6)
      row['threads'].add(thread)
  return {name: {'count': len(r['ms']), 'total_ms': sum(r['ms']),
                 'median_ms': statistics.median(r['ms']),
                 'threads': sorted(r['threads'])}
          for name, r in rows.items()}


def join(step_events: Sequence[Interval], ops: Sequence[Interval],
         spans: Sequence[Span], steps_per_dispatch: int = 1,
         overwritten: int = 0,
         others: Sequence[Tuple[str, float, float]] = (),
         trace_stop: float = float('inf')) -> Optional[Dict]:
  """Everything on the trace's clock, in ns: ``step_events`` the train
  step's program events, ``ops`` every device operation, ``others`` the
  other programs' events (name, start, end), ``spans`` the program's,
  ``overwritten`` how many spans its ring has lost. The trace runs from
  0 to ``trace_stop``; what the ring holds of a later loop is left out.
  Returns None (and says why) where the join cannot be trusted."""
  events = sorted(step_events)
  m = len(events)
  if m < 2:
    say(f'{m} step events in the trace: nothing to join')
    return None
  lo, hi = events[0][0], events[-1][1]
  if not spans:
    say('the ring holds no span')
    return None
  if overwritten and min(b for _, _, b, _, _ in spans) > lo:
    say(f'the ring has wrapped over the window: {overwritten} spans lost '
        'and the oldest kept ends after the window starts')
    return None
  keyed: Dict[str, Dict[int, Interval]] = {
      n: {} for n in (DISPATCH, DEVICE_WAIT, TRANSFER)}
  for name, a, b, _, key in spans:  # oldest first: the newest of a key stays
    if name in keyed and key is not None and a < trace_stop:
      keyed[name][key] = (a, b)
  dispatch = keyed[DISPATCH]
  if not dispatch:
    say('the ring holds no trainer/dispatch span')
    return None
  last = max(dispatch, key=lambda k: dispatch[k][1])  # the last enqueued
  keys = [last - (m - 1 - j) for j in range(m)]
  missing = [k for k in keys if k not in dispatch]
  if missing:
    say(f'{m} step events but no trainer/dispatch span of key '
        f'{missing[0]} (keys kept: {min(dispatch)}..{last})')
    return None
  # Dispatches enqueued inside the trace against the step events in it.
  # One more event than those is the dispatch enqueued just before the
  # trace started and run just after: its span is there, before 0.
  inside = sum(1 for _, b in dispatch.values() if b >= 0)
  if inside != m and not (inside == m - 1 and dispatch[keys[0]][1] < 0):
    say(f'miscount: {m} step events in the trace against {inside} '
        'trainer/dispatch spans enqueued in it')
    return None
  deltas = []
  for (_, end), k in zip(events, keys):
    wait = keyed[DEVICE_WAIT].get(k)
    if wait is not None and wait[1] - wait[0] >= BLOCKED_NS:
      deltas.append(wait[1] - end)
  agreement = None
  if deltas:
    agreement = {
        'pairs': len(deltas),
        'median_abs_ms': statistics.median(abs(d) for d in deltas) / 1e6,
        'median_ms': statistics.median(deltas) / 1e6,
        'max_abs_ms': max(abs(d) for d in deltas) / 1e6,
    }
    if agreement['median_abs_ms'] * 1e6 > AGREE_NS:
      say('the clocks disagree: trainer/device_wait of dispatch n ends '
          f'{agreement["median_ms"]:.3f} ms (median) from the end of step '
          f'event n, over {len(deltas)} pairs')
      return None
  # Where the loop never blocks on the device (its batch comes late), cause
  # and effect still bound the clocks' offset from both sides: a step
  # cannot start before the call that enqueues it began, and a wait for a
  # batch cannot end before the device program it waited for has.
  started_after = min(start - dispatch[k][0]
                      for (start, _), k in zip(events, keys))
  if started_after < -AGREE_NS:
    say(f'the clocks disagree: a step event starts {-started_after / 1e6:.3f}'
        ' ms before the trainer/dispatch that enqueued it began')
    return None
  program_ends = sorted([b for _, b in events] + [b for _, _, b in others])
  woke_after = []
  for name, a, b, _, _ in spans:
    if name == TRANSFER and b - a >= BLOCKED_NS and lo < b < hi:
      i = bisect.bisect_right(program_ends, b + AGREE_NS)
      # The program whose end is nearest the wait's: the one it waited for.
      near = [b - end for end in program_ends[max(0, i - 3):i]]
      if near:
        woke_after.append(min(near, key=abs))

  idle = trace.gaps(sorted(ops), lo, hi)
  idle_starts = [a for a, _ in idle]
  in_step = sum(y - x for a, b in events
                for x, y in _clip(idle, idle_starts, a, b))
  loop = sorted((a, b, name) for name, a, b, _, _ in spans
                if name in LOOP_SPANS and b > lo and a < hi)
  loop_starts = [a for a, _, _ in loop]
  callbacks = sorted((a, b) for name, a, b, _, _ in spans
                     if name == 'trainer/callbacks' and b > lo and a < hi)

  def by_loop_span(pieces, a, b, totals):
    i = max(0, bisect.bisect_right(loop_starts, a) - 1)
    while i < len(loop) and loop[i][0] < b:
      x, y, name = loop[i]
      covered = _overlap(pieces, max(a, x), min(b, y))
      if covered:
        totals[name] = totals.get(name, 0.0) + covered
      i += 1

  placing = sorted((a, b, name, key) for name, a, b, _, key in spans
                   if name in (PUT, TRANSFER) and b > lo and a < hi)
  between = host_late = input_late = gap_total = 0.0
  late_by_span: Dict[str, float] = {}
  between_by_span: Dict[str, float] = {}
  between_by_placing: Dict[str, float] = {}
  late_in_callbacks = 0.0
  for j in range(1, m):
    gap_lo, gap_hi = events[j - 1][1], events[j][0]
    if gap_hi <= gap_lo:
      continue
    gap_total += gap_hi - gap_lo
    pieces = _clip(idle, idle_starts, gap_lo, gap_hi)
    between += sum(b - a for a, b in pieces)
    by_loop_span(pieces, gap_lo, gap_hi, between_by_span)
    enqueued = dispatch[keys[j]][1]
    late_hi = min(gap_hi, enqueued)
    if late_hi > gap_lo:
      host_late += _overlap(pieces, gap_lo, late_hi)
      by_loop_span(pieces, gap_lo, late_hi, late_by_span)
      late_in_callbacks += sum(
          _overlap(pieces, max(gap_lo, x), min(late_hi, y))
          for x, y in callbacks if y > gap_lo and x < late_hi)
    for a, b, name, key in placing:
      covered = _overlap(pieces, max(gap_lo, a), min(gap_hi, b))
      if covered and isinstance(key, int):
        label = f'{name} of batch n{key - keys[j]:+d}'
        between_by_placing[label] = (
            between_by_placing.get(label, 0.0) + covered)
    transfer = keyed[TRANSFER].get(keys[j])
    if transfer is not None:
      input_late += _overlap(pieces, max(gap_lo, enqueued),
                             min(gap_hi, transfer[1]))
  other_ns: Dict[str, List[float]] = {}
  for name, a, b in others:
    if b > lo and a < hi:
      other_ns.setdefault(name, []).append(b - a)

  steps = m * steps_per_dispatch
  table = _table(spans, lo, hi)

  parse = [(max(a, lo), min(b, hi), b <= hi) for name, a, b, _, _ in spans
           if name == PARSE and b > lo and a < hi]
  delivered = sum(1 for _, _, whole in parse if whole)
  out = {
      'window_ns': (lo, hi), 'events': m, 'steps': steps,
      'keys': (keys[0], keys[-1]), 'agreement': agreement,
      'started_after_enqueue_ms': started_after / 1e6,
      'woke_after_program_ms': (statistics.median(woke_after) / 1e6
                                if woke_after else None),
      'idle.in_step_ms': in_step / steps / 1e6,
      'idle.between_steps_ms': between / steps / 1e6,
      'idle.host_late_ms': host_late / steps / 1e6,
      'idle.input_late_ms': input_late / steps / 1e6,
      'dispatch.enqueue_ms': statistics.median(
          (dispatch[k][1] - dispatch[k][0]) / 1e6 for k in keys),
      'place.batch_ms': table.get(PLACE, {}).get('median_ms'),
      'feed.busy_ms': (sum(b - a for a, b, _ in parse) / delivered / 1e6
                       if delivered else None),
      'table': table,
  }

  def per_step(ns):
    return ns / steps / 1e6

  say(f'joined {m} step events to dispatches {keys[0]}..{keys[-1]} '
      f'({inside} enqueued inside the trace); window '
      f'{(hi - lo) / 1e9:.3f} s, {steps} steps, '
      f'{(hi - lo) / steps / 1e6:.3f} ms a step')
  if agreement:
    say('clock agreement: end of trainer/device_wait(n) - end of step '
        f'event n: median {agreement["median_ms"]:.3f} ms, median |.| '
        f'{agreement["median_abs_ms"]:.3f} ms, max |.| '
        f'{agreement["max_abs_ms"]:.3f} ms over {agreement["pairs"]} waits '
        'that blocked')
  else:
    say('clock agreement: no trainer/device_wait blocked in the window (the '
        'loop gets its batch after the step before has ended)')
  say('clock bounds by cause and effect: a step event starts at least '
      f'{started_after / 1e6:.3f} ms after its trainer/dispatch began'
      + (f'; a trainer/place/transfer that blocked ends '
         f'{statistics.median(woke_after) / 1e6:.3f} ms (median, least '
         f'{min(woke_after) / 1e6:.3f}) after the device program nearest its '
         f'end, over {len(woke_after)} waits' if woke_after else ''))
  say(f'device idle a step: in step {per_step(in_step):.3f} ms + between '
      f'steps {per_step(between):.3f} ms = '
      f'{per_step(in_step + between):.3f} ms; the gaps between step events '
      f'are {per_step(gap_total):.3f} ms a step, '
      f'{per_step(gap_total - between):.3f} ms of it other device work')
  rest = between - host_late - input_late
  say(f'between steps: host late {per_step(host_late):.3f} ms + input late '
      f'{per_step(input_late):.3f} ms + enqueued and placed, not yet '
      f'running {per_step(rest):.3f} ms')
  for title, totals in (
      ('host late, by the loop-thread span over it', late_by_span),
      ('all idle between steps, by the loop-thread span over it',
       between_by_span),
      ('all idle between steps before step n, by the placement span open '
       'over it', between_by_placing)):
    say(f'{title}: ' + (', '.join(
        f'{name} {per_step(ns):.3f} ms'
        for name, ns in sorted(totals.items(), key=lambda kv: -kv[1]))
        or 'none'))
  say('host late inside trainer/callbacks (part of trainer/after_dispatch)'
      f': {per_step(late_in_callbacks):.3f} ms')
  for name, durations in sorted(other_ns.items()):
    say(f'other program {name}: {len(durations)} events, '
        f'{per_step(sum(durations)):.3f} ms a step, median '
        f'{statistics.median(durations) / 1e6:.3f} ms')
  if parse:
    workers = len(table[PARSE]['threads'])
    busy = out['feed.busy_ms']
    say(f'feed: {len(parse)} parse_decode spans on {workers} worker(s), '
        f'{delivered} batches finished in the window'
        + (f', {busy:.3f} ms of worker time a batch: ceiling '
           f'{workers * 1e3 / busy:.2f} batches/s' if busy else ''))
  say('span                            count   total_ms  median_ms  threads')
  for name, row in sorted(table.items()):
    say(f'{name:30s} {row["count"]:6d} {row["total_ms"]:10.3f} '
        f'{row["median_ms"]:10.3f}  {",".join(row["threads"])[:60]}')
  return out


def _other_planes(profile) -> None:
  """Once a run: what the trace holds on the chip beside the core."""
  for plane in profile.planes:
    if plane.name.startswith('#Chip'):
      lines = [f'{line.name} ({len(list(line.events))} events)'
               for line in plane.lines]
      say(f'plane {plane.name}: ' + (', '.join(lines) or 'no lines'))


def _stat(profile, plane_name: str, key: str):
  for plane in profile.planes:
    if plane.name == plane_name:
      for k, value in plane.stats:
        if k == key:
          return value
  return None


def joined(ctx: Dict) -> Optional[Dict]:
  cache = ctx['cache']
  if 'program_spans' not in cache:
    cache['program_spans'] = _joined(ctx)
  return cache['program_spans']


def _joined(ctx: Dict) -> Optional[Dict]:
  try:
    from tensor2robot_tpu.observability import metrics, tracing

    anchor = tracing.clock_anchor()
    taken, spans = tracing.taken(), tracing.recent()
  except (ImportError, AttributeError) as e:
    say(f'the program keeps no span ring ({e}): nothing to read')
    return None
  profile = ctx['profile']
  _other_planes(profile)
  start = trace.profile_start_ns(profile)
  if start is None:
    say('the trace does not say when its clock starts')
    return None
  stop = _stat(profile, 'Task Environment', 'profile_stop_time')
  stop = float('inf') if stop is None else int(stop) - start
  step_name = _traced.traced(ctx)['step_name']
  reduced = trace.reduce(profile)
  if not reduced['devices']:
    return None
  device = reduced['devices'][0]
  out = join(
      [(a, b) for n, a, b in device['modules'] if n == step_name],
      [(a, b) for _, a, b in device['ops_in_window']],
      on_trace_clock(spans, anchor, start),
      steps_per_dispatch=ctx['steps_per_dispatch'],
      overwritten=taken - len(spans),
      others=[e for e in device['modules'] if e[0] != step_name],
      trace_stop=stop)
  if out is None:
    return None
  lo, hi = out['window_ns']
  say(f'trace: 0 .. {stop / 1e9:.3f} s; window {lo / 1e9:.3f} .. '
      f'{hi / 1e9:.3f} s; ring: {taken} spans taken, {len(spans)} kept')
  puts = metrics.histogram(PUT + '_ms').snapshot()['count']
  sent = metrics.counter('trainer/h2d/bytes').value
  put, transfer = out['table'].get(PUT), out['table'].get(TRANSFER)
  if puts and sent and put and transfer:
    batch_bytes = sent / puts
    say(f'placement: {batch_bytes / 1e6:.1f} MB a batch; put '
        f'{put["median_ms"]:.3f} ms + transfer {transfer["median_ms"]:.3f} '
        f'ms (medians): {batch_bytes / transfer["median_ms"] / 1e6:.2f} GB/s '
        'over the transfer, '
        f'{batch_bytes / (put["median_ms"] + transfer["median_ms"]) / 1e6:.2f}'
        ' GB/s over both')
  return out


def reader(metric: str):
  """``read(ctx)`` of one metric of the join."""
  def read(ctx):
    out = joined(ctx)
    return None if out is None else out[metric]
  return read
