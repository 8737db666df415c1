"""What every reader of the device trace shares: the traced window and
the train step's program in it, worked out once a run (kept in
``ctx['cache']``).

The train step's program is the one that took most device time. The
window runs from the start of its first event to the end of its last,
so the window holds whole dispatches only and examples can be counted
exactly: events x examples a dispatch.
"""

from __future__ import annotations

from typing import Dict

from benchmark.lib import trace, window

SMALL_GAP_NS = 20e3


def _short(op: str) -> str:
  """'%fusion.163 = bf16[32,118,118,256]{...} fusion(...)' ->
  '%fusion.163 bf16[32,118,118,256] fusion'."""
  lhs, sep, rhs = op.partition(' = ')
  if not sep:
    return op[:96]
  shape = rhs.split('{', 1)[0].split(' ', 1)[0]
  kind = rhs.split('(', 1)[0].rsplit(' ', 1)[-1]
  return f'{lhs} {shape} {kind}'[:96]


def traced(ctx: Dict) -> Dict:
  if 'traced' in ctx['cache']:
    return ctx['cache']['traced']
  spans = [window.FEED_SPAN, window.CALLBACK_SPAN]
  own = ctx.get('own_spans', ())
  first = trace.reduce(ctx['profile'], spans, own_spans=own)
  if not first['devices']:
    raise RuntimeError('the trace holds no device operation')
  dev = first['devices'][0]
  step_name = max(dev['by_module'], key=lambda n: dev['by_module'][n]['ns'])
  events = sorted((a, b) for n, a, b in dev['modules'] if n == step_name)
  lo, hi = events[0][0], events[-1][1]
  reduced = trace.reduce(ctx['profile'], spans, (lo, hi), own_spans=own)
  devices = reduced['devices']
  busy = sum(d['busy_ns'] for d in devices) / len(devices)
  dev = devices[0]
  # Device time inside the step's events: ops clipped to them.
  inside = []
  i = 0
  for _, a, b in sorted(dev['ops_in_window'], key=lambda o: o[1]):
    while i < len(events) and events[i][1] <= a:
      i += 1
    if i < len(events) and b > events[i][0]:
      inside.append((max(a, events[i][0]), min(b, events[i][1])))
  step_busy = trace.union_length(inside)
  big = [g for g in dev['idle_gaps_ns'] if g[1] - g[0] >= SMALL_GAP_NS]
  small = sum(b - a for a, b in dev['idle_gaps_ns']
              if b - a < SMALL_GAP_NS) / 1e9
  gaps = trace.attribute_gaps(big, reduced['host_spans'])
  if small:
    gaps.append(('gaps under 20 us between device ops', small))
  gaps.sort(key=lambda kv: -kv[1])
  ops = sorted(dev['by_op_ns'].items(), key=lambda kv: -kv[1])[:10]
  out = {
      'step_name': step_name,
      'dispatches': len(events),
      'window_s': (hi - lo) / 1e9,
      'busy_s': busy / 1e9,
      'step_busy_s': step_busy / 1e9,
      'breakdown': {
          'device_ops': [[_short(n), ns / 1e9] for n, ns in ops],
          'idle_gaps': [[n, s] for n, s in gaps[:10]],
      },
  }
  ctx['cache']['traced'] = out
  return out
