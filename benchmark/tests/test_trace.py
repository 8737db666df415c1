"""The trace -> metrics reduction on a small recorded trace.

``data/small_trace.xplane.pb`` was recorded on a TPU v5e by
``record_small_trace.py``: three calls of one small jitted program
(``jit_small_step``), each inside the benchmark's callback span, with a
10 ms sleep inside the feed span before each, so the device idles
between the calls. What the recorder printed on the chip is asserted
here; the interval arithmetic is checked on hand-made intervals too.
"""

import os

import pytest

from benchmark.lib import trace, window

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'data',
                     'small_trace.xplane.pb')


def test_union_and_gaps_by_hand():
  intervals = [(0, 10), (5, 12), (20, 30), (22, 25)]
  assert trace.union_length(intervals) == 22
  assert trace.gaps(intervals, 0, 40) == [(12, 20), (30, 40)]
  assert trace.gaps(intervals, 6, 28) == [(12, 20)]
  assert trace.gaps([], 3, 5) == [(3, 5)]


def test_gap_attribution_by_hand():
  spans = {'feed': [(100, 200)], 'callback': [(300, 330)]}
  gaps = [(110, 190), (290, 340), (400, 500)]
  out = dict(trace.attribute_gaps(gaps, spans, other='other'))
  assert out == {'feed': pytest.approx(80e-9),
                 'callback': pytest.approx(50e-9),
                 'other': pytest.approx(100e-9)}


@pytest.fixture(scope='module')
def reduced():
  profile = trace.load(SMALL)
  return trace.reduce(profile, [window.FEED_SPAN, window.CALLBACK_SPAN])


def test_small_trace_programs_and_spans(reduced):
  (device,) = reduced['devices']
  assert device['name'] == '/device:TPU:0'
  steps = {n: m for n, m in device['by_module'].items()
           if n.startswith('jit_small_step')}
  assert len(steps) == 1
  (step,) = steps.values()
  assert step['count'] == 3 and step['whole'] == 3
  assert len(reduced['host_spans'][window.FEED_SPAN]) == 3
  assert len(reduced['host_spans'][window.CALLBACK_SPAN]) == 3


def test_small_trace_busy_idle_and_attribution(reduced):
  (device,) = reduced['devices']
  lo, hi = reduced['window_ns']
  busy = device['busy_ns']
  # Busy is the ops' union: no more than the sum, no more than the window,
  # and all of it inside the three program events.
  assert 0 < busy <= sum(device['by_op_ns'].values()) + 1e-6
  assert busy < hi - lo
  assert busy <= step_ns(device) * 1.001
  # The device idles through each 10 ms sleep: two gaps between three
  # calls, each over 9 ms, and the feed span covers most of each.
  long_gaps = [g for g in device['idle_gaps_ns'] if g[1] - g[0] > 9e6]
  assert len(long_gaps) == 2
  by_span = dict(trace.attribute_gaps(long_gaps, reduced['host_spans']))
  assert by_span.get(window.FEED_SPAN, 0) > 0.018


def test_own_spans_are_placed_on_the_trace_clock():
  profile = trace.load(SMALL)
  start = trace.profile_start_ns(profile)
  assert start > 1.7e18  # nanoseconds since the epoch, 2026
  traced = trace.reduce(profile, [window.FEED_SPAN])['host_spans']
  # A name the trace holds no span of takes the benchmark's own, moved
  # from the epoch to the trace's clock.
  own = [('mine', start + 5_000_000, start + 6_000_000)]
  placed = trace.reduce(profile, ['mine'], own_spans=own)['host_spans']
  assert placed['mine'] == [(5e6, 6e6)]
  # A name the trace holds itself keeps the trace's spans.
  own = [(window.FEED_SPAN, start, start + 1)]
  kept = trace.reduce(profile, [window.FEED_SPAN], own_spans=own)
  assert kept['host_spans'] == traced


def step_ns(device):
  return sum(m['ns'] for n, m in device['by_module'].items()
             if n.startswith('jit_small_step'))
