"""The join of the program's spans to the device's step events, on a
hand-built device plane and hand-built spans with planted gaps.

Three step events (dispatches 5, 6, 7), in ms on the trace's clock:

    E0 100-200   ops 100-150, 152-200            2 ms idle inside
    gap 200-230  another program's op 210-214    26 ms idle
    E1 230-330   one op                          0 ms idle inside
    gap 330-345  nothing                         15 ms idle
    E2 345-445   ops 345-400, 405-445            5 ms idle inside

Dispatch 6 is enqueued at 220, 20 ms into the first gap (host late: 16 ms
of idle, the other program ran for 4), and its batch is on the device at
225 (input late: 5 ms). Dispatch 7 is enqueued in time (225) and its
batch arrives at 340, 10 ms into the second gap (input late).
"""

import json
import os
import time
import types

import pytest

from benchmark import run
from benchmark.metrics import _program_spans as ps

MS = 1e6
STEP, OTHER = 'jit_train_step(1)', 'jit__identity_fn(2)'
EVENTS = [(100, 200), (230, 330), (345, 445)]
OPS = [(100, 150), (152, 200), (210, 214), (230, 330), (345, 400),
       (405, 445)]
OTHERS = [(OTHER, 210, 214)]
LOOP, PLACE, FETCH = 'MainThread', 't2r-prefetch-place', 't2r-prefetch-fetch'
SPANS = [
    # name, start, end, thread, key
    ('trainer/wait_batch', 40, 50, LOOP, 5),
    ('trainer/dispatch', 50, 60, LOOP, 5),
    ('trainer/device_wait', 60, 100.3, LOOP, 4),
    ('trainer/after_dispatch', 100.3, 210, LOOP, 5),
    ('trainer/callbacks', 101, 209, LOOP, 5),
    ('trainer/wait_batch', 210, 212, LOOP, 6),
    ('trainer/dispatch', 212, 220, LOOP, 6),
    ('trainer/device_wait', 220, 220.05, LOOP, 5),   # found it ready
    ('trainer/after_dispatch', 220.05, 222, LOOP, 6),
    ('trainer/wait_batch', 222, 223, LOOP, 7),
    ('trainer/dispatch', 223, 225, LOOP, 7),
    ('trainer/device_wait', 225, 330.2, LOOP, 6),    # blocked: E1 ends 330
    ('trainer/after_dispatch', 330.2, 332, LOOP, 7),
    ('trainer/place/put', 140, 150, PLACE, 6),
    ('trainer/place/transfer', 150, 225, PLACE, 6),
    ('trainer/place_stage', 140, 226, PLACE, 6),
    ('trainer/place/put', 290, 300, PLACE, 7),
    ('trainer/place/transfer', 300, 340, PLACE, 7),
    ('trainer/place_stage', 290, 341, PLACE, 7),
    ('trainer/fetch', 130, 131, FETCH, 8),
    ('data/engine/parse_decode', 50, 110, 'w1', 0),    # 10 ms in the window
    ('data/engine/parse_decode', 120, 180, 'w1', 1),
    ('data/engine/parse_decode', 200, 290, 'w2', 2),
    ('data/engine/parse_decode', 300, 360, 'w1', 3),
    ('data/engine/parse_decode', 400, 500, 'w2', 4),   # unfinished: 45 ms
]


def ns(intervals):
  return [tuple(x * MS if isinstance(x, (int, float)) else x for x in item)
          for item in intervals]


def spans_ns(spans=SPANS):
  return [(n, a * MS, b * MS, t, k) for n, a, b, t, k in spans]


def others_ns():
  return [(n, a * MS, b * MS) for n, a, b in OTHERS]


def check_planted(out):
  assert out['events'] == 3 and out['steps'] == 3 and out['keys'] == (5, 7)
  assert out['idle.in_step_ms'] == pytest.approx(7 / 3, abs=1e-2)
  assert out['idle.between_steps_ms'] == pytest.approx(41 / 3, abs=1e-2)
  assert out['idle.host_late_ms'] == pytest.approx(16 / 3, abs=1e-2)
  assert out['idle.input_late_ms'] == pytest.approx(15 / 3, abs=1e-2)
  assert out['dispatch.enqueue_ms'] == pytest.approx(8, abs=1e-2)
  assert out['place.batch_ms'] == pytest.approx(68.5, abs=1e-2)
  assert out['feed.busy_ms'] == pytest.approx(265 / 4, abs=1e-2)
  # The one wait that blocked ended 0.2 ms after its step event did.
  assert out['agreement']['pairs'] == 1
  assert out['agreement']['median_ms'] == pytest.approx(0.2, abs=1e-2)
  # Cause and effect: dispatch 6 began 18 ms before E1 started; the two
  # transfers ended 11 and 10 ms after the device program before them.
  assert out['started_after_enqueue_ms'] == pytest.approx(18, abs=1e-2)
  assert out['woke_after_program_ms'] == pytest.approx(10.5, abs=1e-2)


def test_join_gives_the_planted_numbers(capfd):
  out = ps.join(ns(EVENTS), ns(OPS), spans_ns(), others=others_ns())
  check_planted(out)
  # The two idle figures are all of the window's idle time.
  busy = sum(b - a for a, b in OPS)
  assert (out['idle.in_step_ms'] + out['idle.between_steps_ms']) * 3 == (
      pytest.approx(345 - busy))
  log = capfd.readouterr().err
  # Host late, by the loop-thread span over it: the tail of dispatch 5
  # (10 ms, 9 of them in its callbacks) and the enqueue of 6 (6 ms; the
  # other program ran through its wait for the batch).
  assert ('host late, by the loop-thread span over it: '
          'trainer/after_dispatch 3.333 ms, trainer/dispatch 2.000 ms'
          ) in log
  assert 'inside trainer/callbacks' in log and ': 3.000 ms' in log
  assert 'host late 5.333 ms + input late 5.000 ms' in log
  assert f'other program {OTHER}: 1 events, 1.333 ms a step' in log
  assert '2 worker(s), 4 batches finished in the window' in log
  assert 'trainer/place/transfer' in log and 't2r-prefetch-place' in log


def test_groups_of_k_steps_divide_by_the_steps():
  out = ps.join(ns(EVENTS), ns(OPS), spans_ns(), steps_per_dispatch=2)
  assert out['steps'] == 6
  assert out['idle.between_steps_ms'] == pytest.approx(41 / 6)
  assert out['dispatch.enqueue_ms'] == pytest.approx(8)  # a dispatch's


def test_a_dispatch_enqueued_before_the_trace_started_still_joins():
  early = [(n, a - 55, b - 55, t, k) if k == 5 and n == 'trainer/dispatch'
           else (n, a, b, t, k) for n, a, b, t, k in SPANS]
  out = ps.join(ns(EVENTS), ns(OPS), spans_ns(early))
  assert out is not None and out['keys'] == (5, 7)


@pytest.mark.parametrize('fault,said', [
    ('wrapped', 'wrapped over the window'),
    ('miscount', 'miscount: 3 step events in the trace against 5'),
    ('missing', 'no trainer/dispatch span of key 4'),
    ('clocks', 'the clocks disagree: trainer/device_wait'),
    ('effect_before_cause', 'before the trainer/dispatch that enqueued it'),
    ('no_ring', 'holds no span'),
])
def test_what_cannot_be_trusted_gives_none(capfd, fault, said):
  events, spans, overwritten = list(EVENTS), list(SPANS), 0
  if fault == 'wrapped':
    # The ring lost spans and what it kept starts inside the window.
    spans = [s for s in spans if s[2] > 150]
    overwritten = 10
  elif fault == 'miscount':
    # Two dispatches enqueued in the trace whose events it does not hold.
    spans += [('trainer/dispatch', 10, 12, LOOP, 3),
              ('trainer/dispatch', 20, 22, LOOP, 4)]
  elif fault == 'missing':
    events.append((460, 500))  # a fourth event, and no fourth dispatch
  elif fault == 'clocks':
    spans = [(n, a + 40, b + 40, t, k) for n, a, b, t, k in spans]
  elif fault == 'effect_before_cause':
    # No wait blocked, and dispatch 7 begins 15 ms after its step event.
    spans = [s for s in spans if s[0] != 'trainer/device_wait'
             and s[:2] != ('trainer/dispatch', 223)]
    spans.append(('trainer/dispatch', 360, 365, LOOP, 7))
  elif fault == 'no_ring':
    spans = []
  assert ps.join(ns(events), ns(OPS), spans_ns(spans),
                 overwritten=overwritten) is None
  assert said in capfd.readouterr().err


def test_a_later_loop_in_the_ring_is_left_out():
  """Spans recorded after the trace stopped (keys start again at 0 with
  every loop) neither count as enqueued in it nor set the last key."""
  later = [('trainer/dispatch', 700 + 10 * k, 705 + 10 * k, LOOP, k)
           for k in range(12)]
  out = ps.join(ns(EVENTS), ns(OPS), spans_ns(SPANS + later),
                others=others_ns(), trace_stop=600 * MS)
  check_planted(out)


def test_a_ring_that_wrapped_before_the_window_still_joins():
  assert ps.join(ns(EVENTS), ns(OPS), spans_ns(), overwritten=10) is not None


# ------------------------------------------- through the readers' own path


def fake_profile(start_wall_ns):
  def line(name, events):
    return types.SimpleNamespace(name=name, events=[
        types.SimpleNamespace(name=n, start_ns=a * MS,
                              duration_ns=(b - a) * MS)
        for n, a, b in events])

  def plane(name, lines=(), stats=()):
    return types.SimpleNamespace(name=name, lines=list(lines),
                                 stats=list(stats))

  return types.SimpleNamespace(planes=[
      plane('/device:TPU:0', [
          line('XLA Modules', [(STEP, a, b) for a, b in EVENTS] + OTHERS),
          line('XLA Ops', [(f'%op.{i}', a, b)
                           for i, (a, b) in enumerate(OPS)])]),
      plane('#Chip0 Host Interface'),
      plane('#Chip0 Misc', [line('Transfers', [('h2d', 1, 2)])]),
      plane('Task Environment', stats=[
          ('profile_start_time', start_wall_ns),
          ('profile_stop_time', start_wall_ns + 600 * MS)]),
  ])


@pytest.fixture
def ctx():
  """The planted spans in the program's own ring, an hour ago on its
  clock (before anything else this process has recorded), and a trace
  whose clock started then and ran for 0.6 s."""
  from tensor2robot_tpu.observability import tracing

  wall_ns, perf_ns = tracing.clock_anchor()
  ago = 3_600_000 * int(MS)
  for name, a, b, _, key in SPANS:
    tracing.record(name, perf_ns - ago + int(a * MS),
                   perf_ns - ago + int(b * MS), key)
  return {'profile': fake_profile(wall_ns - ago), 'cache': {},
          'steps_per_dispatch': 1}


NEW = ['idle.in_step_ms', 'idle.between_steps_ms', 'idle.host_late_ms',
       'idle.input_late_ms', 'dispatch.enqueue_ms', 'place.batch_ms',
       'feed.busy_ms']


def test_readers_place_the_ring_on_the_trace_clock(ctx, capfd):
  with open(os.path.join(run.ROOT, 'BENCHMARK.json')) as f:
    metrics = json.load(f)['per_layer'][-len(NEW):]
  assert [m['name'] for m in metrics] == NEW
  for m in metrics:
    assert m['moves'] == 'train_examples_per_s' and 'workloads' not in m
  values = {name: run.load_reader(name)(ctx) for name in NEW}
  out = ctx['cache']['program_spans']
  assert values == {name: out[name] for name in NEW}
  check_planted(out)
  log = capfd.readouterr().err
  assert log.count('joined 3 step events') == 1  # worked out once a run
  assert 'plane #Chip0 Host Interface: no lines' in log
  assert 'plane #Chip0 Misc: Transfers (1 events)' in log


def test_a_program_without_the_ring_reads_nothing(ctx, monkeypatch, capfd):
  """The parent of the PR that brought the ring: no reader raises."""
  from tensor2robot_tpu.observability import tracing

  monkeypatch.delattr(tracing, 'recent')
  for name in NEW:
    assert run.load_reader(name)(ctx) is None
  assert 'keeps no span ring' in capfd.readouterr().err


def test_a_wrapped_ring_reads_nothing(ctx, capfd):
  from tensor2robot_tpu.observability import tracing

  t = time.perf_counter_ns()
  for _ in range(tracing.RING_CAPACITY):
    tracing.record('fill', t, t + 1)
  assert run.load_reader('idle.host_late_ms')(ctx) is None
  assert 'the ring' in capfd.readouterr().err
