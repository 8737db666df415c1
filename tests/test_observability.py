"""Unified telemetry drills: registry, tracing, trainer breakdown.

Covers the observability subsystem end-to-end on the CPU backend:

  (a) registry semantics — typed create-or-get, thread-safe counting
      under contention, snapshot/delta windows, histogram stats;
  (b) span nesting + Chrome-trace JSON validity (and the
      tools/trace_summary.py roll-up over a dumped trace);
  (c) the trainer's per-dispatch step-time breakdown: components
      present, sane, and summing to the measured dispatch wall time,
      published through the stock MetricsLogger with no call-site
      changes;
  (d) resilience counters flowing registry → train scalars →
      metrics.jsonl, with per-source error-budget attribution.
"""

import json
import os
import threading
import time

import pytest

from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.observability import metrics, tracing
from tensor2robot_tpu.train import Trainer, TrainerConfig
from tensor2robot_tpu.train.callbacks import MetricsLoggerCallback
from tensor2robot_tpu.utils import faults
from tensor2robot_tpu.utils import retry as retry_lib
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel
from tensor2robot_tpu.models import optimizers as opt_lib


def fast_adam():
  return opt_lib.create_adam_optimizer(1e-2)


# --------------------------------------------------------------- registry


class TestRegistry:

  def test_counter_gauge_histogram_basics(self):
    reg = metrics.Registry()
    reg.counter('a/c').inc()
    reg.counter('a/c').inc(4)
    assert reg.counter('a/c').value == 5
    reg.gauge('a/g').set(2.5)
    reg.gauge('a/g').add(0.5)
    assert reg.gauge('a/g').value == 3.0
    h = reg.histogram('a/h')
    for v in (1.0, 2.0, 3.0, 4.0):
      h.observe(v)
    snap = h.snapshot()
    assert snap['count'] == 4 and snap['sum'] == 10.0
    assert snap['min'] == 1.0 and snap['max'] == 4.0
    assert snap['mean'] == pytest.approx(2.5)
    # Power-of-two buckets: estimates within 2x of the true quantile.
    assert 1.0 <= snap['p50'] <= 4.0
    assert snap['p99'] <= snap['max']

  def test_type_collision_raises(self):
    reg = metrics.Registry()
    reg.counter('x')
    with pytest.raises(TypeError):
      reg.gauge('x')

  def test_scope_prefixes_and_composes(self):
    reg = metrics.Registry()
    data = reg.scope('data')
    data.counter('records').inc(7)
    data.scope('native').gauge('depth').set(3)
    assert reg.counter('data/records').value == 7
    assert reg.gauge('data/native/depth').value == 3.0
    assert set(data.snapshot()) == {'data/records', 'data/native/depth'}

  def test_thread_safety_exact_counts(self):
    """16 threads x 2000 increments land exactly — the property the
    per-metric lock exists for (a torn += would lose counts)."""
    reg = metrics.Registry()
    c = reg.counter('hot')
    h = reg.histogram('hot_ms')
    threads, per_thread = 16, 2000

    def work():
      for _ in range(per_thread):
        c.inc()
        h.observe(1.0)

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
      t.start()
    for t in ts:
      t.join()
    assert c.value == threads * per_thread
    assert h.snapshot()['count'] == threads * per_thread

  def test_snapshot_is_stable_and_delta_windows(self):
    reg = metrics.Registry()
    reg.counter('c').inc(10)
    reg.histogram('h').observe(5.0)
    reg.gauge('g').set(1.0)
    snap = reg.snapshot()
    reg.counter('c').inc(3)
    reg.histogram('h').observe(7.0)
    reg.gauge('g').set(9.0)
    reg.counter('born_later').inc(2)
    assert snap['c'] == 10  # snapshot unaffected by later updates
    d = reg.delta(snap)
    assert d['c'] == 3
    assert d['born_later'] == 2  # new metric diffs against zero
    assert d['g'] == 9.0  # gauges report current value
    assert d['h'] == {'count': 1, 'sum': 7.0, 'mean': 7.0}

  def test_report_and_dump(self, tmp_path):
    reg = metrics.Registry()
    reg.counter('n').inc()
    report = reg.report()
    assert report['kind'] == 'metrics_report'
    assert report['metrics']['n'] == 1
    path = reg.dump_report(str(tmp_path / 'sub' / 'report.json'))
    with open(path) as f:
      assert json.load(f)['metrics']['n'] == 1

  def test_global_registry_module_api(self):
    before = metrics.counter('test_observability/global').value
    metrics.counter('test_observability/global').inc()
    assert metrics.counter('test_observability/global').value == before + 1
    assert 'test_observability/global' in metrics.snapshot(
        'test_observability/')


# ---------------------------------------------------------------- tracing


class TestTracing:

  def test_span_accumulates_into_registry(self):
    h = metrics.histogram('test_span/region_ms')
    before = h.snapshot()['count']
    with tracing.span('test_span/region'):
      pass
    snap = h.snapshot()
    assert snap['count'] == before + 1
    assert snap['max'] >= 0.0

  def test_nested_spans_chrome_trace_valid(self, tmp_path):
    with tracing.capture() as events:
      with tracing.span('outer'):
        with tracing.span('inner'):
          pass
        with tracing.span('inner'):
          pass
    assert not tracing.capturing()
    # Two inners close before the outer; ts/dur nest within the parent.
    names = [e['name'] for e in events]
    assert names == ['inner', 'inner', 'outer']
    outer = events[2]
    for inner in events[:2]:
      assert inner['ts'] >= outer['ts']
      assert inner['ts'] + inner['dur'] <= outer['ts'] + outer['dur'] + 1e-3
    for e in events:
      assert e['ph'] == 'X' and e['dur'] >= 0
      assert {'name', 'ph', 'ts', 'dur', 'pid', 'tid'} <= set(e)
    # The dump round-trips as valid Chrome-trace JSON (gz too).
    for name in ('trace.json', 'trace.json.gz'):
      path = tracing.dump_chrome_trace(str(tmp_path / name), events)
      if name.endswith('.gz'):
        import gzip

        with gzip.open(path, 'rt') as f:
          trace = json.load(f)
      else:
        with open(path) as f:
          trace = json.load(f)
      assert len(trace['traceEvents']) == 3
      assert trace['metadata']['dropped_events'] == 0

  def test_capture_bounded(self):
    with tracing.capture(max_events=2) as events:
      for _ in range(5):
        with tracing.span('spam'):
          pass
    assert len(events) == 2  # overflow dropped, not unbounded

  def test_capture_is_a_view_of_the_ring(self, tmp_path):
    """The first ``max_events`` spans since the mark, as Chrome-trace
    ``X`` events in microseconds; the rest are counted as dropped, in
    the dump's metadata too; a span from before the mark is not seen."""
    with tracing.span('before/the_mark'):
      pass
    tracing.start_capture(max_events=3)
    t0 = time.perf_counter_ns()
    tracing.record('view/recorded', t0, t0 + 2_500_000, key=7)
    for i in range(4):
      with tracing.span('view/span', key=i):
        pass
    assert tracing.capturing()
    live = tracing.chrome_trace()
    events = tracing.stop_capture()
    assert not tracing.capturing()
    assert [e['name'] for e in events] == ['view/recorded'] + ['view/span'] * 2
    assert events == live['traceEvents']
    assert live['metadata']['dropped_events'] == 2
    first = events[0]
    assert first['ph'] == 'X' and first['pid'] == os.getpid()
    assert first['ts'] == pytest.approx(t0 / 1e3)
    assert first['dur'] == pytest.approx(2500.0)
    assert isinstance(first['tid'], int)
    assert first['args'] == {
        'thread': threading.current_thread().name, 'key': 7}
    assert [e['args']['key'] for e in events[1:]] == [0, 1]
    # The dropped count outlives the capture, for a dump made after it.
    path = tracing.dump_chrome_trace(str(tmp_path / 'view.json'), events)
    with open(path) as f:
      dumped = json.load(f)
    assert len(dumped['traceEvents']) == 3
    assert dumped['metadata']['dropped_events'] == 2
    assert tracing.stop_capture() == []  # no capture: nothing, no error

  def test_ring_is_bounded_and_counts_overwrites(self):
    """The ring keeps the last RING_CAPACITY spans and counts all it
    ever took, so a reader can tell that a stretch was overwritten."""
    capacity = tracing.RING_CAPACITY
    before = tracing.taken()
    mark = time.perf_counter_ns()
    for i in range(capacity + 10):
      tracing.record('ring/fill', mark + i, mark + i + 1, key=i)
    assert tracing.taken() == before + capacity + 10
    kept = tracing.recent()
    assert len(kept) == capacity
    # The oldest ten of this stretch are gone; what stays is in order.
    assert [s[4] for s in kept] == list(range(10, capacity + 10))
    name, start_ns, end_ns, thread, key = kept[-1]
    assert (name, end_ns - start_ns, key) == ('ring/fill', 1, capacity + 9)
    assert thread == threading.current_thread().name
    assert tracing.taken() - len(kept) >= 10  # the reader's wrap signal
    # A capture that outlives the ring's reach reports what it lost.
    with tracing.capture(max_events=capacity * 2) as events:
      for i in range(capacity + 5):
        tracing.record('ring/fill2', mark, mark + 1)
    assert len(events) == capacity
    assert tracing.chrome_trace()['metadata']['dropped_events'] == 5

  def test_recent_since_and_clock_anchor(self):
    t0 = time.perf_counter_ns()
    tracing.record('since/old', t0 - 2_000, t0 - 1_000)
    tracing.record('since/new', t0, t0 + 1_000, key='k')
    names = [s[0] for s in tracing.recent(since_ns=t0)]
    assert 'since/new' in names and 'since/old' not in names
    assert tracing.recent()[-1] == (
        'since/new', t0, t0 + 1_000, threading.current_thread().name, 'k')
    # The anchor places a perf_counter time on the wall clock.
    wall_ns, perf_ns = tracing.clock_anchor()
    assert abs(wall_ns - time.time_ns()) < 1e9
    assert 0 <= time.perf_counter_ns() - perf_ns < 1e9
    wall2, perf2 = tracing.clock_anchor()
    assert abs((wall2 - wall_ns) - (perf2 - perf_ns)) < 5e6  # one clock rate

  def test_concurrent_appends_lose_nothing(self):
    """8 threads, more than the cores, a shortened switch interval: every
    span is counted and, the ring being large enough, kept."""
    import sys

    per_thread, n_threads = 2000, 8
    before = tracing.taken()
    start = threading.Barrier(n_threads)

    def work(t):
      start.wait(timeout=30)
      for i in range(per_thread):
        with tracing.span('stress/span', key=(t, i), annotate=False):
          pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
      threads = [threading.Thread(target=work, args=(t,), name=f'stress-{t}')
                 for t in range(n_threads)]
      for thread in threads:
        thread.start()
      for thread in threads:
        thread.join(timeout=120)
        assert not thread.is_alive()
    finally:
      sys.setswitchinterval(interval)
    assert tracing.taken() == before + per_thread * n_threads
    mine = [s for s in tracing.recent() if s[0] == 'stress/span']
    assert len(mine) == per_thread * n_threads
    assert {s[4] for s in mine} == {
        (t, i) for t in range(n_threads) for i in range(per_thread)}
    for t in range(n_threads):  # each thread's spans under its own name
      keys = [s[4][1] for s in mine if s[3] == f'stress-{t}']
      assert keys == list(range(per_thread))

  def test_trace_summary_tool(self, tmp_path):
    from tools import trace_summary

    with tracing.capture() as events:
      with tracing.span('data/parse'):
        with tracing.span('data/decode'):
          pass
      with tracing.span('trainer/dispatch'):
        pass
    path = tracing.dump_chrome_trace(str(tmp_path / 'trace.json'), events)
    rows = trace_summary.summarize(trace_summary.load_events(path))
    by_name = {r['name']: r for r in rows}
    assert by_name['data/parse']['count'] == 1
    # Self time excludes the nested child span.
    assert (by_name['data/parse']['self_ms']
            <= by_name['data/parse']['total_ms'])
    scoped = trace_summary.summarize(
        trace_summary.load_events(path), by_scope=True)
    assert {r['name'] for r in scoped} == {'data', 'trainer'}
    assert next(r for r in scoped if r['name'] == 'data')['count'] == 2

  def test_step_annotation_contextmanager(self):
    with tracing.step_annotation(7):  # no active profiler: must not blow up
      pass


# ------------------------------------------------- trainer breakdown e2e


BREAKDOWN_KEYS = (
    'breakdown/wall_ms', 'breakdown/host_wait_ms', 'breakdown/placement_ms',
    'breakdown/dispatch_ms', 'breakdown/device_step_ms',
    'breakdown/callback_ms')


def train_records(tmp_path, max_train_steps=12, train_iter=None,
                  **config_kwargs):
  """Runs the mock model with the stock MetricsLogger; returns records."""
  model = MockT2RModel(device_type='cpu', create_optimizer_fn=fast_adam)
  config = TrainerConfig(
      model_dir=str(tmp_path / 'm'), max_train_steps=max_train_steps,
      save_interval_steps=0, eval_interval_steps=0, log_interval_steps=4,
      async_checkpoints=False, **config_kwargs)
  trainer = Trainer(model, config, callbacks=[MetricsLoggerCallback()])
  gen = MockInputGenerator(batch_size=8)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  it = train_iter if train_iter is not None else gen.create_iterator(
      ModeKeys.TRAIN)
  trainer.train(it, None)
  with open(tmp_path / 'm' / 'metrics.jsonl') as f:
    return [json.loads(line) for line in f]


def test_breakdown_scalars_published_and_sum_to_wall(tmp_path):
  """The acceptance criterion: breakdown components present in
  metrics.jsonl with NO call-site changes to the logger, each sane, and
  summing to within 10% of the measured dispatch wall time."""
  records = [r for r in train_records(tmp_path) if r['kind'] == 'train']
  assert records, 'no train records logged'
  for rec in records:
    for key in BREAKDOWN_KEYS + ('examples_per_sec', 'input_bound_fraction',
                                 'goodput_examples_per_sec'):
      assert key in rec, f'{key} missing from {sorted(rec)}'
    assert rec['examples_per_sec'] > 0
    assert 0.0 <= rec['input_bound_fraction'] <= 1.0
    assert rec['goodput_examples_per_sec'] <= rec['examples_per_sec'] + 1e-6
    components = sum(rec[k] for k in BREAKDOWN_KEYS
                     if k != 'breakdown/wall_ms')
    assert all(rec[k] >= 0.0 for k in BREAKDOWN_KEYS), rec
    assert components == pytest.approx(rec['breakdown/wall_ms'], rel=0.10), (
        f'components {components} vs wall {rec["breakdown/wall_ms"]}')


def test_breakdown_registry_counters_and_gauges(tmp_path):
  start = metrics.snapshot('trainer/')
  train_records(tmp_path, max_train_steps=6)
  d = metrics.delta(start, 'trainer/')
  assert d['trainer/dispatches'] == 6
  assert d['trainer/steps'] == 6
  assert d['trainer/examples'] == 48  # batch 8 x 6 steps
  # Wall histogram excludes the compile-heavy first dispatch.
  assert d['trainer/step_wall_ms']['count'] == 5
  assert metrics.gauge('trainer/examples_per_sec').value > 0


def test_breakdown_disabled_restores_plain_loop(tmp_path):
  start = metrics.snapshot('trainer/')
  records = [r for r in train_records(tmp_path, step_breakdown=False)
             if r['kind'] == 'train']
  assert records
  for rec in records:
    assert 'breakdown/wall_ms' not in rec
    assert 'examples_per_sec' not in rec
  # Counters still tick (they are not the breakdown's sync probe)...
  assert metrics.delta(start, 'trainer/')['trainer/dispatches'] == 12
  # ...but no wall windows were accumulated.
  assert metrics.delta(start, 'trainer/')['trainer/step_wall_ms'][
      'count'] == 0


def test_breakdown_with_steps_per_dispatch(tmp_path):
  records = [r for r in train_records(
      tmp_path, max_train_steps=12, steps_per_dispatch=3,
      prefetch_batches=0)
      if r['kind'] == 'train']
  assert records
  rec = records[-1]
  assert rec['examples_per_sec'] > 0
  components = sum(rec[k] for k in BREAKDOWN_KEYS
                   if k != 'breakdown/wall_ms')
  assert components == pytest.approx(rec['breakdown/wall_ms'], rel=0.10)


def test_prefetch_queue_metrics(tmp_path):
  start = metrics.snapshot('trainer/prefetch/')
  train_records(tmp_path, max_train_steps=8, prefetch_batches=2)
  d = metrics.delta(start, 'trainer/prefetch/')
  assert d['trainer/prefetch/batches'] == 8


# -------------------------------------------- resilience counters e2e


def test_nonfinite_counters_flow_to_train_scalars(tmp_path):
  """A NaN batch under skip_update surfaces in metrics.jsonl as
  resilience/* scalars — the registry is the only plumbing."""
  gen = MockInputGenerator(batch_size=8)
  model = MockT2RModel(device_type='cpu', create_optimizer_fn=fast_adam)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  poisoned = faults.NaNInjector(gen.create_iterator(ModeKeys.TRAIN),
                                nan_at={1, 2})
  records = [r for r in train_records(
      tmp_path, max_train_steps=8, train_iter=poisoned,
      nonfinite_mode='skip_update') if r['kind'] == 'train']
  assert records
  # The guard is on: the scalar series exists in EVERY train record.
  for rec in records:
    assert 'resilience/nonfinite_skipped_steps' in rec
    assert 'resilience/consecutive_bad_dispatches' in rec
  assert records[-1]['resilience/nonfinite_skipped_steps'] == 2.0
  # Goodput discounts the two skipped updates within their window.
  first = records[0]
  assert (first['goodput_examples_per_sec'] < first['examples_per_sec'] or
          first['resilience/nonfinite_skipped_steps'] == 0)


def test_clean_run_has_zero_resilience_scalars(tmp_path):
  records = [r for r in train_records(
      tmp_path, max_train_steps=4, nonfinite_mode='skip_update')
      if r['kind'] == 'train']
  assert records[-1]['resilience/nonfinite_skipped_steps'] == 0.0


def test_error_budget_per_source_attribution():
  budget = retry_lib.ErrorBudget(max_errors=4, name='t_obs stream')
  start = metrics.snapshot('resilience/')
  budget.record(IOError('read failed: /data/shard-00001.tfrecord: crc'))
  budget.record(IOError('read failed: /data/shard-00001.tfrecord: crc'))
  budget.record(IOError('boom, no path'), source='/data/shard-7.tfrecord')
  assert budget.by_source == {
      '/data/shard-00001.tfrecord': 2,
      '/data/shard-7.tfrecord': 1,
  }
  d = metrics.delta(start, 'resilience/')
  assert d['resilience/data_errors'] == 3
  assert d['resilience/data_errors/t_obs stream'
           '//data/shard-00001.tfrecord'] == 2
  # Over budget: the raise carries the per-source accounting.
  budget.record(IOError('x'), source='/data/shard-7.tfrecord')
  with pytest.raises(retry_lib.DataErrorBudgetExceededError) as err:
    budget.record(IOError('x'), source='/data/shard-7.tfrecord')
  assert '/data/shard-00001.tfrecord: 2' in str(err.value)


def test_error_budget_constructor_source_label():
  budget = retry_lib.ErrorBudget(max_errors=2, name='b', source='stream-3')
  budget.record(ValueError('parse error, nothing path-like'))
  assert budget.by_source == {'stream-3': 1}


@pytest.mark.faults
def test_native_reader_budget_attributes_corrupt_file(tmp_path):
  """A corrupt record charges the budget against the FILE that carried
  it, end-to-end through the native reader."""
  native_io = pytest.importorskip('tensor2robot_tpu.data.native_io')
  if not native_io.available():
    pytest.skip('native record_io unavailable')
  path = str(tmp_path / 'shard.tfrecord')
  with native_io.NativeRecordWriter(path) as w:
    for i in range(8):
      w.write(b'payload-%d' % i)
  faults.corrupt_record_file(path, record_index=3)
  budget = retry_lib.ErrorBudget(max_errors=2, name='native test')
  with native_io.NativeRecordReader(path, error_budget=budget) as reader:
    records = list(reader)
  assert len(records) == 3  # truncated at the corruption
  assert budget.by_source == {path: 1}


def test_resilience_logger_reads_registry(tmp_path, caplog):
  import logging as logging_mod

  from tensor2robot_tpu.train.callbacks import ResilienceLoggerCallback

  model = MockT2RModel(device_type='cpu', create_optimizer_fn=fast_adam)
  gen = MockInputGenerator(batch_size=8)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  poisoned = faults.NaNInjector(gen.create_iterator(ModeKeys.TRAIN),
                                nan_at={1})
  trainer = Trainer(
      model,
      TrainerConfig(model_dir='', max_train_steps=4, eval_interval_steps=0,
                    log_interval_steps=1, nonfinite_mode='skip_update'),
      callbacks=[ResilienceLoggerCallback(log_interval_steps=1)])
  with caplog.at_level(logging_mod.INFO):
    trainer.train(poisoned, None)
  assert any('non-finite update(s) skipped' in r.message
             for r in caplog.records)
