"""Parallel host input engine (data/engine.py) and PR-3 satellites.

The engine's load-bearing guarantee — a multi-worker pipeline whose
output stream is BYTE-IDENTICAL to the serial path for any worker count,
including error positions and mid-epoch resume — plus the autotuner's
collapse-to-serial on single-core hosts, the /metricsz endpoint, the
tf-codec per-file budget attribution, and the preemption-aware
continuous evaluator.

All tests carry the ``engine`` marker: ``tools/run_tier1.sh -m engine``
runs them in isolation with the tier-1 harness.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tensor2robot_tpu.data import engine as engine_lib
from tensor2robot_tpu.data import native_io
from tensor2robot_tpu.observability import metrics as metrics_lib

pytestmark = pytest.mark.engine

requires_native = pytest.mark.skipif(
    not native_io.available(), reason='native record_io unavailable')


# --------------------------------------------------- synthetic pipelines


def _records(n):
  return [b'rec%04d' % i for i in range(n)]


def _parse(records):
  return np.array([int(r[3:]) for r in records], np.int64)


def _collect(workers, n=57, batch=5, parse=_parse, records=None):
  eng = engine_lib.ParallelBatchEngine(
      iter(_records(n) if records is None else records), parse, batch,
      num_workers=workers)
  try:
    return list(eng)
  finally:
    eng.close()


class TestEngineStreamEquality:

  def test_byte_identical_for_any_worker_count(self):
    serial = _collect(0)
    assert len(serial) == 11  # 57 // 5
    for workers in (1, 2, 4):
      parallel = _collect(workers)
      assert len(parallel) == len(serial)
      for a, b in zip(serial, parallel):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)

  def test_order_survives_jittered_completion(self):
    """Workers finishing out of order must not reorder delivery."""

    def jittery(records):
      value = int(records[0][3:])
      time.sleep(((value // 5) % 3) * 0.004)  # later tickets finish first
      return _parse(records)

    serial = _collect(0)
    parallel = _collect(3, parse=jittery)
    for a, b in zip(serial, parallel):
      np.testing.assert_array_equal(a, b)

  def test_drop_remainder_parity(self):
    for workers in (0, 2):
      out = _collect(workers, n=23, batch=5)
      assert len(out) == 4  # final 3-record tail dropped, both paths

  def test_delivered_counts_stream_position(self):
    eng = engine_lib.ParallelBatchEngine(
        iter(_records(30)), _parse, 5, num_workers=2)
    with eng:
      next(eng)
      next(eng)
      assert eng.delivered == 2


class TestEngineErrors:

  def test_parse_error_surfaces_at_serial_position(self):
    def bad(records):
      if int(records[0][3:]) >= 15:
        raise ValueError('rotten batch')
      return _parse(records)

    for workers in (0, 3):
      eng = engine_lib.ParallelBatchEngine(
          iter(_records(57)), bad, 5, num_workers=workers)
      got = []
      with pytest.raises(ValueError, match='rotten batch'):
        for batch in eng:
          got.append(batch)
      eng.close()
      assert len(got) == 3  # batches 0..2 delivered, error at batch 3

  def test_record_stream_error_surfaces_in_order(self):
    def broken_stream():
      for i, record in enumerate(_records(40)):
        if i == 12:
          raise IOError('disk on fire')
        yield record

    for workers in (0, 2):
      eng = engine_lib.ParallelBatchEngine(
          broken_stream(), _parse, 5, num_workers=workers)
      got = []
      with pytest.raises(IOError, match='disk on fire'):
        for batch in eng:
          got.append(batch)
      eng.close()
      assert len(got) == 2  # 12 records = 2 full batches before the error

  def test_close_terminates_threads(self):
    eng = engine_lib.ParallelBatchEngine(
        iter(_records(1000)), _parse, 5, num_workers=3)
    next(eng)
    eng.close()
    for thread in eng._threads:  # pylint: disable=protected-access
      thread.join(timeout=5)
      assert not thread.is_alive()
    assert threading.active_count() < 50


# ------------------------------------------------------- ring buffers


def _ring_parse(allocs):
  """A parse_fn implementing the engine's batch-buffer protocol."""

  def parse(records, image_out=None):
    n = len(records)
    buf = (np.empty((n, 2), np.int64) if image_out is None
           else image_out['img'])
    for i, record in enumerate(records):
      value = int(record[3:])
      buf[i] = (value, value * 2)
    return buf

  def make_image_buffers(batch_size):
    allocs.append(batch_size)
    return {'img': np.empty((batch_size, 2), np.int64)}

  parse.make_image_buffers = make_image_buffers
  return parse


class TestRingBuffers:

  def test_ring_stream_equality_and_bounded_allocation(self):
    serial = _collect(0, parse=_ring_parse([]))
    allocs = []
    eng = engine_lib.ParallelBatchEngine(
        iter(_records(57)), _ring_parse(allocs), 5, num_workers=2,
        ring_depth=3, reuse_buffers=True)
    out = []
    with eng:
      for batch in eng:
        out.append(batch.copy())  # lease contract: copy, then release
        eng.release()
    assert len(allocs) == 3  # exactly ring_depth slots, ever
    assert len(out) == len(serial)
    for a, b in zip(serial, out):
      np.testing.assert_array_equal(a, b)

  def test_released_slot_is_reused_and_overwritten(self):
    eng = engine_lib.ParallelBatchEngine(
        iter(_records(60)), _ring_parse([]), 5, num_workers=2,
        ring_depth=3, reuse_buffers=True)
    with eng:
      first = next(eng)
      snapshot = first.copy()
      eng.release()
      # Three further deliveries occupy all three slots, so the released
      # slot MUST have been recycled; the old view now shows new data.
      later = [next(eng) for _ in range(3)]
      for _ in later:
        eng.release()
      assert not np.array_equal(first, snapshot)

  def test_unreleased_leases_fail_loudly_not_deadlock(self):
    # lease_timeout shortened: a consumer that NEVER releases gets the
    # loud error after the grace window an async releaser (the trainer's
    # placement stage) would have used.
    eng = engine_lib.ParallelBatchEngine(
        iter(_records(60)), _ring_parse([]), 5, num_workers=2,
        ring_depth=3, reuse_buffers=True, lease_timeout=0.2)
    with eng:
      for _ in range(3):
        next(eng)  # never released
      with pytest.raises(RuntimeError, match='ring slots are leased'):
        next(eng)

  def test_parse_fn_without_buffer_protocol_degrades(self):
    eng = engine_lib.ParallelBatchEngine(
        iter(_records(20)), _parse, 5, num_workers=2, reuse_buffers=True)
    with eng:
      out = list(eng)
    assert len(out) == 4  # plain allocation mode, stream intact

  def test_ring_release_from_trainer_placement_stage(self):
    """The ROADMAP PR-3 follow-up, closed: the trainer's dedicated
    placement stage releases each lease at transfer completion, so
    reuse_buffers rings work under the three-stage prefetcher — alloc
    count == ring_depth for a stream much longer than the ring, output
    ordered and intact."""
    from tensor2robot_tpu.train.trainer import _DevicePrefetcher

    serial = _collect(0, n=100, parse=_ring_parse([]))
    allocs = []
    eng = engine_lib.ParallelBatchEngine(
        iter(_records(100)), _ring_parse(allocs), 5, num_workers=2,
        ring_depth=3, reuse_buffers=True)
    # place() copies out of the ring slot (what shard_batch's device_put
    # does for real); the prefetcher then releases the lease.
    prefetcher = _DevicePrefetcher(
        eng, place=lambda b: b.copy(), depth=2, place_stage=True,
        release=eng.release)
    out = list(prefetcher)
    prefetcher.close()
    eng.close()
    assert len(allocs) == 3  # exactly ring_depth buffers, ever
    assert len(out) == len(serial) == 20
    for a, b in zip(serial, out):
      np.testing.assert_array_equal(a, b)

  def test_ring_release_from_consumer_place_path(self):
    """CPU backends place on the consumer thread; the release hook must
    fire there too."""
    from tensor2robot_tpu.train.trainer import _DevicePrefetcher

    allocs = []
    eng = engine_lib.ParallelBatchEngine(
        iter(_records(100)), _ring_parse(allocs), 5, num_workers=2,
        ring_depth=3, reuse_buffers=True)
    prefetcher = _DevicePrefetcher(
        eng, place=lambda b: b.copy(), depth=2, place_stage=False,
        release=eng.release)
    out = list(prefetcher)
    prefetcher.close()
    eng.close()
    assert len(allocs) == 3
    assert len(out) == 20


# ----------------------------------------------------------- autotune


@pytest.fixture
def clean_registry():
  metrics_lib.reset()
  yield
  metrics_lib.reset()


class TestAutotune:

  def test_explicit_worker_count_wins(self, clean_registry):
    decision = engine_lib.autotune(3, cpus=1)
    assert decision.num_workers == 3
    assert decision.ring_depth >= 4  # floor: workers + 1

  def test_single_core_collapses_to_serial(self, clean_registry):
    decision = engine_lib.autotune(cpus=1)
    assert decision.serial
    assert decision.num_workers == 0
    assert decision.ring_depth == 0
    assert decision.prefetch_depth == 0
    assert 'single-core' in decision.reason

  def test_mocked_single_core_host(self, clean_registry, monkeypatch):
    import os

    monkeypatch.setattr(os, 'sched_getaffinity', lambda pid: {0},
                        raising=False)
    decision = engine_lib.autotune()
    assert decision.serial and decision.cpus == 1
    assert engine_lib.autotune_prefetch() == 0

  def test_multicore_default(self, clean_registry):
    decision = engine_lib.autotune(cpus=8)
    assert decision.num_workers == 4
    assert decision.ring_depth == 8
    assert decision.prefetch_depth == 2
    assert engine_lib.autotune_prefetch(cpus=8) == 2

  def test_compute_bound_signal_shrinks_workers(self, clean_registry):
    metrics_lib.counter('trainer/dispatches').inc(64)
    metrics_lib.gauge('trainer/input_bound_fraction').set(0.01)
    decision = engine_lib.autotune(cpus=8)
    assert decision.num_workers == 1
    assert 'compute-bound' in decision.reason

  def test_input_bound_signal_escalates_workers(self, clean_registry):
    metrics_lib.counter('trainer/dispatches').inc(64)
    metrics_lib.gauge('trainer/input_bound_fraction').set(0.8)
    decision = engine_lib.autotune(cpus=16)
    assert decision.num_workers == 8
    assert 'input-bound' in decision.reason

  def test_starvation_counts_as_input_bound(self, clean_registry):
    metrics_lib.counter('trainer/dispatches').inc(64)
    metrics_lib.gauge('trainer/input_bound_fraction').set(0.2)
    metrics_lib.counter('trainer/prefetch/starvation').inc(5)
    decision = engine_lib.autotune(cpus=4)
    assert decision.num_workers == 3

  def test_short_window_is_not_trusted(self, clean_registry):
    metrics_lib.counter('trainer/dispatches').inc(3)  # < threshold
    metrics_lib.gauge('trainer/input_bound_fraction').set(0.01)
    assert engine_lib.autotune(cpus=8).num_workers == 4  # default, no shrink

  def test_decision_published(self, clean_registry):
    decision = engine_lib.autotune(cpus=8)
    assert engine_lib.last_decision() == decision
    assert metrics_lib.gauge('data/engine/workers').value == 4
    assert decision.as_dict()['ring_depth'] == 8


class TestMidRunReautotune:
  """ROADMAP PR-3 follow-up: the engine re-evaluates its worker count at
  trainer log-window crossings, at most one change per window, with the
  decision history published as data/engine/* gauges."""

  @staticmethod
  def _engine(records=600, workers=1, ring=8, cpus=4):
    return engine_lib.ParallelBatchEngine(
        iter(_records(records)), _parse, 5, num_workers=workers,
        ring_depth=ring, reautotune=True, cpus=cpus)

  @staticmethod
  def _window(input_bound, starvation=0):
    """Simulates one closed breakdown window with the given signals."""
    metrics_lib.gauge('trainer/input_bound_fraction').set(input_bound)
    if starvation:
      metrics_lib.counter('trainer/prefetch/starvation').inc(starvation)
    metrics_lib.counter('trainer/breakdown_windows').inc()

  def test_grows_when_window_says_input_bound(self, clean_registry):
    metrics_lib.counter('trainer/dispatches').inc(64)
    eng = self._engine()
    with eng:
      next(eng)
      assert eng._num_workers == 1  # no window yet: build decision holds
      self._window(0.8)
      next(eng)
      assert eng._num_workers == 3  # min(cpus-1, 8), capped by ring
      assert metrics_lib.counter(
          'data/engine/reautotune/changes').value == 1
      assert metrics_lib.gauge(
          'data/engine/reautotune/target_workers').value == 3
      assert metrics_lib.gauge('data/engine/workers').value == 3
      assert eng.decision_history[-1]['to'] == 3
      # Same window: NO further change (one re-evaluation per window).
      for _ in range(5):
        next(eng)
      assert metrics_lib.counter(
          'data/engine/reautotune/changes').value == 1

  def test_shrinks_when_window_says_compute_bound(self, clean_registry):
    metrics_lib.counter('trainer/dispatches').inc(64)
    eng = self._engine(workers=3)
    with eng:
      got = [next(eng)]
      self._window(0.01)
      got.append(next(eng))
      assert eng._num_workers == 1
      # Retired threads drain their in-flight tickets; stream intact.
      got.extend(next(eng) for _ in range(10))
    serial = _collect(0, n=600)
    for a, b in zip(serial, got):
      np.testing.assert_array_equal(a, b)

  def test_stream_identical_across_resizes(self, clean_registry):
    serial = _collect(0, n=300)
    metrics_lib.counter('trainer/dispatches').inc(64)
    eng = self._engine(records=300, workers=2)
    got = []
    with eng:
      for i, batch in enumerate(eng):
        got.append(batch)
        if i == 5:
          self._window(0.9)    # grow next delivery
        elif i == 20:
          self._window(0.01)   # shrink back to 1
    assert len(got) == len(serial)
    for a, b in zip(serial, got):
      np.testing.assert_array_equal(a, b)
    assert metrics_lib.counter('data/engine/reautotune/changes').value == 2
    assert [d['to'] for d in eng.decision_history] == [3, 1]

  def test_starvation_delta_not_lifetime_drives_growth(self,
                                                       clean_registry):
    """An hour-old starvation incident must not pin the pool grown: only
    NEW starvation (the per-window delta) counts."""
    metrics_lib.counter('trainer/dispatches').inc(64)
    metrics_lib.counter('trainer/prefetch/starvation').inc(7)  # historical
    eng = self._engine(workers=2)
    with eng:
      next(eng)
      self._window(0.2)  # mid-band fraction, NO new starvation
      next(eng)
      assert eng._num_workers == 2  # unchanged
      self._window(0.2, starvation=3)  # fresh starvation this window
      next(eng)
      assert eng._num_workers == 3

  def test_untrusted_short_window_changes_nothing(self, clean_registry):
    metrics_lib.counter('trainer/dispatches').inc(3)  # below threshold
    eng = self._engine(workers=2)
    with eng:
      next(eng)
      self._window(0.9)
      next(eng)
      assert eng._num_workers == 2

  def test_disabled_without_flag(self, clean_registry):
    metrics_lib.counter('trainer/dispatches').inc(64)
    eng = engine_lib.ParallelBatchEngine(
        iter(_records(100)), _parse, 5, num_workers=1, ring_depth=8,
        cpus=4)  # reautotune defaults off
    with eng:
      next(eng)
      self._window(0.9)
      next(eng)
      assert eng._num_workers == 1


# -------------------------------------------- native end-to-end stream


def _image_specs():
  from tensor2robot_tpu.specs import SpecStruct, TensorSpec

  fspec = SpecStruct({
      'image': TensorSpec((12, 16, 3), np.uint8, name='image',
                          data_format='JPEG'),
      'mask': TensorSpec((12, 16, 1), np.uint8, name='mask',
                         data_format='PNG'),
      'pos': TensorSpec((3,), np.float32, name='pos'),
  })
  lspec = SpecStruct({'y': TensorSpec((), np.float32, name='y')})
  return fspec, lspec


def _write_image_records(tmp_path, n=40, shards=2):
  from tensor2robot_tpu.data import example_codec, records
  from tensor2robot_tpu.specs import SpecStruct

  fspec, lspec = _image_specs()
  combined = SpecStruct(dict(fspec.items()))
  combined['y'] = lspec['y']
  rng = np.random.RandomState(0)
  serialized = []
  for i in range(n):
    serialized.append(example_codec.encode_example(combined, {
        'image': rng.randint(0, 255, (12, 16, 3)).astype(np.uint8),
        'mask': rng.randint(0, 255, (12, 16, 1)).astype(np.uint8),
        'pos': rng.randn(3).astype(np.float32),
        'y': np.float32(i),
    }))
  per_shard = n // shards
  paths = []
  for s in range(shards):
    path = str(tmp_path / f'img{s}.tfrecord')
    records.write_examples(path, serialized[s * per_shard:(s + 1) * per_shard])
    paths.append(path)
  return ','.join(paths)


def _batch_arrays(batch):
  features, labels = batch
  arrays = dict(features.items())
  if labels is not None:
    arrays.update({'label/' + k: v for k, v in labels.items()})
  return arrays


def _assert_batches_equal(a, b):
  fa, fb = _batch_arrays(a), _batch_arrays(b)
  assert sorted(fa) == sorted(fb)
  for key in fa:
    assert fa[key].dtype == fb[key].dtype, key
    np.testing.assert_array_equal(fa[key], fb[key], err_msg=key)


@requires_native
class TestNativeEngineStream:
  """The acceptance-criterion tests: real records, real image decode."""

  def _generator(self, pattern, workers, batch_size=6, **kwargs):
    from tensor2robot_tpu.data.input_generators import (
        NativeRecordInputGenerator)

    fspec, lspec = _image_specs()
    gen = NativeRecordInputGenerator(
        pattern, batch_size=batch_size, shuffle_buffer_size=16, seed=7,
        decode_workers=2, engine_workers=workers, **kwargs)
    gen.set_specification(fspec, lspec)
    return gen

  def test_train_stream_byte_identical_any_worker_count(self, tmp_path):
    from tensor2robot_tpu.modes import ModeKeys

    pattern = _write_image_records(tmp_path)
    reference = None
    for workers in (0, 1, 2, 4):
      it = self._generator(pattern, workers).create_iterator(
          ModeKeys.TRAIN)
      batches = [next(it) for _ in range(8)]  # > one epoch: wraps
      if reference is None:
        reference = batches
        continue
      for a, b in zip(reference, batches):
        _assert_batches_equal(a, b)

  def test_eval_epoch_byte_identical(self, tmp_path):
    from tensor2robot_tpu.modes import ModeKeys

    pattern = _write_image_records(tmp_path, n=20)
    serial = list(self._generator(pattern, 0).create_iterator(
        ModeKeys.EVAL))
    parallel = list(self._generator(pattern, 3).create_iterator(
        ModeKeys.EVAL))
    assert len(serial) == len(parallel) == 3  # 20 // 6, remainder dropped
    for a, b in zip(serial, parallel):
      _assert_batches_equal(a, b)

  def test_ring_buffers_end_to_end(self, tmp_path):
    from tensor2robot_tpu.modes import ModeKeys

    pattern = _write_image_records(tmp_path)
    serial_it = self._generator(pattern, 0).create_iterator(ModeKeys.TRAIN)
    serial = [next(serial_it) for _ in range(6)]
    ring_it = self._generator(
        pattern, 2, reuse_batch_buffers=True).create_iterator(
            ModeKeys.TRAIN)
    for expected in serial:
      got = next(ring_it)
      # Lease contract: compare (copies) before releasing the slot.
      _assert_batches_equal(
          expected,
          tuple(None if part is None else type(part)(
              {k: np.array(v, copy=True) for k, v in part.items()})
                for part in got))
      ring_it.release()

  def test_training_is_bitwise_identical_under_engine(self, tmp_path):
    """The whole point: same trained params, engine on or off."""
    import jax

    from tensor2robot_tpu.data import example_codec, records
    from tensor2robot_tpu.data.input_generators import (
        NativeRecordInputGenerator)
    from tensor2robot_tpu.modes import ModeKeys
    from tensor2robot_tpu.models import optimizers as opt_lib
    from tensor2robot_tpu.specs import SpecStruct
    from tensor2robot_tpu.train import Trainer, TrainerConfig
    from tensor2robot_tpu.utils.mocks import MockT2RModel

    model0 = MockT2RModel(device_type='cpu')
    fspec = model0.get_feature_specification(ModeKeys.TRAIN)
    lspec = model0.get_label_specification(ModeKeys.TRAIN)
    rng = np.random.RandomState(0)
    recs = []
    for i in range(48):
      recs.append(example_codec.encode_example(
          SpecStruct({'measured_position': fspec['measured_position'],
                      'valid_position': lspec['valid_position']}),
          SpecStruct({'measured_position': rng.randn(2).astype(np.float32),
                      'valid_position': np.float32(i % 2)})))
    path = str(tmp_path / 'train.tfrecord')
    records.write_examples(path, recs)

    results = {}
    for workers in (0, 3):
      model = MockT2RModel(
          device_type='cpu',
          create_optimizer_fn=lambda: opt_lib.create_adam_optimizer(1e-2))
      trainer = Trainer(model, TrainerConfig(
          model_dir='', max_train_steps=6, eval_interval_steps=0,
          log_interval_steps=0))
      gen = NativeRecordInputGenerator(
          path, batch_size=8, shuffle_buffer_size=8, seed=1,
          engine_workers=workers)
      gen.set_specification_from_model(model, ModeKeys.TRAIN)
      trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
      results[workers] = jax.device_get(trainer.state.params)
    for a, b in zip(jax.tree_util.tree_leaves(results[0]),
                    jax.tree_util.tree_leaves(results[3])):
      np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@requires_native
class TestNativeEngineResume:
  """Mid-epoch resume stays bit-exact under the parallel engine."""

  def _checkpointable(self, pattern, workers, batch_size=6):
    from tensor2robot_tpu.data.input_generators import (
        NativeRecordInputGenerator)
    from tensor2robot_tpu.modes import ModeKeys

    fspec, lspec = _image_specs()
    gen = NativeRecordInputGenerator(
        pattern, batch_size=batch_size, shuffle_buffer_size=16, seed=11,
        decode_workers=2, engine_workers=workers)
    gen.set_specification(fspec, lspec)
    return gen.create_checkpointable_iterator(ModeKeys.TRAIN)

  def test_mid_epoch_resume_bit_exact(self, tmp_path):
    pattern = _write_image_records(tmp_path)
    prefix = str(tmp_path / 'input_state' / 'state')

    it = self._checkpointable(pattern, workers=2)
    for _ in range(3):
      next(it)
    it.save(prefix)
    expected = [next(it) for _ in range(3)]  # the uninterrupted future
    it.close()

    resumed = self._checkpointable(pattern, workers=2)
    resumed.restore(prefix)
    for want in expected:
      _assert_batches_equal(want, next(resumed))
    resumed.close()

  def test_resume_matches_across_worker_counts(self, tmp_path):
    """Save under the engine, restore into the SERIAL path: positions
    are stream-level, not implementation-level."""
    pattern = _write_image_records(tmp_path)
    prefix = str(tmp_path / 'xw' / 'state')

    it = self._checkpointable(pattern, workers=3)
    for _ in range(4):
      next(it)
    it.save(prefix)
    expected = [next(it) for _ in range(2)]
    it.close()

    serial = self._checkpointable(pattern, workers=0)
    serial.restore(prefix)
    for want in expected:
      _assert_batches_equal(want, next(serial))
    serial.close()

  def test_unseeded_shuffle_refuses_checkpointing(self, tmp_path):
    from tensor2robot_tpu.data.input_generators import (
        NativeRecordInputGenerator)
    from tensor2robot_tpu.modes import ModeKeys

    pattern = _write_image_records(tmp_path, n=20)
    fspec, lspec = _image_specs()
    gen = NativeRecordInputGenerator(pattern, batch_size=4,
                                     shuffle_buffer_size=16)  # no seed
    gen.set_specification(fspec, lspec)
    with pytest.raises(ValueError, match='seed'):
      gen.create_checkpointable_iterator(ModeKeys.TRAIN)

  def test_batch_size_mismatch_refuses_restore(self, tmp_path):
    pattern = _write_image_records(tmp_path)
    prefix = str(tmp_path / 'bs' / 'state')
    it = self._checkpointable(pattern, workers=0, batch_size=6)
    next(it)
    it.save(prefix)
    it.close()
    other = self._checkpointable(pattern, workers=0, batch_size=4)
    with pytest.raises(ValueError, match='batch_size'):
      other.restore(prefix)
    other.close()


# ----------------------------------------------------------- /metricsz


class TestMetricsz:

  def test_serves_registry_report(self):
    from tensor2robot_tpu.observability import metricsz

    metrics_lib.counter('metricsz_test/hits').inc(3)
    with metricsz.MetricsServer(port=0) as server:
      assert server.port
      with urllib.request.urlopen(server.url, timeout=5) as response:
        assert response.headers['Content-Type'] == 'application/json'
        report = json.load(response)
      assert report['kind'] == 'metrics_report'
      assert report['metrics']['metricsz_test/hits'] >= 3
      base = f'http://127.0.0.1:{server.port}'
      with urllib.request.urlopen(f'{base}/healthz', timeout=5) as response:
        assert json.load(response) == {'status': 'ok'}
      with pytest.raises(urllib.error.HTTPError) as excinfo:
        urllib.request.urlopen(f'{base}/nope', timeout=5)
      assert excinfo.value.code == 404

  def test_off_by_default(self, monkeypatch):
    from tensor2robot_tpu.observability import metricsz

    monkeypatch.delenv(metricsz.ENV_VAR, raising=False)
    assert metricsz.maybe_start(None) is None

  def test_env_var_opt_in_and_idempotent(self, monkeypatch):
    from tensor2robot_tpu.observability import metricsz

    monkeypatch.setenv(metricsz.ENV_VAR, '0')
    try:
      server = metricsz.maybe_start(None)
      assert server is not None and server.port
      assert metricsz.maybe_start(0) is server  # one registry, one server
      with urllib.request.urlopen(server.url, timeout=5) as response:
        assert json.load(response)['kind'] == 'metrics_report'
    finally:
      metricsz.stop_global()

  def test_trainer_config_opt_in(self, tmp_path):
    from tensor2robot_tpu.observability import metricsz
    from tensor2robot_tpu.train import Trainer, TrainerConfig
    from tensor2robot_tpu.utils.mocks import MockT2RModel

    try:
      Trainer(MockT2RModel(device_type='cpu'),
              TrainerConfig(model_dir='', metricsz_port=0))
      server = metricsz.global_server()
      assert server is not None
      with urllib.request.urlopen(server.url, timeout=5) as response:
        assert json.load(response)['kind'] == 'metrics_report'
    finally:
      metricsz.stop_global()


# ---------------------------------------- tf-codec budget attribution


class TestMatchFilenameInError:

  def test_full_path_and_unique_basename(self):
    from tensor2robot_tpu.data import pipeline

    files = ['/data/a-00000.tfrecord', '/data/a-00001.tfrecord']
    exc = IOError('corrupt record in /data/a-00001.tfrecord at 12')
    assert pipeline.match_filename_in_error(exc, files) == files[1]
    exc = IOError('failed reading a-00000.tfrecord')
    assert pipeline.match_filename_in_error(exc, files) == files[0]

  def test_ambiguity_returns_none(self):
    from tensor2robot_tpu.data import pipeline

    files = ['/x/shard.tfrecord', '/y/shard.tfrecord']
    exc = IOError('failed reading shard.tfrecord')
    assert pipeline.match_filename_in_error(exc, files) is None
    assert pipeline.match_filename_in_error(IOError(''), files) is None


class TestTfCodecBudgetAttribution:

  def test_corrupt_shard_charged_per_file(self, tmp_path):
    """tf.data's DataLossError names no file; the integrity probe must
    pin the charge on the rotten shard anyway."""
    import tensorflow as tf

    from tensor2robot_tpu.data import example_codec
    from tensor2robot_tpu.data.input_generators import (
        DefaultRecordInputGenerator)
    from tensor2robot_tpu.modes import ModeKeys
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec
    from tensor2robot_tpu.utils import retry as retry_lib

    spec = SpecStruct({'x': TensorSpec((3,), np.float32, name='x')})
    rng = np.random.RandomState(0)
    paths = []
    for s in range(2):
      path = str(tmp_path / f'shard{s}.tfrecord')
      with tf.io.TFRecordWriter(path) as writer:
        for _ in range(8):
          writer.write(example_codec.encode_example(
              spec, {'x': rng.randn(3).astype(np.float32)}))
      paths.append(path)
    with open(paths[1], 'ab') as f:  # rot the tail of shard1
      f.write(b'\x13garbage-not-a-record\x37' * 3)

    gen = DefaultRecordInputGenerator(
        file_patterns=','.join(paths), batch_size=4,
        shuffle_buffer_size=2, seed=0, error_budget=2)
    gen.set_specification(spec, None)
    it = gen.create_iterator(ModeKeys.TRAIN)
    with pytest.raises(retry_lib.DataErrorBudgetExceededError) as excinfo:
      for _ in range(500):
        next(it)
    assert it.budget.by_source.get(paths[1], 0) >= 3  # budget 2 + final
    assert paths[0] not in it.budget.by_source
    assert 'shard1.tfrecord' in str(excinfo.value)

  def test_probe_scans_each_file_once(self, tmp_path):
    from tensor2robot_tpu.data import records as records_lib
    from tensor2robot_tpu.data.input_generators import (
        DefaultRecordInputGenerator)
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    paths = []
    for s in range(2):
      path = str(tmp_path / f'p{s}.tfrecord')
      records_lib.write_examples(path, [b'x' * 10])
      paths.append(path)
    with open(paths[0], 'ab') as f:
      f.write(b'rot')
    gen = DefaultRecordInputGenerator(
        file_patterns=','.join(paths), batch_size=1, error_budget=5)
    gen.set_specification(
        SpecStruct({'x': TensorSpec((1,), np.float32, name='x')}), None)
    exc = IOError('corrupted record at 99')
    assert gen._budget_source(exc) == paths[0]  # pylint: disable=protected-access
    # Second charge reuses the cached probe (no re-scan): same answer.
    assert gen._budget_source(exc) == paths[0]  # pylint: disable=protected-access
    assert gen._budget_file_ok == {paths[0]: False, paths[1]: True}  # pylint: disable=protected-access


# ------------------------------------- preemption-aware continuous eval


class TestContinuousEvalPreemption:

  def test_preempt_persists_position_and_resume_skips(self, tmp_path,
                                                      monkeypatch):
    import os

    from tensor2robot_tpu.modes import ModeKeys
    from tensor2robot_tpu.models import optimizers as opt_lib
    from tensor2robot_tpu.train import (Trainer, TrainerConfig,
                                        train_eval_model)
    from tensor2robot_tpu.train import resilience
    from tensor2robot_tpu.train.trainer import (EVAL_STATE_FILENAME,
                                                TrainerCallback)
    from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

    def fast_adam():
      return opt_lib.create_adam_optimizer(1e-2)

    model_dir = str(tmp_path / 'm')

    def train_to(max_steps):
      model = MockT2RModel(device_type='cpu', create_optimizer_fn=fast_adam)
      train_gen = MockInputGenerator(batch_size=8)
      train_gen.set_specification_from_model(model, ModeKeys.TRAIN)
      trainer = Trainer(model, TrainerConfig(
          model_dir=model_dir, max_train_steps=max_steps,
          save_interval_steps=2, eval_interval_steps=0,
          log_interval_steps=0, async_checkpoints=False))
      trainer.train(train_gen.create_iterator(ModeKeys.TRAIN), None)
      trainer.close()

    train_to(2)  # checkpoint 2 exists when the evaluator starts

    class EvalRecorder(TrainerCallback):

      def __init__(self, on_eval=None):
        self.steps = []
        self._on_eval = on_eval

      def after_eval(self, trainer, step, metrics):
        self.steps.append(int(trainer.step))
        if self._on_eval is not None:
          self._on_eval()

    def run_eval(callbacks):
      eval_gen = MockInputGenerator(batch_size=8)
      return train_eval_model(
          model=MockT2RModel(device_type='cpu',
                             create_optimizer_fn=fast_adam),
          model_dir=model_dir,
          eval_input_generator=eval_gen,
          max_train_steps=4,
          eval_steps=2,
          use_continuous_eval=True,
          eval_timeout_secs=0.5,
          log_interval_steps=0,
          callbacks=callbacks)

    # Run 1: after the step-2 eval, training advances to step 4 AND a
    # preemption lands. The evaluator sees the new checkpoint, must NOT
    # evaluate it, and instead persists its position and raises the
    # RESUMABLE error (the trainer binary converts it to exit 42).
    shutdown = resilience.GracefulShutdown()  # flag only, no signals
    monkeypatch.setattr(resilience, '_GLOBAL_SHUTDOWN', shutdown)

    def extend_then_preempt():
      train_to(4)
      shutdown.request()

    recorder = EvalRecorder(on_eval=extend_then_preempt)
    with pytest.raises(resilience.PreemptedError) as excinfo:
      run_eval([recorder])
    assert excinfo.value.exit_code == 42
    assert recorder.steps == [2]
    state_path = os.path.join(model_dir, EVAL_STATE_FILENAME)
    with open(state_path) as f:
      assert json.load(f) == {'last_evaluated_step': 2}

    # Run 2: the restarted evaluator skips the already-evaluated step 2
    # and finishes step 4.
    monkeypatch.setattr(resilience, '_GLOBAL_SHUTDOWN', None)
    recorder2 = EvalRecorder()
    metrics = run_eval([recorder2])
    assert recorder2.steps == [4]
    assert np.isfinite(metrics['loss'])
    with open(state_path) as f:
      assert json.load(f) == {'last_evaluated_step': 4}


# --------------------------------------------- trainer placement stage


class TestPlacementStage:

  def test_place_stage_preserves_order(self):
    from tensor2robot_tpu.train.trainer import _DevicePrefetcher

    batches = [np.full((2,), i) for i in range(20)]
    prefetcher = _DevicePrefetcher(
        iter(batches), lambda b: b * 10, depth=2, place_stage=True)
    out = [next(prefetcher) for _ in range(20)]
    with pytest.raises(StopIteration):
      next(prefetcher)
    prefetcher.close()
    for i, placed in enumerate(out):
      np.testing.assert_array_equal(placed, np.full((2,), i) * 10)

  def test_place_stage_propagates_errors(self):
    from tensor2robot_tpu.train.trainer import _DevicePrefetcher

    def broken():
      for i in range(10):
        if i == 3:
          raise RuntimeError('reader died')
        yield np.full((2,), i)

    prefetcher = _DevicePrefetcher(
        broken(), lambda b: b, depth=2, place_stage=True)
    with pytest.raises(RuntimeError, match='reader died'):
      for _ in range(10):
        next(prefetcher)
    prefetcher.close()

  def test_place_stage_close_terminates_threads(self):
    import itertools

    from tensor2robot_tpu.train.trainer import _DevicePrefetcher

    prefetcher = _DevicePrefetcher(
        iter(itertools.count()), lambda b: b, depth=1, place_stage=True)
    next(iter(prefetcher))
    prefetcher.close()
    for thread in prefetcher._threads:  # pylint: disable=protected-access
      thread.join(timeout=5)
      assert not thread.is_alive()

  @pytest.mark.parametrize('place_stage', [True, False])
  def test_hands_on_and_releases_at_copy_completion(self, monkeypatch,
                                                    place_stage):
    """The accelerator order of a placement (the CPU branch releases
    before it places, so the backend is a stand-in here): batch n is
    put, its lease returned exactly once when the copies are ready, and
    only then handed on."""
    import tensor2robot_tpu.train.trainer as trainer_mod

    monkeypatch.setattr(trainer_mod.jax, 'default_backend', lambda: 'tpu')
    events = []

    class Copy:

      def __init__(self, n):
        self.n = n

      def block_until_ready(self):
        events.append(('copy ready', self.n))
        return self

    def place(n):
      events.append(('put', n))
      return Copy(n), Copy(n)

    prefetcher = trainer_mod._DevicePrefetcher(
        iter(range(3)), place, depth=1, place_stage=place_stage,
        release=lambda: events.append(('release',)))
    handed_on = []
    for n in range(3):
      features, labels = next(prefetcher)
      handed_on.append(len(events))
      assert isinstance(features, Copy) and isinstance(labels, Copy)
      assert features.n == n
    with pytest.raises(StopIteration):
      next(prefetcher)
    prefetcher.close()
    assert events == [
        event for n in range(3) for event in (
            ('put', n), ('copy ready', n), ('copy ready', n), ('release',))]
    # Batch n was handed on only after its own lease was back.
    assert all(at >= 4 * (n + 1) for n, at in enumerate(handed_on))

  def test_place_stage_training_bitwise_identical(self, forced_place_stage):
    """The three-stage pipeline must not change training — force it on
    (it is TPU-only by default) and compare against the inline path."""
    import jax

    import tensor2robot_tpu.train.trainer as trainer_mod
    from tensor2robot_tpu.modes import ModeKeys
    from tensor2robot_tpu.models import optimizers as opt_lib
    from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

    results = {}
    for mode in ('inline', 'staged'):
      model = MockT2RModel(
          device_type='cpu',
          create_optimizer_fn=lambda: opt_lib.create_adam_optimizer(1e-2))
      trainer = trainer_mod.Trainer(model, trainer_mod.TrainerConfig(
          model_dir='', max_train_steps=12, eval_interval_steps=0,
          log_interval_steps=0,
          prefetch_batches=0 if mode == 'inline' else 2))
      gen = MockInputGenerator(batch_size=8)
      gen.set_specification_from_model(model, ModeKeys.TRAIN)
      trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
      results[mode] = jax.device_get(trainer.state.params)
    for a, b in zip(jax.tree_util.tree_leaves(results['inline']),
                    jax.tree_util.tree_leaves(results['staged'])):
      np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestCloseVsResizeRace:
  """Regression: close() vs a mid-run re-autotune grow.

  PR 8's lock-discipline checker flagged close() iterating ``_threads``
  and reading ``_num_workers`` without ``_workers_lock`` while the
  consumer-side re-autotune path appends new worker threads — a
  'list changed size during iteration' RuntimeError plus workers that
  were never joined or retired. close() now snapshots the pool under
  the lock and flips ``_closed`` first, making any later grow a no-op.
  """

  def _engine(self, workers=1, ring=8):
    def records():
      i = 0
      while True:
        yield f'rec-{i}'.encode()
        i += 1

    return engine_lib.ParallelBatchEngine(
        records(), lambda recs: list(recs), batch_size=2,
        num_workers=workers, ring_depth=ring)

  def test_grow_after_close_is_noop(self):
    eng = self._engine()
    assert next(eng)  # pipeline is live
    eng.close()
    with eng._workers_lock:
      n_threads = len(eng._threads)
    eng._set_num_workers(4, input_bound=0.9, starvation=1)
    with eng._workers_lock:
      assert len(eng._threads) == n_threads, 'grow after close spawned'
      assert not eng.decision_history, 'closed engine recorded a resize'

  def test_concurrent_close_and_grow_never_raises(self):
    for _ in range(15):
      eng = self._engine(workers=1, ring=8)
      next(eng)
      errors = []
      barrier = threading.Barrier(2)

      def grower(eng=eng, errors=errors, barrier=barrier):
        try:
          barrier.wait(timeout=5)
          for target in (2, 3, 4, 5, 6, 7):
            eng._set_num_workers(target, input_bound=0.9, starvation=1)
        except Exception as e:  # pragma: no cover - the regression
          errors.append(e)

      t = threading.Thread(target=grower)
      t.start()
      barrier.wait(timeout=5)
      eng.close()  # pre-fix: RuntimeError iterating a growing list
      t.join(timeout=10)
      assert not t.is_alive()
      assert not errors, errors
      with eng._workers_lock:
        threads = list(eng._threads)
      deadline = time.monotonic() + 5
      for thread in threads:
        thread.join(max(0.0, deadline - time.monotonic()))
      assert not any(th.is_alive() for th in threads)
