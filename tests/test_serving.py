"""Serving-plane tests: batch assembly, bucketed zero-recompile dispatch,
hot swap under load, reload/predict race, /metricsz integration, and the
restart-goodput slice (compilation cache + first-step gauge).

Marker: ``serving`` (tier-1; ``tools/run_tier1.sh -m serving`` selects).
"""

import json
import os
import shutil
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from tensor2robot_tpu import export as export_lib
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.observability import metrics as metrics_lib
from tensor2robot_tpu.predictors import (AbstractPredictor,
                                         CheckpointPredictor,
                                         ExportedModelPredictor)
from tensor2robot_tpu.serving import batching as batching_lib
from tensor2robot_tpu.serving import loadgen
from tensor2robot_tpu.serving import server as server_lib
from tensor2robot_tpu.specs import SpecStruct, TensorSpec
from tensor2robot_tpu.train import Trainer, TrainerConfig
from tensor2robot_tpu.utils.concurrency import ReaderWriterLock
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _loaded_checkpoint_predictor():
  predictor = CheckpointPredictor(
      MockT2RModel(device_type='tpu'), model_dir='/nonexistent')
  predictor.init_randomly()
  return predictor


def _features(value: float, n: int = 1):
  return {'measured_position': np.full((n, 2), value, np.float32)}


def _trained_trainer(tmp_path, steps=5):
  model = MockT2RModel(device_type='tpu')
  config = TrainerConfig(
      model_dir=str(tmp_path / 'm'), max_train_steps=steps,
      save_interval_steps=steps, eval_interval_steps=0, log_interval_steps=0,
      async_checkpoints=False)
  trainer = Trainer(model, config)
  gen = MockInputGenerator(batch_size=8)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
  return trainer, model


# --------------------------------------------------------------- unit: shapes


def test_default_buckets_powers_of_two():
  assert batching_lib.default_buckets(64) == (1, 2, 4, 8, 16, 32, 64)
  assert batching_lib.default_buckets(1) == (1,)
  # Non-power-of-two cap keeps the cap itself as the top bucket.
  assert batching_lib.default_buckets(48) == (1, 2, 4, 8, 16, 32, 48)


def test_bucket_for_smallest_fit():
  buckets = (1, 2, 4, 8)
  assert [batching_lib.bucket_for(n, buckets) for n in (1, 2, 3, 5, 8)] == [
      1, 2, 4, 8, 8]
  with pytest.raises(ValueError):
    batching_lib.bucket_for(9, buckets)


def test_pad_to_bucket_repeats_last_example():
  feats = {'x': np.asarray([[1.0], [2.0], [3.0]], np.float32)}
  padded = batching_lib.pad_to_bucket(feats, 3, 8)
  assert padded['x'].shape == (8, 1)
  np.testing.assert_array_equal(padded['x'][3:], np.full((5, 1), 3.0))
  # Exact fit: no copy, same object.
  assert batching_lib.pad_to_bucket(feats, 3, 3)['x'] is feats['x']


# ------------------------------------------------------------ batch assembly


class TestAssembly:
  """Deadline-vs-max-batch semantics, driven directly on ``_assemble``
  (no dispatcher thread), so the outcomes are deterministic."""

  def _batcher(self, **kwargs):
    # No start(): assembly needs no model; submits skip spec validation.
    return batching_lib.DynamicBatcher(predictor=None, **kwargs)

  def test_max_batch_splits_are_deterministic(self):
    b = self._batcher(max_batch=4, batch_deadline_ms=10_000.0)
    futures = [b.submit({'x': np.zeros((1, 2), np.float32)})
               for _ in range(10)]
    del futures
    t0 = time.monotonic()
    sizes = [sum(r.n for r in b._assemble()) for _ in range(2)]
    # Full batches assemble WITHOUT waiting for the (huge) deadline.
    assert time.monotonic() - t0 < 1.0
    assert sizes == [4, 4]
    b._deadline_s = 0.01  # the 2-example tail flushes on its deadline
    assert [r.n for r in b._assemble()] == [1, 1]

  def test_deadline_flushes_partial_batch(self):
    b = self._batcher(max_batch=64, batch_deadline_ms=50.0)
    b.submit({'x': np.zeros((2, 2), np.float32)})
    t0 = time.monotonic()
    batch = b._assemble()
    elapsed = time.monotonic() - t0
    assert [r.n for r in batch] == [2]
    assert 0.02 <= elapsed < 1.0  # waited for the deadline, not forever

  def test_late_request_joins_open_window(self):
    b = self._batcher(max_batch=64, batch_deadline_ms=300.0)
    b.submit({'x': np.zeros((1, 2), np.float32)})

    def late():
      time.sleep(0.05)
      b.submit({'x': np.zeros((3, 2), np.float32)})

    threading.Thread(target=late, daemon=True).start()
    batch = b._assemble()
    assert sorted(r.n for r in batch) == [1, 3]

  def test_oversized_next_request_rolls_to_next_batch(self):
    b = self._batcher(max_batch=4, batch_deadline_ms=10_000.0)
    b.submit({'x': np.zeros((2, 2), np.float32)})
    b.submit({'x': np.zeros((3, 2), np.float32)})  # 2+3 > 4
    assert [r.n for r in b._assemble()] == [2]
    b._deadline_s = 0.01
    assert [r.n for r in b._assemble()] == [3]

  def test_submit_rejects_oversized_and_inconsistent(self):
    b = self._batcher(max_batch=4, batch_deadline_ms=1.0)
    with pytest.raises(batching_lib.RequestError):
      b.submit({'x': np.zeros((5, 2), np.float32)})  # > max_batch
    with pytest.raises(batching_lib.RequestError):
      b.submit({'x': np.zeros((2, 2), np.float32),
                'y': np.zeros((3,), np.float32)})  # inconsistent batch

  def test_queue_bound_backpressure(self):
    b = self._batcher(max_batch=4, batch_deadline_ms=1.0, max_queue=2)
    b.submit({'x': np.zeros((1, 2), np.float32)})
    b.submit({'x': np.zeros((1, 2), np.float32)})
    with pytest.raises(batching_lib.OverloadedError):
      b.submit({'x': np.zeros((1, 2), np.float32)})


# ------------------------------------------------- bucketed dispatch + swap


class TestBucketedDispatch:

  def test_zero_recompiles_while_client_count_varies(self):
    """The acceptance drill: warm all buckets, then vary concurrency
    1 → N → 1; the compile counter must stay EXACTLY at warmup."""
    predictor = _loaded_checkpoint_predictor()
    compiles = metrics_lib.counter('serving/bucket_compiles')
    with batching_lib.DynamicBatcher(
        predictor, max_batch=16, batch_deadline_ms=0.5) as batcher:
      assert batcher.buckets == (1, 2, 4, 8, 16)
      warm = compiles.value
      submit = loadgen.inproc_submit_fn(batcher, timeout=30.0)
      for clients in (1, 12, 5, 1):
        report = loadgen.run_load(
            submit, lambda i: _features(0.01 * (i + 1)),
            num_clients=clients, requests_per_client=8, warmup_requests=0)
        assert report.errors == 0, report
      assert compiles.value == warm  # ZERO recompiles after warmup
      assert metrics_lib.counter('serving/requests').value > 0

  def test_batched_outputs_match_serial_predict(self):
    predictor = _loaded_checkpoint_predictor()
    with batching_lib.DynamicBatcher(
        predictor, max_batch=8, batch_deadline_ms=5.0) as batcher:
      futures = {}
      for i in range(6):
        futures[i] = batcher.submit(_features(0.1 * i, n=1 + i % 3))
      for i, future in futures.items():
        got = future.result(timeout=30.0)
        want = predictor.predict(_features(0.1 * i, n=1 + i % 3))
        np.testing.assert_allclose(
            got['a_predicted'], want['a_predicted'], rtol=2e-5)

  def test_single_example_requests_expand_batch_dim(self):
    predictor = _loaded_checkpoint_predictor()
    with batching_lib.DynamicBatcher(
        predictor, max_batch=4, batch_deadline_ms=1.0) as batcher:
      out = batcher.submit(
          {'measured_position': np.zeros((2,), np.float32)}).result(10.0)
      assert out['a_predicted'].shape == (1,)

  def test_callable_executor_fallback(self):
    """Predictors without a stateless jax core (the SavedModel flavor)
    still get cross-client batching via whole-batch predict()."""

    class _Callable(AbstractPredictor):

      calls = 0

      def predict(self, features):
        type(self).calls += 1
        return {'doubled': np.asarray(features['x']) * 2.0}

      def get_feature_specification(self):
        spec = SpecStruct()
        spec['x'] = TensorSpec(shape=(2,), dtype=np.float32, name='x')
        return spec

      def restore(self):
        return True

      @property
      def is_loaded(self):
        return True

      @property
      def global_step(self):
        return 3

    with batching_lib.DynamicBatcher(
        _Callable(), max_batch=8, batch_deadline_ms=20.0) as batcher:
      futures = [batcher.submit({'x': np.full((1, 2), i, np.float32)})
                 for i in range(4)]
      outs = [f.result(10.0) for f in futures]
      for i, out in enumerate(outs):
        np.testing.assert_array_equal(out['doubled'], [[2.0 * i, 2.0 * i]])
      # 4 concurrent requests rode FEWER predict() calls than requests.
      assert _Callable.calls < 4
      assert batcher.model_version == 3


class TestHotSwap:

  def test_swap_under_sustained_load_no_failed_requests(self, tmp_path):
    trainer, model = _trained_trainer(tmp_path)
    root = str(tmp_path / 'export')
    exporter = export_lib.ModelExporter()
    exporter.export(model, trainer.state, root, version=1)
    predictor = ExportedModelPredictor(root)
    assert predictor.restore()
    swaps = metrics_lib.counter('serving/model_swaps')
    swaps0 = swaps.value
    with batching_lib.DynamicBatcher(
        predictor, max_batch=8, batch_deadline_ms=1.0,
        reload_interval_secs=0.05) as batcher:
      assert batcher.model_version == 5
      result = {}

      def load():
        result['report'] = loadgen.run_load(
            loadgen.inproc_submit_fn(batcher, timeout=30.0),
            lambda i: _features(0.01 * (i + 1)),
            num_clients=4, duration_secs=3.0)

      thread = threading.Thread(target=load, daemon=True)
      thread.start()
      time.sleep(0.4)  # traffic flowing against v1
      exporter.export(
          model, trainer.state.replace(step=trainer.state.step + 100),
          root, version=2)
      deadline = time.time() + 10.0
      while batcher.model_version != 105 and time.time() < deadline:
        time.sleep(0.05)
      assert batcher.model_version == 105  # swapped while under load
      thread.join(timeout=30.0)
      report = result['report']
      assert report.errors == 0, report  # zero dropped/failed requests
      assert swaps.value >= swaps0 + 1

    # Torn/broken reload drills on a poller-free batcher (the background
    # reload thread above would keep re-attempting the broken export and
    # make the fallback count nondeterministic).
    with batching_lib.DynamicBatcher(
        predictor, max_batch=8, batch_deadline_ms=1.0) as batcher:
      assert batcher.model_version == 105

      # Torn export (no commit marker): invisible — last-good keeps
      # serving, no swap, no error.
      torn = os.path.join(root, '3')
      shutil.copytree(os.path.join(root, '2'), torn)
      os.remove(os.path.join(torn, export_lib.exporters
                             .EXPORT_COMMIT_FILENAME))
      assert batcher.maybe_reload() is False
      assert batcher.model_version == 105

      # Committed-but-broken export (torn files the marker cannot see):
      # predictor falls back last-good; serving continues unswapped.
      broken = os.path.join(root, '4')
      shutil.copytree(os.path.join(root, '2'), broken)
      # Keep state/ present (the version stays a load CANDIDATE — the
      # validation and the commit marker cannot see inside) but gut its
      # payload, so the orbax restore itself fails mid-reload.
      state_dir = os.path.join(broken, export_lib.exporters.STATE_DIRNAME)
      shutil.rmtree(state_dir)
      os.makedirs(state_dir)
      fallbacks = metrics_lib.counter('predictor/load_fallbacks')
      fb0 = fallbacks.value
      assert batcher.maybe_reload() is False
      assert fallbacks.value == fb0 + 1
      assert batcher.model_version == 105
      out = batcher.submit(_features(0.5)).result(30.0)
      assert out['a_predicted'].shape == (1,)


def test_idle_plane_adopts_staged_swap_without_traffic(tmp_path):
  """A rolling deploy must land on an IDLE replica too: the staged
  generation is adopted by the dispatcher without waiting for the next
  request, so model_version / healthz advertise the new version
  (found by the fleet verify drive: an idle replica kept reporting the
  old version until traffic arrived)."""
  trainer, model = _trained_trainer(tmp_path)
  root = str(tmp_path / 'export')
  exporter = export_lib.ModelExporter()
  exporter.export(model, trainer.state, root, version=1)
  predictor = ExportedModelPredictor(root)
  assert predictor.restore()
  with batching_lib.DynamicBatcher(
      predictor, max_batch=4, batch_deadline_ms=1.0,
      reload_interval_secs=0.05) as batcher:
    assert batcher.model_version == 5
    exporter.export(
        model, trainer.state.replace(step=trainer.state.step + 100),
        root, version=2)
    deadline = time.time() + 20.0
    while batcher.model_version != 105 and time.time() < deadline:
      time.sleep(0.05)  # NO submits: the plane is idle the whole time
    assert batcher.model_version == 105
    out = batcher.submit(_features(0.4)).result(30.0)
    assert out['a_predicted'].shape == (1,)


def test_program_key_stable_across_weights_only_exports(tmp_path):
  """Two export versions of the same model are the same PROGRAM: the
  canonical fingerprint (loc-stripped StableHLO — raw artifact bytes
  embed drifting MLIR debug locations) must match, so the bucketed
  executor's compiled cache survives a weights-only hot swap."""
  trainer, model = _trained_trainer(tmp_path, steps=2)
  root = str(tmp_path / 'export')
  exporter = export_lib.ModelExporter()
  exporter.export(model, trainer.state, root, version=1)
  predictor = ExportedModelPredictor(root)
  assert predictor.restore()
  serving_v1 = predictor.stateless_serving_fn()
  exporter.export(
      model, trainer.state.replace(step=trainer.state.step + 7),
      root, version=2)
  assert predictor.restore()
  serving_v2 = predictor.stateless_serving_fn()
  assert serving_v2.version == serving_v1.version + 7
  assert serving_v1.program_key == serving_v2.program_key
  assert serving_v1.params is not serving_v2.params
  executor = batching_lib.JitBucketExecutor(serving_v1, (1, 2))
  executor.warm()
  assert executor.compatible_cache(serving_v2)


# ------------------------------------------------ reload/predict race guard


class TestReloadPredictRace:

  def test_hammer_predict_vs_hot_reload(self, tmp_path):
    """4 predict threads hammer while the main thread hot-reloads
    through 5 export versions: no exceptions, no torn generations
    (before the reader-writer lock this could pair a new serving fn
    with old params mid-predict)."""
    trainer, model = _trained_trainer(tmp_path, steps=2)
    root = str(tmp_path / 'export')
    # serialize_serving=False exercises the model-class path cheaply;
    # the lock scope under test is identical for the StableHLO path.
    exporter = export_lib.ModelExporter(serialize_serving=False)
    exporter.export(model, trainer.state, root, version=1)
    predictor = ExportedModelPredictor(root, t2r_model=model)
    assert predictor.restore()

    stop = threading.Event()
    failures = []

    def hammer():
      while not stop.is_set():
        try:
          out = predictor.predict(_features(0.3, n=2))
          if out['a_predicted'].shape != (2,):
            failures.append(f'bad shape {out["a_predicted"].shape}')
        except Exception as e:  # pylint: disable=broad-except
          failures.append(repr(e))

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(4)]
    for thread in threads:
      thread.start()
    for version in range(2, 7):
      exporter.export(
          model, trainer.state.replace(step=trainer.state.step + version),
          root, version=version)
      assert predictor.restore()
    stop.set()
    for thread in threads:
      thread.join(timeout=30.0)
    assert not failures, failures[:5]
    assert predictor.global_step == int(trainer.state.step) + 6

  def test_reader_writer_lock_exclusion_and_writer_preference(self):
    lock = ReaderWriterLock()
    state = {'writers': 0, 'readers': 0, 'max_readers_during_write': 0}
    errors = []
    stop = threading.Event()

    def reader():
      while not stop.is_set():
        with lock.read_locked():
          state['readers'] += 1
          if state['writers']:
            errors.append('reader inside write section')
          state['readers'] -= 1

    threads = [threading.Thread(target=reader, daemon=True)
               for _ in range(4)]
    for thread in threads:
      thread.start()
    # Writer-preference: the writer must get in despite 4 hot readers.
    for _ in range(20):
      t0 = time.monotonic()
      lock.acquire_write()
      try:
        state['writers'] = 1
        if state['readers']:
          errors.append('writer overlapped readers')
        state['writers'] = 0
      finally:
        lock.release_write()
      assert time.monotonic() - t0 < 5.0  # no starvation
    stop.set()
    for thread in threads:
      thread.join(timeout=10.0)
    assert not errors, errors[:5]


# --------------------------------------------------- stateless predictor API


def test_stateless_serving_fn_matches_predict():
  predictor = _loaded_checkpoint_predictor()
  serving = predictor.stateless_serving_fn()
  assert serving.version == 0
  import jax

  batch = _features(0.25, n=3)
  jitted_fn = jax.jit(serving.fn)
  out = jitted_fn(serving.params, batch)
  want = predictor.predict(batch)
  np.testing.assert_allclose(np.asarray(out['a_predicted']),
                             want['a_predicted'], rtol=2e-5)
  # A later restore produces a NEW snapshot; this one is immutable.
  assert serving.program_key == predictor.stateless_serving_fn().program_key


# ----------------------------------------------------------- HTTP + metricsz


class TestHTTP:

  def test_predict_health_statz_and_errors(self):
    predictor = _loaded_checkpoint_predictor()
    with server_lib.ServingServer(
        predictor, max_batch=8, batch_deadline_ms=1.0) as server:
      url = server.url

      def post(path, payload):
        req = urllib.request.Request(
            url + path, data=json.dumps(payload).encode(),
            headers={'Content-Type': 'application/json'})
        try:
          with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
          return e.code, json.loads(e.read())

      status, body = post(
          '/v1/predict',
          {'features': {'measured_position': [[0.1, 0.2], [0.3, 0.4]]}})
      assert status == 200
      assert len(body['outputs']['a_predicted']) == 2
      assert body['examples'] == 2
      assert body['model_version'] == 0

      # Single example without batch dim: the dim-expansion contract.
      status, body = post('/v1/predict',
                          {'measured_position': [0.1, 0.2]})
      assert status == 200 and body['examples'] == 1

      status, body = post('/v1/predict', {'features': {}})
      assert status == 400
      status, body = post('/v1/predict',
                          {'features': {'measured_position':
                                        [[0.1, 0.2, 0.3]]}})
      assert status == 400 and 'shape' in body['error']

      with urllib.request.urlopen(url + '/healthz', timeout=30) as r:
        health = json.loads(r.read())
      assert health == {'status': 'ok', 'model_version': 0}
      with urllib.request.urlopen(url + '/statz', timeout=30) as r:
        statz = json.loads(r.read())
      assert statz['max_batch'] == 8
      assert statz['requests'] >= 2


def test_metricsz_serving_report_e2e():
  """The serving section rides the registry's /metricsz endpoint via
  register_report_provider — the fleet-scrape integration."""
  from tensor2robot_tpu.observability import metricsz

  predictor = _loaded_checkpoint_predictor()
  with batching_lib.DynamicBatcher(
      predictor, max_batch=4, batch_deadline_ms=1.0) as batcher:
    batcher.submit(_features(0.1)).result(30.0)
    server = metricsz.MetricsServer(port=0).start()
    try:
      with urllib.request.urlopen(
          f'http://127.0.0.1:{server.port}/metricsz', timeout=30) as r:
        report = json.loads(r.read())
    finally:
      server.close()
  serving = report['serving']
  assert serving['max_batch'] == 4
  assert serving['requests'] >= 1
  assert serving['model_version'] == 0
  assert 'request_latency_ms_p99' in serving
  assert report['metrics'].get('serving/requests', 0) >= 1
  # Closing the batcher unregisters the provider.
  assert 'serving' not in metrics_lib.report()


# --------------------------------------------------- restart goodput slice


@pytest.mark.parametrize('placed', [True, False],
                         ids=['env_places_it', 'default_in_checkout'])
def test_compilation_cache_populates_dir(tmp_path, placed):
  """The one cache rule, end-to-end in a clean subprocess (the cache
  config is process-global). With JAX_COMPILATION_CACHE_DIR set, the
  program leaves ``jax_compilation_cache_dir`` as jax read it and the
  executables land there; unset, the cache is the fixed in-checkout
  path. Either way it is on without being asked for."""
  cache_dir = (str(tmp_path / 'xla-cache') if placed
               else os.path.join(REPO, '.jax_cache'))
  script = (
      "import os, sys\n"
      "import jax, jax.numpy as jnp\n"
      "from tensor2robot_tpu.utils import compilation_cache as cc\n"
      "want, placed = sys.argv[1], sys.argv[2] == 'True'\n"
      "assert placed or want == cc.DEFAULT_DIR\n"
      "updated = []\n"
      "update = jax.config.update\n"
      "jax.config.update = lambda k, v: (updated.append(k), update(k, v))\n"
      "assert cc.enable_compilation_cache() == want, cc.enabled_dir()\n"
      "assert cc.enable_compilation_cache() == want  # idempotent\n"
      "assert cc.enabled_dir() == want\n"
      "assert jax.config.jax_compilation_cache_dir == want\n"
      "assert ('jax_compilation_cache_dir' in updated) == (not placed)\n"
      "if placed:\n"
      "  jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0))\n"
      "  assert os.listdir(want), 'no cache entries written'\n"
      "print('CACHE_OK')\n")
  env = dict(os.environ)
  env['PYTHONPATH'] = REPO + os.pathsep + env.get('PYTHONPATH', '')
  env['JAX_PLATFORMS'] = 'cpu'
  # conftest turns the cache off for the suite; this child is the test
  # OF the cache.
  env.pop('JAX_ENABLE_COMPILATION_CACHE', None)
  env.pop('JAX_COMPILATION_CACHE_DIR', None)
  if placed:
    env['JAX_COMPILATION_CACHE_DIR'] = cache_dir
  default_dir = os.path.join(REPO, '.jax_cache')
  before = os.listdir(default_dir) if os.path.isdir(default_dir) else None
  proc = subprocess.run(
      [sys.executable, '-c', script, cache_dir, str(placed)],
      capture_output=True, text=True, timeout=300, env=env)
  assert proc.returncode == 0, proc.stderr[-2000:]
  assert 'CACHE_OK' in proc.stdout
  if placed:
    # ... and nowhere else: the in-checkout default stayed as it was.
    assert before == (os.listdir(default_dir)
                      if os.path.isdir(default_dir) else None)


def test_restart_to_first_step_gauge(tmp_path):
  from tensor2robot_tpu.train import trainer as trainer_mod

  trainer_mod._restart_recorded = False  # per-process latch; re-arm
  gauge = metrics_lib.gauge('trainer/restart_to_first_step_seconds')
  gauge.set(0.0)
  _trained_trainer(tmp_path, steps=2)
  assert gauge.value > 0.0
  # Latched: a SECOND train run in the process is not a restart.
  value = gauge.value
  _trained_trainer(tmp_path / 'second', steps=2)
  assert gauge.value == value


class TestCloseDrainsBacklog:
  """``close()`` under ACTIVE backpressure: the queue is at its bound
  (new submits 503ing) and the in-flight dispatch is stuck — close must
  still complete every queued request before stopping the dispatcher.
  Earlier drills only closed idle or lightly-loaded batchers."""

  class _Gated(AbstractPredictor):
    """Dispatch blocks until ``release`` fires — a deterministic
    backlog."""

    def __init__(self, release):
      self._release = release

    def predict(self, features):
      self._release.wait(timeout=30.0)
      return {'echo': np.asarray(features['x'])}

    def get_feature_specification(self):
      spec = SpecStruct()
      spec['x'] = TensorSpec(shape=(2,), dtype=np.float32, name='x')
      return spec

    def restore(self):
      return True

    @property
    def is_loaded(self):
      return True

    @property
    def global_step(self):
      return 1

  def test_close_completes_full_backlog_under_backpressure(self):
    release = threading.Event()
    batcher = batching_lib.DynamicBatcher(
        self._Gated(release), max_batch=2, batch_deadline_ms=1.0,
        max_queue=6, metrics_prefix='serving/drain_drill',
        register_report=False)
    batcher.start()
    try:
      futures = []
      overloaded = 0
      for i in range(12):
        try:
          futures.append(batcher.submit(
              {'x': np.full((1, 2), float(i), np.float32)}))
        except batching_lib.OverloadedError:
          overloaded += 1
      # The queue hit its bound while the dispatcher was stuck: this IS
      # active backpressure, not a lightly-loaded close.
      assert overloaded >= 1
      assert len(futures) >= 6
      assert batcher.queue_depth >= 6

      closer = threading.Thread(target=batcher.close, daemon=True)
      closer.start()
      time.sleep(0.2)
      assert closer.is_alive()  # close() is WAITING on the backlog
      # Submits during the drain are refused, not queued forever.
      with pytest.raises(batching_lib.OverloadedError):
        batcher.submit({'x': np.zeros((1, 2), np.float32)})
      release.set()
      closer.join(timeout=60.0)
      assert not closer.is_alive()
      # EVERY accepted request completed — none dropped by the drain.
      for i, future in enumerate(futures):
        out = future.result(timeout=1.0)
        np.testing.assert_array_equal(
            out['echo'], np.full((1, 2), float(i), np.float32))
      with pytest.raises(batching_lib.OverloadedError):
        batcher.submit({'x': np.zeros((1, 2), np.float32)})
    finally:
      release.set()
      batcher.close()


class TestModelHandoffAtomicity:
  """Regression: the reload→dispatcher generation handoff is atomic.

  PR 8's lock-discipline checker flagged the dispatcher's bare
  read-then-clear of ``_pending_model``: a generation staged by the
  reload poller between those two steps was silently dropped (the plane
  kept serving the old weights until a later poll noticed the version
  skew). The handoff now lives in ``_adopt_pending_model`` under the
  batcher's condition lock; these tests pin the atomic contract.
  """

  def _bare_batcher(self):
    # No start(): the handoff state machine is exercised directly.
    return batching_lib.DynamicBatcher(predictor=object())

  def test_adopt_returns_staged_and_clears(self):
    batcher = self._bare_batcher()
    staged = object()
    with batcher._cond:
      batcher._pending_model = staged
    assert batcher._adopt_pending_model() is staged
    assert batcher._model is staged
    assert batcher._pending_model is None
    assert batcher._adopt_pending_model() is None  # nothing staged

  def test_no_staged_generation_is_ever_lost(self):
    batcher = self._bare_batcher()
    n_stage = 400
    adopted = []
    done = threading.Event()

    def reloader():
      # The poller's publish step (maybe_reload's tail), hammered.
      for i in range(n_stage):
        with batcher._cond:
          batcher._pending_model = ('gen', i)
      done.set()

    def dispatcher():
      while not done.is_set() or batcher._pending_model is not None:
        model = batcher._adopt_pending_model()
        if model is not None:
          adopted.append(model)

    threads = [threading.Thread(target=reloader),
               threading.Thread(target=dispatcher)]
    for t in threads:
      t.start()
    for t in threads:
      t.join(timeout=30)
      assert not t.is_alive()
    # Overwritten stagings are legal (a newer generation replaces an
    # un-adopted older one) — but the LAST staged generation must never
    # be dropped, and adoption order must be monotonic.
    assert adopted, 'dispatcher never adopted anything'
    assert adopted[-1] == ('gen', n_stage - 1)
    indices = [i for _, i in adopted]
    assert indices == sorted(indices)
    assert batcher._model == ('gen', n_stage - 1)
