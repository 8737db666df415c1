"""E2E slice: mock model trains to convergence, checkpoints, resumes.

Mirrors the reference's ``utils/train_eval_test.py:91-138`` (train on
linearly-separable mock data, assert convergence + artifacts) and the
fixture pattern of ``utils/t2r_test_fixture.py:37-128``.
"""

import os
import threading

import jax
import numpy as np
import pytest

from tensor2robot_tpu import parallel
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.train import (Trainer, TrainerConfig, train_eval_model,
                                    latest_checkpoint_step)
from tensor2robot_tpu.models import optimizers as opt_lib
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel


def fast_adam():
  return opt_lib.create_adam_optimizer(1e-2)


def make_generators(model, batch_size=32):
  train_gen = MockInputGenerator(batch_size=batch_size)
  eval_gen = MockInputGenerator(batch_size=batch_size)
  train_gen.set_specification_from_model(model, ModeKeys.TRAIN)
  eval_gen.set_specification_from_model(model, ModeKeys.EVAL)
  return train_gen, eval_gen


def test_mock_model_converges(tmp_path):
  model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
  metrics = train_eval_model(
      model=model,
      model_dir=str(tmp_path / 'm'),
      train_input_generator=MockInputGenerator(batch_size=32),
      eval_input_generator=MockInputGenerator(batch_size=32),
      max_train_steps=400,
      eval_steps=10,
      eval_interval_steps=200,
      save_interval_steps=200,
      log_interval_steps=100)
  assert metrics['accuracy'] > 0.95, metrics
  assert metrics['loss'] < 0.3, metrics
  # Checkpoint artifacts exist.
  assert latest_checkpoint_step(str(tmp_path / 'm' / 'checkpoints')) == 400


def test_trainer_resumes_from_checkpoint(tmp_path):
  model_dir = str(tmp_path / 'm')

  def run(max_steps):
    model = MockT2RModel(device_type='tpu')
    return train_eval_model(
        model=model,
        model_dir=model_dir,
        train_input_generator=MockInputGenerator(batch_size=16),
        max_train_steps=max_steps,
        save_interval_steps=10,
        eval_interval_steps=0,
        log_interval_steps=0)

  run(10)
  assert latest_checkpoint_step(os.path.join(model_dir, 'checkpoints')) == 10
  run(20)  # restores step 10 and trains 10 more
  assert latest_checkpoint_step(os.path.join(model_dir, 'checkpoints')) == 20


def test_save_interval_zero_disables_periodic_saves(tmp_path):
  """``save_interval_steps=0`` means NO periodic checkpoints (the
  interval==0-disables convention) — it used to modulo-by-zero when a
  model_dir was set. The end-of-training save still happens."""
  model = MockT2RModel(device_type='tpu')
  model_dir = str(tmp_path / 'm')
  gen = MockInputGenerator(batch_size=8)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  config = TrainerConfig(
      model_dir=model_dir, max_train_steps=3, save_interval_steps=0,
      eval_interval_steps=0, log_interval_steps=0, async_checkpoints=False)
  trainer = Trainer(model, config)
  trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
  # Only the final forced save exists.
  assert latest_checkpoint_step(os.path.join(model_dir, 'checkpoints')) == 3


def test_trainer_bf16_boundary():
  """TPU dtype policy: device-side features arrive bfloat16."""
  model = MockT2RModel(device_type='tpu')
  spec = model.preprocessor.get_out_feature_specification(ModeKeys.TRAIN)
  assert spec['measured_position'].dtype.name == 'bfloat16'
  # Host-side (in) spec stays float32.
  in_spec = model.preprocessor.get_in_feature_specification(ModeKeys.TRAIN)
  assert in_spec['measured_position'].dtype.name == 'float32'


def test_trainer_on_8_device_mesh(tmp_path):
  """Data-parallel over the virtual 8-device CPU mesh."""
  mesh = parallel.create_mesh(data=-1)
  assert mesh.shape['data'] == 8
  model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
  metrics = train_eval_model(
      model=model,
      model_dir=str(tmp_path / 'm'),
      train_input_generator=MockInputGenerator(batch_size=32),
      eval_input_generator=MockInputGenerator(batch_size=32),
      max_train_steps=200,
      eval_steps=5,
      eval_interval_steps=0,
      save_interval_steps=100,
      log_interval_steps=0,
      mesh=mesh)
  assert metrics['accuracy'] > 0.9, metrics


def test_trainer_tensor_parallel_rules(tmp_path):
  """Model-declared TP rules shard the named params over `model` and the
  Megatron pair still converges (GSPMD inserts the collectives)."""

  class TPModel(MockT2RModel):

    def param_sharding_rules(self, mesh):
      return (
          (r'Dense_0/kernel$', (None, parallel.MODEL_AXIS)),
          (r'Dense_0/bias$', (parallel.MODEL_AXIS,)),
          (r'Dense_1/kernel$', (parallel.MODEL_AXIS, None)),
      )

  mesh = parallel.create_mesh(data=2, fsdp=2, model=2)
  model = TPModel(device_type='tpu', create_optimizer_fn=fast_adam)
  config = TrainerConfig(model_dir='', max_train_steps=1,
                         eval_interval_steps=0, log_interval_steps=0)
  trainer = Trainer(model, config, mesh=mesh)
  gen = MockInputGenerator(batch_size=32)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  features, _ = next(gen.create_iterator(ModeKeys.TRAIN))
  trainer.initialize(features)
  sharding = trainer._state_sharding()  # pylint: disable=protected-access
  k0 = sharding.params['Dense_0']['kernel'].spec
  k1 = sharding.params['Dense_1']['kernel'].spec
  assert tuple(k0) == (None, parallel.MODEL_AXIS), k0
  assert tuple(k1)[0] == parallel.MODEL_AXIS, k1

  metrics = train_eval_model(
      model=TPModel(device_type='tpu', create_optimizer_fn=fast_adam),
      model_dir='',
      train_input_generator=MockInputGenerator(batch_size=32),
      eval_input_generator=MockInputGenerator(batch_size=32),
      max_train_steps=200,
      eval_steps=5,
      eval_interval_steps=0,
      log_interval_steps=0,
      mesh=mesh)
  assert metrics['accuracy'] > 0.9, metrics


def test_prefetch_is_bitwise_identical(tmp_path):
  """Bounded device prefetch (background staging thread) preserves batch
  order, so training is bit-identical to the inline path."""
  import numpy as np

  results = {}
  for prefetch in (0, 2):
    model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
    config = TrainerConfig(
        model_dir='', max_train_steps=20, eval_interval_steps=0,
        log_interval_steps=0, prefetch_batches=prefetch)
    trainer = Trainer(model, config)
    gen = MockInputGenerator(batch_size=8)
    gen.set_specification_from_model(model, ModeKeys.TRAIN)
    trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
    results[prefetch] = jax.device_get(trainer.state.params)
  flat0 = jax.tree_util.tree_leaves(results[0])
  flat2 = jax.tree_util.tree_leaves(results[2])
  for a, b in zip(flat0, flat2):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prefetch_depth1_close_terminates_worker():
  """close() must fully unblock a depth-1 worker (its final _DONE put
  could otherwise block forever), leaving no leaked thread."""
  import itertools
  import threading

  from tensor2robot_tpu.train.trainer import _DevicePrefetcher

  src = iter(itertools.count())
  prefetcher = _DevicePrefetcher(src, lambda b: b, depth=1)
  next(iter(prefetcher))  # consume one so the worker is mid-stream
  prefetcher.close()
  for thread in prefetcher._threads:  # pylint: disable=protected-access
    thread.join(timeout=5)
    assert not thread.is_alive()
  assert threading.active_count() < 50


def test_prefetch_propagates_iterator_errors():
  """An input-iterator exception surfaces on the training thread."""
  import pytest

  model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
  config = TrainerConfig(model_dir='', max_train_steps=50,
                         eval_interval_steps=0, log_interval_steps=0,
                         prefetch_batches=2)
  trainer = Trainer(model, config)
  gen = MockInputGenerator(batch_size=8)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  real = gen.create_iterator(ModeKeys.TRAIN)

  def broken():
    for i, batch in enumerate(real):
      if i == 5:
        raise RuntimeError('decode failed')
      yield batch

  with pytest.raises(RuntimeError, match='decode failed'):
    trainer.train(broken(), None)


def test_sharding_rule_validation():
  """ADVICE r2: duplicate mesh axes in one rule spec raise a clear error
  up front, and the 'replicated' sentinel pins a param replicated
  instead of falling through to the fsdp default."""
  import numpy as np
  import pytest

  from tensor2robot_tpu.parallel import mesh as mesh_lib

  mesh = parallel.create_mesh(data=2, fsdp=2, model=2)
  param = np.zeros((4, 4), np.float32)
  with pytest.raises(ValueError, match='more than once'):
    mesh_lib.rule_param_sharding(
        mesh, 'dense/kernel', param,
        ((r'kernel$', (parallel.MODEL_AXIS, parallel.MODEL_AXIS)),))
  with pytest.raises(ValueError, match='sentinel'):
    mesh_lib.rule_param_sharding(
        mesh, 'dense/kernel', param, ((r'kernel$', 'bogus'),))
  pinned = mesh_lib.rule_param_sharding(
      mesh, 'dense/kernel', param, ((r'kernel$', mesh_lib.REPLICATED),))
  assert tuple(pinned.spec) == ()
  # An all-degenerate tuple spec still falls through (returns None) so
  # the fsdp default applies — distinct from the explicit sentinel.
  assert mesh_lib.rule_param_sharding(
      mesh, 'dense/kernel', param, ((r'kernel$', (None, None)),)) is None


def test_trainer_fsdp_mesh(tmp_path):
  """Params sharded over the fsdp axis still converge."""
  mesh = parallel.create_mesh(data=2, fsdp=4)
  model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
  metrics = train_eval_model(
      model=model,
      model_dir='',
      train_input_generator=MockInputGenerator(batch_size=32),
      eval_input_generator=MockInputGenerator(batch_size=32),
      max_train_steps=200,
      eval_steps=5,
      eval_interval_steps=0,
      log_interval_steps=0,
      mesh=mesh)
  assert metrics['accuracy'] > 0.9, metrics


def test_ema_params_tracked(tmp_path):
  model = MockT2RModel(device_type='cpu', use_avg_model_params=True)
  config = TrainerConfig(model_dir='', max_train_steps=5,
                         eval_interval_steps=0, log_interval_steps=0)
  trainer = Trainer(model, config)
  gen, _ = make_generators(model, batch_size=8)
  it = gen.create_iterator(ModeKeys.TRAIN)
  trainer.train(it, None)
  assert trainer.state.ema_params is not None
  # EMA differs from live params after updates.
  import jax
  diff = jax.tree_util.tree_reduce(
      lambda acc, x: acc + float(np.sum(np.abs(x))),
      jax.tree_util.tree_map(
          lambda a, b: np.asarray(a) - np.asarray(b),
          trainer.state.params, trainer.state.ema_params),
      0.0)
  assert diff > 0.0


def test_predict_from_model():
  from tensor2robot_tpu.train import predict_from_model

  model = MockT2RModel(device_type='tpu')
  gen = MockInputGenerator(batch_size=4)
  stream = predict_from_model(
      model=model, input_generator=gen, model_dir='')
  out = next(stream)
  assert 'a_predicted' in out
  assert np.asarray(out['a_predicted']).shape == (4,)
  assert np.all(np.asarray(out['a_predicted']) >= 0.0)
  assert np.all(np.asarray(out['a_predicted']) <= 1.0)


def test_eval_backup_survives_trainer_gc(tmp_path):
  """Evaluator backs up the checkpoint; trainer GC can't break eval.

  VERDICT #9 done-criterion (ref utils/train_eval.py:590-707): the trainer
  deletes the checkpoint after the evaluator's backup; eval still
  completes from the backup copy.
  """
  import shutil

  from tensor2robot_tpu.train import checkpoints as ckpt_lib

  model = MockT2RModel(device_type='cpu', create_optimizer_fn=fast_adam)
  train_gen, eval_gen = make_generators(model)
  config = TrainerConfig(
      model_dir=str(tmp_path / 'm'), max_train_steps=4,
      save_interval_steps=4, eval_interval_steps=0, log_interval_steps=0,
      async_checkpoints=False)
  trainer = Trainer(model, config)
  trainer.train(train_gen.create_iterator(ModeKeys.TRAIN), None)
  trainer.close()

  ckpt_dir = str(tmp_path / 'm' / 'checkpoints')
  backup_dir = str(tmp_path / 'm' / ckpt_lib.EVAL_BACKUP_DIRNAME)
  step = latest_checkpoint_step(ckpt_dir)
  assert step == 4

  backup = ckpt_lib.create_backup_checkpoint_for_eval(
      ckpt_dir, step, backup_dir)
  assert backup is not None and os.path.isdir(backup)

  # Trainer GC deletes the original checkpoint mid-eval.
  shutil.rmtree(os.path.join(ckpt_dir, f'ckpt_{step}'))
  assert latest_checkpoint_step(ckpt_dir) is None

  evaluator = Trainer(model, TrainerConfig(
      model_dir='', max_train_steps=4, eval_steps=2,
      eval_interval_steps=0, log_interval_steps=0))
  features, _ = next(eval_gen.create_iterator(ModeKeys.EVAL))
  evaluator.initialize(features)
  restored = ckpt_lib.restore_from_backup(evaluator.state, backup)
  assert restored is not None
  evaluator._state = restored
  metrics = evaluator.evaluate(eval_gen.create_iterator(ModeKeys.EVAL))
  assert np.isfinite(metrics['loss'])
  assert int(restored.step) == 4


def test_backup_detects_gc_race(tmp_path):
  """A checkpoint GC'd before backup returns None instead of a partial copy."""
  from tensor2robot_tpu.train import checkpoints as ckpt_lib

  ckpt_dir = str(tmp_path / 'checkpoints')
  os.makedirs(ckpt_dir)
  backup = ckpt_lib.create_backup_checkpoint_for_eval(
      ckpt_dir, 7, str(tmp_path / 'backup'))
  assert backup is None


def test_warm_start_partial_restore(tmp_path):
  """default_init_from_checkpoint_fn restores a parameter subset.

  VERDICT #10 done-criterion (ref models/abstract_model.py:88-118): warm
  start a fresh model from an Orbax checkpoint, restoring a subset of
  params, leaving the excluded subtree freshly initialized.
  """
  from tensor2robot_tpu.models import default_init_from_checkpoint_fn

  # Train a source model and checkpoint it.
  model = MockT2RModel(device_type='cpu', create_optimizer_fn=fast_adam)
  train_gen, _ = make_generators(model)
  config = TrainerConfig(
      model_dir=str(tmp_path / 'src'), max_train_steps=3,
      save_interval_steps=3, eval_interval_steps=0, log_interval_steps=0,
      async_checkpoints=False)
  trainer = Trainer(model, config)
  trainer.train(train_gen.create_iterator(ModeKeys.TRAIN), None)
  trainer.close()
  src_params = jax.tree_util.tree_map(np.asarray, trainer.state.params)
  ckpt = str(tmp_path / 'src' / 'checkpoints' / 'ckpt_3')

  # Fresh model warm-started from the checkpoint, excluding the out head.
  warm = MockT2RModel(
      device_type='cpu',
      init_from_checkpoint_fn=default_init_from_checkpoint_fn(
          ckpt, exclude=('Dense_2',)))
  gen2, _ = make_generators(warm)
  trainer2 = Trainer(warm, TrainerConfig(
      model_dir='', max_train_steps=1, eval_interval_steps=0,
      log_interval_steps=0))
  features, _ = next(gen2.create_iterator(ModeKeys.TRAIN))
  trainer2.initialize(features)
  new_params = jax.tree_util.tree_map(np.asarray, trainer2.state.params)

  flat_src = {jax.tree_util.keystr(p): v for p, v
              in jax.tree_util.tree_leaves_with_path(src_params)}
  flat_new = {jax.tree_util.keystr(p): v for p, v
              in jax.tree_util.tree_leaves_with_path(new_params)}
  restored = excluded = 0
  for key in flat_src:
    if 'Dense_2' in key:
      excluded += 1
      assert not np.allclose(flat_src[key], flat_new[key]), key
    else:
      restored += 1
      np.testing.assert_allclose(flat_src[key], flat_new[key], err_msg=key)
  assert restored > 0 and excluded > 0


def test_warm_start_no_match_raises(tmp_path):
  from tensor2robot_tpu.models import default_init_from_checkpoint_fn

  model = MockT2RModel(device_type='cpu', create_optimizer_fn=fast_adam)
  train_gen, _ = make_generators(model)
  config = TrainerConfig(
      model_dir=str(tmp_path / 'src'), max_train_steps=1,
      save_interval_steps=1, eval_interval_steps=0, log_interval_steps=0,
      async_checkpoints=False)
  trainer = Trainer(model, config)
  trainer.train(train_gen.create_iterator(ModeKeys.TRAIN), None)
  trainer.close()
  ckpt = str(tmp_path / 'src' / 'checkpoints' / 'ckpt_1')

  warm = MockT2RModel(
      device_type='cpu',
      init_from_checkpoint_fn=default_init_from_checkpoint_fn(
          ckpt, include=('no_such_module',)))
  gen2, _ = make_generators(warm)
  trainer2 = Trainer(warm, TrainerConfig(
      model_dir='', max_train_steps=1, eval_interval_steps=0,
      log_interval_steps=0))
  features, _ = next(gen2.create_iterator(ModeKeys.TRAIN))
  with pytest.raises(ValueError, match='matched no parameters'):
    trainer2.initialize(features)


def test_tensorboard_callback_writes_events(tmp_path):
  from tensor2robot_tpu.train.callbacks import TensorBoardCallback

  model = MockT2RModel(device_type='cpu', create_optimizer_fn=fast_adam)
  train_gen, eval_gen = make_generators(model)
  config = TrainerConfig(
      model_dir=str(tmp_path / 'm'), max_train_steps=4,
      save_interval_steps=4, eval_interval_steps=4, log_interval_steps=2,
      async_checkpoints=False)
  trainer = Trainer(model, config, callbacks=[TensorBoardCallback()])
  trainer.train(train_gen.create_iterator(ModeKeys.TRAIN),
                lambda: eval_gen.create_iterator(ModeKeys.EVAL))
  trainer.close()
  for kind in ('train', 'eval'):
    event_dir = str(tmp_path / 'm' / 'events' / kind)
    assert os.path.isdir(event_dir), event_dir
    assert any(n.startswith('events.out.tfevents')
               for n in os.listdir(event_dir)), os.listdir(event_dir)


def test_steps_per_dispatch_matches_single_step_path():
  """K steps folded into one lax.scan dispatch train IDENTICALLY to K
  single dispatches (same batches, same per-step rng fold_in keyed off
  state.step), including a short final group (7 = 3+3+1)."""
  def run(k):
    model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
    gen = MockInputGenerator(batch_size=8)
    gen.set_specification_from_model(model, ModeKeys.TRAIN)
    trainer = Trainer(model, TrainerConfig(
        model_dir='', max_train_steps=7, eval_interval_steps=0,
        log_interval_steps=0, prefetch_batches=0,
        steps_per_dispatch=k))
    scalars = trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
    return trainer, scalars

  t1, s1 = run(1)
  t3, s3 = run(3)
  assert int(t1.step) == int(t3.step) == 7
  np.testing.assert_allclose(float(s1['loss']), float(s3['loss']), rtol=1e-5)
  p1 = jax.device_get(t1.state.params)
  p3 = jax.device_get(t3.state.params)
  jax.tree_util.tree_map(
      lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7),
      p1, p3)


def test_steps_per_dispatch_quantizes_intervals(tmp_path):
  """Checkpoints fire at the first dispatch boundary on or after each
  save-interval multiple (iterations_per_loop semantics), and the final
  state is saved: K=3, interval 2, 7 steps -> saves at 3, 6, 7."""
  model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
  gen = MockInputGenerator(batch_size=8)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  trainer = Trainer(model, TrainerConfig(
      model_dir=str(tmp_path / 'm'), max_train_steps=7,
      save_interval_steps=2, eval_interval_steps=0, log_interval_steps=0,
      prefetch_batches=0, async_checkpoints=False,
      steps_per_dispatch=3))
  trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
  assert trainer._manager.all_steps() == [3, 6, 7]


def test_steps_per_dispatch_with_prefetch():
  """The grouped path composes with the prefetcher: stacked groups
  placed ahead of the loop, the scan body compiled over them."""
  model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
  gen = MockInputGenerator(batch_size=8)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  trainer = Trainer(model, TrainerConfig(
      model_dir='', max_train_steps=6, eval_interval_steps=0,
      log_interval_steps=0, prefetch_batches=2, steps_per_dispatch=2))
  scalars = trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
  assert int(trainer.step) == 6
  assert np.isfinite(float(scalars['loss']))


def test_steps_per_dispatch_callback_cadence(tmp_path):
  """Stock callbacks keep their interval semantics at K>1 via
  trainer.crossed(): every crossed multiple logs once, at the dispatch
  boundary at-or-after it — not only at lcm(K, interval)."""
  import json

  from tensor2robot_tpu.train.callbacks import MetricsLoggerCallback

  model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
  gen = MockInputGenerator(batch_size=8)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  trainer = Trainer(model, TrainerConfig(
      model_dir=str(tmp_path / 'm'), max_train_steps=9,
      save_interval_steps=0, eval_interval_steps=0, log_interval_steps=2,
      prefetch_batches=0, async_checkpoints=False,
      steps_per_dispatch=3), callbacks=[MetricsLoggerCallback()])
  trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
  with open(tmp_path / 'm' / 'metrics.jsonl') as f:
    steps = [json.loads(line)['step'] for line in f
             if json.loads(line)['kind'] == 'train']
  # Boundaries 3, 6, 9; interval 2 crossings: (0,3]:2, (3,6]:4+6, (6,9]:8.
  assert steps == [3, 6, 9], steps


def test_steps_per_dispatch_handles_ragged_tail():
  """A final smaller batch (ragged tail from a finite iterator) closes
  the current group early and trains in its own short group instead of
  crashing np.stack — the K>1 analogue of the K=1 off-shape fallback."""
  from tensor2robot_tpu.specs import SpecStruct

  rng = np.random.RandomState(0)

  def make_batch(n):
    feats = SpecStruct()
    feats['measured_position'] = rng.uniform(-1, 1, (n, 2)).astype(
        np.float32)
    labels = SpecStruct()
    labels['valid_position'] = (
        feats['measured_position'].sum(axis=1) > 0).astype(np.float32)
    return feats, labels

  model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
  trainer = Trainer(model, TrainerConfig(
      model_dir='', max_train_steps=2, eval_interval_steps=0,
      log_interval_steps=0, prefetch_batches=0,
      steps_per_dispatch=3))
  trainer.train(iter([make_batch(8), make_batch(5)]), None)
  assert int(trainer.step) == 2


@pytest.mark.parametrize('mode,k', [
    ('staged', 1), ('consumer', 1), ('inline', 1),
    ('staged', 2), ('consumer', 2), ('inline', 2)])
def test_spans_are_keyed_by_dispatch_and_tile_the_loop(request, mode, k):
  """Every stage's spans carry the batch ordinal, which is the dispatch
  ordinal (under K > 1, the group's), on every placement path: the
  dedicated place stage (forced on: it is TPU-only by default), the
  consumer-thread placement behind one fetch thread, and no prefetch.
  The four loop-thread spans leave no time between boundaries outside
  them."""
  import time

  from tensor2robot_tpu.observability import metrics, tracing

  if mode == 'staged':
    request.getfixturevalue('forced_place_stage')
  steps = 12
  dispatches = steps // k
  model = MockT2RModel(device_type='cpu', create_optimizer_fn=fast_adam)
  gen = MockInputGenerator(batch_size=8)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  trainer = Trainer(model, TrainerConfig(
      model_dir='', max_train_steps=steps, eval_interval_steps=0,
      log_interval_steps=0, steps_per_dispatch=k,
      prefetch_batches=0 if mode == 'inline' else 2))
  bytes_before = metrics.counter('trainer/h2d/bytes').value
  mark = time.perf_counter_ns()
  trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
  spans = [s for s in tracing.recent(since_ns=mark) if s[1] >= mark]

  def keys(name):
    return [s[4] for s in spans if s[0] == name]

  def threads(name):
    return {s[3] for s in spans if s[0] == name}

  loop_thread = threading.current_thread().name
  every = list(range(dispatches))
  for name in ('trainer/wait_batch', 'trainer/dispatch',
               'trainer/after_dispatch', 'trainer/callbacks'):
    assert keys(name) == every, name
    assert threads(name) == {loop_thread}, name
  # The wait after enqueueing dispatch n is for the outputs of n-1.
  assert keys('trainer/device_wait') == every[:-1]
  # The feed runs ahead of the loop: the first N batches are the N
  # dispatches, in order, and what it fetched beyond them is numbered on.
  staged_names = ['trainer/place_stage', 'trainer/place/put']
  if mode != 'inline':
    staged_names += ['trainer/fetch', 'trainer/fetch_put']
  for name in staged_names:
    got = keys(name)
    assert got[:dispatches] == every and got == list(range(len(got))), name
  if mode == 'staged':
    assert threads('trainer/fetch') == {'t2r-prefetch-fetch'}
    assert threads('trainer/place_stage') == {'t2r-prefetch-place'}
    # The place stage waits for each batch to be on the device.
    assert keys('trainer/place/transfer')[:dispatches] == every
  else:
    assert threads('trainer/place_stage') == {loop_thread}
    # No lease, loop thread: nothing to wait for.
    assert not keys('trainer/place/transfer')
    if mode == 'consumer':
      assert threads('trainer/fetch') == {'t2r-prefetch'}
    else:
      assert not keys('trainer/fetch')
  # Children lie inside their parent, on its thread, under its key, in
  # the order put, transfer.
  stages = {s[4]: s for s in spans if s[0] == 'trainer/place_stage'}
  order = ['trainer/place/put', 'trainer/place/transfer']
  children = {}
  for s in spans:
    if s[0] in order:
      parent = stages[s[4]]
      assert parent[1] <= s[1] and s[2] <= parent[2] and s[3] == parent[3]
      children.setdefault(s[4], []).append(s)
  for parts in children.values():
    parts.sort(key=lambda s: order.index(s[0]))
    assert all(a[2] <= b[1] for a, b in zip(parts, parts[1:]))
  tails = {s[4]: s for s in spans if s[0] == 'trainer/after_dispatch'}
  for s in spans:
    if s[0] == 'trainer/callbacks':
      assert tails[s[4]][1] <= s[1] and s[2] <= tails[s[4]][2]
  # The four loop-thread spans tile the time between the first and the
  # last boundary: each starts where the one before ended.
  tiling = sorted((s for s in spans if s[0] in (
      'trainer/after_dispatch', 'trainer/wait_batch', 'trainer/dispatch',
      'trainer/device_wait')), key=lambda s: s[1:3])
  first = next(s[2] for s in tiling if s[0] == 'trainer/dispatch')
  last = max(s[2] for s in tiling if s[0] == 'trainer/device_wait')
  inside = [s for s in tiling if s[1] >= first and s[2] <= last]
  covered = sum(s[2] - s[1] for s in inside)
  assert covered >= 0.99 * (last - first)
  assert all(a[2] == b[1] for a, b in zip(inside, inside[1:]))
  # Bytes handed to the put: every batch the loop consumed, at least.
  batch_bytes = sum(
      np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(
          next(gen.create_iterator(ModeKeys.TRAIN))))
  sent = metrics.counter('trainer/h2d/bytes').value - bytes_before
  assert sent >= steps * batch_bytes and sent % batch_bytes == 0


@pytest.mark.parametrize('mode', ['inline', 'consumer', 'staged'])
def test_one_step_program_a_run(request, monkeypatch, mode):
  """A run has one step program: the step body is traced once (ledger
  off: no harvest), every dispatch calls the one jitted function that
  ``initialize`` built, and a placement leaves its put, its transfer
  and its stage on record and no other span."""
  import time

  from tensor2robot_tpu.observability import tracing

  if mode == 'staged':
    request.getfixturevalue('forced_place_stage')
  traces = []
  build_body = Trainer._train_step_body

  def counted_body(self):
    step = build_body(self)

    def train_step(*args):
      traces.append(1)
      return step(*args)

    return train_step

  monkeypatch.setattr(Trainer, '_train_step_body', counted_body)
  model = MockT2RModel(device_type='cpu', create_optimizer_fn=fast_adam)
  gen = MockInputGenerator(batch_size=8)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  trainer = Trainer(model, TrainerConfig(
      model_dir='', max_train_steps=12, eval_interval_steps=0,
      log_interval_steps=0, program_ledger=False,
      prefetch_batches=0 if mode == 'inline' else 2))
  batches = gen.create_iterator(ModeKeys.TRAIN)
  trainer.initialize(next(batches)[0])
  step_fn = trainer._train_step_fn
  dispatched = []

  def counted_step(*args):
    dispatched.append(1)
    return step_fn(*args)

  trainer._train_step_fn = counted_step
  mark = time.perf_counter_ns()
  trainer.train(batches, None)
  assert trainer.step == 12
  assert len(traces) == 1
  assert len(dispatched) == 12
  placement = {s[0] for s in tracing.recent(since_ns=mark)
               if s[1] >= mark and s[0].startswith('trainer/place')}
  assert placement == {'trainer/place_stage', 'trainer/place/put'} | (
      {'trainer/place/transfer'} if mode == 'staged' else set())


@pytest.mark.parametrize('name', ['auto_input_layouts', 'fused_update'])
def test_removed_step_knobs_are_refused(name):
  """A config in the wild that still sets a deleted knob is told so, by
  name, and is not silently ignored."""
  from tensor2robot_tpu import config as t2r_config

  with pytest.raises(TypeError, match=name):
    TrainerConfig(**{name: False})
  t2r_config.register_framework_configurables()
  t2r_config.parse_config(f'train_eval_model.{name} = False')
  try:
    with pytest.raises(t2r_config.ConfigError, match=name):
      t2r_config.get_configurable('train_eval_model')(
          model=MockT2RModel(device_type='cpu'))
  finally:
    t2r_config.clear_config()


def test_profiler_callback_window_at_k_dispatch(monkeypatch):
  """The profile window starts at the first dispatch boundary at-or-after
  start_step, stops at the first at-or-after stop_step — and a run
  resumed already past the window never starts a spurious trace."""
  from tensor2robot_tpu.train.callbacks import ProfilerCallback

  events = []
  monkeypatch.setattr(jax.profiler, 'start_trace',
                      lambda logdir: events.append('start'))
  monkeypatch.setattr(jax.profiler, 'stop_trace',
                      lambda: events.append('stop'))

  class FakeTrainer:
    def __init__(self):
      self.dispatch_start_step = 0
    class config:  # noqa: N801 - attribute container
      model_dir = ''

  trainer = FakeTrainer()

  # Fresh run, K=8, window [10, 15): starts at boundary 16, stops at 24.
  cb = ProfilerCallback(start_step=10, num_steps=5)
  for before, after in ((0, 8), (8, 16), (16, 24), (24, 32)):
    trainer.dispatch_start_step = before
    cb.after_step(trainer, after, {})
  assert events == ['start', 'stop']

  # Resumed far past the window: no trace at all.
  events.clear()
  cb = ProfilerCallback(start_step=10, num_steps=5)
  for before, after in ((5000, 5008), (5008, 5016)):
    trainer.dispatch_start_step = before
    cb.after_step(trainer, after, {})
  assert events == []


def test_input_state_resume_is_exact(tmp_path):
  """Interrupted training resumes the DATA STREAM with the model: 4 steps
  + checkpoint + fresh-process resume for 4 more equals 8 straight steps
  bit-for-bit, on a shuffled record stream. Beyond the reference, whose
  estimator input_fns restart from scratch on every job restart."""
  from tensor2robot_tpu.data.input_generators import (
      DefaultRecordInputGenerator)
  from tensor2robot_tpu.research.pose_env import PoseEnvRegressionModel
  from tensor2robot_tpu.train import InputStateCallback

  test_data = os.path.join(
      os.path.dirname(__file__), 'test_data', 'pose_env_test_data.tfrecord')

  def run(model_dir, max_steps):
    model = PoseEnvRegressionModel(device_type='tpu')
    gen = DefaultRecordInputGenerator(
        file_patterns=test_data, batch_size=4, shuffle_buffer_size=16,
        seed=13)
    gen.set_specification_from_model(model, ModeKeys.TRAIN)
    it = gen.create_checkpointable_iterator(ModeKeys.TRAIN)
    trainer = Trainer(model, TrainerConfig(
        model_dir=model_dir, max_train_steps=max_steps,
        save_interval_steps=4, eval_interval_steps=0, log_interval_steps=0,
        prefetch_batches=0,
        async_checkpoints=False), callbacks=[InputStateCallback(it)])
    trainer.train(it, None)
    return jax.device_get(trainer.state.params)

  straight = run(str(tmp_path / 'straight'), 8)
  run(str(tmp_path / 'resumed'), 4)      # "job 1" is preempted at 4
  resumed = run(str(tmp_path / 'resumed'), 8)  # "job 2" resumes to 8

  for a, b in zip(jax.tree_util.tree_leaves(straight),
                  jax.tree_util.tree_leaves(resumed)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_train_eval_model_checkpoint_input_state(tmp_path):
  """The gin-surface flag: train_eval_model(checkpoint_input_state=True)
  wires the resumable stream end-to-end, and rejects generators that
  cannot checkpoint their position instead of silently restarting."""
  from tensor2robot_tpu.data.input_generators import (
      DefaultRandomInputGenerator, DefaultRecordInputGenerator)
  from tensor2robot_tpu.research.pose_env import PoseEnvRegressionModel
  from tensor2robot_tpu.train.input_state import INPUT_STATE_DIRNAME

  test_data = os.path.join(
      os.path.dirname(__file__), 'test_data', 'pose_env_test_data.tfrecord')

  def run(max_steps):
    return train_eval_model(
        model=PoseEnvRegressionModel(device_type='tpu'),
        model_dir=str(tmp_path / 'm'),
        train_input_generator=DefaultRecordInputGenerator(
            file_patterns=test_data, batch_size=4, shuffle_buffer_size=8,
            seed=3),
        max_train_steps=max_steps, save_interval_steps=3,
        eval_interval_steps=0, log_interval_steps=0,
        checkpoint_input_state=True)

  run(3)
  state_root = tmp_path / 'm' / INPUT_STATE_DIRNAME / 'train' / 'process_0'
  assert (state_root / 'step_3').is_dir(), list(state_root.iterdir())
  run(6)  # resumes model AND stream
  assert (state_root / 'step_6').is_dir()
  assert latest_checkpoint_step(str(tmp_path / 'm' / 'checkpoints')) == 6

  with pytest.raises(ValueError, match='create_checkpointable_iterator'):
    train_eval_model(
        model=PoseEnvRegressionModel(device_type='tpu'),
        model_dir=str(tmp_path / 'm2'),
        train_input_generator=DefaultRandomInputGenerator(batch_size=4),
        max_train_steps=2, eval_interval_steps=0, log_interval_steps=0,
        checkpoint_input_state=True)


def test_input_state_missing_falls_back_to_fresh_stream(tmp_path, caplog):
  """A resumed run whose checkpoint predates the input-state feature (or
  whose state dir was deleted) warns and trains on a fresh stream — the
  reference's behavior, never an error."""
  import logging

  from tensor2robot_tpu.data.input_generators import (
      DefaultRecordInputGenerator)
  from tensor2robot_tpu.research.pose_env import PoseEnvRegressionModel
  from tensor2robot_tpu.train import InputStateCallback

  test_data = os.path.join(
      os.path.dirname(__file__), 'test_data', 'pose_env_test_data.tfrecord')

  def run(max_steps, with_callback):
    model = PoseEnvRegressionModel(device_type='tpu')
    gen = DefaultRecordInputGenerator(
        file_patterns=test_data, batch_size=4, shuffle_buffer_size=8,
        seed=5)
    gen.set_specification_from_model(model, ModeKeys.TRAIN)
    it = gen.create_checkpointable_iterator(ModeKeys.TRAIN)
    callbacks = [InputStateCallback(it)] if with_callback else []
    trainer = Trainer(model, TrainerConfig(
        model_dir=str(tmp_path / 'm'), max_train_steps=max_steps,
        save_interval_steps=2, eval_interval_steps=0, log_interval_steps=0,
        prefetch_batches=0,
        async_checkpoints=False), callbacks=callbacks)
    trainer.train(it, None)
    return trainer

  run(2, with_callback=False)   # checkpoint WITHOUT input state
  with caplog.at_level(logging.WARNING):
    trainer = run(4, with_callback=True)  # resumes; no state for step 2
  assert int(trainer.step) == 4
  assert any('no' in r.message.lower() and 'input state' in r.message.lower()
             for r in caplog.records), [r.message for r in caplog.records]

class TestCrossedInterval:
  """`crossed_interval` is the ONE interval authority for logging, eval,
  and checkpoint cadence — its edge cases gate all three."""

  def test_zero_interval_disables(self):
    from tensor2robot_tpu.train.trainer import crossed_interval
    assert not crossed_interval(0, 0, 1)
    assert not crossed_interval(0, 99, 100)

  def test_k1_reduces_to_modulo(self):
    from tensor2robot_tpu.train.trainer import crossed_interval
    for step in range(1, 50):
      assert crossed_interval(10, step - 1, step) == (step % 10 == 0)

  def test_fires_once_per_multiple_when_jumping(self):
    """With steps_per_dispatch > 1 the counter may jump over a multiple;
    the interval fires at the first boundary ON OR AFTER the multiple."""
    from tensor2robot_tpu.train.trainer import crossed_interval
    # Stride 7, interval 10: boundaries 7, 14, 21, 28, ...
    fired = [after for after in range(7, 71, 7)
             if crossed_interval(10, after - 7, after)]
    assert fired == [14, 21, 35, 42, 56, 63, 70]

  def test_jump_across_many_multiples_fires_once(self):
    from tensor2robot_tpu.train.trainer import crossed_interval
    # One dispatch crossing 3 multiples still reports a single crossing.
    assert crossed_interval(10, 0, 35)
    assert not crossed_interval(10, 35, 39)

  def test_exact_landing_does_not_refire_next_dispatch(self):
    from tensor2robot_tpu.train.trainer import crossed_interval
    assert crossed_interval(10, 5, 10)
    assert not crossed_interval(10, 10, 15)


class TestGroupedBatches:
  """`_grouped_batches` stacks K host batches per dispatch; its clipping
  and ragged-tail behavior decide how many steps actually train."""

  @staticmethod
  def _batches(shapes):
    for i, shape in enumerate(shapes):
      features = np.full(shape, float(i), np.float32)
      labels = np.full((shape[0],), float(i), np.float32)
      yield features, labels

  def test_groups_of_k(self):
    from tensor2robot_tpu.train.trainer import _grouped_batches
    groups = list(_grouped_batches(
        self._batches([(4, 2)] * 6), k=3, start_step=0, max_steps=6))
    assert [g[0].shape for g in groups] == [(3, 4, 2), (3, 4, 2)]

  def test_max_steps_clips_final_group(self):
    from tensor2robot_tpu.train.trainer import _grouped_batches
    groups = list(_grouped_batches(
        self._batches([(4, 2)] * 10), k=4, start_step=0, max_steps=6))
    # 4 + 2 (clipped), never overshooting max_steps.
    assert [g[0].shape[0] for g in groups] == [4, 2]

  def test_start_step_offsets_budget(self):
    from tensor2robot_tpu.train.trainer import _grouped_batches
    groups = list(_grouped_batches(
        self._batches([(4, 2)] * 10), k=4, start_step=4, max_steps=6))
    assert [g[0].shape[0] for g in groups] == [2]

  def test_ragged_tail_closes_group_early(self):
    """A batch with different shapes (ragged tail) must not be stacked
    into the open group — it starts its own group."""
    from tensor2robot_tpu.train.trainer import _grouped_batches
    groups = list(_grouped_batches(
        self._batches([(4, 2), (4, 2), (3, 2)]), k=3, start_step=0,
        max_steps=10))
    assert [g[0].shape for g in groups] == [(2, 4, 2), (1, 3, 2)]

  def test_early_close_respects_max_steps(self):
    """An early close that reaches max_steps stops consuming entirely."""
    from tensor2robot_tpu.train.trainer import _grouped_batches
    groups = list(_grouped_batches(
        self._batches([(4, 2), (4, 2), (3, 2), (3, 2)]), k=4, start_step=0,
        max_steps=2))
    assert [g[0].shape for g in groups] == [(2, 4, 2)]

  def test_exhausted_input_flushes_partial_group(self):
    from tensor2robot_tpu.train.trainer import _grouped_batches
    groups = list(_grouped_batches(
        self._batches([(4, 2)] * 2), k=5, start_step=0, max_steps=100))
    assert [g[0].shape for g in groups] == [(2, 4, 2)]

  def test_values_preserved_in_order(self):
    from tensor2robot_tpu.train.trainer import _grouped_batches
    groups = list(_grouped_batches(
        self._batches([(2, 2)] * 4), k=2, start_step=0, max_steps=4))
    flat = [g[0][i, 0, 0] for g in groups for i in range(g[0].shape[0])]
    assert flat == [0.0, 1.0, 2.0, 3.0]


def test_prefetcher_delivers_worker_error_promptly():
  """A worker exception must surface at the NEXT __next__, not after the
  consumer drains all already-staged batches — the loop must not train
  `depth` extra steps on a dead pipeline."""
  from tensor2robot_tpu.train.trainer import _DevicePrefetcher

  def source():
    yield ('b0', 'l0')
    yield ('b1', 'l1')
    raise IOError('pipeline died')

  prefetcher = _DevicePrefetcher(
      source(), place=lambda b: b, depth=4)
  for thread in prefetcher._threads:  # pylint: disable=protected-access
    thread.join(timeout=5)
    assert not thread.is_alive()
  # Both good batches are staged, but the error beats them out.
  with pytest.raises(IOError, match='pipeline died'):
    next(iter(prefetcher))
  prefetcher.close()
