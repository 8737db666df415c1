"""Memory-efficiency subsystem: microbatch grad accumulation + remat.

Pins the semantics ISSUE-4 promises (on CPU, mock-scale models — these
run in tier-1 on every PR):

* ``grad_accum_microbatches=M`` is numerically EQUIVALENT to the
  full-batch step for mean-reduced losses with no cross-example
  coupling: params, EMA, rng stream (preprocessing draws included), and
  step counter match allclose at f32 accumulators.
* For BatchNorm models the coupling caveat is pinned explicitly: batch
  statistics see the MICRObatch (ghost batch norm — the GPipe
  convention, Huang et al. 2019), and the scan path matches a naive
  python-loop reference accumulation exactly (qtopt + grasp2vec mock
  configs, EMA and the optimizer epilogue included).
* ``nonfinite_mode='skip_update'`` evaluates all-finite over the
  ACCUMULATED gradients: one bad microbatch skips the whole effective
  batch's update, bitwise.
* ``steps_per_dispatch=K`` × ``grad_accum_microbatches=M`` nest as one
  program and K=2×M=2 matches the K=1, M=1 trajectory; GracefulShutdown
  checkpoints land only on effective-batch (dispatch) boundaries.
* The scan path traces the step body ONCE regardless of M (no
  per-microbatch re-trace).
* ``remat_policy`` keeps the parameter tree and the training math
  byte-compatible ('none' vs 'conv_towers' vs 'full').
* HBM telemetry degrades to empty on stat-less backends and publishes
  ``device/memory/*`` gauges when the allocator reports.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.models import optimizers as opt_lib
from tensor2robot_tpu.models.classification_model import ClassificationModel
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.preprocessors.base import AbstractPreprocessor
from tensor2robot_tpu.specs import SpecStruct, TensorSpec, make_random_numpy
from tensor2robot_tpu.train import Trainer, TrainerConfig
from tensor2robot_tpu.train import resilience
from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

pytestmark = pytest.mark.memory


def fast_adam():
  return opt_lib.create_adam_optimizer(1e-2)


# ----------------------------------------------------- BN-free exactness


class _NoisePreprocessor(AbstractPreprocessor):
  """Adds rng-drawn noise: any drift in the per-step rng stream (the
  fold_in key or the pre/net split) changes training detectably."""

  def _preprocess_fn(self, features, labels, mode, rng):
    if mode == ModeKeys.TRAIN and rng is not None:
      x = features['measured_position']
      features['measured_position'] = x + 0.01 * jax.random.normal(
          rng, x.shape, x.dtype)
    return features, labels

  def get_in_feature_specification(self, mode):
    return self.model_feature_specification(mode)

  def get_in_label_specification(self, mode):
    return self.model_label_specification(mode)

  def get_out_feature_specification(self, mode):
    return self.model_feature_specification(mode)

  def get_out_label_specification(self, mode):
    return self.model_label_specification(mode)


class NoBNModel(ClassificationModel):
  """2-layer MLP with NO BatchNorm: zero cross-example coupling, so
  microbatch accumulation must equal the full-batch step EXACTLY."""

  def create_module(self):
    import flax.linen as nn

    class MLP(nn.Module):

      @nn.compact
      def __call__(self, features, train: bool = False):
        x = features['measured_position'].astype(jnp.float32)
        x = nn.relu(nn.Dense(16)(x))
        x = nn.relu(nn.Dense(16)(x))
        return {'a_predicted': jnp.squeeze(nn.Dense(1)(x), axis=-1)}

    return MLP()

  @property
  def default_preprocessor_cls(self):
    return _NoisePreprocessor

  def get_feature_specification(self, mode):
    del mode
    spec = SpecStruct()
    spec['measured_position'] = TensorSpec(
        shape=(2,), dtype=np.float32, name='measured_position')
    return spec

  def get_label_specification(self, mode):
    del mode
    spec = SpecStruct()
    spec['valid_position'] = TensorSpec(
        shape=(), dtype=np.float32, name='valid_position')
    return spec


def _train_no_bn(accum_m, steps=6, k=1, batch=8, ema=True):
  model = NoBNModel(device_type='cpu', create_optimizer_fn=fast_adam,
                    use_avg_model_params=ema)
  gen = MockInputGenerator(batch_size=batch)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  trainer = Trainer(model, TrainerConfig(
      model_dir='', max_train_steps=steps, eval_interval_steps=0,
      log_interval_steps=0, prefetch_batches=0,
      steps_per_dispatch=k, grad_accum_microbatches=accum_m))
  scalars = trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
  return trainer, scalars


def _assert_states_allclose(t_ref, t_new, rtol=1e-6, atol=1e-7):
  assert int(t_ref.step) == int(t_new.step)
  for name in ('params', 'ema_params'):
    a = getattr(t_ref.state, name)
    b = getattr(t_new.state, name)
    assert (a is None) == (b is None), name
    if a is None:
      continue
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            rtol=rtol, atol=atol), jax.device_get(a), jax.device_get(b))
  np.testing.assert_array_equal(
      np.asarray(jax.device_get(t_ref.state.rng)),
      np.asarray(jax.device_get(t_new.state.rng)))


def test_grad_accum_matches_full_batch_exactly_without_bn():
  """M=2 and M=4 over the same host batches reproduce the M=1 param AND
  EMA trajectory — including the rng-noised preprocessing, which pins
  the per-step fold_in stream (preprocess runs once over the full batch
  in both arms)."""
  t1, s1 = _train_no_bn(1)
  for m in (2, 4):
    tm, sm = _train_no_bn(m)
    _assert_states_allclose(t1, tm)
    np.testing.assert_allclose(float(s1['loss']), float(sm['loss']),
                               rtol=1e-5)


def test_grad_accum_requires_divisible_batch():
  with pytest.raises(ValueError, match='must divide the batch dim'):
    _train_no_bn(3, steps=1, batch=8)


def test_microbatch_split_shapes_and_passthrough():
  tree = {'x': np.zeros((8, 3), np.float32)}
  out = mesh_lib.microbatch_split(tree, 4)
  assert out['x'].shape == (4, 2, 3)
  assert mesh_lib.microbatch_split(tree, 1) is tree


def test_steps_per_dispatch_composes_with_grad_accum():
  """K=2 × M=2 over 8 host batches nests as one scan-in-scan program and
  matches the K=1, M=1 trajectory (BN-free model, so equality is exact,
  not just reference-pinned)."""
  t_ref, _ = _train_no_bn(1, steps=8, k=1)
  t_km, _ = _train_no_bn(2, steps=8, k=2)
  _assert_states_allclose(t_ref, t_km)
  # And the mixed arms agree too.
  t_m, _ = _train_no_bn(2, steps=8, k=1)
  t_k, _ = _train_no_bn(1, steps=8, k=2)
  _assert_states_allclose(t_ref, t_m)
  _assert_states_allclose(t_ref, t_k)


# ------------------------------------- BN models: reference accumulation


def _reference_accum_step(model, optimizer, state, features, labels, m):
  """Naive python-loop reference for ONE accumulation step.

  Recomputes what the scan path must produce, independently of lax.scan
  and the donated accumulators: fold_in rng, full-batch preprocessing,
  per-microbatch grads with model_state THREADED (ghost-BN running
  stats), f32 mean of gradients, one optimizer update, one EMA update.
  """
  from tensor2robot_tpu.train.train_state import apply_ema
  import optax

  preprocessor = model.preprocessor
  step_rng = jax.random.fold_in(state.rng, state.step)
  pre_rng, net_rng = jax.random.split(step_rng)
  features_p, labels_p = preprocessor.preprocess(
      features, labels, ModeKeys.TRAIN, pre_rng)
  micro_f = mesh_lib.microbatch_split(features_p, m)
  micro_l = (None if labels_p is None
             else mesh_lib.microbatch_split(labels_p, m))

  def loss_fn(params, model_state, f, l):
    variables = dict(model_state)
    variables['params'] = params
    outputs, new_variables = model.inference_network_fn(
        variables, f, l, ModeKeys.TRAIN, net_rng)
    loss, scalars = model.model_train_fn(f, l, outputs, ModeKeys.TRAIN)
    new_ms = {k: v for k, v in dict(new_variables).items() if k != 'params'}
    return loss, (scalars, new_ms)

  grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
  model_state = state.model_state
  acc = jax.tree_util.tree_map(
      lambda p: jnp.zeros(jnp.shape(p), jnp.float32), state.params)
  for i in range(m):
    f = jax.tree_util.tree_map(lambda x: x[i], micro_f)
    l = (None if micro_l is None
         else jax.tree_util.tree_map(lambda x: x[i], micro_l))
    (_, (_, model_state)), grads = grad_fn(
        state.params, model_state, f, l)
    acc = jax.tree_util.tree_map(
        lambda a, g: a + g.astype(jnp.float32), acc, grads)
  grads = jax.tree_util.tree_map(
      lambda a, p: (a / m).astype(jnp.asarray(p).dtype), acc, state.params)
  updates, new_opt_state = optimizer.update(
      grads, state.opt_state, state.params)
  new_params = optax.apply_updates(state.params, updates)
  return state.replace(
      step=state.step + 1,
      params=new_params,
      model_state=model_state,
      opt_state=new_opt_state,
      ema_params=apply_ema(state, new_params,
                           model.avg_model_params_decay))


def _mock_workload(name):
  if name == 'qtopt':
    from tensor2robot_tpu.research.qtopt import GraspingModelWrapper

    model = GraspingModelWrapper(
        device_type='tpu', input_shape=(96, 112, 3), target_shape=(80, 80),
        num_convs=(2, 2, 1))
    return model, 4
  from tensor2robot_tpu.research.grasp2vec import Grasp2VecModel
  from tensor2robot_tpu.research.grasp2vec.grasp2vec_model import (
      Grasp2VecPreprocessor)

  class TinyGrasp2Vec(Grasp2VecModel):
    """472-crop defaults shrunk to 64 so the full raw-jpeg-spec pipeline
    (512×640 uint8 → crop → flips) runs at mock scale."""

    @property
    def default_preprocessor_cls(self):

      class TinyCrop(Grasp2VecPreprocessor):

        def __init__(self, **kwargs):
          super().__init__(scene_crop=(0, 40, 64, 0, 168, 64),
                           goal_crop=(0, 40, 64, 0, 168, 64), **kwargs)

      return TinyCrop

  # f32 towers (device_type='cpu') + SGD-momentum instead of the
  # bf16/Adam defaults: measured here, the SAME eager reference differs
  # from its own jitted form by 0.15 max-abs through the bf16 resnet —
  # XLA reduction ordering at 8-bit mantissas, not semantics — and
  # Adam's per-element normalization further turns near-zero-grad noise
  # into ±lr sign flips. The bf16 path's numerics are pinned by the
  # qtopt arm (shallow tower, production momentum+EMA builder) and by
  # test_grasp2vec's own bf16-parity soaks; THIS test pins accumulation
  # semantics, so it runs where float ordering cannot mask a real bug.
  return TinyGrasp2Vec(device_type='cpu', scene_size=(64, 64),
                       goal_size=(64, 64), resnet_size=18,
                       use_avg_model_params=True,
                       create_optimizer_fn=lambda:
                       opt_lib.create_momentum_optimizer(1e-2)), 4


@pytest.mark.parametrize('workload', ['qtopt', 'grasp2vec'])
def test_grad_accum_matches_reference_accumulation(workload):
  """The scan path == the naive loop, for the real research configs at
  mock scale: f32 accumulators, rng fold_in, ghost-BN model_state
  threading, EMA, and the optimizer epilogue all pinned. (With
  BatchNorm, batch STATISTICS see the microbatch — the GPipe/ghost-BN
  convention — so the reference accumulates per-microbatch too; the
  BN-free test above pins exact full-batch equality.)"""
  model, batch = _mock_workload(workload)
  preprocessor = model.preprocessor
  fspec = preprocessor.get_in_feature_specification(ModeKeys.TRAIN)
  lspec = preprocessor.get_in_label_specification(ModeKeys.TRAIN)
  features = make_random_numpy(fspec, batch_size=batch, seed=0)
  labels = (make_random_numpy(lspec, batch_size=batch, seed=7)
            if lspec is not None and len(lspec) else None)

  trainer = Trainer(model, TrainerConfig(
      model_dir='', max_train_steps=1, eval_interval_steps=0,
      log_interval_steps=0, prefetch_batches=0,
      grad_accum_microbatches=2))
  state0 = trainer.initialize(features)
  state0 = jax.device_get(state0)
  # The naive loop, compiled: run op by op it is not its own oracle —
  # on jaxlib 0.9.0 the eager form of this very function differs from
  # its jitted form by 3.5e-3 on the grasp2vec arm (ghost BatchNorm over
  # 2-example microbatches amplifies XLA's eager-vs-fused rounding),
  # while the trainer's scan agrees with the jitted form to 7e-7.
  reference = jax.jit(
      lambda state, f, l: _reference_accum_step(
          model, trainer._optimizer, state, f, l, m=2))(  # pylint: disable=protected-access
              jax.tree_util.tree_map(jnp.asarray, state0), features, labels)

  trainer.train(iter([(features, labels)]), None)
  got = trainer.state
  assert int(got.step) == 1
  for name in ('params', 'ema_params', 'model_state'):
    a, b = getattr(reference, name), getattr(got, name)
    assert (a is None) == (b is None), name
    if a is None:
      continue
    # Tolerance: the reference is the unrolled loop while the trainer's
    # step is a scan with accumulator carries — summation orders
    # differ, so pin semantics at ~1e-5 absolute (params are O(1e-2); a
    # wrong rng key, a missed EMA update, or f32-vs-bf16 accumulators
    # all blow past this by orders of magnitude).
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            rtol=2e-3, atol=2e-5), jax.device_get(a), jax.device_get(b))


# --------------------------------------------- non-finite guard over accum


def test_nonfinite_skip_update_over_accumulated_grads():
  """One NaN MICROBATCH poisons the accumulated gradient and the guard
  skips the WHOLE effective batch's update — training equals a run that
  never drew the bad batch (params, rng reuse, step counter)."""
  rng = np.random.RandomState(3)

  def make_batch(poison_second_half=False):
    pts = rng.uniform(-1, 1, (8, 2)).astype(np.float32)
    if poison_second_half:
      pts = pts.copy()
      pts[4:] = np.nan  # only microbatch 1 of 2 is bad
    f = SpecStruct()
    f['measured_position'] = pts
    l = SpecStruct()
    l['valid_position'] = (pts.sum(axis=1) > 0).astype(np.float32)
    return f, l

  clean = [make_batch() for _ in range(4)]
  bad = make_batch(poison_second_half=True)

  def run(batches, max_steps):
    model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
    trainer = Trainer(model, TrainerConfig(
        model_dir='', max_train_steps=max_steps, eval_interval_steps=0,
        log_interval_steps=0, prefetch_batches=0,
        grad_accum_microbatches=2, nonfinite_mode='skip_update'))
    trainer.train(iter(batches), None)
    return trainer

  with_bad = run([clean[0], bad, clean[1]], max_steps=3)
  without = run([clean[0], clean[1]], max_steps=2)
  # The skipped slot reused its rng key and did not advance state.step,
  # so the two runs are the same training trajectory.
  assert int(with_bad.state.step) == int(without.state.step) == 2
  for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(
      with_bad.state.params)),
                  jax.tree_util.tree_leaves(jax.device_get(
                      without.state.params))):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  assert with_bad.nonfinite_policy.bad_steps == 1


def test_nonfinite_raise_fires_for_single_bad_microbatch():
  model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
  f = SpecStruct()
  pts = np.ones((8, 2), np.float32)
  pts[6:] = np.inf
  f['measured_position'] = pts
  l = SpecStruct()
  l['valid_position'] = np.ones((8,), np.float32)
  trainer = Trainer(model, TrainerConfig(
      model_dir='', max_train_steps=3, eval_interval_steps=0,
      log_interval_steps=0, prefetch_batches=0,
      grad_accum_microbatches=4, nonfinite_mode='raise'))
  with pytest.raises(resilience.NonFiniteError):
    trainer.train(iter([(f, l)] * 3), None)


# ---------------------------------------------- dispatch/boundary behavior


def test_graceful_shutdown_checkpoints_on_effective_batch_boundary(tmp_path):
  """With K=2 × M=2 the preemption checkpoint lands on a dispatch
  boundary (a multiple of K effective batches) — never mid-accumulation,
  never mid-group."""
  from tensor2robot_tpu.train.trainer import TrainerCallback
  from tensor2robot_tpu.train import latest_checkpoint_step

  shutdown = resilience.GracefulShutdown()

  class RequestAt(TrainerCallback):

    def after_step(self, trainer, step, scalars):
      if step >= 4:
        shutdown.request()

  model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
  gen = MockInputGenerator(batch_size=8)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  trainer = Trainer(model, TrainerConfig(
      model_dir=str(tmp_path / 'm'), max_train_steps=20,
      save_interval_steps=100, eval_interval_steps=0, log_interval_steps=0,
      prefetch_batches=0, async_checkpoints=False,
      steps_per_dispatch=2, grad_accum_microbatches=2),
      callbacks=[RequestAt()], shutdown=shutdown)
  with pytest.raises(resilience.PreemptedError):
    trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
  step = latest_checkpoint_step(str(tmp_path / 'm' / 'checkpoints'))
  assert step is not None and step % 2 == 0 and step >= 4
  assert int(trainer.state.step) == step  # state and checkpoint agree


def test_no_per_microbatch_retrace():
  """lax.scan traces the microbatch body ONCE: the python-level network
  fn runs the same (small) number of times whether M is 2 or 8."""
  counts = {}

  def run(m):
    model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
    inner = model.inference_network_fn
    calls = [0]

    def counting(*args, **kwargs):
      calls[0] += 1
      return inner(*args, **kwargs)

    model.inference_network_fn = counting
    gen = MockInputGenerator(batch_size=8)
    gen.set_specification_from_model(model, ModeKeys.TRAIN)
    trainer = Trainer(model, TrainerConfig(
        model_dir='', max_train_steps=4, eval_interval_steps=0,
        log_interval_steps=0, prefetch_batches=0,
        grad_accum_microbatches=m))
    trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
    counts[m] = calls[0]

  run(2)
  run(8)
  # Same trace count regardless of M (init + one step trace; dispatches
  # never re-enter python).
  assert counts[2] == counts[8], counts
  assert counts[8] <= 4, counts


# ----------------------------------------------------------------- remat


@pytest.mark.parametrize('policy', ['conv_towers', 'full'])
def test_remat_training_step_is_equivalent_qtopt(policy):
  """remat changes backward-pass scheduling, not math: one train step of
  the qtopt mock config produces the same loss and params with and
  without remat (same seed, same batch)."""
  from tensor2robot_tpu.research.qtopt import GraspingModelWrapper

  def run(remat):
    model = GraspingModelWrapper(
        device_type='tpu', input_shape=(96, 112, 3), target_shape=(80, 80),
        num_convs=(2, 2, 1), remat_policy=remat)
    preprocessor = model.preprocessor
    fspec = preprocessor.get_in_feature_specification(ModeKeys.TRAIN)
    lspec = preprocessor.get_in_label_specification(ModeKeys.TRAIN)
    features = make_random_numpy(fspec, batch_size=4, seed=0)
    labels = make_random_numpy(lspec, batch_size=4, seed=7)
    trainer = Trainer(model, TrainerConfig(
        model_dir='', max_train_steps=2, eval_interval_steps=0,
        log_interval_steps=0, prefetch_batches=0))
    scalars = trainer.train(iter([(features, labels)] * 2), None)
    return trainer, float(scalars['loss'])

  t_none, loss_none = run('none')
  t_remat, loss_remat = run(policy)
  np.testing.assert_allclose(loss_none, loss_remat, rtol=1e-5)
  jax.tree_util.tree_map(
      lambda a, b: np.testing.assert_allclose(
          np.asarray(a, np.float32), np.asarray(b, np.float32),
          rtol=1e-5, atol=1e-6),
      jax.device_get(t_none.state.params),
      jax.device_get(t_remat.state.params))


def test_remat_param_trees_interchange():
  """Checkpoint compatibility: remat'd and plain modules have IDENTICAL
  variable trees (lifted transforms preserve scopes), for every tower
  that supports the hook."""
  from tensor2robot_tpu.layers import ImagesToFeaturesModel, ResNet
  from tensor2robot_tpu.research.qtopt.networks import Grasping44

  x = jnp.ones((2, 48, 48, 3))
  for policy in ('conv_towers', 'full'):
    a = ResNet(resnet_size=18).init(jax.random.PRNGKey(0), x, train=False)
    b = ResNet(resnet_size=18, remat_policy=policy).init(
        jax.random.PRNGKey(0), x, train=False)
    assert (jax.tree_util.tree_structure(a) ==
            jax.tree_util.tree_structure(b))
    a = ImagesToFeaturesModel().init(jax.random.PRNGKey(0),
                                     jnp.ones((2, 64, 64, 3)), train=True)
    b = ImagesToFeaturesModel(remat_policy=policy).init(
        jax.random.PRNGKey(0), jnp.ones((2, 64, 64, 3)), train=True)
    assert (jax.tree_util.tree_structure(a) ==
            jax.tree_util.tree_structure(b))
    a = Grasping44(num_convs=(2, 2, 1)).init(
        jax.random.PRNGKey(0), jnp.ones((1, 96, 112, 3)),
        jnp.ones((1, 15)), train=True)
    b = Grasping44(num_convs=(2, 2, 1), remat_policy=policy).init(
        jax.random.PRNGKey(0), jnp.ones((1, 96, 112, 3)),
        jnp.ones((1, 15)), train=True)
    assert (jax.tree_util.tree_structure(a) ==
            jax.tree_util.tree_structure(b))


def test_remat_film_grads_match():
  """FiLM-conditioned vision tower: remat'd gradients equal plain ones
  (the FiLM γ/β path crosses the checkpoint boundary)."""
  from tensor2robot_tpu.layers import ImagesToFeaturesModel
  from tensor2robot_tpu.layers.vision_layers import film_params_size

  x = jnp.asarray(np.random.RandomState(0).randn(2, 64, 64, 3),
                  jnp.float32)
  film = jnp.asarray(
      np.random.RandomState(1).randn(2, film_params_size(5)), jnp.float32)

  def loss(module, variables):
    points, _ = module.apply(variables, x, film)
    return jnp.sum(points ** 2)

  plain = ImagesToFeaturesModel()
  remat = ImagesToFeaturesModel(remat_policy='conv_towers')
  variables = plain.init(jax.random.PRNGKey(0), x, film)
  g_plain = jax.grad(lambda v: loss(plain, v))(variables)
  g_remat = jax.grad(lambda v: loss(remat, v))(variables)
  jax.tree_util.tree_map(
      lambda a, b: np.testing.assert_allclose(
          np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7),
      g_plain, g_remat)


def test_invalid_remat_policy_rejected():
  from tensor2robot_tpu.layers.remat import validate_remat_policy

  with pytest.raises(ValueError, match='Unknown remat_policy'):
    validate_remat_policy('everything')
  with pytest.raises(ValueError, match='Unknown remat_policy'):
    MockT2RModel(device_type='cpu', remat_policy='bogus')


# ------------------------------------------------------------ telemetry


def test_memory_scalars_empty_on_statless_backend():
  """XLA CPU exposes no allocator stats: the scalar schema must stay
  clean (no fake zeros) and nothing raises."""
  from tensor2robot_tpu.observability import memory as memory_lib

  assert memory_lib.device_memory_stats() is None
  assert memory_lib.memory_scalars() == {}
  assert memory_lib.device_memory_peak_mb() is None


def test_memory_gauges_published_from_stats():
  from tensor2robot_tpu.observability import memory as memory_lib
  from tensor2robot_tpu.observability import metrics as metrics_lib

  class FakeDevice:

    def memory_stats(self):
      return {'bytes_in_use': 11 * 10**6, 'peak_bytes_in_use': 42 * 10**6,
              'bytes_limit': 100 * 10**6, 'largest_alloc_size': 5 * 10**6,
              'num_allocs': 7}

  scalars = memory_lib.memory_scalars(FakeDevice())
  assert scalars['memory/device_peak_mb'] == pytest.approx(42.0)
  assert scalars['memory/device_mb'] == pytest.approx(11.0)
  assert scalars['memory/device_limit_mb'] == pytest.approx(100.0)
  assert scalars['memory/device_peak_fraction'] == pytest.approx(0.42)
  assert metrics_lib.gauge('device/memory/peak_bytes_in_use').value == (
      42 * 10**6)
  assert memory_lib.device_memory_peak_mb(FakeDevice()) == pytest.approx(
      42.0)


def test_trainer_merges_memory_scalars_at_log_crossings(monkeypatch):
  """The scalar merge is live: when the backend reports stats, the log
  window's scalars carry memory/device_peak_mb."""
  from tensor2robot_tpu.observability import memory as memory_lib
  from tensor2robot_tpu.train.trainer import TrainerCallback

  monkeypatch.setattr(
      memory_lib, 'device_memory_stats',
      lambda device=None: {'bytes_in_use': 10**6,
                           'peak_bytes_in_use': 2 * 10**6})

  seen = []

  class Capture(TrainerCallback):

    def after_step(self, trainer, step, scalars):
      if 'memory/device_peak_mb' in scalars:
        seen.append((step, scalars['memory/device_peak_mb']))

  model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam)
  gen = MockInputGenerator(batch_size=8)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  trainer = Trainer(model, TrainerConfig(
      model_dir='', max_train_steps=4, eval_interval_steps=0,
      log_interval_steps=2, prefetch_batches=0),
      callbacks=[Capture()])
  trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
  assert seen and seen[0][1] == pytest.approx(2.0), seen


# ------------------------------------------------- optimizer-level accum


def test_optimizer_multistep_accumulation():
  """with_gradient_accumulation: one real update per N dispatches —
  params move only on the N-th step, matching optax.MultiSteps."""
  import optax

  opt = opt_lib.with_gradient_accumulation(
      opt_lib.create_gradient_descent_optimizer(0.1), 2)
  params = {'w': jnp.ones((2,))}
  state = opt.init(params)
  g = {'w': jnp.ones((2,))}
  updates, state = opt.update(g, state, params)
  params1 = optax.apply_updates(params, updates)
  np.testing.assert_array_equal(np.asarray(params1['w']),
                                np.asarray(params['w']))  # buffered
  updates, state = opt.update(g, state, params1)
  params2 = optax.apply_updates(params1, updates)
  np.testing.assert_allclose(np.asarray(params2['w']),
                             np.ones(2) - 0.1, rtol=1e-6)
  assert opt_lib.with_gradient_accumulation(
      opt_lib.create_adam_optimizer(), 1) is not None
