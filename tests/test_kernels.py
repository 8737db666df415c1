"""Pallas pool/conv kernels + fp8 training: interpret-mode parity drills.

The PR-15 acceptance gates (`kernels` marker, tier-1):

* pool fwd/bwd BITWISE vs ``nn.max_pool`` + autodiff — odd shapes,
  paddings (SAME/VALID/explicit), tie-breaking, overlapping windows;
* s2d-conv fwd/dW/dx within a 1e-5 band vs ``lax.conv_general_dilated``
  (matmul reassociation: banded, not bitwise);
* kernel-policy-on-vs-off training-step equivalence for the qtopt and
  resnet mocks (pool arm bitwise; pool_conv via the loss-curve band);
* fp8 parity band vs the bf16 run + f32-master-weight assertions,
  skipped cleanly where ``fp8_supported()`` is false.

Everything runs the REAL kernel code through the Pallas interpreter
(``_pallas_dispatch.use_interpret``) — the same path a TPU compiles.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn

from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.ops import _pallas_dispatch as dispatch
from tensor2robot_tpu.ops import conv_s2d, pool
from tensor2robot_tpu.quantize import fp8_training
from tensor2robot_tpu.quantize.quantization import fp8_supported
from tensor2robot_tpu.specs import make_random_numpy
from tensor2robot_tpu.train import Trainer, TrainerConfig
from tensor2robot_tpu.train.callbacks import TrainerCallback

pytestmark = pytest.mark.kernels


def _tied(shape, seed):
  """Random data with injected ties (channel 0 rounded to halves) so the
  first-maximal-slot routing is actually exercised."""
  rng = np.random.RandomState(seed)
  x = rng.randn(*shape).astype(np.float32)
  x[..., 0] = np.round(x[..., 0] * 2) / 2
  return jnp.asarray(x)


# ------------------------------------------------------------------- pool


POOL_CASES = [
    # the REAL tower spatial geometries (channels cut 64 → 8; the
    # kernel's channel-block loop is the only thing that changes):
    # qtopt pool1 236→79 and resnet initial_max_pool 236→118
    ((1, 236, 236, 8), (3, 3), (3, 3), 'SAME'),
    ((1, 236, 236, 8), (3, 3), (2, 2), ((1, 1), (1, 1))),
    # qtopt pool1/pool2/pool3 geometry at mock scale
    ((2, 24, 24, 8), (3, 3), (3, 3), 'SAME'),
    ((1, 27, 27, 16), (2, 2), (2, 2), 'SAME'),
    # resnet initial pool: overlapping 3×3/s2 with explicit (1,1) pads
    ((2, 23, 23, 8), (3, 3), (2, 2), ((1, 1), (1, 1))),
    # odd shapes, VALID tails in no window, asymmetric windows/strides
    ((1, 7, 9, 8), (2, 2), (2, 2), 'VALID'),
    ((1, 11, 13, 16), (3, 2), (1, 2), 'SAME'),
    ((1, 10, 10, 8), (2, 3), (2, 3), 'VALID'),
]


@pytest.mark.parametrize('shape,window,strides,padding', POOL_CASES)
def test_pool_fwd_bwd_bitwise(shape, window, strides, padding):
  """Kernel fwd AND routed bwd bitwise-equal to reduce_window+autodiff,
  ties included."""
  x = _tied(shape, seed=hash((shape, window)) % 2**31)
  assert pool.is_supported(shape, window, strides, padding)
  pads = pool.resolve_padding(padding, window, strides, shape[1:3])
  ref = nn.max_pool(x, window, strides, padding)
  got = pool.pallas_max_pool(x, window, strides, pads)
  np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

  g = _tied(ref.shape, seed=7)
  ref_dx = jax.grad(
      lambda v: jnp.sum(nn.max_pool(v, window, strides, padding) * g))(x)
  got_dx = jax.grad(
      lambda v: jnp.sum(pool.pallas_max_pool(v, window, strides, pads) * g))(
          x)
  np.testing.assert_array_equal(np.asarray(got_dx), np.asarray(ref_dx))


def test_pool_argmax_slots_route_to_first_max():
  """The emitted slot is the row-major-first maximal window position."""
  x = np.zeros((1, 4, 4, 8), np.float32)
  x[0, 1, 1, :] = 5.0       # window (0,0): max at slot dy=1,dx=1 → 3
  x[0, 0, 2, :] = 7.0       # window (0,1): max at slot dy=0,dx=0 → 0
  x[0, 2, 2, :] = 9.0
  x[0, 3, 3, :] = 9.0       # window (1,1): tie → FIRST (slot 0) wins
  out, idx = pool.max_pool_argmax(
      jnp.asarray(x), (2, 2), (2, 2), ((0, 0), (0, 0)))
  idx = np.asarray(idx)
  assert (idx[0, 0, 0] == 3).all()
  assert (idx[0, 0, 1] == 0).all()
  assert (idx[0, 1, 1] == 0).all()
  assert (np.asarray(out)[0, 1, 1] == 9.0).all()


def test_pool_dispatch_gate_and_fallback():
  """Off-TPU the model-facing entry uses the stock form unless forced;
  unsupported geometry falls back without error either way."""
  assert not dispatch.tpu_available()
  with dispatch.force_kernels(False):
    assert not dispatch.kernels_enabled()
  with dispatch.force_kernels(True):
    assert dispatch.kernels_enabled()
    # C=7 (not a lane multiple) is gated out → stock path, same values.
    x = _tied((1, 9, 9, 7), seed=3)
    assert not pool.is_supported(x.shape, (2, 2), (2, 2), 'SAME')
    got = pool.max_pool(x, (2, 2), strides=(2, 2), padding='SAME')
    ref = nn.max_pool(x, (2, 2), strides=(2, 2), padding='SAME')
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_pool_gate_rejects_degenerate_pads():
  # a pad as wide as the window would put a whole window inside padding
  assert not pool.is_supported((1, 8, 8, 8), (2, 2), (2, 2),
                               ((2, 0), (0, 0)))
  assert not pool.is_supported((1, 8, 8, 8), (2, 2), (2, 2),
                               ((0, 0), (0, 2)))


# ------------------------------------------------------------------- conv


CONV_CASES = [
    # the REAL conv1 spatial geometry (cout cut 64 → 8: the matmul's
    # lane width is the only thing that changes)
    ((1, 472, 472, 3), (6, 6, 3, 8), (2, 2), 'SAME'),
    # conv1 geometry at mock scale (6×6/s2 SAME, cin 3)
    ((2, 48, 48, 3), (6, 6, 3, 16), (2, 2), 'SAME'),
    ((2, 29, 31, 3), (6, 6, 3, 8), (2, 2), 'SAME'),
    # resnet initial_conv fixed padding (7×7/s2, explicit (2,3))
    ((1, 20, 20, 3), (7, 7, 3, 8), (2, 2), ((2, 3), (2, 3))),
    ((1, 17, 17, 2), (3, 3, 2, 8), (1, 1), 'SAME'),
    ((2, 15, 11, 3), (5, 3, 3, 8), (3, 2), 'VALID'),
]


@pytest.mark.parametrize('xshape,wshape,strides,padding', CONV_CASES)
def test_conv_s2d_fwd_dw_dx_band(xshape, wshape, strides, padding):
  rng = np.random.RandomState(11)
  x = jnp.asarray(rng.randn(*xshape).astype(np.float32))
  w = jnp.asarray((rng.randn(*wshape) * 0.1).astype(np.float32))
  assert conv_s2d.is_supported(xshape, wshape, strides, padding)
  pads = conv_s2d.resolve_padding(padding, wshape[:2], strides, xshape[1:3])

  ref = conv_s2d.reference_conv2d(x, w, strides, padding)
  got = conv_s2d.pallas_conv2d(x, w, strides, pads)
  np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                             rtol=1e-5, atol=1e-5)

  g = jnp.asarray(rng.randn(*ref.shape).astype(np.float32))
  ref_dx, ref_dw = jax.grad(
      lambda a, b: jnp.sum(conv_s2d.reference_conv2d(a, b, strides,
                                                     padding) * g),
      argnums=(0, 1))(x, w)
  got_dx, got_dw = jax.grad(
      lambda a, b: jnp.sum(conv_s2d.pallas_conv2d(a, b, strides,
                                                  pads) * g),
      argnums=(0, 1))(x, w)
  # 1e-5 RELATIVE band: dW sums O(batch·H·W) products, so its absolute
  # scale is large; reassociation noise scales with it.
  for got_t, ref_t in ((got_dx, ref_dx), (got_dw, ref_dw)):
    scale = float(jnp.max(jnp.abs(ref_t))) or 1.0
    np.testing.assert_allclose(np.asarray(got_t) / scale,
                               np.asarray(ref_t) / scale,
                               rtol=0, atol=1e-5)


def test_conv_gate_rejects_deep_cin():
  # deep-C_in convs are MXU-shaped already; the gate keeps XLA's form
  assert not conv_s2d.is_supported((1, 16, 16, 64), (3, 3, 64, 64),
                                   (1, 1), 'SAME')


def test_s2d_conv_module_param_tree_matches_nn_conv():
  """SpaceToDepthConv and nn.Conv trees are byte-identical — the
  kernel_policy on/off checkpoint-interchange guarantee."""
  init = nn.initializers.truncated_normal(stddev=0.01)
  a = conv_s2d.SpaceToDepthConv(8, (6, 6), strides=(2, 2), padding='SAME',
                                use_bias=False, kernel_init=init)
  b = nn.Conv(8, (6, 6), strides=(2, 2), padding='SAME', use_bias=False,
              kernel_init=init)
  x = jnp.zeros((1, 16, 16, 3), jnp.float32)
  va = a.init(jax.random.PRNGKey(0), x)
  vb = b.init(jax.random.PRNGKey(0), x)
  assert (jax.tree_util.tree_structure(va) ==
          jax.tree_util.tree_structure(vb))
  for la, lb in zip(jax.tree_util.tree_leaves(va),
                    jax.tree_util.tree_leaves(vb)):
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ----------------------------------------------- training-step equivalence


class _LossRecorder(TrainerCallback):

  def __init__(self):
    self.losses = []

  def after_step(self, trainer, step, scalars):
    if 'loss' in scalars:
      self.losses.append(float(np.asarray(scalars['loss'])))


def _qtopt_mock(**kwargs):
  from tensor2robot_tpu.research.qtopt import GraspingModelWrapper

  return GraspingModelWrapper(
      device_type='tpu', input_shape=(96, 112, 3), target_shape=(80, 80),
      num_convs=(2, 2, 1), **kwargs)


def _train_qtopt(kernel_policy='none', matmul_precision=None, steps=3,
                 remat_policy='none', **config_kwargs):
  model = _qtopt_mock(kernel_policy=kernel_policy,
                      remat_policy=remat_policy)
  recorder = _LossRecorder()
  trainer = Trainer(
      model,
      TrainerConfig(model_dir='', max_train_steps=steps,
                    eval_interval_steps=0, log_interval_steps=1,
                    prefetch_batches=0,
                    matmul_precision=matmul_precision, **config_kwargs),
      callbacks=[recorder])
  pre = model.preprocessor
  fs = pre.get_in_feature_specification(ModeKeys.TRAIN)
  ls = pre.get_in_label_specification(ModeKeys.TRAIN)
  batches = [(make_random_numpy(fs, batch_size=4, seed=s),
              make_random_numpy(ls, batch_size=4, seed=100 + s))
             for s in range(steps)]
  with dispatch.force_kernels(True):
    trainer.train(iter(batches), None)
  return jax.device_get(trainer.state), recorder.losses


def test_qtopt_kernel_policy_pool_training_bitwise():
  """kernel_policy='pool' (bitwise kernels only) trains BIT-IDENTICAL to
  'none' — params, EMA, BN stats, the whole state."""
  s_off, _ = _train_qtopt('none')
  s_on, _ = _train_qtopt('pool')
  for a, b in zip(jax.tree_util.tree_leaves((s_off.params, s_off.ema_params,
                                             s_off.model_state)),
                  jax.tree_util.tree_leaves((s_on.params, s_on.ema_params,
                                             s_on.model_state))):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_qtopt_kernel_policy_pool_conv_loss_band():
  """kernel_policy='pool_conv' (banded conv kernel) reaches the same
  loss curve within the parity band — the grasp2vec-soak discipline."""
  _, losses_off = _train_qtopt('none')
  _, losses_on = _train_qtopt('pool_conv')
  assert losses_off and len(losses_off) == len(losses_on)
  for a, b in zip(losses_off, losses_on):
    assert np.isfinite(a) and np.isfinite(b)
    assert abs(a - b) <= 1e-3 + 0.02 * abs(a), (losses_off, losses_on)


def test_kernel_policy_composes_with_accum_remat_nonfinite():
  """kernel_policy='pool' under grad_accum=2 + remat='conv_towers' +
  nonfinite_mode='skip_update' (jax.checkpoint over the custom_vjp,
  the accumulation scan, and the guarded state update all stacked)
  still trains bit-identical to the same configuration without the
  kernels."""
  compose = dict(steps=2, remat_policy='conv_towers',
                 grad_accum_microbatches=2, nonfinite_mode='skip_update')
  s_off, _ = _train_qtopt('none', **compose)
  s_on, _ = _train_qtopt('pool', **compose)
  for a, b in zip(jax.tree_util.tree_leaves((s_off.params,
                                             s_off.model_state)),
                  jax.tree_util.tree_leaves((s_on.params,
                                             s_on.model_state))):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resnet_kernel_policy_forward_and_grads_bitwise():
  """ResNet initial_max_pool through the Pallas kernel (overlapping
  3×3/s2): forward endpoints and full grads bitwise vs policy 'none'."""
  from tensor2robot_tpu.layers.resnet import ResNet

  x = _tied((2, 32, 32, 3), seed=5)
  m0 = ResNet(resnet_size=18, num_classes=4, kernel_policy='none')
  m1 = ResNet(resnet_size=18, num_classes=4, kernel_policy='pool')
  v = m0.init(jax.random.PRNGKey(0), x, train=False)
  with dispatch.force_kernels(True):
    v1 = m1.init(jax.random.PRNGKey(0), x, train=False)
    assert (jax.tree_util.tree_structure(v) ==
            jax.tree_util.tree_structure(v1))
    out0, _ = m0.apply(v, x, train=False)
    out1, _ = m1.apply(v, x, train=False)
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))
    g0 = jax.grad(lambda p: jnp.sum(m0.apply(p, x, train=False)[0] ** 2))(v)
    g1 = jax.grad(lambda p: jnp.sum(m1.apply(p, x, train=False)[0] ** 2))(v)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
      np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_policy_validation():
  with pytest.raises(ValueError, match='kernel_policy'):
    dispatch.validate_kernel_policy('conv')
  assert dispatch.validate_kernel_policy(None) == 'none'
  with pytest.raises(ValueError, match='kernel_policy'):
    _qtopt_mock(kernel_policy='yes')


# -------------------------------------------------------------------- fp8


def test_matmul_precision_validation():
  with pytest.raises(ValueError, match='matmul_precision'):
    fp8_training.validate_matmul_precision('int8')
  assert fp8_training.validate_matmul_precision(None) == 'bf16'


@pytest.mark.skipif(not fp8_supported(),
                    reason='jaxlib/ml_dtypes lacks float8_e4m3fn')
def test_fp8_training_parity_band_and_master_weights():
  """matmul_precision='fp8' holds the loss-curve parity band vs the bf16
  run AND keeps f32 master weights in params/opt state; amax histories
  live in 'fp8_stats' and advance with training."""
  s_bf16, losses_bf16 = _train_qtopt('none', steps=4)
  s_fp8, losses_fp8 = _train_qtopt('none', matmul_precision='fp8', steps=4)
  assert losses_bf16 and len(losses_bf16) == len(losses_fp8)
  for a, b in zip(losses_bf16, losses_fp8):
    assert np.isfinite(b)
    # fp8 rounding moves per-step losses a little; the band is the
    # acceptance certificate (same discipline as the grasp2vec bf16
    # gate: low precision must track, not match bitwise).
    assert abs(a - b) <= 0.02 + 0.1 * abs(a), (losses_bf16, losses_fp8)
  # Master weights: params AND optimizer slots stay f32 — fp8 exists
  # only inside the jitted program's qdq ops.
  for leaf in jax.tree_util.tree_leaves(s_fp8.params):
    assert np.asarray(leaf).dtype == np.float32
  for leaf in jax.tree_util.tree_leaves(s_fp8.opt_state):
    if hasattr(leaf, 'dtype') and np.issubdtype(
        np.asarray(leaf).dtype, np.floating):
      assert np.asarray(leaf).dtype == np.float32
  # amax state threads model_state and advances.
  assert 'fp8_stats' in s_fp8.model_state
  hists = jax.tree_util.tree_leaves(s_fp8.model_state['fp8_stats'])
  assert hists and any(float(np.asarray(h)[-1]) > 0 for h in hists)
  # and the bf16 arm carries none of it
  assert 'fp8_stats' not in s_bf16.model_state


@pytest.mark.skipif(not fp8_supported(),
                    reason='jaxlib/ml_dtypes lacks float8_e4m3fn')
def test_fp8_qdq_roundtrip_and_straight_through_grad():
  x = jnp.asarray(np.linspace(-600, 600, 41, dtype=np.float32))
  scale = fp8_training.amax_scale(jnp.float32(448.0), jnp.float8_e4m3fn)
  y = fp8_training.quantize_dequantize(x, scale, jnp.float8_e4m3fn)
  assert np.all(np.isfinite(np.asarray(y)))          # saturates, never NaN
  assert float(jnp.max(jnp.abs(y))) <= 448.0 + 1e-3  # clamped to range
  g = jax.grad(lambda v: jnp.sum(
      fp8_training.quantize_dequantize(v, scale, jnp.float8_e4m3fn)))(x)
  np.testing.assert_array_equal(np.asarray(g), np.ones_like(x))


def test_trainer_config_overrides_model_precision():
  model = _qtopt_mock()
  assert model.matmul_precision == 'bf16'
  if fp8_supported():
    Trainer(model, TrainerConfig(model_dir='', max_train_steps=1,
                                 eval_interval_steps=0,
                                 log_interval_steps=0,
                                 matmul_precision='fp8'))
    assert model.matmul_precision == 'fp8'
  with pytest.raises(ValueError, match='matmul_precision'):
    Trainer(_qtopt_mock(), TrainerConfig(model_dir='', max_train_steps=1,
                                         eval_interval_steps=0,
                                         log_interval_steps=0,
                                         matmul_precision='int4'))
