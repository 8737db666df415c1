"""Device-resident multi-step (``device_feed``) drills.

The knob, pinned on the CPU backend:

  (a) ``device_feed=True`` trains BITWISE identically to the
      K-individual-dispatch path over the (K, M) grid, including
      rng-noised device-side preprocessing — one superbatch
      ``device_put`` + one dispatch per K steps, counted exactly, with
      the program-ledger recompile sentinel flat;
  (b) a NaN slice inside a superbatch skips exactly its own update
      (the guarded scan slot), leaving the run equal to one that never
      drew the bad batch;
  (c) a SIGTERM mid-dispatch checkpoints at the dispatch boundary and
      a fresh trainer resumes BIT-exactly against an uninterrupted run
      fed the same stream.
"""

import os
import signal

import jax
import numpy as np
import pytest

from tensor2robot_tpu.models import optimizers as opt_lib
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.observability import metrics as metrics_lib
from tensor2robot_tpu.preprocessors import NoOpPreprocessor
from tensor2robot_tpu.specs import SpecStruct
from tensor2robot_tpu.train import (GracefulShutdown, PreemptedError, Trainer,
                                    TrainerConfig, latest_checkpoint_step)
from tensor2robot_tpu.utils import faults
from tensor2robot_tpu.utils.mocks import MockT2RModel

pytestmark = pytest.mark.feed


def fast_adam():
  return opt_lib.create_adam_optimizer(1e-2)


class _NoisyPreprocessor(NoOpPreprocessor):
  """Rng-noised device-side preprocessing: the feed path must hand the
  scanned program the same per-step fold_in rng the individual
  dispatches use, or the noise (crop offsets, photometric distortions
  in real models) silently diverges."""

  def _preprocess_fn(self, features, labels, mode, rng):
    features, labels = super()._preprocess_fn(features, labels, mode, rng)
    if rng is not None and mode == ModeKeys.TRAIN:
      pos = features['measured_position']
      features['measured_position'] = pos + 0.01 * jax.random.normal(
          rng, np.shape(pos), pos.dtype)
    return features, labels


def make_batches(n, batch_size=8, seed=0):
  rng = np.random.RandomState(seed)
  batches = []
  for _ in range(n):
    points = rng.uniform(-1.0, 1.0, (batch_size, 2)).astype(np.float32)
    features = SpecStruct()
    features['measured_position'] = points
    labels = SpecStruct()
    labels['valid_position'] = (points.sum(axis=1) > 0).astype(np.float32)
    batches.append((features, labels))
  return batches


def make_trainer(model_dir='', callbacks=(), shutdown=None,
                 preprocessor_cls=None, **cfg):
  model = MockT2RModel(device_type='tpu', create_optimizer_fn=fast_adam,
                       preprocessor_cls=preprocessor_cls)
  cfg.setdefault('prefetch_batches', 0)
  config = TrainerConfig(
      model_dir=model_dir, eval_interval_steps=0, log_interval_steps=0, **cfg)
  return Trainer(model, config, callbacks=list(callbacks), shutdown=shutdown)


def assert_tree_bitwise(a, b):
  la = jax.tree_util.tree_leaves(jax.device_get(a))
  lb = jax.tree_util.tree_leaves(jax.device_get(b))
  assert len(la) == len(lb)
  for x, y in zip(la, lb):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def assert_state_bitwise(s1, s2, ema=True):
  assert int(s1.step) == int(s2.step)
  assert_tree_bitwise(s1.params, s2.params)
  assert_tree_bitwise(s1.opt_state, s2.opt_state)
  assert_tree_bitwise(s1.model_state, s2.model_state)
  if ema:
    assert (s1.ema_params is None) == (s2.ema_params is None)
    if s1.ema_params is not None:
      assert_tree_bitwise(s1.ema_params, s2.ema_params)


# ------------------------------------- (a) bitwise parity + exact counters


@pytest.mark.parametrize('k', [1, 2, 4])
@pytest.mark.parametrize('m', [1, 2])
def test_device_feed_bitwise_equals_k_dispatches(k, m):
  """device_feed over the (K, M) grid == the K-individual-dispatch path
  (steps_per_dispatch=1, device_feed off), bit for bit, with rng-noised
  preprocessing active so the per-step fold_in keying is pinned too."""
  batches = make_batches(8)

  def run(feed, kk, prefetch):
    trainer = make_trainer(
        preprocessor_cls=_NoisyPreprocessor, max_train_steps=8,
        steps_per_dispatch=kk, grad_accum_microbatches=m,
        device_feed=feed, prefetch_batches=prefetch)
    trainer.train(iter(list(batches)), None)
    return trainer.state

  reference = run(False, 1, 0)
  state_feed = run(True, k, 2)
  assert_state_bitwise(reference, state_feed)
  # Same K, feed off: identical executable on CPU (donation is
  # accelerator-only), so this leg is bitwise by construction.
  assert_state_bitwise(run(False, k, 0), state_feed)


def test_device_feed_exactly_one_put_and_dispatch_per_k():
  """The acceptance counters: trainer/h2d/device_puts ==
  trainer/dispatches == ceil(steps / K), and the steady-state recompile
  sentinel stays flat (one executable serves every superbatch)."""
  # Third tuple entry: expected sentinel delta. Divisible runs stay flat
  # (one executable serves every superbatch); the ragged 7=3+3+1 run
  # records the one-time K=1 tail program under the same name — a single
  # deliberate re-record, not steady-state churn.
  for k, steps, want_recompiles in ((2, 8, 0), (4, 8, 0), (3, 7, 1)):
    puts0 = metrics_lib.counter('trainer/h2d/device_puts').value
    disp0 = metrics_lib.counter('trainer/dispatches').value
    recomp0 = metrics_lib.counter('programs/steady_state_recompiles').value
    trainer = make_trainer(max_train_steps=steps, steps_per_dispatch=k,
                           device_feed=True, prefetch_batches=2)
    trainer.train(iter(make_batches(steps)), None)
    assert int(trainer.step) == steps
    puts = metrics_lib.counter('trainer/h2d/device_puts').value - puts0
    disp = metrics_lib.counter('trainer/dispatches').value - disp0
    expected = -(-steps // k)  # ceil: the ragged tail is its own group
    assert puts == disp == expected, (k, steps, puts, disp)
    recomp = (metrics_lib.counter('programs/steady_state_recompiles').value
              - recomp0)
    assert recomp == want_recompiles, (k, steps, recomp)


# ----------------------------------------------- (b) guarded NaN slice


def test_nan_superbatch_slice_skips_exactly_its_own_update():
  """A NaN batch in the MIDDLE of a K=3 superbatch: its scan slot skips
  the update (step unadvanced, rng slot reused) and every other slot
  applies — so the run equals (bitwise) both the non-feed guarded run
  and a feed run that never drew the bad batch."""
  b = make_batches(6)
  poisoned = [b[0], b[1], faults.nanify(b[2]), b[3], b[4], b[5]]

  def run(batches, feed):
    trainer = make_trainer(max_train_steps=len(batches),
                           steps_per_dispatch=3, device_feed=feed,
                           nonfinite_mode='skip_update')
    trainer.train(iter(list(batches)), None)
    return trainer

  run_feed = run(poisoned, True)
  assert run_feed.nonfinite_policy.bad_steps == 1
  assert int(run_feed.step) == 5  # 6 batches, 1 skipped update
  for leaf in jax.tree_util.tree_leaves(
      jax.device_get(run_feed.state.params)):
    assert np.isfinite(np.asarray(leaf)).all()

  assert_state_bitwise(run(poisoned, False).state, run_feed.state)
  clean = run([b[0], b[1], b[3], b[4], b[5]], True)
  assert clean.nonfinite_policy.bad_steps == 0
  assert_state_bitwise(clean.state, run_feed.state)


# ------------------------------------------- (c) SIGTERM bit-exact resume


def test_sigterm_mid_dispatch_resumes_bit_exact(tmp_path):
  """A real OS SIGTERM landing mid-dispatch (step 4 of a K=3 group)
  checkpoints at the NEXT dispatch boundary (6); a fresh device-feed
  trainer restores it, consumes the remaining stream (probe batch
  included in its first superbatch), and finishes bit-identical to an
  uninterrupted run over the same 9 batches."""
  batches = make_batches(9)
  model_dir = str(tmp_path / 'm')

  reference = make_trainer(max_train_steps=9, steps_per_dispatch=3,
                           device_feed=True)
  reference.train(iter(list(batches)), None)

  prev = signal.getsignal(signal.SIGTERM)
  shutdown = GracefulShutdown(signals=(signal.SIGTERM,)).install()
  try:
    cb = faults.PreemptionCallback(at_step=4, signum=signal.SIGTERM)
    trainer = make_trainer(model_dir=model_dir, callbacks=[cb],
                           shutdown=shutdown, max_train_steps=9,
                           save_interval_steps=1000, async_checkpoints=False,
                           steps_per_dispatch=3, device_feed=True)
    with pytest.raises(PreemptedError):
      trainer.train(iter(list(batches)), None)
  finally:
    shutdown.uninstall()
    signal.signal(signal.SIGTERM, prev)
  saved = latest_checkpoint_step(os.path.join(model_dir, 'checkpoints'))
  assert saved == 6  # the dispatch boundary at-or-after the signal

  resumed = make_trainer(model_dir=model_dir, max_train_steps=9,
                         save_interval_steps=1000, async_checkpoints=False,
                         steps_per_dispatch=3, device_feed=True)
  # On resume the first pulled batch is only the shape probe and is
  # dropped (trainer pulls it before the loop): lead with one extra.
  resumed.train(iter(list(batches[saved - 1:])), None)
  assert int(resumed.step) == 9
  assert_state_bitwise(reference.state, resumed.state)

