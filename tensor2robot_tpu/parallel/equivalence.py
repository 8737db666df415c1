"""Does a sharded train step compute what one device computes?

The harness behind every multi-device check: the virtual-CPU-device
rehearsal (``__graft_entry__.dryrun_multichip``) and the
four-chip phase of ``chip_smoke.py --multichip``. One *arm* is a
``Trainer`` over some mesh taking a few steps on a fixed batch from a
fixed seed; two arms agree when their losses, their initial parameters
and their per-leaf parameter updates agree.

Why the arms are float32 and plain SGD:

* bf16 activations re-round differently under each mesh's tiling and
  drown small gradients in noise; f32 is deterministic up to reduction
  order.
* Adam's m̂/√v̂ update is scale-invariant in the gradient, so post-Adam
  parameters would pass even with a missing psum on a gradient partial.
  Post-SGD parameter DELTAS are exactly lr·grad and verify the
  collective sums: a missing psum scales a leaf's delta by the device
  count, far outside any band.

On a real TPU, f32 matmuls at default precision run as bf16 passes on
the MXU, so the caller wraps both arms in
``jax.default_matmul_precision('highest')`` there (the check's business,
not a program option).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import numpy as np
import optax

LEARNING_RATE = 1e-2


@dataclasses.dataclass
class Arm:
  """What one run left behind, on the host."""

  loss: float
  init: Any  # params before the steps
  delta: Any  # params after - before, float64


def run_arm(make_model: Callable[[], Any], mesh, batch, config) -> Arm:
  """Trains ``config.max_train_steps`` SGD steps on ``batch`` (repeated)
  over ``mesh`` from ``config.seed``."""
  from tensor2robot_tpu.train import Trainer

  model = make_model()
  model.create_optimizer = lambda: optax.sgd(LEARNING_RATE)
  trainer = Trainer(model, config, mesh=mesh)
  trainer.initialize(batch[0])
  init = jax.device_get(trainer.state.params)
  scalars = trainer.train(
      iter([batch] * config.max_train_steps), None)
  after = jax.device_get(trainer.state.params)
  delta = jax.tree_util.tree_map(
      lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
      after, init)
  return Arm(loss=float(scalars['loss']), init=init, delta=delta)


def worst_delta_error(got, want) -> float:
  """Largest per-leaf ``(|got - want| - floor) / |want|``, by norm.

  Leaves the batch barely trains (reference delta norms near float-noise
  zero) get an absolute floor tied to the GLOBAL update magnitude —
  relative comparison on a ~1e-11 delta is meaningless, and a wrong
  collective would still blow past the floor on the leaves that carry
  the update.
  """
  global_norm = float(np.sqrt(sum(
      float(np.sum(np.square(np.asarray(leaf))))
      for leaf in jax.tree_util.tree_leaves(want))))
  floor = 1e-6 * global_norm + 1e-12

  def error(a, b):
    diff = float(np.linalg.norm(np.asarray(a) - np.asarray(b)))
    return max(diff - floor, 0.0) / max(
        float(np.linalg.norm(np.asarray(b))), 1e-300)

  return max(jax.tree_util.tree_leaves(
      jax.tree_util.tree_map(error, got, want)))


def compare_arms(sharded: Arm, reference: Arm, what: str,
                 loss_rtol: float = 1e-5, delta_rtol: float = 0.02,
                 check_deltas: bool = True) -> Dict[str, float]:
  """Raises AssertionError unless the arms agree; returns what it saw."""
  seen = {
      'loss': sharded.loss,
      'reference_loss': reference.loss,
      'loss_rel_err': abs(sharded.loss - reference.loss) /
                      max(abs(reference.loss), 1e-300),
      'worst_delta_rel_err': worst_delta_error(sharded.delta,
                                               reference.delta),
  }
  # Same seed → identical initialization regardless of mesh.
  jax.tree_util.tree_map(
      lambda a, b: np.testing.assert_allclose(
          np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7,
          err_msg=f'{what}: initial parameters'),
      sharded.init, reference.init)
  assert np.isfinite(sharded.loss) and seen['loss_rel_err'] <= loss_rtol, (
      what, seen)
  if check_deltas:
    assert seen['worst_delta_rel_err'] <= delta_rtol, (what, seen)
  return seen


def equivalence_pair(make_sharded, make_ref, batch, sharded_mesh, ref_mesh,
                     config, what: str, **bands) -> Dict[str, float]:
  """Both arms, then :func:`compare_arms`."""
  return compare_arms(
      run_arm(make_sharded, sharded_mesh, batch, config),
      run_arm(make_ref, ref_mesh, batch, config), what, **bands)
