"""Shared kernel-dispatch scaffolding for the hand-written Pallas ops.

Every Pallas kernel in :mod:`tensor2robot_tpu.ops` follows one dispatch
contract, first established by ``flash_attention`` and lifted here so
``pool`` / ``conv_s2d`` consume the same code instead of copies:

* **Interpret-mode probe** (:func:`use_interpret`): off-TPU backends run
  the *same kernel code* through the Pallas interpreter, so the CPU-mesh
  tier-1 suite exercises the real kernels (values and gradients) without
  a Mosaic lowering. Anything that is not a TPU interprets — the
  framework is TPU-first, but kernels must not hard-fail on gpu/cpu.
* **Lane-tile minimum** (:func:`min_lane_block`): interpret mode accepts
  any 8-aligned block; a real Mosaic lowering rejects sub-lane-tile
  (<128) vector stores (found on hardware with a T=8 SNAIL episode —
  the CPU suite cannot see this class of constraint, so ``is_supported``
  gates must consult the *target's* minimum, not the host's).
* **Dispatch gate** (:func:`kernels_enabled`): the model-level call
  sites (``kernel_policy`` towers) use the hand kernels on TPU and fall
  back to the stock XLA form elsewhere — interpret mode is a
  correctness harness, orders of magnitude slower than XLA:CPU, so it
  must never be the *training* path off-TPU. Tests force the kernel
  path on CPU with :func:`force_kernels` (or ``T2R_FORCE_PALLAS_KERNELS
  =1``) to drill policy-on-vs-off equivalence through the interpreter.
  The gate is consulted at TRACE time: a jitted program bakes in
  whichever path was live when it traced.
* **Loud refusal** (:func:`refuse`): a kernel the caller asked for
  (``kernel_policy``) that the live target cannot
  run gives way to its XLA reference with one WARNING and a
  ``kernels/refused`` count — never silently. Each kernel's
  ``is_supported`` answers for the target it would actually lower to:
  what the interpreter accepts and what Mosaic lowers differ (lane
  blocks, scoped VMEM, unimplemented primitives), and only a compile for
  the chip shows the second (tests/test_chip_compile.py).

The ``kernel_policy`` model knob (``'none' | 'pool' | 'pool_conv'``,
same shape as ``remat_policy``) also lives here: it names which kernel
families a tower routes through its gated call sites.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
from typing import Optional

import jax

from tensor2robot_tpu.observability import metrics as metrics_lib

# ------------------------------------------------------- kernel policies

KERNEL_NONE = 'none'
KERNEL_POOL = 'pool'
KERNEL_POOL_CONV = 'pool_conv'
KERNEL_POLICIES = (KERNEL_NONE, KERNEL_POOL, KERNEL_POOL_CONV)


def validate_kernel_policy(policy: Optional[str]) -> str:
  """Normalizes/validates a kernel-policy name (None → 'none')."""
  policy = KERNEL_NONE if policy is None else str(policy)
  if policy not in KERNEL_POLICIES:
    raise ValueError(
        f'Unknown kernel_policy {policy!r}; expected one of '
        f'{KERNEL_POLICIES}.')
  return policy


def policy_enables_pool(policy: Optional[str]) -> bool:
  """Whether the policy routes max-pools through ``ops.pool``."""
  return validate_kernel_policy(policy) in (KERNEL_POOL, KERNEL_POOL_CONV)


def policy_enables_conv(policy: Optional[str]) -> bool:
  """Whether the policy routes the first conv through ``ops.conv_s2d``."""
  return validate_kernel_policy(policy) == KERNEL_POOL_CONV


# ------------------------------------------------------- backend probes


def use_interpret() -> bool:
  """Interpret everywhere Mosaic can't lower (cpu, gpu, ...), not just
  cpu: the framework is TPU-first, but the kernels must not hard-fail
  on other hosts."""
  return jax.default_backend() != 'tpu'


def tpu_available() -> bool:
  return not use_interpret()


# Scoped VMEM one Mosaic kernel instance may allocate on a TPU v5e with
# default compiler parameters — the limit the chip's compiler enforced
# when the pool kernel was compiled for a described v5e ("Scoped
# allocation with size 40.27M and limit 16.00M").
MOSAIC_SCOPED_VMEM_BYTES = 16 * 1024 * 1024
# A VMEM-resident block is stored in (sublane, lane) tiles: the last dim
# pads to 128 lanes, the second-to-last to 8 sublanes of 32-bit words.
MOSAIC_LANES = 128
MOSAIC_SUBLANES = 8


def refuse(kernel: str, reason: str) -> None:
  """Records that a REQUESTED hand kernel gave way to its XLA reference.

  Called at trace time by the gated entry points when the caller's
  explicit ask (a ``kernel_policy`` tower) cannot
  be honoured at the shapes in hand. chip_smoke.py fails a run whose
  report shows ``kernels/refused`` > 0.
  """
  metrics_lib.counter('kernels/refused').inc()
  logging.warning(
      'Pallas %s kernel was requested but cannot run here (%s; lowering '
      'target: %s); the XLA reference runs instead.', kernel, reason,
      'the Pallas interpreter' if use_interpret() else 'Mosaic')


def min_lane_block(interpret: Optional[bool] = None) -> int:
  """Smallest block length a kernel may place in the lane dimension:
  8 under the interpreter, 128 for a real Mosaic lowering (sub-tile
  vector stores are rejected). ``None`` resolves from the backend."""
  if interpret is None:
    interpret = use_interpret()
  return 8 if interpret else 128


# ------------------------------------------------- model-dispatch gate

_FORCE_ENV = 'T2R_FORCE_PALLAS_KERNELS'
_force_override = threading.local()


def kernels_enabled() -> bool:
  """Whether gated model call sites should take the Pallas path.

  True on TPU backends; off-TPU the stock XLA form wins (interpret mode
  is for tests, not training throughput) unless a :func:`force_kernels`
  context or ``T2R_FORCE_PALLAS_KERNELS=1`` overrides. Resolved at
  trace time — see module docstring.
  """
  override = getattr(_force_override, 'value', None)
  if override is not None:
    return bool(override)
  env = os.environ.get(_FORCE_ENV)
  if env is not None:
    return env.strip().lower() not in ('', '0', 'false', 'off')
  return tpu_available()


@contextlib.contextmanager
def force_kernels(enabled: bool = True):
  """Forces :func:`kernels_enabled` within the context (tests: drill the
  interpret-mode kernel path through a CPU training step)."""
  previous = getattr(_force_override, 'value', None)
  _force_override.value = enabled
  try:
    yield
  finally:
    _force_override.value = previous
