"""Optimizer and learning-rate factories (optax).

Capability-equivalent of the reference's gin-exposed factories
(``/root/reference/models/optimizers.py:29-167``): Adam / SGD / Momentum
with constant or exponentially-decaying learning rates, plus
moving-average ("Polyak") parameter averaging.

The reference implements averaging with ``MovingAverageOptimizer`` and a
*swapping saver* so checkpoints contain averaged weights
(``models/optimizers.py:140-167``). In JAX the trainer simply keeps an
``ema_params`` tree in the train state (see ``train/train_state.py``) and
evaluates/exports it — no saver machinery needed, so this module only
provides the decay schedule helpers and the gradient transformations.
"""

from __future__ import annotations

from typing import Callable, Optional, Union

import optax

Schedule = Callable[[int], float]
LearningRate = Union[float, Schedule]


# ------------------------------------------------------------ lr schedules


def create_constant_learning_rate_fn(learning_rate: float = 1e-4) -> Schedule:
  """Mirrors ``create_constant_learning_rate`` (optimizers.py:102-110)."""
  return optax.constant_schedule(learning_rate)


def create_exp_decaying_learning_rate_fn(
    initial_learning_rate: float = 1e-4,
    decay_steps: int = 10000,
    decay_rate: float = 0.9,
    staircase: bool = True) -> Schedule:
  """Mirrors ``create_exp_decaying_learning_rate`` (optimizers.py:113-137)."""
  return optax.exponential_decay(
      init_value=initial_learning_rate,
      transition_steps=decay_steps,
      decay_rate=decay_rate,
      staircase=staircase)


# --------------------------------------------------------------- optimizers


def create_adam_optimizer(
    learning_rate: LearningRate = 1e-4,
    beta1: float = 0.9,
    beta2: float = 0.999,
    epsilon: float = 1e-8) -> optax.GradientTransformation:
  """Mirrors ``create_adam_optimizer`` (optimizers.py:29-50)."""
  return optax.adam(learning_rate, b1=beta1, b2=beta2, eps=epsilon)


def create_gradient_descent_optimizer(
    learning_rate: LearningRate = 1e-4) -> optax.GradientTransformation:
  """Mirrors ``create_gradient_descent_optimizer`` (optimizers.py:53-70)."""
  return optax.sgd(learning_rate)


def create_momentum_optimizer(
    learning_rate: LearningRate = 1e-4,
    momentum: float = 0.9,
    use_nesterov: bool = False) -> optax.GradientTransformation:
  """Mirrors ``create_momentum_optimizer`` (optimizers.py:73-99)."""
  return optax.sgd(learning_rate, momentum=momentum, nesterov=use_nesterov)


def create_rms_prop_optimizer(
    learning_rate: LearningRate = 1e-4,
    decay: float = 0.9,
    momentum: float = 0.0,
    epsilon: float = 1e-10) -> optax.GradientTransformation:
  """RMSProp, used by the QT-Opt optimizer builder."""
  return optax.rmsprop(
      learning_rate, decay=decay, momentum=momentum, eps=epsilon)


def with_gradient_clipping(
    optimizer: optax.GradientTransformation,
    clip_norm: Optional[float] = None,
    clip_value: Optional[float] = None) -> optax.GradientTransformation:
  """Global-norm / value clipping composed in front of an optimizer."""
  transforms = []
  if clip_norm is not None:
    transforms.append(optax.clip_by_global_norm(clip_norm))
  if clip_value is not None:
    transforms.append(optax.clip(clip_value))
  transforms.append(optax.with_extra_args_support(optimizer))
  return optax.chain(*transforms)


def with_gradient_accumulation(
    optimizer: optax.GradientTransformation,
    accumulate_steps: int) -> optax.GradientTransformation:
  """Optimizer-level accumulation ACROSS dispatches (``optax.MultiSteps``).

  Complements ``TrainerConfig.grad_accum_microbatches``, which slices one
  host batch into microbatches INSIDE the jitted step (the memory lever —
  activations never exist at the full effective batch). This wrapper
  instead averages gradients over ``accumulate_steps`` consecutive host
  batches and applies one real update per window — useful when the
  effective batch should exceed what the host pipeline can deliver as a
  single batch. The trainer's ``state.step`` still advances every
  dispatch, so logging/checkpoint cadence is unchanged; only every
  ``accumulate_steps``-th dispatch moves the params.
  """
  if accumulate_steps <= 1:
    return optimizer
  return optax.MultiSteps(optimizer, every_k_schedule=accumulate_steps)


def default_create_optimizer_fn() -> optax.GradientTransformation:
  """The reference default: Adam at 1e-4 (abstract_model.py:168-178)."""
  return create_adam_optimizer()
