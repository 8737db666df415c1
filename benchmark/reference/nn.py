"""The few layer equations both references share, in float32.

``quant`` names the precision computed in. ``None`` is the reference
itself: float32, matmul precision ``highest``. The configurations state
bfloat16 as the compute type: the program keeps its parameters in
float32 and casts the activations wholesale, so every product's
operands and every activation it stores are bfloat16. ``'fp8'`` is the
control, the same one precision down, as float8 training computes:
operands and stored activations rounded per tensor to e4m3, a product's
incoming gradient to e5m2, sums and parameters in float32. ``'bf16'``
does the same in the configurations' own precision: a witness of what
that precision alone does, and no control.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round_to(x: jnp.ndarray, quant: str) -> jnp.ndarray:
  """``x`` rounded to ``quant``. Rounding is ``lax.reduce_precision``,
  which the compiler keeps: a cast down and up again is taken out on the
  TPU as excess precision (the bfloat16 witness made of casts read 0
  there; PERF.md, Findings). The 8-bit formats are scaled per tensor so
  that the largest magnitude lands on the format's largest number."""
  if quant == 'bf16':
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
  amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
  if quant == 'fp8':  # e4m3 forward: 4 exponent bits, 3 of mantissa
    scale = amax / 240.0
    return jax.lax.reduce_precision(x / scale, 4, 3) * scale
  if quant == 'fp8_gradient':  # e5m2 backward
    scale = amax / 57344.0
    return jax.lax.reduce_precision(x / scale, 5, 2) * scale
  raise ValueError(f'unknown control precision {quant!r}')


def operand(x: jnp.ndarray, quant: Optional[str]) -> jnp.ndarray:
  """``x`` as a product sees it; gradients pass straight through."""
  if quant is None:
    return x
  return x + jax.lax.stop_gradient(_round_to(x, quant) - x)


def stored(x: jnp.ndarray, quant: Optional[str]) -> jnp.ndarray:
  """An activation as the compute type keeps it between two layers."""
  return operand(x, quant) if quant in ('fp8', 'bf16') else x


@jax.custom_vjp
def _fp8_gradient(y):
  """The identity, whose incoming gradient is rounded to e5m2."""
  return y


_fp8_gradient.defvjp(lambda y: (y, None),
                     lambda _, g: (_round_to(g, 'fp8_gradient'),))


def product(y, quant: Optional[str]):
  """A product's result as the way back sees it."""
  return _fp8_gradient(y) if quant == 'fp8' else y


def conv(x, kernel, stride: int, padding, quant: Optional[str] = None):
  """NHWC convolution with an HWIO kernel."""
  return stored(product(jax.lax.conv_general_dilated(
      operand(x, quant), operand(kernel, quant), (stride, stride), padding,
      dimension_numbers=('NHWC', 'HWIO', 'NHWC'), precision=HIGHEST), quant),
                quant)


def dense(x, kernel, bias=None, quant: Optional[str] = None):
  y = product(jnp.matmul(operand(x, quant), operand(kernel, quant),
                         precision=HIGHEST), quant)
  return stored(y if bias is None else y + bias, quant)


def batch_norm(x, bias, scale=None, eps: float = 1e-3,
               quant: Optional[str] = None):
  """Training-mode batch normalisation over every axis but the last."""
  axes = tuple(range(x.ndim - 1))
  mean = jnp.mean(x, axis=axes)
  var = jnp.maximum(jnp.mean(jnp.square(x), axis=axes) - mean * mean, 0.0)
  y = (x - mean) * jax.lax.rsqrt(var + eps)
  if scale is not None:
    y = y * scale
  return stored(y + bias, quant)


def max_pool(x, window: int, stride: int, padding):
  if not isinstance(padding, str):
    padding = ((0, 0),) + tuple(padding) + ((0, 0),)
  return jax.lax.reduce_window(
      x, -jnp.inf, jax.lax.max, (1, window, window, 1),
      (1, stride, stride, 1), padding)


def he_normal(key, shape):
  """Normal weights of variance 2 / fan_in (the last axis is fan-out)."""
  fan_in = 1
  for d in shape[:-1]:
    fan_in *= d
  return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5
