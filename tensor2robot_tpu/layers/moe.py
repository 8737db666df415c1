"""A sparse expert layer that holds a share of its experts.

The layer is told which experts it holds (``experts_held``, ids among
the ``num_experts`` the router scores). It scores and chooses over ALL
experts, computes the shared expert and the weighted outputs of its own
experts for the tokens that chose them, and leaves out what the absent
experts would add: under expert parallelism that partial sum is this
chip's part of the layer's result. On one chip it runs without an
exchange.

Routing (afmoe / torchtitan's MoE): ``s = sigmoid(Wr x)`` in float32;
chosen = top-k of ``s + b`` (``b``: a non-gradient bias an expert, used
for the choice only); ``w = s_chosen / (sum + 1e-20)`` where
``route_norm``, times ``route_scale``; weights are applied after the
expert. After a training step's forward pass, outside the gradient,
``b += load_balance_coeff * sign(mean(count) - count)``, minus its mean;
``b`` and the step's ``count`` live in the mutable collection
``moe_state``, which the trainer carries as ``model_state``.

No token is dropped. The (token, choice) pairs are sorted by the slot of
the expert they chose, held experts first; the buffer of routed rows has
room for the worst case the share allows (every token choosing held
experts only: ``tokens * min(k, held)`` rows), and the grouped matrix
product (``jax.lax.ragged_dot``: on the TPU a grouped-matmul kernel that
visits the tiles of live rows only) computes the rows that are there.
Rows move by gathers in both directions (the sort's permutation and its
inverse), never by a scatter.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

# Rows a tile of the TPU's grouped product holds: ``ragged_dot`` lowers
# there to a kernel whose tile table has rows/512 + groups - 1 entries
# (tests/test_chip_compile.py reads that shape back). A tile that two
# experts' rows share is computed once for each, so the rows the product
# computes are (tile, expert) visits x this.
GROUPED_ROW_TILE = 512

MOE_STATE = 'moe_state'
HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------- moving rows by gathers

@jax.custom_vjp
def _dispatch(x, source, back, back_live):
  """``x[source]``; on the way back each row of ``x`` sums the
  cotangents of the buffer rows ``back`` (where ``back_live``) that were
  copies of it: [tokens, k] of them."""
  del back, back_live
  return x[source]


def _dispatch_fwd(x, source, back, back_live):
  return x[source], (back, back_live)


def _dispatch_bwd(res, g):
  back, back_live = res
  picked = jnp.where(back_live[..., None], g[back], 0)
  return jnp.sum(picked.astype(jnp.float32), axis=1).astype(g.dtype), \
      None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _collect(y, back, back_live, source, source_live):
  """``y[back]`` where ``back_live`` else 0: each (token, choice) pair
  reads its row of the buffer. On the way back buffer row ``p`` reads
  the cotangent of pair ``source[p]``."""
  del source, source_live
  return jnp.where(back_live[..., None], y[back], 0)


def _collect_fwd(y, back, back_live, source, source_live):
  return jnp.where(back_live[..., None], y[back], 0), (source, source_live)


def _collect_bwd(res, g):
  source, source_live = res
  flat = g.reshape((-1, g.shape[-1]))
  return (jnp.where(source_live[:, None], flat[source], 0),
          None, None, None, None)


_collect.defvjp(_collect_fwd, _collect_bwd)


# ------------------------------------------------------------------ routing

def route(scores, bias, experts_per_token: int, route_norm: bool,
          route_scale: float):
  """(chosen ids [T, k], weights [T, k], counts [experts]) from float32
  scores [T, experts]."""
  _, chosen = jax.lax.top_k(scores + bias, experts_per_token)
  weights = jnp.take_along_axis(scores, chosen, axis=-1)
  if route_norm:
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
  weights = weights * route_scale
  experts = scores.shape[-1]
  counts = jnp.sum(chosen[..., None] == jnp.arange(experts), axis=(0, 1),
                   dtype=jnp.int32)
  return chosen, weights, counts


def updated_bias(bias, counts, coeff: float):
  counts = counts.astype(jnp.float32)
  bias = bias + coeff * jnp.sign(jnp.mean(counts) - counts)
  return bias - jnp.mean(bias)


def normal_init(std):
  return nn.initializers.normal(stddev=std)


class SwiGLU(nn.Module):
  """``down(silu(gate x) * up x)``; float32 parameters, products in
  ``dtype``."""

  width: int
  dtype: Any = jnp.float32
  init_std: float = 0.02

  @nn.compact
  def __call__(self, x):
    d = x.shape[-1]
    gate = self.param('gate', normal_init(self.init_std), (d, self.width))
    up = self.param('up', normal_init(self.init_std), (d, self.width))
    down = self.param('down', normal_init(self.init_std), (self.width, d))
    x = x.astype(self.dtype)
    h = jax.nn.silu(x @ gate.astype(self.dtype)) * (x @ up.astype(self.dtype))
    return h @ down.astype(self.dtype)


class _Experts(nn.Module):
  """The held experts' three stacked matrices and the grouped products
  over rows sorted by expert."""

  held: int
  width: int
  dtype: Any
  init_std: float

  @nn.compact
  def __call__(self, rows, group_sizes):
    d = rows.shape[-1]
    gate = self.param('gate', normal_init(self.init_std),
                      (self.held, d, self.width))
    up = self.param('up', normal_init(self.init_std),
                    (self.held, d, self.width))
    down = self.param('down', normal_init(self.init_std),
                      (self.held, self.width, d))

    def product(lhs, rhs):
      return jax.lax.ragged_dot(lhs, rhs.astype(self.dtype), group_sizes,
                                preferred_element_type=self.dtype)

    h = jax.nn.silu(product(rows, gate)) * product(rows, up)
    return product(h, down)


class ExpertLayer(nn.Module):
  """See the module docstring. ``__call__`` takes [..., hidden] and
  returns the same shape and a dict of this call's counts (int32
  scalars: ``tokens``, ``rows_routed``, ``rows_computed``,
  ``rows_max_expert``, ``rows_dropped``)."""

  num_experts: int                 # the router's width: all published
  experts_per_token: int
  expert_width: int
  experts_held: Optional[Tuple[int, ...]] = None   # None: all of them
  route_norm: bool = True
  route_scale: float = 1.0
  load_balance_coeff: float = 0.0
  dtype: Any = jnp.float32
  init_std: float = 0.02

  @nn.compact
  def __call__(self, x, train: bool = False):
    shape = x.shape
    x = x.reshape((-1, shape[-1])).astype(self.dtype)
    tokens, k = x.shape[0], self.experts_per_token
    held_ids = (tuple(range(self.num_experts)) if self.experts_held is None
                else tuple(self.experts_held))
    held = len(held_ids)
    pairs = tokens * k
    room = tokens * min(k, held)   # the worst case this share allows

    router = self.param('router', normal_init(self.init_std),
                        (shape[-1], self.num_experts))
    bias = self.variable(MOE_STATE, 'bias', jnp.zeros, (self.num_experts,),
                         jnp.float32)
    last_counts = self.variable(MOE_STATE, 'counts', jnp.zeros,
                                (self.num_experts,), jnp.int32)

    with jax.named_scope('afmoe/moe/route'):
      scores = jax.nn.sigmoid(jnp.matmul(
          x.astype(jnp.float32), router, precision=HIGHEST))
      chosen, weights, counts = route(
          scores, jax.lax.stop_gradient(bias.value), k, self.route_norm,
          self.route_scale)
      # Slot of each chosen expert among the held ones; ``held`` = absent.
      slot_of = jnp.full((self.num_experts,), held, jnp.int32).at[
          jnp.asarray(held_ids)].set(jnp.arange(held, dtype=jnp.int32))
      slot = slot_of[chosen]                              # [T, k]
      flat = slot.reshape(pairs)
      order = jnp.argsort(flat, stable=True).astype(jnp.int32)
      position = jnp.argsort(order).astype(jnp.int32).reshape(tokens, k)
      group_sizes = jnp.sum(flat[:, None] == jnp.arange(held), axis=0,
                            dtype=jnp.int32)
      is_held = slot < held
      placed = jnp.logical_and(is_held, position < room)
      source = order[:room]
      back = jnp.minimum(position, room - 1)
      rows = _dispatch(x, source // k, back, placed)

    with jax.named_scope('afmoe/moe/experts'):
      y = _Experts(held, self.expert_width, self.dtype, self.init_std,
                   name='experts')(rows, group_sizes)

    with jax.named_scope('afmoe/moe/route'):
      source_live = jnp.arange(room) < jnp.sum(group_sizes)
      picked = _collect(y, back, placed, source, source_live)   # [T, k, D]
      routed = jnp.sum(picked.astype(jnp.float32) *
                       jnp.where(placed, weights, 0.0)[..., None], axis=1)

    with jax.named_scope('afmoe/moe/shared'):
      shared = SwiGLU(self.expert_width, self.dtype, self.init_std,
                      name='shared')(x)
    out = (shared.astype(jnp.float32) + routed).astype(self.dtype)

    if train and not self.is_initializing():
      # In a deployment the counts are summed over the data-parallel
      # chips first; here they are this chip's tokens.
      bias.value = updated_bias(bias.value, counts, self.load_balance_coeff)
      last_counts.value = counts
    stats = _stats(tokens, is_held, placed, group_sizes)
    return out.reshape(shape), stats


def _stats(tokens: int, is_held, placed, group_sizes) -> Dict[str, jax.Array]:
  starts = jnp.cumsum(group_sizes) - group_sizes
  last = starts + group_sizes - 1
  visits = jnp.where(group_sizes > 0,
                     last // GROUPED_ROW_TILE - starts // GROUPED_ROW_TILE + 1,
                     0)
  return {
      'tokens': jnp.asarray(tokens, jnp.int32),
      'rows_routed': jnp.sum(group_sizes),
      'rows_computed': jnp.sum(visits) * GROUPED_ROW_TILE,
      'rows_max_expert': jnp.max(group_sizes),
      'rows_dropped': jnp.sum(jnp.logical_and(is_held,
                                              jnp.logical_not(placed)),
                              dtype=jnp.int32),
  }
