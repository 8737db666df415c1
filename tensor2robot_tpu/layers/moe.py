"""A sparse expert layer that holds a share of its experts.

The layer is told which experts it holds (``experts_held``, ids among
the ``num_experts`` the router scores). It scores and chooses over ALL
experts, computes the shared expert and the weighted outputs of its own
experts for the tokens that chose them, and leaves out what the absent
experts would add: under expert parallelism that partial sum is this
chip's part of the layer's result. On one chip it runs without an
exchange.

Routing: float32 scores ``s`` over all experts; chosen = top-k of
``s + b`` (``b``: a non-gradient bias an expert, used for the choice
only); ``w = s_chosen / (sum + 1e-20)`` where ``route_norm``, times
``route_scale``; weights are applied after the expert. The scores are
the caller's (``__call__``'s ``scores``: layers/zaya.py hands over the
softmax of its router MLP, chooses one expert and weighs it by its
probability) or, where none are given, afmoe's / torchtitan's:
``sigmoid(Wr x)`` with ``Wr`` the layer's own (``sigmoid_scores``).
``shared_expert`` says whether a SwiGLU that every token passes is
added. After a training step's forward pass, outside the gradient,
``b += load_balance_coeff * sign(mean(count) - count)``, minus its mean;
``b`` and the step's ``count`` live in the mutable collection
``moe_state``, which the trainer carries as ``model_state``.

No token is dropped. The (token, choice) pairs are sorted by the slot of
the expert they chose, held experts first, and the held ones' rows are
copied into a buffer for the grouped matrix product
(``jax.lax.ragged_dot``: on the TPU a grouped-matmul kernel that visits
the tiles of live rows only). Rows move by gathers in both directions
(the sort's permutation and its inverse), never by a scatter.

The buffer's size follows the rows that were routed. It has two sizes
(``ladder``), which the layer's shapes alone decide: twice the rows a
balanced router sends to the held experts, and the worst case the share
allows (every token choosing held experts only: ``tokens * min(k,
held)`` rows). Inside the step, from this step's own count of routed
rows, an on-device conditional takes the lowest rung that holds them;
what lies between the sort and the weighted sum (the gather into the
buffer, the three grouped products with silu x up, the gather back, and
their gradients) runs at that rung's size. The top rung holds every
case, so nothing is dropped whatever the router does. A layer that
holds half the experts or more has one rung and no conditional. A rung's
backward pass computes its buffer again from the layer's input rather
than keep it: what passes from the forward conditional to the backward
one then has the same shape on every rung (a conditional's branches
must agree on their results, so a kept buffer of one rung would be
written as zeros by the others). Each rung is compiled, and loaded at
every start, with its own grouped products: rungs at the balance itself
and between these two were tried and bought too little for that
(PERF.md, PR 29).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# Rows a tile of the TPU's grouped product holds: ``ragged_dot`` lowers
# there to a kernel whose tile table has rows/512 + groups - 1 entries
# (tests/test_chip_compile.py reads that shape back). A tile that two
# experts' rows share is computed once for each, so the rows the product
# computes are (tile, expert) visits x this.
GROUPED_ROW_TILE = 512

MOE_STATE = 'moe_state'
# ``jax.ad_checkpoint.checkpoint_name``s of ``route``'s top-k choice and
# of the experts' output [T, k, hidden], for a remat policy that keeps
# them (layers/afmoe.py does), and then both: the output is a trip through
# the buffer to rebuild and the weighted sum's way back needs it, and the
# choice is what laid it out. A near-tie that falls the other way when
# the scores are computed again would send a pair's gradient to another
# expert than its kept output came from.
CHOSEN_NAME, PICKED_NAME = 'moe_chosen', 'moe_picked'
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
  chosen = checkpoint_name(chosen, CHOSEN_NAME)
  weights = jnp.take_along_axis(scores, chosen, axis=-1)
  if route_norm:
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
  weights = weights * route_scale
  experts = scores.shape[-1]
  counts = jnp.sum(chosen[..., None] == jnp.arange(experts), axis=(0, 1),
                   dtype=jnp.int32)
  return chosen, weights, counts


def sigmoid_scores(layer: 'ExpertLayer', x):
  """afmoe's router, a matrix of ``layer``'s own (``router``):
  ``sigmoid(x Wr)`` in float32."""
  std = layer.init_std if layer.router_std is None else layer.router_std
  router = layer.param('router', normal_init(std),
                       (x.shape[-1], layer.num_experts))
  return jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), router,
                                   precision=HIGHEST))


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
  """The held experts' three stacked matrices."""

  held: int
  width: int
  init_std: float

  @nn.compact
  def __call__(self, d: int):
    gate = self.param('gate', normal_init(self.init_std),
                      (self.held, d, self.width))
    up = self.param('up', normal_init(self.init_std),
                    (self.held, d, self.width))
    down = self.param('down', normal_init(self.init_std),
                      (self.held, self.width, d))
    return gate, up, down


# ------------------------------------------------- the routed-row buffer

def ladder(tokens: int, k: int, held: int, num_experts: int
           ) -> Tuple[int, ...]:
  """The routed-row buffer's sizes, rising: twice the rows a balanced
  router sends to ``held`` of ``num_experts`` experts, then the worst
  case the share allows; the worst case alone where twice the balance
  reaches it."""
  worst = tokens * min(k, held)
  twice_balanced = 2 * -(-tokens * k * held // num_experts)
  return (twice_balanced, worst) if twice_balanced < worst else (worst,)


def _through_buffer(k: int, room: int, x, experts, aux):
  """[tokens, k, hidden]: each held pair's row of its expert's output
  (other pairs 0), by way of a buffer of ``room`` rows sorted by expert:
  gathered from ``x``, through the grouped products, gathered back."""
  gate, up, down = experts
  order, back, placed, group_sizes = aux
  with jax.named_scope('afmoe/moe/route'):
    source = order[:room]
    rows = _dispatch(x, source // k, back, placed)

  def product(lhs, rhs):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=x.dtype)

  with jax.named_scope('afmoe/moe/experts'):
    y = product(jax.nn.silu(product(rows, gate)) * product(rows, up), down)
  with jax.named_scope('afmoe/moe/route'):
    source_live = jnp.arange(room) < jnp.sum(group_sizes)
    return _collect(y, back, placed, source, source_live)


def _cast(tree, dtype):
  return jax.tree_util.tree_map(lambda leaf: leaf.astype(dtype), tree)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _on_rung(k, rungs, rung, x, experts, aux):
  """``_through_buffer(k, rungs[rung], x, experts cast to x's dtype,
  aux)``, the rung taken by a conditional on the device. The way back
  takes the same rung and goes through its buffer again there: between
  the two conditionals pass the arguments alone, which no rung shapes."""
  return _on_rung_fwd(k, rungs, rung, x, experts, aux)[0]


def _on_rung_fwd(k, rungs, rung, x, experts, aux):
  cast = _cast(experts, x.dtype)
  return _forward(k, rungs, rung, x, cast, aux), (rung, x, cast, aux)


def _on_rung_bwd(k, rungs, res, g):
  dx, dexperts = _backward(k, rungs, *res, g)
  return None, dx, dexperts, None


_on_rung.defvjp(_on_rung_fwd, _on_rung_bwd)


# Jitted so that layers of one shape are traced and lowered once: every
# rung is a branch, and a start pays for each trace (PERF.md, PR 29).
@functools.partial(jax.jit, static_argnums=(0, 1))
def _forward(k, rungs, rung, x, cast, aux):
  return jax.lax.switch(
      rung, [functools.partial(_through_buffer, k, room) for room in rungs],
      x, cast, aux)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _backward(k, rungs, rung, x, cast, aux, g):
  def back(room, x, cast, aux, g):
    # The experts' gradients leave the branch in the parameters' dtype,
    # converted where they are made: handed on in ``x``'s dtype, the
    # conversion and the optimizer's first use of them ran as passes of
    # their own over every expert matrix (PERF.md, PR 29).
    dx, dcast = jax.vjp(
        lambda x, cast: _through_buffer(k, room, x, cast, aux), x, cast)[1](g)
    return dx, _cast(dcast, jnp.float32)

  return jax.lax.switch(
      rung, [functools.partial(back, room) for room in rungs],
      x, cast, aux, g)


class ExpertLayer(nn.Module):
  """See the module docstring. ``__call__`` takes [..., hidden] and,
  where the router is the caller's, its float32 ``scores`` [..., experts];
  it returns the input's shape and a dict of this call's counts (int32
  scalars: ``tokens``, ``rows_routed``, ``rows_computed``,
  ``rows_max_expert``, ``rows_dropped``, and ``rows_room``, the rows of
  the buffer's rung this call took: over ``tokens * min(k, held)`` it is
  the share of the worst case that was moved; with the caller's scores
  also ``weight_e6``, the mean weight of a (token, choice) pair in
  millionths: how decided a softmax router is)."""

  num_experts: int                 # the router's width: all published
  experts_per_token: int
  expert_width: int
  experts_held: Optional[Tuple[int, ...]] = None   # None: all of them
  route_norm: bool = True
  route_scale: float = 1.0
  load_balance_coeff: float = 0.0
  dtype: Any = jnp.float32
  init_std: float = 0.02
  shared_expert: bool = True
  router_std: Optional[float] = None   # of ``sigmoid_scores``' matrix

  @nn.compact
  def __call__(self, x, train: bool = False, scores=None):
    shape = x.shape
    x = x.reshape((-1, shape[-1])).astype(self.dtype)
    tokens, k = x.shape[0], self.experts_per_token
    held_ids = (tuple(range(self.num_experts)) if self.experts_held is None
                else tuple(self.experts_held))
    held = len(held_ids)
    pairs = tokens * k
    rungs = ladder(tokens, k, held, self.num_experts)

    given = scores is not None
    if not given:
      with jax.named_scope('afmoe/moe/route'):
        scores = sigmoid_scores(self, x)
    bias = self.variable(MOE_STATE, 'bias', jnp.zeros, (self.num_experts,),
                         jnp.float32)
    last_counts = self.variable(MOE_STATE, 'counts', jnp.zeros,
                                (self.num_experts,), jnp.int32)

    with jax.named_scope('afmoe/moe/route'):
      chosen, weights, counts = route(
          scores.reshape((tokens, self.num_experts)),
          jax.lax.stop_gradient(bias.value), k, self.route_norm,
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
      # The lowest rung that holds this call's rows (the last holds any).
      rung = jnp.sum(jnp.sum(group_sizes) > jnp.asarray(rungs[:-1]),
                     dtype=jnp.int32)
      room = jnp.asarray(rungs, jnp.int32)[rung]
      is_held = slot < held
      placed = jnp.logical_and(is_held, position < room)
      back = jnp.minimum(position, room - 1)

    experts = _Experts(held, self.expert_width, self.init_std,
                       name='experts')(shape[-1])
    aux = (order, back, placed, group_sizes)
    if len(rungs) == 1:                                   # [T, k, D]
      picked = _through_buffer(k, rungs[0], x, _cast(experts, self.dtype),
                               aux)
    else:
      picked = _on_rung(k, rungs, rung, x, experts, aux)
    picked = checkpoint_name(picked, PICKED_NAME)
    with jax.named_scope('afmoe/moe/route'):
      out = jnp.sum(picked.astype(jnp.float32) *
                    jnp.where(placed, weights, 0.0)[..., None], axis=1)

    if self.shared_expert:
      with jax.named_scope('afmoe/moe/shared'):
        shared = SwiGLU(self.expert_width, self.dtype, self.init_std,
                        name='shared')(x)
      out = shared.astype(jnp.float32) + out
    out = out.astype(self.dtype)

    if train and not self.is_initializing():
      # In a deployment the counts are summed over the data-parallel
      # chips first; here they are this chip's tokens.
      bias.value = updated_bias(bias.value, counts, self.load_balance_coeff)
      last_counts.value = counts
    stats = _stats(tokens, is_held, placed, group_sizes, room)
    if given:
      stats['weight_e6'] = jnp.round(1e6 * jnp.mean(weights)).astype(
          jnp.int32)
    return out.reshape(shape), stats


def _stats(tokens: int, is_held, placed, group_sizes,
           room) -> Dict[str, jax.Array]:
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
      'rows_room': room,
  }
