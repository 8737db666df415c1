"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has no long-context machinery (SURVEY §5: episodes ≤ ~100
steps), but this framework treats sequence parallelism as first-class so
the same trainer scales to long-horizon/context workloads:

* :func:`ring_attention` — blockwise attention over the ``seq`` mesh axis:
  each device holds a query block; key/value blocks rotate around the ring
  with ``jax.lax.ppermute`` while a numerically-stable online softmax
  (flash-attention style m/l/o accumulators) folds in one block per hop.
  Communication rides ICI neighbor links; memory per device is O(T/n).
* :func:`ulysses_attention` — all-to-all alternative: resharding
  [seq-sharded, all heads] → [full seq, head-sharded] with
  ``jax.lax.all_to_all``, full local attention per head group, and the
  inverse all-to-all. Cheaper at moderate T when heads ≥ mesh axis size.

Both are pure functions designed for use INSIDE ``shard_map`` over a mesh
``seq`` axis; :func:`make_ring_attention` / :func:`make_ulysses_attention`
build the sharded callable for a given mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from tensor2robot_tpu.parallel.mesh import SEQ_AXIS


def _block_attention(q, k, v, mask, m_prev, l_prev, o_prev):
  """One online-softmax accumulation step (flash-attention recurrence).

  q: [B, Tq, H, D]; k/v: [B, Tk, H, D]; mask: [Tq, Tk] or None.
  Accumulators: m [B, H, Tq], l [B, H, Tq], o [B, Tq, H, D].
  """
  scale = 1.0 / np.sqrt(q.shape[-1])
  # [B, H, Tq, Tk]
  logits = jnp.einsum('bqhd,bkhd->bhqk', q, k) * scale
  if mask is not None:
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
  m_block = jnp.max(logits, axis=-1)  # [B, H, Tq]
  m_new = jnp.maximum(m_prev, m_block)
  # Guard fully-masked rows: exp(-inf - -inf) → exp(0); zero them via l.
  safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
  p = jnp.exp(logits - safe_m[..., None])
  p = jnp.where(jnp.isfinite(logits), p, 0.0)
  correction = jnp.where(
      jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)  # [B, H, Tq]
  l_new = l_prev * correction + jnp.sum(p, axis=-1)
  o_scaled = o_prev * correction.transpose(0, 2, 1)[..., None]
  o_new = o_scaled + jnp.einsum('bhqk,bkhd->bqhd', p, v)
  return m_new, l_new, o_new


def ring_attention(q: jax.Array,
                   k: jax.Array,
                   v: jax.Array,
                   axis_name: str = SEQ_AXIS,
                   causal: bool = False,
                   kv_chunk: Optional[int] = None) -> jax.Array:
  """Blockwise ring attention; call INSIDE shard_map over ``axis_name``.

  Args:
    q, k, v: process-local blocks [B, T_local, H, D]; the global sequence
      is the concatenation over the mesh axis.
    axis_name: the mesh axis the sequence is sharded over.
    causal: apply a causal mask over GLOBAL positions.
    kv_chunk: process each hop's K/V in chunks of this many positions so
      the per-hop logits tensor is [B, H, T_local, kv_chunk] instead of
      [B, H, T_local, T_local] — the memory knob for long per-device
      shards. Must divide ``T_local``; default = one chunk per hop.

  Returns:
    [B, T_local, H, D] attention output for the local query block.
  """
  axis_size = jax.lax.psum(1, axis_name)
  my_index = jax.lax.axis_index(axis_name)
  batch, t_local, heads, dim = q.shape
  chunk = t_local if kv_chunk is None else kv_chunk
  if chunk <= 0 or t_local % chunk:
    raise ValueError(
        f'kv_chunk ({chunk}) must divide the local sequence ({t_local}).')
  n_chunks = t_local // chunk

  m0 = jnp.full((batch, heads, t_local), -jnp.inf, jnp.float32)
  l0 = jnp.zeros((batch, heads, t_local), jnp.float32)
  o0 = jnp.zeros((batch, t_local, heads, dim), jnp.float32)
  q32 = q.astype(jnp.float32)

  def hop(i, carry):
    m, l, o, k_blk, v_blk = carry
    # This hop's kv block originated on device (my_index - i) % axis_size.
    src = (my_index - i) % axis_size

    def chunk_step(c, inner):
      m, l, o = inner
      k_c = jax.lax.dynamic_slice_in_dim(k_blk, c * chunk, chunk, axis=1)
      v_c = jax.lax.dynamic_slice_in_dim(v_blk, c * chunk, chunk, axis=1)
      if causal:
        q_pos = my_index * t_local + jnp.arange(t_local)  # [Tq]
        k_pos = src * t_local + c * chunk + jnp.arange(chunk)  # [chunk]
        mask = q_pos[:, None] >= k_pos[None, :]
      else:
        mask = None
      return _block_attention(
          q32, k_c.astype(jnp.float32), v_c.astype(jnp.float32), mask,
          m, l, o)

    if n_chunks == 1:  # unchunked hot path: no nested scan under grad
      m, l, o = chunk_step(0, (m, l, o))
    else:
      m, l, o = jax.lax.fori_loop(0, n_chunks, chunk_step, (m, l, o))
    # Rotate kv around the ring: device d sends to d+1 (next hop's block
    # on this device then originates one device further back).
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
    v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    return m, l, o, k_blk, v_blk

  m, l, o, _, _ = jax.lax.fori_loop(0, axis_size, hop, (m0, l0, o0, k, v))
  l = jnp.maximum(l, 1e-20)
  out = o / l.transpose(0, 2, 1)[..., None]
  return out.astype(q.dtype)


def ulysses_attention(q: jax.Array,
                      k: jax.Array,
                      v: jax.Array,
                      axis_name: str = SEQ_AXIS,
                      causal: bool = False) -> jax.Array:
  """All-to-all (Ulysses) sequence parallelism; call INSIDE shard_map.

  Reshards [B, T/n, H, D] → [B, T, H/n, D] with one all-to-all, runs full
  local attention over the complete sequence for its head group, and
  reshards back. Requires ``H % axis_size == 0``.
  """
  axis_size = jax.lax.psum(1, axis_name)
  heads = q.shape[2]
  if heads % axis_size:
    raise ValueError(
        f'ulysses_attention needs heads ({heads}) divisible by the '
        f'sequence axis size ({axis_size}).')

  def to_headsharded(x):  # [B, T/n, H, D] -> [B, T, H/n, D]
    return jax.lax.all_to_all(
        x, axis_name, split_axis=2, concat_axis=1, tiled=True)

  def to_seqsharded(x):  # [B, T, H/n, D] -> [B, T/n, H, D]
    return jax.lax.all_to_all(
        x, axis_name, split_axis=1, concat_axis=2, tiled=True)

  ql, kl, vl = to_headsharded(q), to_headsharded(k), to_headsharded(v)
  t, d = ql.shape[1], ql.shape[3]
  from tensor2robot_tpu.ops.flash_attention import (flash_attention,
                                                    is_supported)

  if is_supported(t, d, itemsize=ql.dtype.itemsize):
    # The full-sequence local attention is exactly the flash kernel's
    # job: O(T·D) HBM memory instead of the [B, H, T, T] logits tensor.
    out = flash_attention(ql, kl, vl, causal)
  else:
    mask = (jnp.tril(jnp.ones((t, t), bool)) if causal else None)
    m0 = jnp.full(ql.shape[:1] + (ql.shape[2], t), -jnp.inf, jnp.float32)
    l0 = jnp.zeros_like(m0)
    o0 = jnp.zeros(ql.shape, jnp.float32)
    m, l, o = _block_attention(
        ql.astype(jnp.float32), kl.astype(jnp.float32),
        vl.astype(jnp.float32), mask, m0, l0, o0)
    out = (o / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]).astype(
        q.dtype)
  return to_seqsharded(out)


def _sharded_apply(fn, mesh: Mesh, axis_name: str, causal: bool):
  spec = P(None, axis_name, None, None)

  def apply(q, k, v):
    return fn(q, k, v, axis_name=axis_name, causal=causal)

  # Replication checking stays off: the attention bodies use unchecked
  # collectives.
  return jax.shard_map(apply, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)


def make_ring_attention(mesh: Mesh,
                        axis_name: str = SEQ_AXIS,
                        causal: bool = False,
                        kv_chunk: Optional[int] = None):
  """Jittable [B, T, H, D] → [B, T, H, D] ring attention over ``mesh``."""
  fn = functools.partial(ring_attention, kv_chunk=kv_chunk)
  return _sharded_apply(fn, mesh, axis_name, causal)


def make_ulysses_attention(mesh: Mesh,
                           axis_name: str = SEQ_AXIS,
                           causal: bool = False):
  """Jittable [B, T, H, D] → [B, T, H, D] Ulysses attention over ``mesh``."""
  return _sharded_apply(ulysses_attention, mesh, axis_name, causal)


def reference_attention(q, k, v, causal: bool = False):
  """Plain full attention (the numerics oracle for tests)."""
  scale = 1.0 / np.sqrt(q.shape[-1])
  logits = jnp.einsum('bqhd,bkhd->bhqk', q.astype(jnp.float32),
                      k.astype(jnp.float32)) * scale
  if causal:
    t = q.shape[1]
    mask = jnp.tril(jnp.ones((t, t), bool))
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
  probs = jax.nn.softmax(logits, axis=-1)
  return jnp.einsum('bhqk,bkhd->bqhd', probs,
                    v.astype(jnp.float32)).astype(q.dtype)
