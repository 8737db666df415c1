"""Pallas TPU flash attention: online-softmax attention without the
[B, H, T, T] logits materialization.

The long-context compute primitive backing
:mod:`tensor2robot_tpu.parallel.sequence_parallel`: plain XLA attention
writes the full logits/probs tensors to HBM (O(T²) memory and traffic);
this kernel keeps flash-style (m, l, acc) accumulators in registers/VMEM
and loops over K/V blocks, so HBM memory is O(T·D) and the MXU sees
back-to-back ``q·kᵀ`` / ``p·v`` matmuls. Trace-measured on a v5e chip at
[2, 4096, 8, 64]: 1.2 ms vs 4.5 ms for the XLA einsum+softmax chain
(3.7×), with the gap growing quadratically in T.

Backward follows FlashAttention-2: the forward additionally saves the
per-row logsumexp ``L``; backward recomputes probabilities blockwise,
with ``D = rowsum(dO ⊙ O)`` precomputed. The staged kernels produce dq in
a q-block grid and dk/dv in a k-block grid. The streamed backward is ONE
kernel (``flash_attention_bwd``) that walks the block pairs once, key
block innermost: each live pair computes its scores, probabilities, dP
and dS once and feeds dq (summed over the key blocks in a block of
scratch) and dk and dv (summed into float32 scratch that holds a whole
key/value head, written out when the head ends): 5 matrix products a
pair where a kernel each for dq and dk/dv run 7. That holds while a
head's dk and dv fit VMEM (``_fuses_backward``: 16,384 tokens at D=128,
8,192 at D=256, in bfloat16); past it the two streamed kernels
(``flash_attention_dq``, ``flash_attention_dkv``) remain, O(block) in
VMEM both.

Constraints (see :func:`is_supported`): ``T`` divisible by the
(8-aligned) block sizes; head dim ≤ 256, whole lane tiles (a multiple
of 128) past 128: latent attention's heads are 192 content + 64 rotary
dimensions wide. Two implementations behind one API: up to
``T·D ≤ 2M`` elements (~32k tokens at D=64; half of that past one lane
tile a head, ``_use_streamed``) the per-sequence K/V are staged
into VMEM wholesale (fewer DMAs, dynamic causal early-exit); past that
the streamed kernels take over — K/V blocks
become an inner sequential grid dimension with the flash accumulators in
VMEM scratch, so memory is O(block) and T is bounded only by HBM. Runs
in interpret mode off-TPU so the CPU-mesh test suite exercises the same
code paths.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu.ops import _pallas_dispatch as dispatch

_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp/corr math
                  # finite without isfinite guards in the inner loop

# ``jax.ad_checkpoint.checkpoint_name``s of the forward kernel's folded
# output and log-sum-exp, the two residuals that cost a kernel pass to
# rebuild: a remat policy that names them keeps them (layers/afmoe.py
# does), and under any other the tags are identities.
OUT_NAME, LSE_NAME = 'attn_out', 'attn_lse'


def _use_interpret() -> bool:
  # Shared dispatch scaffolding (ops/_pallas_dispatch.py): interpret
  # everywhere Mosaic can't lower, not just cpu.
  return dispatch.use_interpret()

def _block_live(q0, bq, k0, bk=None, window=None):
  """Causal block-liveness: a key block [k0, k0+bk) contributes to a
  query block [q0, q0+bq) iff its first key is not past the last query
  and, under a ``window``, its last key is not behind the first query's
  window (the companion of _scores' per-element mask)."""
  live = q0 + bq - 1 >= k0
  if window is not None:
    live = jnp.logical_and(live, k0 + bk - 1 > q0 - window)
  return live


def _live_key_blocks(qb, bq, bk, nk, window):
  """[first, last] key blocks that :func:`_block_live` admits for query
  block ``qb`` of a causal problem."""
  last = jnp.minimum((qb * bq + bq - 1) // bk, nk - 1)
  if window is None:
    return 0, last
  return jnp.maximum(qb * bq - window + 1, 0) // bk, last


def _live_query_blocks(kb, bq, bk, nq, window):
  """[first, last] query blocks that see key block ``kb``."""
  first = (kb * bk) // bq
  if window is None:
    return first, nq - 1
  return first, jnp.minimum((kb * bk + bk + window - 2) // bq, nq - 1)


def _scores(q, k, q0, k0, causal, scale=None, window=None):
  """Scaled (optional) masked q·kᵀ block scores; (q0, k0) are the global
  offsets of the blocks — THE shared definition of the mask (causal:
  ``i >= j``; with a ``window`` also ``i - j < window``) and score math
  for every kernel variant (staged and streamed)."""
  s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)
  if scale is not None:
    s = s * scale
  if causal:
    bq, bk = s.shape
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    seen = qpos >= kpos
    if window is not None:
      seen = jnp.logical_and(seen, qpos - kpos < window)
    s = jnp.where(seen, s, _NEG_INF)
  return s


def _online_softmax_step(s, m, l, acc, v):
  """One flash accumulator update from a block of scores."""
  m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
  # Rows with every key masked so far have m_new == _NEG_INF; clamp the
  # subtrahend so exp(_NEG_INF - m_new) stays 0 instead of exp(0) = 1.
  m_sub = jnp.maximum(m_new, 0.5 * _NEG_INF)
  p = jnp.exp(s - m_sub)
  corr = jnp.exp(m - m_sub)
  l = l * corr + jnp.sum(p, axis=1, keepdims=True)
  acc = acc * corr + jax.lax.dot_general(
      p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
      preferred_element_type=jnp.float32)
  return m_new, l, acc


def _ds_block(s, lse, do, v, delta):
  """FlashAttention-2 backward core: (p, ds) from saved logsumexp."""
  p = jnp.exp(s - lse)
  dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)
  return p, p * (dp - delta)



# ----------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, bk, causal, scale):
  qb = pl.program_id(1)
  bq, d = q_ref.shape[1], q_ref.shape[2]
  t = k_ref.shape[1]
  nk = t // bk
  q = q_ref[0].astype(jnp.float32) * scale
  m = jnp.full((bq, 1), _NEG_INF, jnp.float32)
  l = jnp.zeros((bq, 1), jnp.float32)
  acc = jnp.zeros((bq, d), jnp.float32)

  def body(i, carry):
    m, l, acc = carry
    k = k_ref[0, pl.dslice(i * bk, bk), :].astype(jnp.float32)
    v = v_ref[0, pl.dslice(i * bk, bk), :].astype(jnp.float32)
    s = _scores(q, k, qb * bq, i * bk, causal)
    return _online_softmax_step(s, m, l, acc, v)

  if causal:
    # Only key blocks at/before this q block's diagonal contribute.
    nk_eff = jnp.minimum((qb * bq + bq + bk - 1) // bk, nk)
  else:
    nk_eff = nk
  m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m, l, acc))
  l = jnp.maximum(l, 1e-30)
  o_ref[0] = (acc / l).astype(o_ref.dtype)
  lse_ref[0, 0] = (m[:, 0] + jnp.log(l[:, 0]))


# ----------------------------------------------------- streamed variants
#
# For sequences past the whole-KV-in-VMEM bound, K/V blocks become a
# THIRD (innermost, sequential) grid dimension and the flash accumulators
# live in VMEM scratch across those steps — VMEM usage is O(block), so T
# is bounded only by HBM. Slightly slower than the staged kernels at
# small T (per-block DMAs; causal skipping via pl.when instead of a
# shortened loop), so the dispatcher uses these only when needed.


def _fwd_kernel_streamed(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                         acc_scr, *, causal, scale, nk, window):
  qb, kb = pl.program_id(1), pl.program_id(2)
  bq, d = q_ref.shape[1], q_ref.shape[2]
  bk = k_ref.shape[1]

  @pl.when(kb == 0)
  def _():
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

  # Causal: key blocks strictly above the diagonal (and, windowed, wholly
  # behind the window) contribute nothing.
  live = _block_live(qb * bq, bq, kb * bk, bk, window) if causal else True

  @pl.when(live)
  def _():
    # Products take their operands as the caller holds them (bfloat16
    # stays bfloat16 for the MXU); every sum is float32.
    dt = q_ref.dtype
    q = (q_ref[0].astype(jnp.float32) * scale).astype(dt)
    s = _scores(q, k_ref[0].astype(dt), qb * bq, kb * bk, causal,
                window=window)
    m_new, l_new, acc_new = _online_softmax_step(
        s, m_scr[...], l_scr[...], acc_scr[...], v_ref[0].astype(dt))
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

  @pl.when(kb == nk - 1)
  def _():
    l = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m_scr[...][:, 0] + jnp.log(l[:, 0])


def _dq_kernel_streamed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dq_scr, *, causal, scale, nk, window):
  qb, kb = pl.program_id(1), pl.program_id(2)
  bq, d = q_ref.shape[1], q_ref.shape[2]
  bk = k_ref.shape[1]

  @pl.when(kb == 0)
  def _():
    dq_scr[...] = jnp.zeros_like(dq_scr)

  live = _block_live(qb * bq, bq, kb * bk, bk, window) if causal else True

  @pl.when(live)
  def _():
    q = q_ref[0]
    k, v, do = (r[0].astype(q.dtype) for r in (k_ref, v_ref, do_ref))
    lse = lse_ref[0, 0][:, None]
    delta = delta_ref[0, 0][:, None]
    s = _scores(q, k, qb * bq, kb * bk, causal, scale, window)
    _, ds = _ds_block(s, lse, do, v, delta)
    dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
        ds.astype(q.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

  @pl.when(kb == nk - 1)
  def _():
    dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel_streamed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_scr, dv_scr, *, causal, scale,
                         nq, group, window):
  # The innermost grid dimension walks the ``group`` query heads that
  # share this key/value head, ``nq`` query blocks each.
  kb, r = pl.program_id(1), pl.program_id(2)
  qb = r % nq
  bk, d = k_ref.shape[1], k_ref.shape[2]
  bq = q_ref.shape[1]

  @pl.when(r == 0)
  def _():
    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)

  live = _block_live(qb * bq, bq, kb * bk, bk, window) if causal else True

  @pl.when(live)
  def _():
    q = q_ref[0]
    k, v, do = (r[0].astype(q.dtype) for r in (k_ref, v_ref, do_ref))
    lse = lse_ref[0, 0][:, None]
    delta = delta_ref[0, 0][:, None]
    s = _scores(q, k, qb * bq, kb * bk, causal, scale, window)
    p, ds = _ds_block(s, lse, do, v, delta)
    dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
        p.astype(q.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

  @pl.when(r == group * nq - 1)
  def _():
    dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_kernel_fused(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                      causal, scale, nq, nk, group, window):
  # Grid (kv head, group x query blocks, key blocks), key block innermost:
  # dq's order, with the ``group`` query heads of one key/value head
  # folded into the middle dimension as ``_dkv_kernel_streamed`` walks
  # them. A live block pair computes s, p, dp, ds once and feeds three
  # sums: dq over its key blocks, and the key block's rows of the WHOLE
  # key/value head's dk and dv, which stay in VMEM until the head ends.
  # For one key block the pairs come in ``_dkv_kernel_streamed``'s order,
  # for one query block in ``_dq_kernel_streamed``'s: the same sums.
  r, kb = pl.program_id(1), pl.program_id(2)
  qb = r % nq
  bq, bk = q_ref.shape[1], k_ref.shape[1]

  @pl.when(jnp.logical_and(r == 0, kb == 0))
  def _():
    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)

  @pl.when(kb == 0)
  def _():
    dq_scr[...] = jnp.zeros_like(dq_scr)

  live = _block_live(qb * bq, bq, kb * bk, bk, window) if causal else True

  @pl.when(live)
  def _():
    q = q_ref[0]
    k, v, do = (x[0].astype(q.dtype) for x in (k_ref, v_ref, do_ref))
    lse = lse_ref[0, 0][:, None]
    delta = delta_ref[0, 0][:, None]
    s = _scores(q, k, qb * bq, kb * bk, causal, scale, window)
    p, ds = _ds_block(s, lse, do, v, delta)
    ds = ds.astype(q.dtype)
    dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    rows = pl.ds(pl.multiple_of(kb * bk, bk), bk)
    dv_scr[rows, :] = dv_scr[rows, :] + jax.lax.dot_general(
        p.astype(q.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dk_scr[rows, :] = dk_scr[rows, :] + jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

  @pl.when(kb == nk - 1)
  def _():
    dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)

  @pl.when(jnp.logical_and(r == group * nq - 1, kb == nk - 1))
  def _():
    dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               bk, causal, scale):
  qb = pl.program_id(1)
  bq, d = q_ref.shape[1], q_ref.shape[2]
  t = k_ref.shape[1]
  nk = t // bk
  q = q_ref[0].astype(jnp.float32)
  do = do_ref[0].astype(jnp.float32)
  lse = lse_ref[0, 0][:, None]        # [bq, 1]
  delta = delta_ref[0, 0][:, None]    # [bq, 1]
  dq = jnp.zeros((bq, d), jnp.float32)

  def body(i, dq):
    k = k_ref[0, pl.dslice(i * bk, bk), :].astype(jnp.float32)
    v = v_ref[0, pl.dslice(i * bk, bk), :].astype(jnp.float32)
    s = _scores(q, k, qb * bq, i * bk, causal, scale)
    _, ds = _ds_block(s, lse, do, v, delta)
    return dq + jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

  if causal:
    nk_eff = jnp.minimum((qb * bq + bq + bk - 1) // bk, nk)
  else:
    nk_eff = nk
  dq = jax.lax.fori_loop(0, nk_eff, body, dq)
  dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, *, bq, causal, scale):
  kb = pl.program_id(1)
  bk, d = k_ref.shape[1], k_ref.shape[2]
  t = q_ref.shape[1]
  nq = t // bq
  k = k_ref[0].astype(jnp.float32)
  v = v_ref[0].astype(jnp.float32)
  dk = jnp.zeros((bk, d), jnp.float32)
  dv = jnp.zeros((bk, d), jnp.float32)

  def body(i, carry):
    dk, dv = carry
    q = q_ref[0, pl.dslice(i * bq, bq), :].astype(jnp.float32)
    do = do_ref[0, pl.dslice(i * bq, bq), :].astype(jnp.float32)
    lse = lse_ref[0, 0, pl.dslice(i * bq, bq)][:, None]
    delta = delta_ref[0, 0, pl.dslice(i * bq, bq)][:, None]
    s = _scores(q, k, i * bq, kb * bk, causal, scale)
    p, ds = _ds_block(s, lse, do, v, delta)
    dv = dv + jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dk = dk + jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return dk, dv

  if causal:
    # Only q blocks at/after this k block's diagonal contribute.
    start = (kb * bk) // bq
  else:
    start = 0
  dk, dv = jax.lax.fori_loop(start, nq, body, (dk, dv))
  dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
  dv_ref[0] = dv.astype(dv_ref.dtype)


# -------------------------------------------------------------- public api


def _fold_heads(x):
  b, t, h, d = x.shape
  return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unfold_heads(x, b, h):
  bh, t, d = x.shape
  return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512

# Whole-sequence K/V staging fits VMEM up to 2·t·d·itemsize ≤ ~8 MB of
# the ~16 MB; beyond it the streamed kernels (K/V blocks as an inner grid
# dim, scratch accumulators) take over, bounded only by HBM. A byte (not
# element) budget: float32 q/k/v halves the staged-T range vs bfloat16.
_MAX_STAGED_KV_BYTES = 8 * 1024 * 1024

# Heads are at most two lane tiles wide, whole tiles past one.
MAX_HEAD_DIM = 256


def _use_streamed(t: int, d: int, itemsize: int = 2) -> bool:
  # The staged kernels compute in float32 throughout: past one lane tile
  # a head their [block, d] float32 blocks and accumulators double
  # beside the staged K/V, whose budget halves.
  return 2 * t * d * itemsize > _MAX_STAGED_KV_BYTES // max(1, d // 128)


# Streamed-regime default tile: much larger than the staged default.
# Measured r4 at [1, 65536, 8, 64] bf16 causal fwd (float32 products):
# 256/512 → 187.6 ms, 512/512 → 146.0, 512/1024 → 91.3, 1024/1024 →
# 75.5 ms (2.5×); 2048/2048 fails Mosaic compile (VMEM). The staged
# kernels keep the smaller q blocks so whole-KV staging + accumulators
# fit VMEM. The streamed backward shares these blocks, as one kernel
# where ``_fuses_backward`` says so and as dq + dk/dv beyond: at
# [1, 8192, 32/4, 128] bf16 causal the fused kernel read 8.10 us a live
# 1024/1024 pair against the two kernels' 10.82, and 8.11 at 512/1024,
# 7.88 at 1024/512 (where the forward reads 7.09 ms for 4.87) and 8.57 at
# 512/512, each for the same area (v5e, PERF.md PR 33).
_STREAMED_BLOCKS = (1024, 512, 256, 128, 64, 32, 16, 8)


def _streams(t: int, d: int, itemsize: int, group: int = 1,
             window: Optional[int] = None) -> bool:
  """Grouped heads and a window exist in the streamed kernels only."""
  return _use_streamed(t, d, itemsize) or group > 1 or window is not None


# The streamed backward is one kernel while a key/value head's dk and dv
# stay in VMEM for the head's whole walk: their float32 sums [t, d] and
# the two out blocks they are written to at its end (double-buffered).
# 16 MiB at 8,192 x 128 in bfloat16; a v5e core holds 128 MiB, of which
# the [block, block] float32 intermediates take some 30. Past the budget
# (65,536 x 64: 64 MiB) dq and dk/dv keep a kernel each, O(block) both.
_MAX_RESIDENT_DKV_BYTES = 32 * 1024 * 1024


def _resident_dkv_bytes(t: int, d: int, itemsize: int) -> int:
  return 2 * t * d * (4 + 2 * itemsize)


def _fuses_backward(t: int, d: int, itemsize: int = 2) -> bool:
  return _resident_dkv_bytes(t, d, itemsize) <= _MAX_RESIDENT_DKV_BYTES


def _resolve_blocks(t: int, d: int, block_q: Optional[int],
                    block_k: Optional[int], itemsize: int = 2,
                    group: int = 1,
                    window: Optional[int] = None) -> Tuple[int, int]:
  """Regime-dependent block defaults (None → auto)."""
  if block_q is None or block_k is None:
    if _streams(t, d, itemsize, group, window):
      best = next((blk for blk in _STREAMED_BLOCKS if t % blk == 0),
                  DEFAULT_BLOCK_Q)
      while window is not None and best > max(window, 8):
        best //= 2  # a block wider than the window is mostly masked
      block_q = block_q if block_q is not None else best
      block_k = block_k if block_k is not None else best
    else:
      block_q = block_q if block_q is not None else DEFAULT_BLOCK_Q
      block_k = block_k if block_k is not None else DEFAULT_BLOCK_K
  return block_q, block_k


def is_supported(t: int, d: int, block_q: Optional[int] = None,
                 block_k: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 itemsize: int = 2) -> bool:
  """Whether ``flash_attention`` handles a [_, t, _, d] problem.

  The dispatch predicate shared with the sequence-parallel wrappers —
  callers fall back to plain attention when this is False.
  ``block_q``/``block_k`` default to the same regime-dependent
  resolution ``flash_attention`` itself applies; pass the input's
  ``dtype.itemsize`` so the staged/streamed regime (a VMEM *byte*
  budget) resolves exactly as the kernel will — the default 2 models
  bfloat16, and float32 inputs with T·D in the (1M, 2M] band stream
  where bf16 would stage. The head dim is a multiple of 8 up to one lane
  tile (128), or two whole tiles (``MAX_HEAD_DIM`` 256: a latent-attention
  head of 192 content + 64 rotary dimensions).

  On a real TPU the blocks must additionally be at least a lane tile
  (128): the logsumexp output places the q-block dim in lanes, and
  Mosaic rejects sub-tile vector stores (found by driving a T=8 SNAIL
  episode on hardware — interpret mode accepts any 8-aligned block, so
  the CPU suite can't see this). ``interpret=None`` resolves from the
  current backend.
  """
  if interpret is None:
    interpret = _use_interpret()
  block_q, block_k = _resolve_blocks(t, d, block_q, block_k, itemsize)
  bq, bk = min(block_q, t), min(block_k, t)
  min_block = dispatch.min_lane_block(interpret)
  return (0 < d <= MAX_HEAD_DIM and d % (8 if d <= 128 else 128) == 0 and
          t % bq == 0 and t % bk == 0 and
          bq % min_block == 0 and bk % min_block == 0)


def _check(q, block_q, block_k):
  b, t, h, d = q.shape
  if d > MAX_HEAD_DIM:
    raise ValueError(
        f'flash_attention requires head dim <= {MAX_HEAD_DIM}, got {d}')
  block_q, block_k = _resolve_blocks(t, d, block_q, block_k,
                                     q.dtype.itemsize)
  bq, bk = min(block_q, t), min(block_k, t)
  if t % bq or t % bk:
    raise ValueError(
        f'sequence length {t} must be divisible by block sizes '
        f'({bq}, {bk}); pad the sequence.')
  if not is_supported(t, d, block_q, block_k,
                      itemsize=q.dtype.itemsize):
    raise ValueError(
        f'flash_attention unsupported for T={t}, D={d} '
        f'(alignment; see is_supported).')
  return bq, bk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None):
  """[B, T, H, D] attention, O(T·D) memory. Same contract as
  ``sequence_parallel.reference_attention``. ``block_q``/``block_k``
  default per regime: staged 256/512; streamed 1024/1024 (see
  ``_resolve_blocks``).

  ``k`` and ``v`` may carry fewer heads than ``q`` ([B, T, Hkv, D], H a
  multiple of Hkv): query head ``h`` attends to key/value head
  ``h // (H // Hkv)``. ``window`` (causal only) keeps keys with
  ``0 <= i - j < window``. Either one takes the streamed kernels, whose
  key/value index maps skip the blocks the mask kills and whose matrix
  products take their operands in ``q``'s own dtype (sums are float32
  always; the staged kernels compute in float32 throughout)."""
  out, _ = _flash_fwd(q, k, v, causal, block_q, block_k, window)
  return out


def _plan(t, d, itemsize, group, block_q, block_k, window):
  """(bq, bk, streamed?) of a problem, the same on the way back."""
  block_q, block_k = _resolve_blocks(t, d, block_q, block_k, itemsize,
                                     group, window)
  return (min(block_q, t), min(block_k, t),
          _streams(t, d, itemsize, group, window))


def _flash_call(q, k, v, causal, bq, bk, group, streamed, window):
  bh, t, d = q.shape
  scale = 1.0 / np.sqrt(d)
  if streamed:
    nk = t // bk

    def kv_map(i, j, g):
      if causal:  # a dead block re-reads a live one: no copy is made
        first, last = _live_key_blocks(j, bq, bk, nk, window)
        g = jnp.clip(g, first, last)
      return (i // group, g, 0)

    kern = functools.partial(_fwd_kernel_streamed, causal=causal,
                             scale=scale, nk=nk, window=window)
    # Two lane tiles a head outgrow the compiler's own VMEM bound: the
    # [bq, bk] float32 intermediates, the double-buffered blocks and the
    # accumulator. (One tile asks for nothing and lowers as it always did.)
    vmem = None if d <= 128 else pltpu.CompilerParams(vmem_limit_bytes=(
        6 * bq * bk * 4 + 4 * (bq + bk) * d * q.dtype.itemsize +
        2 * bq * d * 4))
    return pl.pallas_call(
        kern,
        grid=(bh, t // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, g: (i, j, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, g: (i, j, 0)),
            pl.BlockSpec((1, 1, bq), lambda i, j, g: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=_use_interpret(),
        name='flash_attention_fwd',
        compiler_params=vmem,
    )(q, k, v)
  kern = functools.partial(_fwd_kernel, bk=bk, causal=causal, scale=scale)
  return pl.pallas_call(
      kern,
      grid=(bh, t // bq),
      in_specs=[
          pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
          pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
          pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
      ],
      out_specs=[
          pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
          pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j)),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((bh, t, d), q.dtype),
          jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
      ],
      interpret=_use_interpret(),
  )(q, k, v)


def _flash_fwd(q, k, v, causal, block_q, block_k, window=None):
  b, t, h, d = q.shape
  hkv = k.shape[2]
  if h % hkv:
    raise ValueError(f'{h} query heads do not divide over {hkv} key/value '
                     'heads')
  if window is not None and not causal:
    raise ValueError('a window is causal: pass causal=True')
  group = h // hkv
  bq, bk, streamed = _plan(t, d, q.dtype.itemsize, group, block_q, block_k,
                           window)
  _check(q, bq, bk)
  qr, kr, vr = _fold_heads(q), _fold_heads(k), _fold_heads(v)
  out, lse = _flash_call(qr, kr, vr, causal, bq, bk, group, streamed,
                         window)
  out = checkpoint_name(out, OUT_NAME)
  lse = checkpoint_name(lse, LSE_NAME)
  return _unfold_heads(out, b, h), (qr, kr, vr, out, lse, (b, t, h, d))


def _fused_bwd_call(q, k, v, do, lse, delta, causal, bq, bk, group, window):
  """(dq, dk, dv), folded, from ``_bwd_kernel_fused``."""
  bh, t, d = q.shape
  bhkv, itemsize = k.shape[0], q.dtype.itemsize
  nq, nk = t // bq, t // bk

  def q_map(i, r, g):
    return (i * group + r // nq, r % nq, 0)

  def row_map(i, r, g):
    return (i * group + r // nq, 0, r % nq)

  def kv_map(i, r, g):
    if causal:  # a dead block re-reads a live one: no copy is made
      first, last = _live_key_blocks(r % nq, bq, bk, nk, window)
      g = jnp.clip(g, first, last)
    return (i, g, 0)

  def head_map(i, r, g):
    return (i, 0, 0)

  kern = functools.partial(_bwd_kernel_fused, causal=causal,
                           scale=1.0 / np.sqrt(d), nq=nq, nk=nk, group=group,
                           window=window)
  # What the kernel holds: dk and dv resident, the [bq, bk] float32
  # intermediates (s, p, dp, ds, their casts and transposes), the
  # double-buffered blocks and dq's sum.
  vmem = (_resident_dkv_bytes(t, d, itemsize) + 8 * bq * bk * 4 +
          (6 * bq + 4 * bk) * d * itemsize + bq * d * 4 + 4 * 8 * bq * 4)
  return pl.pallas_call(
      kern,
      grid=(bhkv, group * nq, nk),
      in_specs=[
          pl.BlockSpec((1, bq, d), q_map),
          pl.BlockSpec((1, bk, d), kv_map),
          pl.BlockSpec((1, bk, d), kv_map),
          pl.BlockSpec((1, bq, d), q_map),
          pl.BlockSpec((1, 1, bq), row_map),
          pl.BlockSpec((1, 1, bq), row_map),
      ],
      out_specs=[
          pl.BlockSpec((1, bq, d), q_map),
          pl.BlockSpec((1, t, d), head_map),
          pl.BlockSpec((1, t, d), head_map),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((bh, t, d), q.dtype),
          jax.ShapeDtypeStruct((bhkv, t, d), k.dtype),
          jax.ShapeDtypeStruct((bhkv, t, d), v.dtype),
      ],
      scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                      pltpu.VMEM((t, d), jnp.float32),
                      pltpu.VMEM((t, d), jnp.float32)],
      compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
      interpret=_use_interpret(),
      name='flash_attention_bwd',
  )(q, k, v, do, lse, delta)


def _flash_bwd(causal, block_q, block_k, window, res, g):
  qr, kr, vr, out, lse, (b, t, h, d) = res
  group = qr.shape[0] // kr.shape[0]
  bq, bk, streamed = _plan(t, d, qr.dtype.itemsize, group, block_q, block_k,
                           window)
  scale = 1.0 / np.sqrt(d)
  do = _fold_heads(g)
  bh = qr.shape[0]
  bhkv = kr.shape[0]
  delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                  axis=-1)[:, None, :]  # [bh, 1, t]

  if streamed and _fuses_backward(t, d, qr.dtype.itemsize):
    dq, dk, dv = _fused_bwd_call(qr, kr, vr, do, lse, delta, causal, bq, bk,
                                 group, window)
    return (_unfold_heads(dq, b, h), _unfold_heads(dk, b, h // group),
            _unfold_heads(dv, b, h // group))

  if streamed:
    nk, nq = t // bk, t // bq

    def kv_map(i, j, g):
      if causal:
        first, last = _live_key_blocks(j, bq, bk, nk, window)
        g = jnp.clip(g, first, last)
      return (i // group, g, 0)

    dq_kern = functools.partial(_dq_kernel_streamed, causal=causal,
                                scale=scale, nk=nk, window=window)
    dq = pl.pallas_call(
        dq_kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, g: (i, j, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bq, d), lambda i, j, g: (i, j, 0)),
            pl.BlockSpec((1, 1, bq), lambda i, j, g: (i, 0, j)),
            pl.BlockSpec((1, 1, bq), lambda i, j, g: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j, g: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), qr.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=_use_interpret(),
        name='flash_attention_dq',
    )(qr, kr, vr, do, lse, delta)

    def q_block(j, r):
      qb = r % nq
      if causal:
        first, last = _live_query_blocks(j, bq, bk, nq, window)
        qb = jnp.clip(qb, first, last)
      return qb

    def q_map(i, j, r):
      return (i * group + r // nq, q_block(j, r), 0)

    def row_map(i, j, r):
      return (i * group + r // nq, 0, q_block(j, r))

    dkv_kern = functools.partial(_dkv_kernel_streamed, causal=causal,
                                 scale=scale, nq=nq, group=group,
                                 window=window)
    dk, dv = pl.pallas_call(
        dkv_kern,
        grid=(bhkv, nk, group * nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, 1, bq), row_map),
            pl.BlockSpec((1, 1, bq), row_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, r: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhkv, t, d), kr.dtype),
            jax.ShapeDtypeStruct((bhkv, t, d), vr.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=_use_interpret(),
        name='flash_attention_dkv',
    )(qr, kr, vr, do, lse, delta)
    return (_unfold_heads(dq, b, h), _unfold_heads(dk, b, h // group),
            _unfold_heads(dv, b, h // group))

  dq_kern = functools.partial(_dq_kernel, bk=bk, causal=causal, scale=scale)
  dq = pl.pallas_call(
      dq_kern,
      grid=(bh, t // bq),
      in_specs=[
          pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
          pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
          pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
          pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
          pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j)),
          pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j)),
      ],
      out_specs=pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
      out_shape=jax.ShapeDtypeStruct((bh, t, d), qr.dtype),
      interpret=_use_interpret(),
  )(qr, kr, vr, do, lse, delta)

  dkv_kern = functools.partial(_dkv_kernel, bq=bq, causal=causal,
                               scale=scale)
  dk, dv = pl.pallas_call(
      dkv_kern,
      grid=(bh, t // bk),
      in_specs=[
          pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
          pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0)),
          pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0)),
          pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
          pl.BlockSpec((1, 1, t), lambda i, j: (i, 0, 0)),
          pl.BlockSpec((1, 1, t), lambda i, j: (i, 0, 0)),
      ],
      out_specs=[
          pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0)),
          pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0)),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((bh, t, d), kr.dtype),
          jax.ShapeDtypeStruct((bh, t, d), vr.dtype),
      ],
      interpret=_use_interpret(),
  )(qr, kr, vr, do, lse, delta)

  return (_unfold_heads(dq, b, h), _unfold_heads(dk, b, h),
          _unfold_heads(dv, b, h))


flash_attention.defvjp(_flash_fwd, _flash_bwd)
