"""Pallas flash attention vs the full-attention oracle (interpret mode)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensor2robot_tpu.ops.flash_attention import flash_attention
from tensor2robot_tpu.parallel.sequence_parallel import reference_attention


def _qkv(shape, seed=0, dtype=jnp.float32):
  rng = np.random.RandomState(seed)
  return tuple(jnp.asarray(rng.randn(*shape), dtype) for _ in range(3))


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('shape,bq,bk', [
    ((2, 256, 2, 32), 64, 128),
    ((1, 512, 4, 64), 256, 512),
    ((1, 128, 2, 16), 128, 128),
    # block_q > block_k: causal q blocks contain fully-masked rows for
    # trailing key blocks (regression for the m == -inf exp guard).
    ((1, 256, 2, 16), 128, 64),
])
def test_matches_reference(shape, bq, bk, causal):
  q, k, v = _qkv(shape)
  out = flash_attention(q, k, v, causal, bq, bk)
  ref = reference_attention(q, k, v, causal=causal)
  np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize('causal', [False, True])
def test_grads_match_reference(causal):
  q, k, v = _qkv((2, 256, 2, 32), seed=1)
  ct = jnp.asarray(np.random.RandomState(2).randn(2, 256, 2, 32),
                   jnp.float32)

  def loss(fn):
    return jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * ct),
        argnums=(0, 1, 2))

  got = loss(lambda q, k, v: flash_attention(q, k, v, causal, 64, 128))(
      q, k, v)
  ref = loss(lambda q, k, v: reference_attention(q, k, v, causal=causal))(
      q, k, v)
  for g, r in zip(got, ref):
    np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-4)


def test_rejects_bad_shapes():
  q, k, v = _qkv((1, 100, 2, 16))
  with pytest.raises(ValueError, match='divisible'):
    flash_attention(q, k, v, False, 64, 64)
  q, k, v = _qkv((1, 128, 2, 384))
  with pytest.raises(ValueError, match='head dim'):
    flash_attention(q, k, v, False, 128, 128)


@pytest.mark.parametrize('causal', [False, True])
def test_streamed_variant_matches(monkeypatch, causal):
  """Force the streamed (scratch-accumulator) kernels and re-verify
  forward + gradients against the oracle."""
  from tensor2robot_tpu.ops import flash_attention as fa

  monkeypatch.setattr(fa, '_MAX_STAGED_KV_BYTES', 1)
  q, k, v = _qkv((2, 256, 2, 32), seed=3)
  out = fa.flash_attention(q, k, v, causal, 64, 128)
  ref = reference_attention(q, k, v, causal=causal)
  np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

  ct = jnp.asarray(np.random.RandomState(4).randn(2, 256, 2, 32),
                   jnp.float32)

  def loss(fn):
    return jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * ct),
        argnums=(0, 1, 2))

  got = loss(lambda q, k, v: fa.flash_attention(q, k, v, causal, 64, 128))(
      q, k, v)
  ref_g = loss(lambda q, k, v: reference_attention(q, k, v, causal=causal))(
      q, k, v)
  for g, r in zip(got, ref_g):
    np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-4)


def test_bf16_inputs():
  q, k, v = _qkv((1, 256, 2, 32), dtype=jnp.bfloat16)
  out = flash_attention(q, k, v, True, 128, 128)
  ref = reference_attention(q, k, v, causal=True)
  assert out.dtype == jnp.bfloat16
  np.testing.assert_allclose(
      np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2)

def test_streamed_threshold_is_dtype_aware():
  """ADVICE r2: the staged/streamed dispatch budgets BYTES, not elements —
  float32 K/V near the boundary must stream where bfloat16 stages."""
  from tensor2robot_tpu.ops import flash_attention as fa

  t, d = 32768, 64  # 2·t·d·2B = 8 MiB: exactly at the bf16 budget
  assert not fa._use_streamed(t, d, itemsize=2)
  assert fa._use_streamed(t, d, itemsize=4)


def test_interpret_on_any_non_tpu_backend(monkeypatch):
  """VERDICT r2 #8: a gpu host must fall back to interpret mode rather
  than attempting (and failing) a real Mosaic lowering."""
  from tensor2robot_tpu.ops import flash_attention as fa

  monkeypatch.setattr(fa.jax, 'default_backend', lambda: 'gpu')
  assert fa._use_interpret()
  q, k, v = _qkv((1, 64, 1, 16), seed=7)
  out = fa.flash_attention(q, k, v, False, 64, 64)
  ref = reference_attention(q, k, v)
  np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_is_supported_requires_lane_tile_blocks_on_tpu():
  """On real TPU the blocks must be >=128 (the lse output puts the
  q-block dim in lanes; Mosaic rejects sub-tile stores — found on
  hardware with a T=8 SNAIL episode). Interpret mode keeps 8-aligned."""
  from tensor2robot_tpu.ops import flash_attention as fa

  assert fa.is_supported(8, 64, interpret=True)
  assert not fa.is_supported(8, 64, interpret=False)
  assert fa.is_supported(128, 64, interpret=False)
  assert fa.is_supported(4096, 64, interpret=False)


# ------------------------------------------- window and grouped heads


def _masked_softmax_attention(q, k, v, window):
  """Plain attention: causal, ``0 <= i - j < window``, query head h on
  key/value head h // (H // Hkv)."""
  t, h, d = q.shape[1], q.shape[2], q.shape[3]
  group = h // k.shape[2]
  k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
  s = jnp.einsum('bqhd,bkhd->bhqk', q, k) / np.sqrt(d)
  i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
  seen = i >= j
  if window is not None:
    seen = seen & (i - j < window)
  p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
  return jnp.einsum('bhqk,bkhd->bqhd', p, v)


@pytest.mark.parametrize('t,heads,kv_heads,window,bq,bk', [
    (256, 4, 2, 64, 64, 64),       # a window of one block
    (256, 8, 2, 96, 32, 64),       # a window across blocks, bq < bk
    (256, 4, 1, None, 64, 128),    # grouped heads, no window
    (256, 4, 4, 100, None, None),  # default blocks, cut to the window
    (128, 2, 2, 128, 64, 64),      # a window as long as the sequence
    (256, 8, 2, None, None, None),  # as layers/zaya.py calls it: 8 / 2 heads
])
def test_window_and_grouped_heads_match_masked_softmax(t, heads, kv_heads,
                                                       window, bq, bk):
  rng = np.random.RandomState(4)
  q = jnp.asarray(rng.randn(2, t, heads, 16), jnp.float32)
  k = jnp.asarray(rng.randn(2, t, kv_heads, 16), jnp.float32)
  v = jnp.asarray(rng.randn(2, t, kv_heads, 16), jnp.float32)
  ct = jnp.asarray(rng.randn(2, t, heads, 16), jnp.float32)

  def flash(q, k, v):
    return flash_attention(q, k, v, True, bq, bk, window)

  np.testing.assert_allclose(
      np.asarray(flash(q, k, v)),
      np.asarray(_masked_softmax_attention(q, k, v, window)), atol=2e-5)
  got = jax.grad(lambda *a: jnp.sum(flash(*a) * ct), (0, 1, 2))(q, k, v)
  want = jax.grad(lambda *a: jnp.sum(
      _masked_softmax_attention(*a, window) * ct), (0, 1, 2))(q, k, v)
  for g, w in zip(got, want):
    assert g.shape == w.shape
    np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-4)


@pytest.mark.parametrize('shape,bq,bk', [
    ((2, 256, 2, 32), 64, 128),    # staged kernels
    ((1, 256, 2, 16), 128, 64),
])
def test_no_window_output_is_what_it_was(shape, bq, bk):
  """Without a window and with one head count the new argument changes
  no bit: positional and keyword calls agree, and a window as long as
  the sequence (the streamed kernels) agrees to rounding."""
  q, k, v = _qkv(shape, seed=7)
  old = flash_attention(q, k, v, True, bq, bk)
  new = flash_attention(q, k, v, True, bq, bk, window=None)
  np.testing.assert_array_equal(np.asarray(old), np.asarray(new))
  windowed = flash_attention(q, k, v, True, bq, bk, shape[1])
  np.testing.assert_allclose(np.asarray(old), np.asarray(windowed),
                             atol=2e-6)


def test_streamed_products_take_the_inputs_dtype():
  """bfloat16 q/k/v keep bfloat16 operands in the streamed kernels'
  products (sums in float32): close to the float32 answer on the same
  rounded inputs, forward and backward, and bfloat16 out."""
  rng = np.random.RandomState(5)
  q, k, v, ct = (jnp.asarray(rng.randn(1, 256, h, 16), jnp.bfloat16)
                 for h in (4, 2, 2, 4))

  def flash(q, k, v):
    return flash_attention(q, k, v, True, 64, 64, 96)

  def plain(q, k, v):
    return _masked_softmax_attention(*(x.astype(jnp.float32)
                                       for x in (q, k, v)), 96)

  out = flash(q, k, v)
  assert out.dtype == jnp.bfloat16
  np.testing.assert_allclose(np.asarray(out, np.float32),
                             np.asarray(plain(q, k, v)), atol=3e-2)
  got = jax.grad(lambda *a: jnp.sum(
      flash(*a).astype(jnp.float32) * ct), (0, 1, 2))(q, k, v)
  want = jax.grad(lambda *a: jnp.sum(plain(*a) * ct), (0, 1, 2))(q, k, v)
  for g, w in zip(got, want):
    assert g.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(w, np.float32), atol=0.15)


def test_window_needs_causal_and_heads_must_divide():
  q, k, v = _qkv((1, 128, 4, 16))
  with pytest.raises(ValueError, match='causal'):
    flash_attention(q, k, v, False, 64, 64, 32)
  with pytest.raises(ValueError, match='divide'):
    flash_attention(q, k[:, :, :3], v[:, :, :3], True, 64, 64)


# --------------------------------------- the residuals' names under remat


@pytest.mark.parametrize('streamed,window', [
    (False, None), (True, None), (True, 96)],
    ids=['staged', 'streamed', 'streamed-window'])
def test_residual_names_engage_under_a_policy_that_names_them_only(
    streamed, window, monkeypatch):
  """``attn_out`` / ``attn_lse`` are identities: values and gradients
  under ``jax.grad``, under ``jax.checkpoint`` with no policy and under
  the policy that names them are the same bits, and only that policy
  spares the backward pass the second forward kernel."""
  from tensor2robot_tpu.ops import flash_attention as fa

  if streamed:
    monkeypatch.setattr(fa, '_MAX_STAGED_KV_BYTES', 1)
  q, k, v = _qkv((2, 256, 2, 32), seed=9)
  ct = jnp.asarray(np.random.RandomState(10).randn(2, 256, 2, 32),
                   jnp.float32)

  def loss(q, k, v):
    return jnp.sum(fa.flash_attention(q, k, v, True, 64, 128, window) * ct)

  names = jax.checkpoint_policies.save_only_these_names(
      fa.OUT_NAME, fa.LSE_NAME)
  # The way back: the staged dq and dk/dv, or the streamed kernels' one.
  backward = 1 if streamed else 2
  # The forward kernel once; remat's second forward where it runs.
  variants = [(loss, 1), (jax.checkpoint(loss), 2),
              (jax.checkpoint(loss, policy=names), 1)]
  results = []
  for fn, forward in variants:
    fn = jax.value_and_grad(fn, (0, 1, 2))
    text = str(jax.make_jaxpr(fn)(q, k, v))
    assert text.count('pallas_call[') == forward + backward
    if streamed:
      assert text.count('name=flash_attention_fwd') == forward
      assert text.count('name=flash_attention_bwd') == 1
    results.append(fn(q, k, v))
  (want, want_grads) = results[0]
  for value, grads in results[1:]:
    assert float(value) == float(want)
    for g, w in zip(grads, want_grads):
      np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ------------------------------------- the streamed backward in one kernel


def _streamed_grads(fa, q, k, v, ct, window, bq, bk):
  fn = jax.grad(lambda *a: jnp.sum(
      fa.flash_attention(*a, True, bq, bk, window).astype(jnp.float32) * ct),
                (0, 1, 2))
  return fn(q, k, v), str(jax.make_jaxpr(fn)(q, k, v))


@pytest.mark.parametrize('batch,t,heads,kv_heads,d,window,bq,bk,dtype', [
    (2, 256, 8, 2, 16, None, 64, 64, jnp.float32),     # full causal, 8:2
    (1, 256, 32, 4, 16, None, 64, 64, jnp.bfloat16),   # 32:4 as afmoe's
    (2, 256, 8, 2, 16, 64, 64, 64, jnp.bfloat16),      # window < t, a block
    (1, 512, 8, 2, 16, 128, 64, 64, jnp.float32),      # window = 2 blocks
    (2, 256, 4, 2, 32, 100, 64, 64, jnp.bfloat16),     # no block multiple
    (1, 256, 8, 2, 16, None, 32, 64, jnp.float32),     # bq < bk
    (1, 256, 4, 1, 16, 96, 128, 64, jnp.bfloat16),     # bq > bk, a window
    # Query blocks in the middle have dead key blocks behind (the window)
    # and ahead (the diagonal): both ends of the clipped index maps.
    (2, 512, 4, 2, 16, 96, 32, 64, jnp.float32),
    (1, 256, 2, 2, 16, 128, 64, 64, jnp.float32),      # a window, no groups
    (1, 256, 8, 2, 16, None, None, None, jnp.bfloat16),  # default blocks
])
def test_fused_backward_is_the_two_kernels_and_the_reference(
    batch, t, heads, kv_heads, d, window, bq, bk, dtype, monkeypatch):
  """One kernel gives dq, dk and dv: the two streamed kernels' bits (the
  same sums in the same order, interpret mode) and the masked-softmax
  reference's gradients to the dtype's rounding."""
  from tensor2robot_tpu.ops import flash_attention as fa

  rng = np.random.RandomState(11)
  q, k, v = (jnp.asarray(rng.randn(batch, t, h, d), dtype)
             for h in (heads, kv_heads, kv_heads))
  ct = jnp.asarray(rng.randn(batch, t, heads, d), jnp.float32)
  got, text = _streamed_grads(fa, q, k, v, ct, window, bq, bk)
  assert text.count('name=flash_attention_bwd') == 1
  assert 'name=flash_attention_dq' not in text
  monkeypatch.setattr(fa, '_MAX_RESIDENT_DKV_BYTES', 0)
  two, text = _streamed_grads(fa, q, k, v, ct, window, bq, bk)
  assert 'name=flash_attention_bwd' not in text
  assert (text.count('name=flash_attention_dq'),
          text.count('name=flash_attention_dkv')) == (1, 1)
  want = jax.grad(lambda *a: jnp.sum(_masked_softmax_attention(
      *(x.astype(jnp.float32) for x in a), window) * ct), (0, 1, 2))(q, k, v)
  atol = 5e-4 if dtype == jnp.float32 else 0.15
  for g, w, r in zip(got, two, want):
    assert g.dtype == dtype and g.shape == r.shape
    np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(r),
                               atol=atol)


@pytest.mark.parametrize('t,d,itemsize,fused', [
    (8192, 128, 2, True),     # both token cells' sequences
    (8192, 128, 4, True),
    (16384, 128, 2, True),
    (65536, 64, 2, False),    # dk and dv of a head would be 64 MiB
    (32768, 128, 2, False),
    (8192, 256, 2, True),     # latent attention's heads: at the edge
    (16384, 256, 2, False),
])
def test_backward_fuses_while_a_heads_dk_and_dv_fit(t, d, itemsize, fused):
  from tensor2robot_tpu.ops import flash_attention as fa

  assert fa._streams(t, d, itemsize, group=4)
  assert fa._fuses_backward(t, d, itemsize) == fused


# ------------------------------------------- two lane tiles a head (D=256)


@pytest.mark.parametrize('streamed', [False, True])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_heads_of_256_match_plain_attention(streamed, dtype, monkeypatch):
  """Latent attention's head (192 content + 64 rotary dimensions, 256
  of value): forward and backward against the masked-softmax reference,
  staged and streamed; the streamed way back is the one kernel."""
  from tensor2robot_tpu.ops import flash_attention as fa

  if streamed:
    monkeypatch.setattr(fa, '_MAX_STAGED_KV_BYTES', 1)
  rng = np.random.RandomState(21)
  shape = (1, 256, 2, 256)
  q, k, v = (jnp.asarray(rng.randn(*shape), dtype) for _ in range(3))
  ct = jnp.asarray(rng.randn(*shape), jnp.float32)
  assert fa._streams(256, 256, q.dtype.itemsize) == streamed
  got, text = _streamed_grads(fa, q, k, v, ct, None, 64, 128)
  assert text.count('name=flash_attention_bwd') == int(streamed)
  assert 'name=flash_attention_dq' not in text
  out = fa.flash_attention(q, k, v, True, 64, 128)
  f32 = tuple(x.astype(jnp.float32) for x in (q, k, v))
  want_out = _masked_softmax_attention(*f32, None)
  want = jax.grad(lambda *a: jnp.sum(
      _masked_softmax_attention(*a, None) * ct), (0, 1, 2))(*f32)
  exact = dtype == jnp.float32
  assert out.dtype == dtype
  np.testing.assert_allclose(np.asarray(out, np.float32),
                             np.asarray(want_out), atol=2e-5 if exact else 3e-2)
  for g, w in zip(got, want):
    assert g.dtype == dtype and g.shape == w.shape
    np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(w),
                               atol=5e-4 if exact else 0.15)


def test_is_supported_at_two_lane_tiles_and_no_further():
  from tensor2robot_tpu.ops import flash_attention as fa

  assert fa.is_supported(8192, 256, interpret=False)
  assert fa.is_supported(256, 256, interpret=True)
  assert not fa.is_supported(8192, 192, interpret=False)   # no whole tiles
  assert not fa.is_supported(8192, 384, interpret=False)
  assert fa.is_supported(8192, 128, interpret=False)
  assert fa.is_supported(8192, 72, interpret=False)        # as it was
  # 8,192 tokens of bfloat16 heads of 256 stream (the staged kernels'
  # float32 blocks double with the head, so their K/V budget halves) and
  # fuse the way back, at the resident budget's edge.
  assert fa._use_streamed(8192, 256, 2) and not fa._use_streamed(4096, 256, 2)
  assert fa._resident_dkv_bytes(8192, 256, 2) == fa._MAX_RESIDENT_DKV_BYTES
  assert fa._resolve_blocks(8192, 256, None, None) == (1024, 1024)


def test_staged_backward_keeps_its_two_kernels():
  """Small ``t x d`` with one head count and no window stages K/V whole:
  no streamed kernel, fused or not, is on that path."""
  from tensor2robot_tpu.ops import flash_attention as fa

  assert not fa._streams(4096, 64, 2)
  q, k, v = _qkv((1, 256, 2, 16), seed=12)
  ct = jnp.ones((1, 256, 2, 16), jnp.float32)
  _, text = _streamed_grads(fa, q, k, v, ct, None, 64, 64)
  assert text.count('pallas_call[') == 3
  assert 'name=flash_attention_' not in text
