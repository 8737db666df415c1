"""The afmoe decoder trunk (arcee-ai Trinity family): window and full
attention mixed, grouped key/value heads, gated attention output, sparse
experts after the leading dense layers.

One layer, four RMS norms in sandwich order::

    a  = h + N2(Attn(N1(h)))
    h' = a + N4(FFN(N3(a)))

Attn: q, k, v and a gate as wide as q, no biases; RMS norm over the head
dimension of q and of k; on ``sliding_attention`` layers RoPE over the
whole head and the mask ``0 <= i - j < sliding_window``, on
``full_attention`` layers no positional embedding and the causal mask;
``out = Wo (o * sigmoid(g))``. Attention runs in
``ops/flash_attention.py`` (window and grouped heads there): at 8,192
tokens x 32 heads plain logits are 8.6 GB, so there is no other path.
FFN: SwiGLU on the leading dense layers, ``layers/moe.ExpertLayer`` on
the others.

Parameters are float32 and flat under each module (``q``, ``gate``,
``norm1`` ...: the names of ``benchmark/reference/trinity_mini.py``);
products take operands in ``dtype``, norms, the router, softmax and the
loss are float32. The trunk returns the mean next-token loss itself, the
head and the log-softmax computed a chunk of positions at a time: the
[tokens, vocabulary] logits never exist whole, and under ``grad`` each
chunk's logits give the loss and its gradient in one pass
(``next_token_loss``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from tensor2robot_tpu.layers import moe
from tensor2robot_tpu.ops import flash_attention as fa

SLIDING = 'sliding_attention'
FULL = 'full_attention'

# What a decoder layer's remat keeps besides the layer's input, by the
# names the values are given where they are made: the kernel's two
# results, q, k, v and the gate as ``Attention`` hands them on, the
# experts' choice and output. ``Trunk`` says what each weighs; each is
# kept because the chip read it faster (PERF.md, PR 31).
Q_NAME, K_NAME, V_NAME, GATE_NAME = 'attn_q', 'attn_k', 'attn_v', 'attn_gate'
KEPT_NAMES = (fa.OUT_NAME, fa.LSE_NAME, Q_NAME, K_NAME, V_NAME, GATE_NAME,
              moe.CHOSEN_NAME, moe.PICKED_NAME)
KEPT_IN_LAYER = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)


def rms_norm(x, scale, eps: float, dtype):
  x = x.astype(jnp.float32)
  y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
  return (y * scale).astype(dtype)


def rope(x, theta: float):
  """Rotary embedding over the whole head (halves rotated against each
  other) of [B, S, heads, head_dim], in float32."""
  s, hd = x.shape[1], x.shape[-1]
  half = hd // 2
  freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
  angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
  cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
  x = x.astype(jnp.float32)
  a, b = x[..., :half], x[..., half:]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


class Attention(nn.Module):
  num_heads: int
  num_kv_heads: int
  head_dim: int
  kind: str                      # SLIDING | FULL
  sliding_window: int
  rope_theta: float
  eps: float
  dtype: Any = jnp.float32
  init_std: float = 0.02

  @nn.compact
  def __call__(self, x):
    b, s, d = x.shape
    init = moe.normal_init(self.init_std)
    q_width = self.num_heads * self.head_dim
    kv_width = self.num_kv_heads * self.head_dim
    wq = self.param('q', init, (d, q_width))
    wk = self.param('k', init, (d, kv_width))
    wv = self.param('v', init, (d, kv_width))
    wg = self.param('gate', init, (d, q_width))
    wo = self.param('o', init, (q_width, d))
    q_scale = self.param('q_norm', nn.initializers.ones, (self.head_dim,))
    k_scale = self.param('k_norm', nn.initializers.ones, (self.head_dim,))
    dt = self.dtype
    x = x.astype(dt)
    with jax.named_scope('afmoe/attn/project'):
      q = (x @ wq.astype(dt)).reshape(b, s, self.num_heads, self.head_dim)
      k = (x @ wk.astype(dt)).reshape(b, s, self.num_kv_heads, self.head_dim)
      v = (x @ wv.astype(dt)).reshape(b, s, self.num_kv_heads, self.head_dim)
      gate = x @ wg.astype(dt)
    q = rms_norm(q, q_scale, self.eps, dt)
    k = rms_norm(k, k_scale, self.eps, dt)
    window = None
    if self.kind == SLIDING:
      q, k = rope(q, self.rope_theta).astype(dt), rope(
          k, self.rope_theta).astype(dt)
      window = self.sliding_window
    # As the kernel and the gate take them, by name (``KEPT_NAMES``).
    q = checkpoint_name(q, Q_NAME)
    k = checkpoint_name(k, K_NAME)
    v = checkpoint_name(v, V_NAME)
    gate = checkpoint_name(gate, GATE_NAME)
    scope = 'afmoe/attn/window' if window else 'afmoe/attn/full'
    with jax.named_scope(scope):
      o = fa.flash_attention(q, k, v, True, None, None, window)
    o = o.reshape(b, s, q_width) * jax.nn.sigmoid(
        gate.astype(jnp.float32)).astype(dt)
    with jax.named_scope('afmoe/attn/project'):
      return o @ wo.astype(dt)


class DecoderLayer(nn.Module):
  """One layer; returns (hidden, the expert layer's counts or None)."""

  kind: str
  sparse: bool
  num_heads: int
  num_kv_heads: int
  head_dim: int
  sliding_window: int
  rope_theta: float
  eps: float
  dense_width: int
  expert_kwargs: Optional[Dict[str, Any]] = None
  dtype: Any = jnp.float32
  init_std: float = 0.02

  @nn.compact
  def __call__(self, h, train: bool = False):
    d = h.shape[-1]
    norms = [self.param(f'norm{i}', nn.initializers.ones, (d,))
             for i in (1, 2, 3, 4)]
    dt = self.dtype
    a = Attention(self.num_heads, self.num_kv_heads, self.head_dim,
                  self.kind, self.sliding_window, self.rope_theta, self.eps,
                  dt, self.init_std, name='attn')(
                      rms_norm(h, norms[0], self.eps, dt))
    a = h + rms_norm(a, norms[1], self.eps, dt)
    x = rms_norm(a, norms[2], self.eps, dt)
    stats = None
    if self.sparse:
      y, stats = moe.ExpertLayer(dtype=dt, init_std=self.init_std,
                                 name='moe', **self.expert_kwargs)(x, train)
    else:
      with jax.named_scope('afmoe/dense_mlp'):
        y = moe.SwiGLU(self.dense_width, dt, self.init_std, name='mlp')(x)
    return a + rms_norm(y, norms[3], self.eps, dt), stats


class Trunk(nn.Module):
  """Embedding, the layers, the final norm, the untied head and the
  next-token loss over ``tokens`` ([B, S] integers)."""

  vocab_size: int
  hidden_size: int
  layer_types: Sequence[str]        # of the layers run, in order
  num_dense_layers: int
  num_heads: int
  num_kv_heads: int
  head_dim: int
  sliding_window: int
  rope_theta: float
  eps: float
  dense_width: int
  expert_kwargs: Dict[str, Any]
  mup_enabled: bool = True
  loss_chunk: int = 2048
  dtype: Any = jnp.float32
  init_std: float = 0.02

  @nn.compact
  def __call__(self, features, train: bool = False):
    tokens = features['tokens'].astype(jnp.int32)
    b, s = tokens.shape
    init = moe.normal_init(self.init_std)
    embed = self.param('embed', init, (self.vocab_size, self.hidden_size))
    h = embed[tokens]
    if self.mup_enabled:
      h = h * self.hidden_size ** 0.5
    h = h.astype(self.dtype)
    # The backward pass computes a layer again from its input (bf16 [B, S,
    # hidden]: 32 MiB at 8,192 x 2,048), all but what ``KEPT_NAMES`` keeps.
    # At those sizes, 32 / 4 heads of 128 and 8 experts a token: the
    # attention kernel's output 64 MiB and log-sum-exp 1 MiB (a kernel
    # pass to rebuild: a fifth of a step's attention time), q and the gate
    # 64 MiB each, k and v 8 MiB each (the gate's and v's projections and
    # the float32 norm and RoPE passes), the experts' output 256 MiB (a
    # trip through the routed-row buffer: a gather, three grouped
    # products, a gather back) with the choice that laid it out, 256 KiB.
    # Computed again: the norms, the q and k projections (the head norm's
    # way back needs them before the norm), the MLP or the router's
    # scores, the sorts on the choice and the shared expert.
    layer_cls = nn.remat(DecoderLayer, static_argnums=(2,),
                         policy=KEPT_IN_LAYER)
    all_stats = []
    for j, kind in enumerate(self.layer_types):
      sparse = j >= self.num_dense_layers
      h, stats = layer_cls(
          kind, sparse, self.num_heads, self.num_kv_heads, self.head_dim,
          self.sliding_window, self.rope_theta, self.eps, self.dense_width,
          self.expert_kwargs if sparse else None, self.dtype, self.init_std,
          name=f'layer{j}')(h, train)
      if stats is not None:
        all_stats.append(stats)
    scale = self.param('final_norm', nn.initializers.ones,
                       (self.hidden_size,))
    head = self.param('head', init, (self.hidden_size, self.vocab_size))
    h = rms_norm(h, scale, self.eps, self.dtype)
    with jax.named_scope('afmoe/head_loss'):
      loss = next_token_loss(h, head, tokens, self.loss_chunk, self.dtype)
      last_logits = jnp.matmul(h[:, -1], head.astype(self.dtype),
                               preferred_element_type=jnp.float32)
    outputs = {'loss': loss, 'next_token_logits': last_logits}
    if all_stats:
      for key in all_stats[0]:
        column = jnp.stack([st[key] for st in all_stats])
        outputs[f'moe/{key}'] = (jnp.max(column) if key == 'rows_max_expert'
                                 else jnp.sum(column))
    return outputs


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def next_token_loss(h, head, tokens, chunk: int, dtype, shift: int = 1):
  """Mean cross-entropy of position i's prediction of token i + ``shift``
  over each sequence's first S - ``shift`` positions (``shift`` 2: a
  multi-token-prediction module's pass), a ``chunk`` of positions at a
  time; log-softmax in float32.

  Under ``jax.grad`` the gradient is computed on the way forward: a
  chunk's logits give its loss and, from the same logits, their cotangent
  ``(softmax - onehot) * counted / N``, hence the chunk's ``dh`` and its
  part of ``dhead`` (summed over the chunks in float32): three
  vocabulary-wide products a chunk and no logits computed again. Both are
  kept, and the way back multiplies them by the loss's scalar cotangent.
  Called outside ``grad`` it runs the loss alone, one product a chunk. A
  ``custom_vjp`` has no forward-mode rule: nothing in the tree takes
  ``jvp`` or a second derivative of a token trunk.
  """
  return _chunked_loss(h, head, tokens, chunk, dtype, shift, False)[0]


def _chunked_loss(h, head, tokens, chunk, dtype, shift, with_grads):
  """``(loss, (dh, dhead))``; ``(loss, None)`` unless ``with_grads``."""
  b, s, d = h.shape
  labels = jnp.roll(tokens, -shift, axis=1).reshape(b * s)
  counted = jnp.broadcast_to(jnp.arange(s) < s - shift,
                             (b, s)).reshape(b * s)
  rows, count = b * s, b * (s - shift)
  chunk = min(chunk, rows)
  if rows % chunk:
    raise ValueError(f'{rows} positions do not divide into chunks of {chunk}')
  weight = head.astype(dtype)

  def one_chunk(dhead, args):
    hc, lc, mc = args
    logits = jnp.matmul(hc, weight, preferred_element_type=jnp.float32)
    top = jnp.max(logits, axis=-1)
    log_total = jnp.log(jnp.sum(jnp.exp(logits - top[:, None]), axis=-1))
    picked = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
    part = -jnp.sum(jnp.where(mc, picked - top - log_total, 0.0))
    if not with_grads:
      return dhead, (part, None)
    # From the logits again and not from ``logits - top``: a value shared
    # with the sum above is written out whole, a float32 [chunk, vocabulary].
    softmax = jnp.exp(logits - (top + log_total)[:, None])
    onehot = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) == lc[:, None]
    dl = (jnp.where(onehot, softmax - 1.0, softmax) *
          jnp.where(mc, 1.0 / count, 0.0)[:, None]).astype(dtype)
    dhc = jax.lax.dot_general(dl, weight, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dhead += jax.lax.dot_general(hc, dl, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    return dhead, (part, dhc.astype(h.dtype))

  dhead = jnp.zeros(head.shape, jnp.float32) if with_grads else None
  dhead, (parts, dh) = jax.lax.scan(one_chunk, dhead, (
      h.reshape(rows // chunk, chunk, d), labels.reshape(rows // chunk, chunk),
      counted.reshape(rows // chunk, chunk)))
  loss = jnp.sum(parts) / count
  if not with_grads:
    return loss, None
  return loss, (dh.reshape(h.shape), dhead.astype(head.dtype))


def _loss_backward(chunk, dtype, shift, kept, g):
  del chunk, dtype, shift
  return tuple((g * grad).astype(grad.dtype) for grad in kept) + (None,)


next_token_loss.defvjp(functools.partial(_chunked_loss, with_grads=True),
                       _loss_backward)
