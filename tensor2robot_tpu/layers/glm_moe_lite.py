"""The glm4_moe_lite decoder trunk (zai-org GLM-4.7-Flash; the layer is
DeepSeek-V3's): multi-head latent attention (MLA), leading dense layers,
sigmoid-routed experts with a shared one, and a multi-token-prediction
(MTP) module that shares the embedding and the head.

One layer, pre-norm::

    a  = h + MLA(N1(h))
    h' = a + F(N2(a))        F: SwiGLU on the leading dense layers,
                             ``layers/moe.ExpertLayer`` on the others

MLA projects the input DOWN twice (``q_a`` to ``q_rank``; ``kv_a`` to
``kv_rank`` and, beside it, one rotary key of ``rope_dim`` that every
head shares), RMS-normalises each latent and projects UP a head
(``q_b`` to ``nope_dim + rope_dim``; ``kv_b`` to ``nope_dim`` of key and
``v_dim`` of value). RoPE (pairs interleaved, all of ``rope_dim``) turns
the rotary parts; q and k are the content and rotary parts joined, the
shared key copied to every head. Training materialises k and v a head
and attends in ``ops/flash_attention.py`` at the joined width (192 + 64
= 256 = ``v_dim``: two lane tiles a head); the absorbed form, which
attends inside the latent, is a decode path and is not here.

MTP (depth 1), with ``hf`` the trunk's state after the final norm and
``E`` the embedding: position i joins token i + 1, ``u_i = [N_e(E[t_{i+1}])
; N_h(hf_i)] Wm``, passes one expert decoder layer and a norm of its own
and predicts token i + 2 through the SAME head; the trunk's loss is
``main + mtp_loss_weight * mtp``, so the embedding and the head each
take the gradients of two passes (one leaf each).

Parameters are float32 and flat under each module, by the names of
``benchmark/reference/glm_4_7_flash.py``; products take operands in
``dtype``; norms, the router, rotary, softmax and the losses are
float32. The trunk returns its loss itself, the vocabulary losses a
chunk of positions at a time (``afmoe.next_token_loss``, which computes
each pass's gradients on the way forward and scales them on the way back
by the pass's weight in the total: 1 and ``mtp_loss_weight``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensor2robot_tpu.layers import afmoe, moe
from tensor2robot_tpu.ops import flash_attention as fa

# What a decoder layer's remat keeps besides the layer's input, by name:
# the attention kernel's two results (a kernel pass to rebuild) and the
# experts' output with the choice that laid it out (``moe.py`` says why
# both). q, k and v are computed again from the latents: 80 MiB each a
# layer at 8,192 tokens x 20 heads of 256 for two small products.
KEPT_NAMES = (fa.OUT_NAME, fa.LSE_NAME, moe.CHOSEN_NAME, moe.PICKED_NAME)
KEPT_IN_LAYER = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)


def rope_interleaved(x, theta: float):
  """Rotary embedding over the whole last axis of [B, S, heads, rot],
  in float32: the pairs are (x[2i], x[2i+1]) and come out with the first
  members in the first half and the second in the second (DeepSeek-V3's
  ``apply_rotary_pos_emb_interleave``; q and k alike, so their product
  is the interleaved one's)."""
  s, half = x.shape[1], x.shape[-1] // 2
  freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
  angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
  cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
  x = x.astype(jnp.float32)
  a, b = x[..., 0::2], x[..., 1::2]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


class MLA(nn.Module):
  num_heads: int
  q_rank: int
  kv_rank: int
  nope_dim: int
  rope_dim: int
  v_dim: int
  rope_theta: float
  eps: float
  dtype: Any = jnp.float32
  init_std: float = 0.02
  latent_gain: Optional[float] = None   # q_b, kv_b: gain / sqrt(fan_in)

  @nn.compact
  def __call__(self, x):
    b, s, d = x.shape
    heads, nope, rot, vd = (self.num_heads, self.nope_dim, self.rope_dim,
                            self.v_dim)
    if nope + rot != vd:
      raise ValueError('the attention kernel takes one head width: '
                       f'{nope} + {rot} != {vd}')
    init = moe.normal_init(self.init_std)

    def up_init(fan_in):
      return init if self.latent_gain is None else moe.normal_init(
          self.latent_gain / fan_in ** 0.5)

    ones = nn.initializers.ones
    q_a = self.param('q_a', init, (d, self.q_rank))
    q_a_norm = self.param('q_a_norm', ones, (self.q_rank,))
    q_b = self.param('q_b', up_init(self.q_rank),
                     (self.q_rank, heads * (nope + rot)))
    kv_a = self.param('kv_a', init, (d, self.kv_rank + rot))
    kv_a_norm = self.param('kv_a_norm', ones, (self.kv_rank,))
    kv_b = self.param('kv_b', up_init(self.kv_rank),
                      (self.kv_rank, heads * (nope + vd)))
    wo = self.param('o', init, (heads * vd, d))
    dt = self.dtype
    x = x.astype(dt)
    with jax.named_scope('glm/mla/project'):
      down = x @ jnp.concatenate([q_a, kv_a], axis=1).astype(dt)
    with jax.named_scope('glm/mla/mix'):
      cq = afmoe.rms_norm(down[..., :self.q_rank], q_a_norm, self.eps, dt)
      ckv = afmoe.rms_norm(down[..., self.q_rank:self.q_rank + self.kv_rank],
                           kv_a_norm, self.eps, dt)
      k_rope = down[..., None, self.q_rank + self.kv_rank:]   # one, shared
    with jax.named_scope('glm/mla/project'):
      q = (cq @ q_b.astype(dt)).reshape(b, s, heads, nope + rot)
      kv = (ckv @ kv_b.astype(dt)).reshape(b, s, heads, nope + vd)
    with jax.named_scope('glm/mla/mix'):
      q_rope = rope_interleaved(q[..., nope:], self.rope_theta).astype(dt)
      k_rope = rope_interleaved(k_rope, self.rope_theta).astype(dt)
      q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
      k = jnp.concatenate(
          [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, heads, rot))],
          axis=-1)
      v = kv[..., nope:]
    with jax.named_scope('glm/mla/attn'):
      o = fa.flash_attention(q, k, v, True, None, None, None)
    with jax.named_scope('glm/mla/project'):
      return o.reshape(b, s, heads * vd) @ wo.astype(dt)


class DecoderLayer(nn.Module):
  """One layer; returns (hidden, the expert layer's counts or None)."""

  sparse: bool
  attn_kwargs: Dict[str, Any]
  eps: float
  dense_width: int
  expert_kwargs: Optional[Dict[str, Any]] = None
  dtype: Any = jnp.float32
  init_std: float = 0.02

  @nn.compact
  def __call__(self, h, train: bool = False):
    d, dt = h.shape[-1], self.dtype
    norm1 = self.param('norm1', nn.initializers.ones, (d,))
    norm2 = self.param('norm2', nn.initializers.ones, (d,))
    a = h + MLA(eps=self.eps, dtype=dt, init_std=self.init_std, name='attn',
                **self.attn_kwargs)(afmoe.rms_norm(h, norm1, self.eps, dt))
    x = afmoe.rms_norm(a, norm2, self.eps, dt)
    stats = None
    if self.sparse:
      with jax.named_scope('glm'):     # round the layer's own afmoe/moe/*
        y, stats = moe.ExpertLayer(dtype=dt, init_std=self.init_std,
                                   name='moe', **self.expert_kwargs)(x, train)
    else:
      with jax.named_scope('glm/dense_mlp'):
        y = moe.SwiGLU(self.dense_width, dt, self.init_std, name='mlp')(x)
    return a + y, stats


def _remat_layer():
  # The backward pass computes a layer again from its input, all but
  # what ``KEPT_NAMES`` keeps: at 8,192 tokens the kernel's output 80 MiB
  # and log-sum-exp 0.6 MiB, the experts' output 128 MiB.
  return nn.remat(DecoderLayer, static_argnums=(2,), policy=KEPT_IN_LAYER)


class MTP(nn.Module):
  """The multi-token-prediction module, depth 1: (mean cross-entropy of
  token i + 2 over the S - 2 positions that have one, its expert layer's
  counts) from the trunk's normed state, the tokens and the trunk's own
  embedding and head."""

  attn_kwargs: Dict[str, Any]
  eps: float
  dense_width: int
  expert_kwargs: Dict[str, Any]
  loss_chunk: int = 2048
  dtype: Any = jnp.float32
  init_std: float = 0.02

  @nn.compact
  def __call__(self, hf, embed, head, tokens, train: bool = False):
    d, dt = hf.shape[-1], self.dtype
    ones = nn.initializers.ones
    embed_norm = self.param('embed_norm', ones, (d,))
    hidden_norm = self.param('hidden_norm', ones, (d,))
    proj = self.param('proj', moe.normal_init(self.init_std), (2 * d, d))
    with jax.named_scope('glm/mtp/project'):
      # Position i joins token i + 1. The last position has none and takes
      # the roll's wrap: causal, so no counted position sees it.
      ahead = embed[jnp.roll(tokens, -1, axis=1)].astype(dt)
      u = jnp.concatenate(
          [afmoe.rms_norm(ahead, embed_norm, self.eps, dt),
           afmoe.rms_norm(hf, hidden_norm, self.eps, dt)],
          axis=-1) @ proj.astype(dt)
    with jax.named_scope('glm/mtp/layer'):
      u, stats = _remat_layer()(
          True, self.attn_kwargs, self.eps, self.dense_width,
          self.expert_kwargs, dt, self.init_std, name='layer')(u, train)
    final_norm = self.param('final_norm', ones, (d,))
    u = afmoe.rms_norm(u, final_norm, self.eps, dt)
    with jax.named_scope('glm/head'):
      return afmoe.next_token_loss(u, head, tokens, self.loss_chunk, dt,
                                   shift=2), stats


class Trunk(nn.Module):
  """Embedding, the layers, the final norm, the untied head, the MTP
  module and both losses over ``tokens`` ([B, S] integers)."""

  vocab_size: int
  hidden_size: int
  num_layers: int
  num_dense_layers: int
  attn_kwargs: Dict[str, Any]       # MLA's sizes, rope_theta, latent_gain
  eps: float
  dense_width: int
  expert_kwargs: Dict[str, Any]
  num_mtp_modules: int = 1
  mtp_loss_weight: float = 0.3
  loss_chunk: int = 2048
  dtype: Any = jnp.float32
  init_std: float = 0.02
  embed_std: Optional[float] = None   # init_std where None

  @nn.compact
  def __call__(self, features, train: bool = False):
    if self.num_mtp_modules != 1:
      raise ValueError('one MTP module (depth 1) is what is written here')
    tokens = features['tokens'].astype(jnp.int32)
    init = moe.normal_init(self.init_std)
    embed = self.param(
        'embed', init if self.embed_std is None else moe.normal_init(
            self.embed_std), (self.vocab_size, self.hidden_size))
    h = embed[tokens].astype(self.dtype)
    layer_cls = _remat_layer()
    all_stats = []
    for j in range(self.num_layers):
      sparse = j >= self.num_dense_layers
      h, stats = layer_cls(
          sparse, self.attn_kwargs, self.eps, self.dense_width,
          self.expert_kwargs if sparse else None, self.dtype, self.init_std,
          name=f'layer{j}')(h, train)
      if stats is not None:
        all_stats.append(stats)
    scale = self.param('final_norm', nn.initializers.ones,
                       (self.hidden_size,))
    head = self.param('head', init, (self.hidden_size, self.vocab_size))
    hf = afmoe.rms_norm(h, scale, self.eps, self.dtype)
    with jax.named_scope('glm/head'):
      loss_main = afmoe.next_token_loss(hf, head, tokens, self.loss_chunk,
                                        self.dtype)
      last_logits = jnp.matmul(hf[:, -1], head.astype(self.dtype),
                               preferred_element_type=jnp.float32)
    loss_mtp, stats = MTP(
        self.attn_kwargs, self.eps, self.dense_width, self.expert_kwargs,
        self.loss_chunk, self.dtype, self.init_std, name='mtp')(
            hf, embed, head, tokens, train)
    all_stats.append(stats)

    def millionths(x):
      return jnp.round(1e6 * x).astype(jnp.int32)

    outputs = {'loss': loss_main + self.mtp_loss_weight * loss_mtp,
               'next_token_logits': last_logits,
               'glm/loss_main_e6': millionths(loss_main),
               'glm/loss_mtp_e6': millionths(loss_mtp)}
    for key in all_stats[0]:
      column = jnp.stack([st[key] for st in all_stats])
      outputs[f'moe/{key}'] = (jnp.max(column) if key == 'rows_max_expert'
                               else jnp.sum(column))
    return outputs
