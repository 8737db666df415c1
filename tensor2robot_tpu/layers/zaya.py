"""The ZAYA decoder trunk (Zyphra ZAYA1): attention inside a compressed,
convolved latent (CCA), a top-1 router that is an MLP with a state
carried from layer to layer, learned residual scaling, a tied head.

One layer, hidden ``h`` and the router's state ``r`` (zero before the
first layer)::

    a  = RS1(h, CCA(N1(h)))
    (m, r') = MoE(N2(a), r);  h' = RS2(a, m)
    RS(res, out) = s_res * (res + b_res) + s_out * (out + b_out)

CCA projects q, k and v DOWN (q to ``heads * head_dim``, k and v to
``kv_heads * head_dim``), mixes the joint q-k latent along the sequence
with two causal convolutions of kernel 2 (one filter a channel, then
one ``head_dim x head_dim`` matrix a head and tap), adds the mean of
the q and k projections, L2-normalises each head (a learned temperature
a key/value head on k), rotates the first part of each head, attends
inside the latent (``ops/flash_attention.py``, causal, grouped heads)
and projects UP. Half the values (key/value head 1) come from the
previous token. MoE: ``layers/moe.ExpertLayer`` given the scores of
``Router`` (down-projection, the previous layer's state added, RMS
norm, a three-layer MLP, softmax); one expert a token, weighed by its
probability; no shared expert.

Parameters are float32 and flat under each module, by the names of
``benchmark/reference/zaya1_8b.py``; products take operands in
``dtype``; norms, the whole router, the L2 norms, softmax and the loss
are float32. The trunk returns the mean next-token loss itself
(``afmoe.next_token_loss`` with the embedding transposed: one leaf, its
gradient the sum of both uses; the loss hands back the head's gradient
summed over the chunks in float32, and the transpose carries it to the
embedding's).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from tensor2robot_tpu.layers import afmoe, moe
from tensor2robot_tpu.ops import flash_attention as fa

# What a decoder layer's remat keeps besides the layer's two inputs, by
# name: the kernel's two results, q, k and v as the kernel takes them,
# the experts' choice and output (``afmoe.KEPT_NAMES`` says why each).
Q_NAME, K_NAME, V_NAME = 'cca_q', 'cca_k', 'cca_v'
KEPT_NAMES = (fa.OUT_NAME, fa.LSE_NAME, Q_NAME, K_NAME, V_NAME,
              moe.CHOSEN_NAME, moe.PICKED_NAME)
KEPT_IN_LAYER = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)
L2_EPS = 1e-12


def shift1(x):
  """Position t takes position t - 1 of [B, S, ...]; position 0 takes 0."""
  return jnp.pad(x[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2))


def rope(x, theta: float, rotary: int):
  """Rotary embedding over the first ``rotary`` dimensions of each head
  (their halves rotated against each other) of [B, S, heads, head_dim],
  in float32."""
  s, half = x.shape[1], rotary // 2
  freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
  angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
  cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
  x = x.astype(jnp.float32)
  a, b, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                         axis=-1)


def l2_heads(x, gain):
  """``gain * x / |x|`` over the last axis, in float32."""
  return x * (gain * jax.lax.rsqrt(
      jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS))


class CCA(nn.Module):
  num_heads: int
  num_kv_heads: int              # 2: the value halves are its two heads
  head_dim: int
  conv_taps: int                 # cca_time0 = cca_time1 = 2
  rotary_dim: int
  rope_theta: float
  dtype: Any = jnp.float32
  init_std: float = 0.02

  @nn.compact
  def __call__(self, x):
    b, s, d = x.shape
    heads, kv_heads, hd = self.num_heads, self.num_kv_heads, self.head_dim
    if kv_heads != 2 or self.conv_taps != 2:
      raise ValueError('CCA is written for 2 key/value heads and kernel 2')
    group, q_width, kv_width = heads // kv_heads, heads * hd, kv_heads * hd
    latent = q_width + kv_width
    init = moe.normal_init(self.init_std)
    zeros, ones = nn.initializers.zeros, nn.initializers.ones
    wq = self.param('q', init, (d, q_width))
    wk = self.param('k', init, (d, kv_width))
    wv1 = self.param('v1', init, (d, hd))
    wv2 = self.param('v2', init, (d, hd))
    # Filters start at 1 / sqrt(taps x inputs): the convolved latent is
    # then as large as the mean it is added to.
    conv0_w = self.param('conv0_w', moe.normal_init(2 ** -0.5), (2, latent))
    conv0_b = self.param('conv0_b', zeros, (latent,))
    conv1_w = self.param('conv1_w', moe.normal_init((2 * hd) ** -0.5),
                         (2, heads + kv_heads, hd, hd))
    conv1_b = self.param('conv1_b', zeros, (latent,))
    temp = self.param('temp', ones, (kv_heads,))
    wo = self.param('o', init, (q_width, d))
    dt, f32 = self.dtype, jnp.float32
    x = x.astype(dt)
    with jax.named_scope('zaya/cca/project'):
      z = x @ jnp.concatenate([wq, wk], axis=1).astype(dt)   # [q0 ; k0]
      # The second half of the values comes a token late: the product of
      # the shifted input is the shifted product.
      v = jnp.stack([x @ wv1.astype(dt), shift1(x @ wv2.astype(dt))], axis=2)
    with jax.named_scope('zaya/cca/mix'):
      zf = z.astype(f32)
      z1 = (conv0_w[0] * shift1(zf) + conv0_w[1] * zf + conv0_b).astype(dt)
      zh = z1.reshape(b, s, heads + kv_heads, hd)
      # Both taps in one product a head, [previous ; this] x [2 hd, hd]:
      # 2.19 ms a layer forward and backward on the chip where a product
      # a tap read 2.76 (PERF.md, PR 32).
      taps = conv1_w.astype(dt).transpose(1, 0, 2, 3).reshape(
          heads + kv_heads, 2 * hd, hd)
      z2 = jnp.einsum('bsgi,gio->bsgo',
                      jnp.concatenate([shift1(zh), zh], axis=-1), taps,
                      preferred_element_type=f32) + conv1_b.reshape(
                          heads + kv_heads, hd)
      q0 = zf[..., :q_width].reshape(b, s, heads, hd)
      k0 = zf[..., q_width:].reshape(b, s, kv_heads, hd)
      q = z2[:, :, :heads] + 0.5 * (q0 + jnp.repeat(k0, group, axis=2))
      k = z2[:, :, heads:] + 0.5 * (
          jnp.mean(q0.reshape(b, s, kv_heads, group, hd), axis=3) + k0)
      q = l2_heads(q, hd ** 0.5)
      k = l2_heads(k, hd ** 0.5) * temp[None, None, :, None]
      q = rope(q, self.rope_theta, self.rotary_dim).astype(dt)
      k = rope(k, self.rope_theta, self.rotary_dim).astype(dt)
    q = checkpoint_name(q, Q_NAME)
    k = checkpoint_name(k, K_NAME)
    v = checkpoint_name(v, V_NAME)
    with jax.named_scope('zaya/cca/attn'):
      o = fa.flash_attention(q, k, v, True, None, None, None)
    with jax.named_scope('zaya/cca/project'):
      return o.reshape(b, s, q_width) @ wo.astype(dt)


class ResidualScaling(nn.Module):
  """``s_res * (res + b_res) + s_out * (out + b_out)``; ``s_out`` starts
  at ``branch_scale`` (what closes a residual branch starts small)."""

  branch_scale: float = 1.0
  dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, res, out):
    d = res.shape[-1]
    zeros = nn.initializers.zeros
    res_scale = self.param('res_scale', nn.initializers.ones, (d,))
    res_bias = self.param('res_bias', zeros, (d,))
    out_scale = self.param('out_scale', nn.initializers.constant(
        self.branch_scale), (d,))
    out_bias = self.param('out_bias', zeros, (d,))
    f32 = jnp.float32
    return (res_scale * (res.astype(f32) + res_bias) +
            out_scale * (out.astype(f32) + out_bias)).astype(self.dtype)


class Router(nn.Module):
  """(probabilities [..., experts], the state handed on [..., hidden]),
  all of it float32: ``u = y Wd + bd``; ``r' = u + gamma * r``; a
  three-layer MLP with GELU (erf) of ``RMSNorm(r')``; softmax. The MLP's
  matrices start at ``mlp_init_gain / sqrt(fan_in)``: wide enough that
  the one expert a token is a choice and not a tie among equals. ``Wd``
  starts at ``down_std`` (``init_std`` where None): the norm after it
  makes its size nothing to the forward pass and everything to how soon
  an optimizer whose steps have one size rewrites it."""

  hidden: int
  num_experts: int
  eps: float
  init_std: float = 0.02
  mlp_init_gain: float = 1.0
  down_std: Optional[float] = None

  @nn.compact
  def __call__(self, y, r):
    d, rh = y.shape[-1], self.hidden
    zeros = nn.initializers.zeros
    mlp_init = moe.normal_init(self.mlp_init_gain / rh ** 0.5)
    down_w = self.param('down_w', moe.normal_init(
        self.init_std if self.down_std is None else self.down_std), (d, rh))
    down_b = self.param('down_b', zeros, (rh,))
    eda = self.param('eda', nn.initializers.ones, (rh,))
    scale = self.param('norm', nn.initializers.ones, (rh,))
    w1 = self.param('w1', mlp_init, (rh, rh))
    b1 = self.param('b1', zeros, (rh,))
    w2 = self.param('w2', mlp_init, (rh, rh))
    b2 = self.param('b2', zeros, (rh,))
    w3 = self.param('w3', mlp_init, (rh, self.num_experts))

    def product(a, w):
      return jnp.matmul(a, w, precision=moe.HIGHEST)

    state = product(y.astype(jnp.float32), down_w) + down_b + eda * r
    g = afmoe.rms_norm(state, scale, self.eps, jnp.float32)
    g = jax.nn.gelu(product(g, w1) + b1, approximate=False)
    g = jax.nn.gelu(product(g, w2) + b2, approximate=False)
    return jax.nn.softmax(product(g, w3), axis=-1), state


class DecoderLayer(nn.Module):
  """One layer: (hidden, router state) to the same two and the expert
  layer's counts."""

  num_heads: int
  num_kv_heads: int
  head_dim: int
  conv_taps: int
  rotary_dim: int
  rope_theta: float
  eps: float
  router_hidden: int
  expert_kwargs: Dict[str, Any]
  branch_scale: float = 1.0
  router_init_gain: float = 1.0
  dtype: Any = jnp.float32
  init_std: float = 0.02
  router_down_std: Optional[float] = None

  @nn.compact
  def __call__(self, h, r, train: bool = False):
    d, dt = h.shape[-1], self.dtype
    norm1 = self.param('norm1', nn.initializers.ones, (d,))
    norm2 = self.param('norm2', nn.initializers.ones, (d,))
    out = CCA(self.num_heads, self.num_kv_heads, self.head_dim,
              self.conv_taps, self.rotary_dim, self.rope_theta, dt,
              self.init_std, name='attn')(
                  afmoe.rms_norm(h, norm1, self.eps, dt))
    a = ResidualScaling(self.branch_scale, dt, name='rs1')(h, out)
    y = afmoe.rms_norm(a, norm2, self.eps, dt)
    with jax.named_scope('zaya/router'):
      probs, r = Router(self.router_hidden, self.expert_kwargs['num_experts'],
                        self.eps, self.init_std, self.router_init_gain,
                        self.router_down_std, name='router')(y, r)
    with jax.named_scope('zaya'):    # round the layer's own afmoe/moe/*
      m, stats = moe.ExpertLayer(
          dtype=dt, init_std=self.init_std, route_norm=False,
          route_scale=1.0, shared_expert=False, name='moe',
          **self.expert_kwargs)(y, train, probs)
    return ResidualScaling(self.branch_scale, dt, name='rs2')(a, m), r, stats


class Trunk(nn.Module):
  """Embedding, the layers, the final norm, the tied head and the
  next-token loss over ``tokens`` ([B, S] integers)."""

  vocab_size: int
  hidden_size: int
  num_layers: int
  num_heads: int
  num_kv_heads: int
  head_dim: int
  conv_taps: int
  rotary_dim: int
  rope_theta: float
  eps: float
  router_hidden: int
  expert_kwargs: Dict[str, Any]
  branch_scale: float = 1.0
  router_init_gain: float = 1.0
  loss_chunk: int = 2048
  dtype: Any = jnp.float32
  init_std: float = 0.02
  router_down_std: Optional[float] = None

  @nn.compact
  def __call__(self, features, train: bool = False):
    tokens = features['tokens'].astype(jnp.int32)
    embed = self.param('embed', moe.normal_init(self.init_std),
                       (self.vocab_size, self.hidden_size))
    h = embed[tokens].astype(self.dtype)
    r = jnp.zeros(tokens.shape + (self.router_hidden,), jnp.float32)
    # The backward pass computes a layer again from its two inputs (h in
    # ``dtype``, r float32), all but what ``KEPT_NAMES`` keeps. At 2 x
    # 8,192 tokens of 2,048, 8 / 2 heads of 128 and one expert a token:
    # the attention kernel's output 32 MiB and log-sum-exp 0.5 MiB, q 32
    # MiB, k and v 8 MiB each, the experts' output 64 MiB with the choice
    # that laid it out.
    layer_cls = nn.remat(DecoderLayer, static_argnums=(3,),
                         policy=KEPT_IN_LAYER)
    all_stats = []
    for j in range(self.num_layers):
      h, r, stats = layer_cls(
          self.num_heads, self.num_kv_heads, self.head_dim, self.conv_taps,
          self.rotary_dim, self.rope_theta, self.eps, self.router_hidden,
          self.expert_kwargs, self.branch_scale, self.router_init_gain,
          self.dtype, self.init_std, self.router_down_std,
          name=f'layer{j}')(h, r, train)
      all_stats.append(stats)
    scale = self.param('final_norm', nn.initializers.ones,
                       (self.hidden_size,))
    h = afmoe.rms_norm(h, scale, self.eps, self.dtype)
    with jax.named_scope('zaya/head'):
      head = embed.T                                     # tied
      loss = afmoe.next_token_loss(h, head, tokens, self.loss_chunk,
                                   self.dtype)
      last_logits = jnp.matmul(h[:, -1], head.astype(self.dtype),
                               preferred_element_type=jnp.float32)
    outputs = {'loss': loss, 'next_token_logits': last_logits}
    for key in all_stats[0]:
      column = jnp.stack([st[key] for st in all_stats])
      # One expert a token: the mean weight is the mean chosen probability.
      name = 'top1_weight_e6' if key == 'weight_e6' else key
      outputs[f'moe/{name}'] = (jnp.max(column) if key == 'rows_max_expert'
                                else jnp.sum(column))
    return outputs
