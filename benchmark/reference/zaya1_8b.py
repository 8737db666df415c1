"""ZAYA1-8B (Zyphra, ``model_type: zaya``): a decoder trunk whose
attention runs inside a compressed, convolved latent (CCA) and whose
top-1 router is an MLP with a state carried from layer to layer,
trained as next-token prediction. Source: https://huggingface.co/Zyphra/
ZAYA1-8B/blob/main/config.json; the mixing follows the CCA paper
(arXiv:2510.04476) and the ZAYA1 report (arXiv:2511.17127) as far as
they are known here. What the config's keys do not settle is listed
under ``assumed`` in ``benchmark/configs/zaya1-8b-ep2.json`` and marked
``ASSUMED`` below.

One ``hybrid`` layer, ``h`` [S, 2048], router state ``r`` [S, 256]
(zero before layer 0)::

    x  = RMSNorm1(h);  a  = RS1(h, CCA(x))
    y  = RMSNorm2(a);  (m, r') = MoE(y, r);  h' = RS2(a, m)
    RS(res, out) = s_res * (res + b_res) + s_out * (out + b_out)

CCA (8 query / 2 key-value heads of 128)::

    q0 = x Wq (2048 -> 1024);  k0 = x Wk (2048 -> 256)
    v  = [x Wv1 ; shift1(x) Wv2]       kv head 0 of this token, 1 of the last
    z  = [q0 ; k0];  z1_t = w0 * z_{t-1} + w1 * z_t + b      depthwise, causal
    z2_t[g] = z1_{t-1}[g] A0[g] + z1_t[g] A1[g] + c[g]     10 heads, 128 -> 128
    (qc, kc) = split(z2)
    q = qc + (q0 + repeat(k0)) / 2;  k = kc + (mean of the group's q0 + k0) / 2
    q^ = sqrt(128) q / |q|;  k^ = tau_g sqrt(128) k / |k|    per head
    RoPE on the first 64 of 128 dimensions (theta 5e6)
    o = softmax_causal(q^ k^T / sqrt(128)) v;  CCA(x) = o Wo (1024 -> 2048)

MoE (router 16 wide, one expert a token, no shared expert)::

    u = y Wd + bd (2048 -> 256);  r' = u + gamma * r
    g = W3 gelu(W2 gelu(W1 RMSNorm_r(r') + b1) + b2);  p = softmax(g)
    e = argmax(p + bias);  m = p_e Expert_e(y) if e is held here, else 0

``logits = RMSNorm_f(h_L) E^T`` with the tied embedding ``E``. **No skip
("mixture-of-depths") expert**: the family's description mentions one,
the config declares 16 experts and no seventeenth router output, so the
router here is 16 wide: a departure if the published model has it.

Everything is float32 at matmul precision ``highest``; no kernel, no
import from the program. Attention is computed by query blocks, the
loss by chunks and the experts one at a time, with ``jax.checkpoint`` a
layer, so that the step fits beside 16 bytes a parameter (compiled for
a described v5e: 8.50 GB of state + 5.71 GB of temporaries). ``quant``
rounds the operands and stored activations of every product as
``reference/nn.py`` says (the float8 control; the router stays float32,
as the program's does); ``fault`` plants one of ``FAULTS``.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from benchmark.reference import nn

# ASSUMED (the published run's optimizer is not in the config): the other
# token cell's.
OPTIMIZER = {'kind': 'adam', 'learning_rate': 1e-4, 'b1': 0.9, 'b2': 0.999,
             'eps': 1e-8}
FAULTS = ('drop_routed', 'no_conv', 'no_value_shift', 'no_qk_mean', 'no_eda',
          'full_rotary')
HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-12                                       # ASSUMED


def _sizes(cfg):
  d, hd = cfg['hidden_size'], cfg['head_dim']
  heads, kv_heads = cfg['num_attention_heads'], cfg['num_key_value_heads']
  return d, hd, heads, kv_heads, heads * hd, kv_heads * hd


# ------------------------------------------------------------- parameters

def param_shapes(cfg) -> Dict[str, tuple]:
  d, hd, heads, kv_heads, q, kv = _sizes(cfg)
  if kv_heads != 2:
    raise ValueError('the value halves are kv head 0 and kv head 1')
  latent, groups = q + kv, heads + kv_heads
  rh, fe = cfg['router_hidden_size'], cfg['moe_intermediate_size']
  held, experts = len(cfg['experts_held']), cfg['num_experts_published']
  shapes = {'embed': (cfg['vocab_size'], d)}
  for j in range(cfg['num_hidden_layers']):
    p = f'layer{j}'
    shapes.update({
        f'{p}/norm1': (d,), f'{p}/norm2': (d,),
        f'{p}/attn/q': (d, q), f'{p}/attn/k': (d, kv),
        f'{p}/attn/v1': (d, hd), f'{p}/attn/v2': (d, hd),
        f'{p}/attn/conv0_w': (cfg['cca_time0'], latent),
        f'{p}/attn/conv0_b': (latent,),
        f'{p}/attn/conv1_w': (cfg['cca_time1'], groups, hd, hd),
        f'{p}/attn/conv1_b': (latent,),
        f'{p}/attn/temp': (kv_heads,), f'{p}/attn/o': (q, d)})
    for rs in ('rs1', 'rs2'):
      for n in ('res_scale', 'res_bias', 'out_scale', 'out_bias'):
        shapes[f'{p}/{rs}/{n}'] = (d,)
    shapes.update({
        f'{p}/router/down_w': (d, rh), f'{p}/router/down_b': (rh,),
        f'{p}/router/eda': (rh,), f'{p}/router/norm': (rh,),
        f'{p}/router/w1': (rh, rh), f'{p}/router/b1': (rh,),
        f'{p}/router/w2': (rh, rh), f'{p}/router/b2': (rh,),
        f'{p}/router/w3': (rh, experts)})
    for n, shape in (('gate', (d, fe)), ('up', (d, fe)), ('down', (fe, d))):
      shapes[f'{p}/moe/experts/{n}'] = (held,) + shape
  shapes['final_norm'] = (d,)
  return shapes


def init_leaf(key, name: str, cfg):
  """One leaf, a pure function of (key, name). ASSUMED, all of it (the
  configuration's ``assumed.weights``): matrices normal(init_std) but
  the router MLP's three, normal(router_init_gain / sqrt(fan_in)), the
  router's down-projection, normal(router_down_std), and the two
  convolutions' filters, normal(1 / sqrt(taps x inputs)); norm
  scales, ``res_scale``, the temperature and ``eda`` one; biases zero;
  ``out_scale`` (what closes a residual branch) ``branch_scale_init``."""
  shapes = param_shapes(cfg)
  shape = shapes[name]
  leaf = name.rsplit('/', 1)[-1]
  if leaf == 'out_scale':
    return jnp.full(shape, cfg['branch_scale_init'], jnp.float32)
  if leaf in ('res_bias', 'out_bias', 'down_b', 'b1', 'b2', 'conv0_b',
              'conv1_b'):
    return jnp.zeros(shape, jnp.float32)
  if len(shape) == 1:
    return jnp.ones(shape, jnp.float32)
  k = jax.random.fold_in(key, list(shapes).index(name))
  std = cfg['init_std']
  if leaf in ('w1', 'w2', 'w3') and '/router/' in name:
    std = cfg['router_init_gain'] / shape[0] ** 0.5
  elif leaf == 'down_w':
    std = cfg.get('router_down_std', std)
  elif leaf == 'conv0_w':
    std = shape[0] ** -0.5
  elif leaf == 'conv1_w':
    std = (shape[0] * shape[2]) ** -0.5
  return jax.random.normal(k, shape, jnp.float32) * std


def init_params(key, cfg) -> Dict[str, jnp.ndarray]:
  return {name: init_leaf(key, name, cfg) for name in param_shapes(cfg)}


def init_state(cfg) -> Dict[str, jnp.ndarray]:
  """The non-gradient state: one bias an expert, every layer."""
  return {f'layer{j}/moe/bias': jnp.zeros((cfg['num_experts_published'],),
                                          jnp.float32)
          for j in range(cfg['num_hidden_layers'])}


def program_path(name: str, cfg) -> tuple:
  """Where the program's parameter tree keeps this leaf."""
  del cfg
  return tuple(name.split('/'))


def program_state_path(name: str, cfg) -> tuple:
  """Where the program's ``moe_state`` collection keeps a bias (its
  counts lie beside it, under ``counts``)."""
  del cfg
  return tuple(name.split('/'))


# ------------------------------------------------------------ the mathematics

def rms_norm(x, scale, eps, quant=None):
  x = x.astype(jnp.float32)
  y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
  return nn.stored(y * scale, quant)


def shift1(x):
  """Row t becomes row t - 1; row 0 becomes 0."""
  return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def rope(x, theta: float, rotary: int):
  """Rotary embedding over the first ``rotary`` dimensions of the head
  (their halves rotated against each other); ``x`` is [S, heads, hd]."""
  s = x.shape[0]
  half = rotary // 2
  freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
  angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
  cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
  a, b, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                         axis=-1)


def l2_heads(x, gain):
  """``gain * x / |x|`` over the last axis."""
  return x * (gain * jax.lax.rsqrt(
      jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS))


def swiglu(x, gate, up, down, quant):
  h = nn.stored(jax.nn.silu(nn.dense(x, gate, quant=quant)) *
                nn.dense(x, up, quant=quant), quant)
  return nn.dense(h, down, quant=quant)


def cca_mix(p: Dict, q0, k0, cfg, quant, fault):
  """(q^, k^) [S, heads, hd], [S, kv_heads, hd] from the projections."""
  _, hd, heads, kv_heads, qw, _ = _sizes(cfg)
  s, group = q0.shape[0], heads // kv_heads
  z = jnp.concatenate([q0, k0], axis=-1)
  if fault == 'no_conv':
    z2 = z
  else:
    # ASSUMED: both convolutions on the joint q-k latent, with biases; a
    # tap's row 0 is the previous position's, row 1 this one's.
    w = p['conv0_w']
    z1 = nn.stored(w[0] * shift1(z) + w[1] * z + p['conv0_b'], quant)
    # ASSUMED: the grouped convolution's groups are the heads.
    zh = nn.operand(z1.reshape(s, heads + kv_heads, hd), quant)
    taps = nn.operand(p['conv1_w'], quant)
    z2 = nn.product(
        jnp.einsum('sgi,gio->sgo', shift1(zh), taps[0], precision=HIGHEST) +
        jnp.einsum('sgi,gio->sgo', zh, taps[1], precision=HIGHEST), quant)
    z2 = nn.stored(z2.reshape(s, -1) + p['conv1_b'], quant)
  qc = z2[:, :qw].reshape(s, heads, hd)
  kc = z2[:, qw:].reshape(s, kv_heads, hd)
  qh, kh = q0.reshape(s, heads, hd), k0.reshape(s, kv_heads, hd)
  if fault == 'no_qk_mean':
    q, k = qc, kc
  else:
    # ASSUMED: the q-k mean of grouped heads, from BEFORE the convolutions.
    q = qc + 0.5 * (qh + jnp.repeat(kh, group, axis=1))
    k = kc + 0.5 * (jnp.mean(qh.reshape(s, kv_heads, group, hd), axis=2) + kh)
  # ASSUMED: the temperature on k only; the norm, then RoPE.
  q = l2_heads(q, hd ** 0.5)
  k = l2_heads(k, hd ** 0.5) * p['temp'][None, :, None]
  rotary = hd if fault == 'full_rotary' else int(
      hd * cfg['partial_rotary_factor'])
  theta = cfg['rope_parameters']['hybrid']['rope_theta']
  return (nn.stored(rope(q, theta, rotary), quant),
          nn.stored(rope(k, theta, rotary), quant))


def attention(p: Dict, x, cfg, quant, fault):
  """CCA of ``x`` [S, hidden]; queries in blocks, each against every key."""
  _, hd, heads, kv_heads, qw, _ = _sizes(cfg)
  s, group = x.shape[0], heads // kv_heads
  q0 = nn.dense(x, p['q'], quant=quant)
  k0 = nn.dense(x, p['k'], quant=quant)
  # ASSUMED: the second value half is the one that comes a token late.
  late = x if fault == 'no_value_shift' else shift1(x)
  v = jnp.stack([nn.dense(x, p['v1'], quant=quant),
                 nn.dense(late, p['v2'], quant=quant)], axis=1)
  q, k = cca_mix(p, q0, k0, cfg, quant, fault)
  block = min(cfg.get('reference_query_block', s), s)
  if s % block:
    raise ValueError(f'{s} queries do not divide into blocks of {block}')
  kq, vq = nn.operand(k, quant), nn.operand(v, quant)
  key_pos = jnp.arange(s)[None, :]

  @jax.checkpoint
  def one_block(args):
    qb, q0 = args                                    # [block, heads, hd]
    qb = nn.operand(qb, quant).reshape(block, kv_heads, group, hd)
    logits = nn.product(jnp.einsum('qngd,knd->ngqk', qb, kq,
                                   precision=HIGHEST), quant) / hd ** 0.5
    seen = q0 + jnp.arange(block)[:, None] >= key_pos
    probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
    out = nn.product(jnp.einsum('ngqk,knd->qngd', nn.operand(probs, quant),
                                vq, precision=HIGHEST), quant)
    return out.reshape(block, qw)

  o = jax.lax.map(one_block, (q.reshape(s // block, block, heads, hd),
                              jnp.arange(0, s, block))).reshape(s, qw)
  return nn.dense(nn.stored(o, quant), p['o'], quant=quant)


def router(p: Dict, y, r, cfg, fault):
  """(probabilities [S, experts], the state handed on). float32,
  unrounded. ASSUMED: the biases, the norm's place, GELU by erf, gamma."""
  y = y.astype(jnp.float32)
  u = jnp.matmul(y, p['down_w'], precision=HIGHEST) + p['down_b']
  state = u if fault == 'no_eda' else u + p['eda'] * r
  n = rms_norm(state, p['norm'], cfg['rms_norm_eps'])
  g = jax.nn.gelu(jnp.matmul(n, p['w1'], precision=HIGHEST) + p['b1'],
                  approximate=False)
  g = jax.nn.gelu(jnp.matmul(g, p['w2'], precision=HIGHEST) + p['b2'],
                  approximate=False)
  return jax.nn.softmax(jnp.matmul(g, p['w3'], precision=HIGHEST),
                        axis=-1), state


def moe(p: Dict, bias, probs, y, cfg, quant, fault):
  """The held experts' weighted parts and the count of tokens each of the
  16 was chosen for. Every held expert is computed for every token and
  weighted by 0 where the token did not choose it: plain, the same sum."""
  chosen = jnp.argmax(probs + bias, axis=-1)
  weight = jnp.take_along_axis(probs, chosen[:, None], axis=-1)[:, 0]
  counts = jnp.zeros((probs.shape[-1],), jnp.int32).at[chosen].add(1)
  m = jnp.zeros(y.shape, jnp.float32)
  if fault == 'drop_routed':
    return m, counts

  @jax.checkpoint
  def one_expert(m, expert):
    expert_id, gate, up, down = expert
    w = jnp.where(chosen == expert_id, weight, 0.0)
    return m + w[:, None] * swiglu(y, gate, up, down, quant), None

  ids = jnp.asarray(cfg['experts_held'], jnp.int32)
  m, _ = jax.lax.scan(one_expert, m, (ids, p['gate'], p['up'], p['down']))
  return m, counts


def residual_scaling(p: Dict, res, out, quant):
  return nn.stored(p['res_scale'] * (res + p['res_bias']) +
                   p['out_scale'] * (out + p['out_bias']), quant)


def _nest(flat: Dict[str, jnp.ndarray], prefix: str) -> Dict:
  out: Dict = {}
  for name, value in flat.items():
    if name.startswith(prefix):
      node = out
      *parents, leaf = name[len(prefix):].split('/')
      for part in parents:
        node = node.setdefault(part, {})
      node[leaf] = value
  return out


def layer(p: Dict, bias, h, r, cfg, quant=None, fault=None):
  """One hybrid layer: (h', r', counts)."""
  eps = cfg['rms_norm_eps']
  a = residual_scaling(
      p['rs1'], h, attention(p['attn'], rms_norm(h, p['norm1'], eps, quant),
                             cfg, quant, fault), quant)
  y = rms_norm(a, p['norm2'], eps, quant)
  probs, r = router(p['router'], y, r, cfg, fault)
  m, counts = moe(p['moe']['experts'], bias, probs, y, cfg, quant, fault)
  return residual_scaling(p['rs2'], a, m, quant), r, counts


def sequence_loss(params, state, tokens, cfg, quant, fault):
  """One sequence: (summed next-token loss, [layers, 16] counts)."""
  h = nn.stored(params['embed'][tokens], quant)
  r = jnp.zeros((tokens.shape[0], cfg['router_hidden_size']), jnp.float32)
  counts = []
  for j in range(cfg['num_hidden_layers']):
    h, r, count = jax.checkpoint(
        lambda p, bias, h, r: layer(p, bias, h, r, cfg, quant, fault))(
            _nest(params, f'layer{j}/'), state[f'layer{j}/moe/bias'], h, r)
    counts.append(count)
  h = rms_norm(h, params['final_norm'], cfg['rms_norm_eps'], quant)
  s = tokens.shape[0]
  chunk = min(cfg.get('reference_loss_chunk', s), s)
  if s % chunk:
    raise ValueError(f'{s} positions do not divide into chunks of {chunk}')
  labels = jnp.roll(tokens, -1)
  counted = (jnp.arange(s) < s - 1).astype(jnp.float32)
  head = params['embed'].T                           # tied

  @jax.checkpoint
  def one_chunk(args):
    hc, lc, mc = args
    logits = nn.dense(hc, head, quant=quant).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, lc[:, None], axis=-1)[:, 0]
                    * mc)

  parts = jax.lax.map(one_chunk, (h.reshape(s // chunk, chunk, -1),
                                  labels.reshape(s // chunk, chunk),
                                  counted.reshape(s // chunk, chunk)))
  return jnp.sum(parts), jnp.stack(counts)


def preprocess(batch: Dict, key, cfg) -> Dict:
  del key, cfg
  return {'tokens': jnp.asarray(batch['features/tokens']).astype(jnp.int32)}


def loss(params, state, inputs, cfg, quant: Optional[str] = None,
         fault: Optional[str] = None):
  """Mean next-token cross-entropy over the batch's sequences, and the
  batch's counts by layer and expert. ASSUMED: no masking across packed
  documents."""
  tokens = inputs['tokens']
  # The sequences side by side, not one after the other: a loop over them
  # would hold the gradients twice (the sum so far and the sequence's
  # own, 2.8 GB each at the published widths), which does not fit beside
  # 16 bytes a parameter; the blocks and chunks are sized for two.
  sums, counts = jax.vmap(
      lambda t: sequence_loss(params, state, t, cfg, quant, fault))(tokens)
  positions = tokens.shape[0] * (tokens.shape[1] - 1)
  return jnp.sum(sums) / positions, jnp.sum(counts, axis=0)


def update_state(state, counts, cfg):
  """The bias update from one step's counts ([layers, 16]). ASSUMED: the
  published balancing optimiser is not in the config."""
  out = {}
  for row, name in enumerate(sorted(state, key=lambda n: int(
      n.split('/')[0][len('layer'):]))):
    count = counts[row].astype(jnp.float32)
    b = state[name] + cfg['load_balance_coeff'] * jnp.sign(
        jnp.mean(count) - count)
    out[name] = b - jnp.mean(b)
  return out


# ------------------------------------------------- what the FLOPs are read from

def layers(cfg) -> List[Dict]:
  """One entry a layer run, then the head: what ``lib/zaya_flops.py``
  counts required operations from."""
  _, hd, heads, kv_heads, _, _ = _sizes(cfg)
  entry = {
      'kind': 'hybrid', 'hidden': cfg['hidden_size'], 'heads': heads,
      'kv_heads': kv_heads, 'head_dim': hd,
      'conv_taps': cfg['cca_time1'],
      'router_hidden': cfg['router_hidden_size'],
      'router_width': cfg['num_experts_published'],
      'experts_per_token': cfg['num_experts_per_tok'],
      'experts_held': len(cfg['experts_held']),
      'expert_width': cfg['moe_intermediate_size']}
  return [dict(entry) for _ in range(cfg['num_hidden_layers'])] + [
      {'kind': 'head', 'hidden': cfg['hidden_size'],
       'vocab': cfg['vocab_size']}]
