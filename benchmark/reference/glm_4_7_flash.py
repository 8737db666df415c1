"""GLM-4.7-Flash (zai-org, ``model_type: glm4_moe_lite``; 30B-A3B): a
decoder trunk with multi-head latent attention (MLA), a dense first
layer, sigmoid-routed experts with a shared one and a multi-token
prediction (MTP) module that shares the embedding and the head, trained
as next-token prediction with the MTP loss on. Source:
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json; the
layer is DeepSeek-V3's (arXiv:2412.19437) at this config's numbers. What
the config's keys do not settle is listed under ``assumed`` in
``benchmark/configs/glm-4.7-flash-ep8.json`` and marked ``ASSUMED``
below.

One layer, ``h`` [S, 2048], pre-norm::

    a  = h + MLA(RMSNorm1(h))
    h' = a + F(RMSNorm2(a))      F: SwiGLU 10240 in layer 0, MoE elsewhere

MLA (20 heads; 192 content + 64 rotary dimensions for q and k, 256 for
v)::

    cq  = RMSNorm_768(x Wqa);  q = cq Wqb -> [S, 20, 256] = [q_nope ; q_rope]
    a   = x Wkva -> [S, 576];  ckv = RMSNorm_512(a[:, :512])
    k_rope = a[:, 512:] -> [S, 1, 64], one for all heads
    ckv Wkvb -> [S, 20, 448] = [k_nope (192) ; v (256)]
    RoPE (theta 1e6, all 64 dimensions, pairs interleaved) on q_rope, k_rope
    q = [q_nope ; q_rope];  k = [k_nope ; k_rope for every head]
    o = softmax_causal(q k^T / sqrt(256)) v -> [S, 5120];  MLA(x) = o Wo

MoE (router 64 wide, 4 experts a token, one shared expert)::

    s = sigmoid(x Wg) over all 64, float32;  chosen = top-4 of s + bias
    w = s_chosen / (sum + 1e-20) * 1.8
    F(x) = Shared(x) + sum over the chosen experts HELD here of w_e Expert_e(x)

MTP (depth 1; DeepSeek-V3 section 2.2), with ``hf = RMSNorm_f(h_L)``
and ``E`` the shared embedding::

    u_i = [RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(hf_i)] Wm      (4096 -> 2048)
    one expert layer as above over u, RMSNorm_m, the SHARED head
    loss = CE(main, t_{i+1}) + mtp_loss_weight * CE(mtp, t_{i+2})

The MTP module's decoder layer is named as the layer after the last
(``layer5`` of the cut's five): its expert bias lies in the state beside
the others'.

Everything is float32 at matmul precision ``highest``; no kernel, no
import from the program. Attention is computed by query blocks, the
vocabulary losses by chunks and the experts one at a time (every held
expert over every token, masked), with ``jax.checkpoint`` a layer, so
that the step fits beside 16 bytes a parameter. ``quant`` rounds the
operands and stored activations of every product as ``reference/nn.py``
says (the float8 control; the router stays float32, as the program's
does); ``fault`` plants one of ``FAULTS``.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from benchmark.reference import nn

# ASSUMED (the published run's optimizer is not in the config): the other
# token cells'.
OPTIMIZER = {'kind': 'adam', 'learning_rate': 1e-4, 'b1': 0.9, 'b2': 0.999,
             'eps': 1e-8}
FAULTS = ('no_key_rotary', 'no_kv_norm', 'drop_routed', 'no_mtp_loss',
          'mtp_same_token')
HIGHEST = jax.lax.Precision.HIGHEST


def _sizes(cfg):
  return (cfg['hidden_size'], cfg['num_attention_heads'], cfg['q_lora_rank'],
          cfg['kv_lora_rank'], cfg['qk_nope_head_dim'],
          cfg['qk_rope_head_dim'], cfg['v_head_dim'])


def layer_plan(cfg) -> List[tuple]:
  """(name, has experts) of every decoder layer run: the trunk's, then
  the MTP module's, named as the layer after the last."""
  if cfg['num_nextn_predict_layers'] != 1:
    raise ValueError('one MTP module (depth 1) is what is written here')
  count = cfg['num_hidden_layers']
  return [(f'layer{j}', j >= cfg['first_k_dense_replace'])
          for j in range(count)] + [(f'layer{count}', True)]


# ------------------------------------------------------------- parameters

def param_shapes(cfg) -> Dict[str, tuple]:
  d, heads, q_rank, kv_rank, nope, rot, vd = _sizes(cfg)
  fe, held = cfg['moe_intermediate_size'], len(cfg['experts_held'])
  if cfg['n_shared_experts'] != 1:
    raise ValueError('one shared expert')
  shapes = {'embed': (cfg['vocab_size'], d)}

  def layer(p, sparse):
    shapes.update({
        f'{p}/norm1': (d,), f'{p}/norm2': (d,),
        f'{p}/attn/q_a': (d, q_rank), f'{p}/attn/q_a_norm': (q_rank,),
        f'{p}/attn/q_b': (q_rank, heads * (nope + rot)),
        f'{p}/attn/kv_a': (d, kv_rank + rot),
        f'{p}/attn/kv_a_norm': (kv_rank,),
        f'{p}/attn/kv_b': (kv_rank, heads * (nope + vd)),
        f'{p}/attn/o': (heads * vd, d)})
    if not sparse:
      width = cfg['intermediate_size']
      shapes.update({f'{p}/mlp/gate': (d, width), f'{p}/mlp/up': (d, width),
                     f'{p}/mlp/down': (width, d)})
      return
    shapes[f'{p}/moe/router'] = (d, cfg['num_experts_published'])
    for n, shape in (('gate', (d, fe)), ('up', (d, fe)), ('down', (fe, d))):
      shapes[f'{p}/moe/shared/{n}'] = shape
      shapes[f'{p}/moe/experts/{n}'] = (held,) + shape

  plan = layer_plan(cfg)
  for name, sparse in plan[:-1]:
    layer(name, sparse)
  shapes['final_norm'] = (d,)
  shapes['head'] = (d, cfg['vocab_size'])
  shapes.update({'mtp/embed_norm': (d,), 'mtp/hidden_norm': (d,),
                 'mtp/proj': (2 * d, d)})
  layer(*plan[-1])
  shapes['mtp/final_norm'] = (d,)
  return shapes


def init_leaf(key, name: str, cfg):
  """One leaf, a pure function of (key, name). ASSUMED, all of it (the
  configuration's ``assumed.weights``): norm scales one; the embedding
  normal(embed_std); the two matrices that lead out of a latent (``q_b``,
  ``kv_b``) normal(latent_gain / sqrt(fan_in)); the routers
  normal(router_std); every other matrix normal(init_std)."""
  shapes = param_shapes(cfg)
  shape = shapes[name]
  if len(shape) == 1:
    return jnp.ones(shape, jnp.float32)
  leaf = name.rsplit('/', 1)[-1]
  std = cfg['init_std']
  if name == 'embed':
    std = cfg['embed_std']
  elif leaf in ('q_b', 'kv_b'):
    std = cfg['latent_gain'] / shape[0] ** 0.5
  elif leaf == 'router':
    std = cfg['router_std']
  k = jax.random.fold_in(key, list(shapes).index(name))
  return jax.random.normal(k, shape, jnp.float32) * std


def init_params(key, cfg) -> Dict[str, jnp.ndarray]:
  return {name: init_leaf(key, name, cfg) for name in param_shapes(cfg)}


def init_state(cfg) -> Dict[str, jnp.ndarray]:
  """The non-gradient state: one bias an expert, every expert layer, the
  MTP module's among them."""
  return {f'{name}/moe/bias': jnp.zeros((cfg['num_experts_published'],),
                                        jnp.float32)
          for name, sparse in layer_plan(cfg) if sparse}


def program_path(name: str, cfg) -> tuple:
  """Where the program's parameter tree keeps this leaf: the MTP module's
  decoder layer lies under ``mtp/layer``."""
  parts = tuple(name.split('/'))
  if parts[0] == layer_plan(cfg)[-1][0]:
    return ('mtp', 'layer') + parts[1:]
  return parts


def program_state_path(name: str, cfg) -> tuple:
  """Where the program's ``moe_state`` collection keeps a bias (its
  counts lie beside it, under ``counts``)."""
  return program_path(name, cfg)


# ------------------------------------------------------------ the mathematics

def rms_norm(x, scale, eps, quant=None):
  x = x.astype(jnp.float32)
  y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
  return nn.stored(y * scale, quant)


def rope(x, theta: float):
  """Rotary embedding over the whole of ``x``'s last axis, [S, heads,
  rot]. ASSUMED: the pairs are interleaved, (x[2i], x[2i+1]), and come
  out as DeepSeek-V3's ``apply_rotary_pos_emb_interleave`` leaves them:
  the first members in the first half, the second in the second."""
  s, half = x.shape[0], x.shape[-1] // 2
  freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
  angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
  cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
  a, b = x[..., 0::2], x[..., 1::2]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def swiglu(x, gate, up, down, quant):
  h = nn.stored(jax.nn.silu(nn.dense(x, gate, quant=quant)) *
                nn.dense(x, up, quant=quant), quant)
  return nn.dense(h, down, quant=quant)


def mla(p: Dict, x, cfg, quant, fault):
  """MLA of ``x`` [S, hidden]; k and v materialised a head (the absorbed
  form is a decode path), queries in blocks, each against every key."""
  _, heads, _, kv_rank, nope, rot, vd = _sizes(cfg)
  s, eps, theta = x.shape[0], cfg['rms_norm_eps'], float(cfg['rope_theta'])
  cq = rms_norm(nn.dense(x, p['q_a'], quant=quant), p['q_a_norm'], eps, quant)
  q = nn.dense(cq, p['q_b'], quant=quant).reshape(s, heads, nope + rot)
  a = nn.dense(x, p['kv_a'], quant=quant)
  ckv = a[:, :kv_rank]
  if fault != 'no_kv_norm':
    ckv = rms_norm(ckv, p['kv_a_norm'], eps, quant)
  kv = nn.dense(ckv, p['kv_b'], quant=quant).reshape(s, heads, nope + vd)
  k_rope = a[:, None, kv_rank:]                    # one key for all heads
  if fault != 'no_key_rotary':
    k_rope = rope(k_rope, theta)
  q = nn.stored(jnp.concatenate(
      [q[..., :nope], rope(q[..., nope:], theta)], axis=-1), quant)
  k = nn.stored(jnp.concatenate(
      [kv[..., :nope], jnp.broadcast_to(k_rope, (s, heads, rot))], axis=-1),
                quant)
  v = kv[..., nope:]
  block = min(cfg.get('reference_query_block', s), s)
  if s % block:
    raise ValueError(f'{s} queries do not divide into blocks of {block}')
  kq, vq = nn.operand(k, quant), nn.operand(v, quant)
  key_pos = jnp.arange(s)[None, :]

  @jax.checkpoint
  def one_block(args):
    qb, q0 = args                                    # [block, heads, qk]
    logits = nn.product(jnp.einsum('qhd,khd->hqk', nn.operand(qb, quant), kq,
                                   precision=HIGHEST), quant) / (
                                       nope + rot) ** 0.5
    seen = q0 + jnp.arange(block)[:, None] >= key_pos
    probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
    out = nn.product(jnp.einsum('hqk,khd->qhd', nn.operand(probs, quant), vq,
                                precision=HIGHEST), quant)
    return out.reshape(block, heads * vd)

  o = jax.lax.map(one_block, (q.reshape(s // block, block, heads, nope + rot),
                              jnp.arange(0, s, block))).reshape(s, heads * vd)
  return nn.dense(nn.stored(o, quant), p['o'], quant=quant)


def route(router, bias, x, cfg):
  """Scores over every published expert, the choice, the weights and
  the count of tokens each expert was chosen for. float32, unrounded.
  ``topk_method: noaux_tc`` is the bias that only chooses; ``n_group`` =
  ``topk_group`` = 1 make the group limit empty."""
  scores = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), router,
                                     precision=HIGHEST))
  _, chosen = jax.lax.top_k(scores + bias, cfg['num_experts_per_tok'])
  weights = jnp.take_along_axis(scores, chosen, axis=-1)
  if cfg['norm_topk_prob']:
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
  weights = weights * cfg['routed_scaling_factor']
  counts = jnp.zeros((scores.shape[-1],), jnp.int32).at[
      chosen.reshape(-1)].add(1)
  return chosen, weights, counts


def moe(p: Dict, bias, x, cfg, quant, fault):
  """The shared expert plus the held experts' weighted parts. Every held
  expert is computed for every token and weighted by 0 where the token
  did not choose it: plain, and the same sum."""
  chosen, weights, counts = route(p['router'], bias, x, cfg)
  y = swiglu(x, p['shared']['gate'], p['shared']['up'], p['shared']['down'],
             quant)
  if fault == 'drop_routed':
    return y, counts

  @jax.checkpoint
  def one_expert(y, expert):
    expert_id, gate, up, down = expert
    w = jnp.sum(jnp.where(chosen == expert_id, weights, 0.0), axis=-1)
    return y + w[:, None] * swiglu(x, gate, up, down, quant), None

  ids = jnp.asarray(cfg['experts_held'], jnp.int32)
  y, _ = jax.lax.scan(one_expert, y, (ids, p['experts']['gate'],
                                      p['experts']['up'],
                                      p['experts']['down']))
  return y, counts


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


def layer(p: Dict, bias, h, cfg, quant=None, fault=None):
  """One decoder layer: (h', counts or None); ``bias`` None: the dense
  one."""
  eps = cfg['rms_norm_eps']
  a = nn.stored(h + mla(p['attn'], rms_norm(h, p['norm1'], eps, quant), cfg,
                        quant, fault), quant)
  x = rms_norm(a, p['norm2'], eps, quant)
  if bias is None:
    mlp = p['mlp']
    y, counts = swiglu(x, mlp['gate'], mlp['up'], mlp['down'], quant), None
  else:
    y, counts = moe(p['moe'], bias, x, cfg, quant, fault)
  return nn.stored(a + y, quant), counts


def _vocabulary_loss(h, head, tokens, shift: int, cfg, quant):
  """Summed cross-entropy of position i's prediction of token i + shift
  over the S - shift positions that have one, a chunk at a time."""
  s = tokens.shape[0]
  chunk = min(cfg.get('reference_loss_chunk', s), s)
  if s % chunk:
    raise ValueError(f'{s} positions do not divide into chunks of {chunk}')
  labels = jnp.roll(tokens, -shift)
  counted = (jnp.arange(s) < s - shift).astype(jnp.float32)

  @jax.checkpoint
  def one_chunk(args):
    hc, lc, mc = args
    logits = nn.dense(hc, head, quant=quant).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, lc[:, None], axis=-1)[:, 0]
                    * mc)

  return jnp.sum(jax.lax.map(one_chunk, (
      h.reshape(s // chunk, chunk, -1), labels.reshape(s // chunk, chunk),
      counted.reshape(s // chunk, chunk))))


def sequence_loss(params, state, tokens, cfg, quant, fault):
  """One sequence: (summed main loss, summed MTP loss, [expert layers,
  64] counts, the MTP module's last)."""
  eps = cfg['rms_norm_eps']
  plan = layer_plan(cfg)
  counts = []

  def run(name, sparse, h):
    bias = state[f'{name}/moe/bias'] if sparse else None
    h, count = jax.checkpoint(
        lambda p, bias, h: layer(p, bias, h, cfg, quant, fault))(
            _nest(params, f'{name}/'), bias, h)
    if sparse:
      counts.append(count)
    return h

  h = nn.stored(params['embed'][tokens], quant)
  for name, sparse in plan[:-1]:
    h = run(name, sparse, h)
  # ASSUMED: the MTP module takes the trunk's state AFTER the final norm
  # (as vLLM's ``glm4_moe_mtp`` consumes it).
  hf = rms_norm(h, params['final_norm'], eps, quant)
  main = _vocabulary_loss(hf, params['head'], tokens, 1, cfg, quant)
  # Position i is joined with token i + 1 (the last position, which has
  # none, with the roll's wrap: causal, so no counted position sees it;
  # the expert layer routes and counts it like any other).
  ahead = tokens if fault == 'mtp_same_token' else jnp.roll(tokens, -1)
  e = rms_norm(nn.stored(params['embed'][ahead], quant),
               params['mtp/embed_norm'], eps, quant)
  g = rms_norm(hf, params['mtp/hidden_norm'], eps, quant)
  # ASSUMED: the embedding's half goes first into ``Wm``.
  u = nn.dense(jnp.concatenate([e, g], axis=-1), params['mtp/proj'],
               quant=quant)
  u = run(*plan[-1], u)
  um = rms_norm(u, params['mtp/final_norm'], eps, quant)
  mtp = _vocabulary_loss(um, params['head'], tokens, 2, cfg, quant)
  return main, mtp, jnp.stack(counts)


def preprocess(batch: Dict, key, cfg) -> Dict:
  del key, cfg
  return {'tokens': jnp.asarray(batch['features/tokens']).astype(jnp.int32)}


def losses(params, state, inputs, cfg, quant: Optional[str] = None,
           fault: Optional[str] = None):
  """(mean main loss, mean MTP loss, counts by expert layer and expert)
  over the batch's sequences. ASSUMED: no masking across packed
  documents."""
  tokens = inputs['tokens']
  b, s = tokens.shape
  main, mtp, counts = jax.vmap(
      lambda t: sequence_loss(params, state, t, cfg, quant, fault))(tokens)
  return (jnp.sum(main) / (b * (s - 1)), jnp.sum(mtp) / (b * (s - 2)),
          jnp.sum(counts, axis=0))


def loss(params, state, inputs, cfg, quant: Optional[str] = None,
         fault: Optional[str] = None):
  """``main + mtp_loss_weight * mtp`` (ASSUMED weight: DeepSeek-V3's
  first-phase 0.3) and the batch's counts."""
  main, mtp, counts = losses(params, state, inputs, cfg, quant, fault)
  weight = 0.0 if fault == 'no_mtp_loss' else cfg['mtp_loss_weight']
  return main + weight * mtp, counts


def update_state(state, counts, cfg):
  """The bias update from one step's counts ([expert layers, 64]).
  ASSUMED: DeepSeek-V3's speed (0.001), from this chip's counts; the mean
  taken out afterwards changes no choice."""
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
  """One entry a decoder layer run (the MTP module's last), then the
  MTP projection and the head, which two passes go through: what
  ``lib/glm_flops.py`` counts required operations from."""
  d, heads, q_rank, kv_rank, nope, rot, vd = _sizes(cfg)
  out = []
  for _, sparse in layer_plan(cfg):
    entry = {'kind': 'mla', 'hidden': d, 'heads': heads, 'q_rank': q_rank,
             'kv_rank': kv_rank, 'nope': nope, 'rope': rot, 'v_dim': vd}
    if sparse:
      entry.update({
          'router_width': cfg['num_experts_published'],
          'experts_per_token': cfg['num_experts_per_tok'],
          'experts_held': len(cfg['experts_held']),
          'expert_width': cfg['moe_intermediate_size'],
          'shared_experts': cfg['n_shared_experts']})
    else:
      entry['dense_width'] = cfg['intermediate_size']
    out.append(entry)
  return out + [
      {'kind': 'mtp_projection', 'hidden': d},
      {'kind': 'head', 'hidden': d, 'vocab': cfg['vocab_size'],
       'passes': 1 + cfg['num_nextn_predict_layers']}]
