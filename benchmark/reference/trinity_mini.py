"""Trinity-Mini (arcee-ai, ``model_type: afmoe``): a decoder trunk with
sparse experts, window and full attention mixed, trained as next-token
prediction. Source: https://huggingface.co/arcee-ai/Trinity-Mini/blob/
main/config.json; what the config's keys do not settle follows the
family's published modelling code as far as it is known here and is
listed under ``assumed`` in ``benchmark/configs/trinity-mini-ep8.json``.

One layer ``l`` (four RMS norms, sandwich order)::

    a  = h + N2(Attn(N1(h)))
    h' = a + N4(FFN(N3(a)))

Attn: q (32 heads of 128), k and v (4 heads), a gate as wide as q; RMS
norm over the head dimension of q and k; on ``sliding_attention`` layers
RoPE (whole head) and the mask ``0 <= i - j < sliding_window``, on
``full_attention`` layers no positional embedding and the causal mask;
softmax of ``q k^T / sqrt(128)`` in groups of 8 query heads a key/value
head; ``out = Wo (o * sigmoid(g))``. No biases anywhere.

FFN of the leading dense layers: SwiGLU of width ``intermediate_size``.
FFN of the others: ``s = sigmoid(Wr x)`` over all published experts;
chosen = top-k of ``s + b`` (``b`` the expert bias, for the choice
only); ``w = s_chosen / (sum + 1e-20) * route_scale``;
``y = Shared(x) + sum_i w_i Expert_i(x)`` over the experts HELD here
(``experts_held``): what the absent experts would add is left out and
the partial sum goes on. After a step, outside the gradient:
``b += load_balance_coeff * sign(mean(count) - count)``, minus its mean.

Everything is float32 at matmul precision ``highest``; no kernel, no
import from the program. At the published widths it is computed in
blocks (queries, the vocabulary loss, one expert at a time) and
``jax.checkpoint`` a layer, so that the step fits beside 16 bytes a
parameter. ``quant`` rounds the operands and stored activations of every
product as ``reference/nn.py`` says (the float8 control); ``fault``
plants one of ``FAULTS``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference import nn

OPTIMIZER = {'kind': 'adam', 'learning_rate': 1e-4, 'b1': 0.9, 'b2': 0.999,
             'eps': 1e-8}
FAULTS = ('no_window', 'drop_routed', 'unnormalised_route')
HIGHEST = jax.lax.Precision.HIGHEST


def layer_plan(cfg) -> List[Tuple[int, str, bool]]:
  """(published index, attention kind, has experts) of each layer run."""
  kept = cfg['layers_kept']
  if len(kept) != cfg['num_hidden_layers']:
    raise ValueError('layers_kept and num_hidden_layers disagree')
  return [(i, cfg['layer_types'][i], j >= cfg['num_dense_layers'])
          for j, i in enumerate(kept)]


# ------------------------------------------------------------- parameters

def param_shapes(cfg) -> Dict[str, tuple]:
  d, hd = cfg['hidden_size'], cfg['head_dim']
  q = cfg['num_attention_heads'] * hd
  kv = cfg['num_key_value_heads'] * hd
  f, fe = cfg['intermediate_size'], cfg['moe_intermediate_size']
  held = len(cfg['experts_held'])
  shapes = {'embed': (cfg['vocab_size'], d)}
  for j, (_, _, sparse) in enumerate(layer_plan(cfg)):
    p = f'layer{j}'
    for n in ('norm1', 'norm2', 'norm3', 'norm4'):
      shapes[f'{p}/{n}'] = (d,)
    shapes.update({
        f'{p}/attn/q': (d, q), f'{p}/attn/k': (d, kv), f'{p}/attn/v': (d, kv),
        f'{p}/attn/gate': (d, q), f'{p}/attn/o': (q, d),
        f'{p}/attn/q_norm': (hd,), f'{p}/attn/k_norm': (hd,)})
    if sparse:
      shapes[f'{p}/moe/router'] = (d, cfg['num_experts_published'])
      for n, shape in (('gate', (d, fe)), ('up', (d, fe)), ('down', (fe, d))):
        shapes[f'{p}/moe/shared/{n}'] = shape
        shapes[f'{p}/moe/experts/{n}'] = (held,) + shape
    else:
      shapes.update({f'{p}/mlp/gate': (d, f), f'{p}/mlp/up': (d, f),
                     f'{p}/mlp/down': (f, d)})
  shapes['final_norm'] = (d,)
  shapes['head'] = (d, cfg['vocab_size'])
  return shapes


def init_leaf(key, name: str, cfg):
  """One leaf, a pure function of (key, name): the kind makes the
  starting weights again leaf by leaf, so that no second copy of 2.8 GB
  stays on the device."""
  shapes = param_shapes(cfg)
  shape = shapes[name]
  if name.endswith(('/norm2', '/norm4')):
    # Damped residual branches (the configuration's ``assumed`` says why).
    return jnp.full(shape, cfg['branch_scale_init'], jnp.float32)
  if len(shape) == 1:
    return jnp.ones(shape, jnp.float32)
  k = jax.random.fold_in(key, list(shapes).index(name))
  return jax.random.normal(k, shape, jnp.float32) * cfg['init_std']


def init_params(key, cfg) -> Dict[str, jnp.ndarray]:
  return {name: init_leaf(key, name, cfg) for name in param_shapes(cfg)}


def init_state(cfg) -> Dict[str, jnp.ndarray]:
  """The non-gradient state: one bias an expert, a layer with experts."""
  return {f'layer{j}/moe/bias': jnp.zeros((cfg['num_experts_published'],),
                                          jnp.float32)
          for j, (_, _, sparse) in enumerate(layer_plan(cfg)) if sparse}


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


def rope(x, theta: float):
  """Rotary embedding over the whole head, halves rotated against each
  other; ``x`` is [S, heads, head_dim]."""
  s, _, hd = x.shape
  half = hd // 2
  freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
  angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
  cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
  a, b = x[..., :half], x[..., half:]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def swiglu(x, gate, up, down, quant):
  h = nn.stored(jax.nn.silu(nn.dense(x, gate, quant=quant)) *
                nn.dense(x, up, quant=quant), quant)
  return nn.dense(h, down, quant=quant)


def attention(p: Dict, x, kind: str, cfg, quant, fault):
  """``x`` is [S, hidden]; queries in blocks, each against every key."""
  s = x.shape[0]
  heads, kv_heads, hd = (cfg['num_attention_heads'],
                         cfg['num_key_value_heads'], cfg['head_dim'])
  group = heads // kv_heads
  q = nn.dense(x, p['q'], quant=quant).reshape(s, heads, hd)
  k = nn.dense(x, p['k'], quant=quant).reshape(s, kv_heads, hd)
  v = nn.dense(x, p['v'], quant=quant).reshape(s, kv_heads, hd)
  g = nn.dense(x, p['gate'], quant=quant)
  q = rms_norm(q, p['q_norm'], cfg['rms_norm_eps'], quant)
  k = rms_norm(k, p['k_norm'], cfg['rms_norm_eps'], quant)
  window = None
  if kind == 'sliding_attention':
    q, k = rope(q, cfg['rope_theta']), rope(k, cfg['rope_theta'])
    q, k = nn.stored(q, quant), nn.stored(k, quant)
    window = None if fault == 'no_window' else cfg['sliding_window']
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
    pos = q0 + jnp.arange(block)[:, None]
    seen = pos >= key_pos
    if window is not None:
      seen = jnp.logical_and(seen, pos - key_pos < window)
    probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
    out = nn.product(jnp.einsum('ngqk,knd->qngd', nn.operand(probs, quant),
                                vq, precision=HIGHEST), quant)
    return out.reshape(block, heads * hd)

  starts = jnp.arange(0, s, block)
  o = jax.lax.map(one_block, (q.reshape(s // block, block, heads, hd),
                              starts)).reshape(s, heads * hd)
  o = nn.stored(nn.stored(o, quant) * jax.nn.sigmoid(g), quant)
  return nn.dense(o, p['o'], quant=quant)


def route(router, bias, x, cfg, fault):
  """Scores over every published expert, the choice, the weights and
  the count of tokens each expert was chosen for. float32, unrounded."""
  scores = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), router,
                                     precision=HIGHEST))
  _, chosen = jax.lax.top_k(scores + bias, cfg['num_experts_per_tok'])
  weights = jnp.take_along_axis(scores, chosen, axis=-1)
  if cfg['route_norm'] and fault != 'unnormalised_route':
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
  weights = weights * cfg['route_scale']
  counts = jnp.zeros((scores.shape[-1],), jnp.int32).at[
      chosen.reshape(-1)].add(1)
  return chosen, weights, counts


def moe(p: Dict, bias, x, cfg, quant, fault):
  """The shared expert plus the held experts' weighted parts. Every held
  expert is computed for every token and weighted by 0 where the token
  did not choose it: plain, and the same sum."""
  chosen, weights, counts = route(p['router'], bias, x, cfg, fault)
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


def sequence_loss(params, state, tokens, cfg, quant, fault):
  """One sequence: (summed next-token loss, [layers with experts, 128]
  counts)."""
  eps = cfg['rms_norm_eps']
  h = params['embed'][tokens]
  if cfg['mup_enabled']:
    h = h * cfg['hidden_size'] ** 0.5
  h = nn.stored(h, quant)
  counts = []
  for j, (_, kind, sparse) in enumerate(layer_plan(cfg)):
    p = _nest(params, f'layer{j}/')
    bias = state[f'layer{j}/moe/bias'] if sparse else None

    @jax.checkpoint
    def layer(h, p, bias, kind=kind, sparse=sparse):
      a = attention(p['attn'], rms_norm(h, p['norm1'], eps, quant), kind,
                    cfg, quant, fault)
      a = nn.stored(h + rms_norm(a, p['norm2'], eps, quant), quant)
      x = rms_norm(a, p['norm3'], eps, quant)
      if sparse:
        y, count = moe(p['moe'], bias, x, cfg, quant, fault)
      else:
        y = swiglu(x, p['mlp']['gate'], p['mlp']['up'], p['mlp']['down'],
                   quant)
        count = None
      return nn.stored(a + rms_norm(y, p['norm4'], eps, quant), quant), count

    h, count = layer(h, p, bias)
    if sparse:
      counts.append(count)
  h = rms_norm(h, params['final_norm'], eps, quant)
  s = tokens.shape[0]
  chunk = min(cfg.get('reference_loss_chunk', s), s)
  if s % chunk:
    raise ValueError(f'{s} positions do not divide into chunks of {chunk}')
  labels = jnp.roll(tokens, -1)
  counted = (jnp.arange(s) < s - 1).astype(jnp.float32)

  @jax.checkpoint
  def one_chunk(args):
    hc, lc, mc = args
    logits = nn.dense(hc, params['head'], quant=quant).astype(jnp.float32)
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
  batch's counts by layer and expert."""
  tokens = inputs['tokens']
  sums, counts = jax.lax.map(
      lambda t: sequence_loss(params, state, t, cfg, quant, fault), tokens)
  positions = tokens.shape[0] * (tokens.shape[1] - 1)
  return jnp.sum(sums) / positions, jnp.sum(counts, axis=0)


def update_state(state, counts, cfg):
  """The bias update from one step's counts ([layers with experts,
  128])."""
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
  """One entry a layer run, then the head: what ``lib/lm_flops.py``
  counts required operations from."""
  out = []
  for _, kind, sparse in layer_plan(cfg):
    entry = {
        'kind': 'decoder', 'hidden': cfg['hidden_size'],
        'heads': cfg['num_attention_heads'],
        'kv_heads': cfg['num_key_value_heads'], 'head_dim': cfg['head_dim'],
        'window': (cfg['sliding_window'] if kind == 'sliding_attention'
                   else None),
    }
    if sparse:
      entry.update(
          router_width=cfg['num_experts_published'],
          experts_per_token=cfg['num_experts_per_tok'],
          experts_held=len(cfg['experts_held']),
          expert_width=cfg['moe_intermediate_size'],
          shared_experts=cfg['num_shared_experts'])
    else:
      entry['dense_width'] = cfg['intermediate_size']
    out.append(entry)
  out.append({'kind': 'head', 'hidden': cfg['hidden_size'],
              'vocab': cfg['vocab_size']})
  return out
