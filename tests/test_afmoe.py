"""The afmoe token policy against its plain reference
(``benchmark/reference/trinity_mini.py``), tiny sizes, float32, seeded
weights: attention blocks, the expert layer whole and as a share, the
whole model through the ``Trainer``, the record feed and the counters."""

import functools
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import trinity_mini as ref
from tensor2robot_tpu.layers import afmoe, moe
from tensor2robot_tpu.research.token_policy.afmoe_model import (
    AfmoeTokenPolicyModel)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cfg(**over):
  with open(os.path.join(ROOT, 'benchmark/configs/trinity-mini-ep8.json')) as f:
    cfg = json.load(f)
  rehearsal = cfg.pop('rehearsal')
  rehearsal.pop('program')
  cfg.update(rehearsal)
  cfg.update(over)
  return cfg


def model_for(cfg, **kwargs):
  kept = [cfg['layer_types'][i] for i in cfg['layers_kept']]
  keys = ('sequence_length', 'vocab_size', 'hidden_size', 'num_dense_layers',
          'num_attention_heads', 'num_key_value_heads', 'head_dim',
          'intermediate_size', 'moe_intermediate_size', 'num_experts_per_tok',
          'sliding_window', 'rope_theta', 'rms_norm_eps', 'route_norm',
          'route_scale', 'load_balance_coeff', 'mup_enabled', 'learning_rate',
          'loss_chunk', 'init_std')
  return AfmoeTokenPolicyModel(
      layer_types=kept, num_experts=cfg['num_experts_published'],
      experts_held=cfg['experts_held'], device_type='cpu',
      **{k: cfg[k] for k in keys}, **kwargs)


def to_tree(flat, path_fn, cfg):
  tree = {}
  for name, value in flat.items():
    node = tree
    *parents, leaf = path_fn(name, cfg)
    for part in parents:
      node = node.setdefault(part, {})
    node[leaf] = value
  return tree


def tokens_for(cfg, seed=0, batch=None):
  rng = np.random.RandomState(seed)
  return rng.randint(0, cfg['vocab_size'],
                     (batch or cfg['batch_size'], cfg['sequence_length']))


def close(a, b, tol=2e-5):
  a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
  scale = max(float(np.abs(b).max()), 1e-6)
  assert float(np.abs(a - b).max()) <= tol * scale, (
      float(np.abs(a - b).max()), scale)


# ---------------------------------------------------------------- attention

@pytest.mark.parametrize('kind', [afmoe.SLIDING, afmoe.FULL])
def test_attention_block_matches_reference(kind):
  cfg = tiny_cfg()
  key = jax.random.PRNGKey(3)
  params = {k[len('layer0/attn/'):]: v for k, v in
            ref.init_params(key, cfg).items()
            if k.startswith('layer0/attn/')}
  # Scales away from one, so that a missing norm would show.
  params['q_norm'] = params['q_norm'] * 1.3
  params['k_norm'] = params['k_norm'] * 0.7
  params = {k: v * (8.0 if v.ndim == 2 else 1.0) for k, v in params.items()}
  x = jax.random.normal(jax.random.fold_in(key, 1),
                        (2, cfg['sequence_length'], cfg['hidden_size']))
  module = afmoe.Attention(
      cfg['num_attention_heads'], cfg['num_key_value_heads'], cfg['head_dim'],
      kind, cfg['sliding_window'], cfg['rope_theta'], cfg['rms_norm_eps'])
  weight = jax.random.normal(jax.random.fold_in(key, 2), x.shape)

  def program(p, x):
    return jnp.sum(module.apply({'params': p}, x) * weight)

  def reference(p, x):
    with jax.default_matmul_precision('highest'):
      out = jax.vmap(lambda row: ref.attention(p, row, kind, cfg, None,
                                               None))(x)
    return jnp.sum(out * weight)

  close(module.apply({'params': params}, x),
        jax.vmap(lambda row: ref.attention(params, row, kind, cfg, None,
                                           None))(x))
  got = jax.grad(program, (0, 1))(params, x)
  want = jax.grad(reference, (0, 1))(params, x)
  close(got[1], want[1], 1e-4)
  for name in params:
    close(got[0][name], want[0][name], 1e-4)


# ------------------------------------------------------------- expert layer

def _expert_setup(cfg, seed=5, scale=6.0):
  key = jax.random.PRNGKey(seed)
  flat = {k[len('layer1/moe/'):]: v * (scale if v.ndim >= 2 else 1.0)
          for k, v in ref.init_params(key, cfg).items()
          if k.startswith('layer1/moe/')}
  params = to_tree(flat, lambda n, _: tuple(n.split('/')), cfg)
  x = jax.random.normal(jax.random.fold_in(key, 1),
                        (cfg['sequence_length'] * 2, cfg['hidden_size']))
  bias = 0.05 * jax.random.normal(jax.random.fold_in(key, 2),
                                  (cfg['num_experts_published'],))
  return params, x, bias


def _layer_for(cfg):
  return moe.ExpertLayer(
      num_experts=cfg['num_experts_published'],
      experts_per_token=cfg['num_experts_per_tok'],
      expert_width=cfg['moe_intermediate_size'],
      experts_held=tuple(cfg['experts_held']),
      route_norm=cfg['route_norm'], route_scale=cfg['route_scale'],
      load_balance_coeff=cfg['load_balance_coeff'])


def _apply(layer, params, bias, x, train=False):
  variables = {'params': params, moe.MOE_STATE: {
      'bias': bias, 'counts': jnp.zeros(bias.shape, jnp.int32)}}
  if train:
    (out, stats), new = layer.apply(variables, x, True,
                                    mutable=[moe.MOE_STATE])
    return out, stats, new[moe.MOE_STATE]
  out, stats = layer.apply(variables, x)
  return out, stats, None


@pytest.mark.parametrize('held', [tuple(range(16)), (0, 1, 2, 3), (5, 9)])
def test_expert_layer_matches_reference_whole_and_as_a_share(held):
  cfg = tiny_cfg(experts_held=list(held), num_experts=len(held))
  params, x, bias = _expert_setup(cfg)
  layer = _layer_for(cfg)
  weight = jax.random.normal(jax.random.PRNGKey(9), x.shape)

  def program(p, x):
    return jnp.sum(_apply(layer, p, bias, x)[0] * weight)

  def reference(p, x):
    with jax.default_matmul_precision('highest'):
      return jnp.sum(ref.moe(p, bias, x, cfg, None, None)[0] * weight)

  out, stats, _ = _apply(layer, params, bias, x)
  want, counts = ref.moe(params, bias, x, cfg, None, None)
  close(out, want)
  assert int(stats['rows_routed']) == int(counts[np.asarray(held)].sum())
  assert int(stats['rows_dropped']) == 0
  assert int(stats['tokens']) == x.shape[0]
  got = jax.grad(program, (0, 1))(params, x)
  expect = jax.grad(reference, (0, 1))(params, x)
  close(got[1], expect[1], 1e-4)
  for a, b in zip(jax.tree_util.tree_leaves(got[0]),
                  jax.tree_util.tree_leaves(expect[0])):
    close(a, b, 1e-4)


def test_eight_shares_add_up_to_the_uncut_layer():
  """Each share's routed part, plus the shared expert once, is the
  whole layer of the uncut reference."""
  whole = tiny_cfg(experts_held=list(range(16)), num_experts=16)
  params, x, bias = _expert_setup(whole)
  want, _ = ref.moe(params, bias, x, whole, None, None)
  shared = ref.swiglu(x, params['shared']['gate'], params['shared']['up'],
                      params['shared']['down'], None)
  total = shared
  for share in range(8):
    held = (2 * share, 2 * share + 1)
    cfg = tiny_cfg(experts_held=list(held), num_experts=2)
    part = dict(params, experts={k: v[np.asarray(held)]
                                 for k, v in params['experts'].items()})
    out, stats, _ = _apply(_layer_for(cfg), part, bias, x)
    assert int(stats['rows_dropped']) == 0
    total = total + (out - shared)
  close(total, want, 5e-5)


def test_no_row_dropped_when_every_token_chooses_held_experts():
  cfg = tiny_cfg()            # holds 0-3 of 16, four chosen a token
  params, x, bias = _expert_setup(cfg)
  bias = jnp.where(jnp.arange(bias.shape[0]) < 4, 10.0, 0.0)
  out, stats, _ = _apply(_layer_for(cfg), params, bias, x)
  want, counts = ref.moe(params, bias, x, cfg, None, None)
  assert int(stats['rows_routed']) == x.shape[0] * 4   # the worst case
  assert np.all(np.asarray(counts[:4]) == x.shape[0])
  assert int(stats['rows_dropped']) == 0
  # The buffer's top rung was taken, and it is the worst case.
  assert int(stats['rows_room']) == x.shape[0] * 4 == moe.ladder(
      x.shape[0], 4, 4, 16)[-1]
  close(out, want)


def _routed_exactly(cfg, live, dtype):
  """Parameters and tokens of which exactly ``live`` (token, choice)
  pairs choose a held expert: the first three hidden dimensions say
  which kind a token is (all four choices held; one held and three
  absent; all absent) and the router's first three rows answer them far
  above what the other dimensions add."""
  params, x, _ = _expert_setup(cfg)
  tokens, experts = x.shape[0], cfg['num_experts_published']
  all_held, one_held = divmod(live, 4)
  assert all_held + one_held <= tokens
  kind = np.full((tokens,), 2)
  kind[:all_held] = 0
  kind[all_held:all_held + one_held] = 1
  kind = np.random.RandomState(live).permutation(kind)
  x = x.at[:, :3].set(jnp.asarray(np.eye(3)[kind], x.dtype))
  answers = np.full((3, experts), -3.0, np.float32)
  answers[0, [0, 1, 2, 3]] = 3.0
  answers[1, [0, 4, 5, 6]] = 3.0
  answers[2, [4, 5, 6, 7]] = 3.0
  router = (params['router'] / 6.0).at[:3].set(jnp.asarray(answers))
  return dict(params, router=router), x.astype(dtype)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('live,room', [
    (50, 256), (255, 256), (256, 256), (257, 512), (400, 512), (512, 512)])
def test_laddered_buffer_equals_the_worst_case_buffer(live, room, dtype,
                                                      monkeypatch):
  """Whatever rung the routed rows take (inside one, at its edge, one row
  past it, the worst case), the layer under remat and grad is the layer
  with the worst-case buffer alone: the expert and shared leaves' and the
  input's gradients to the last bit, the rest within float32 summation
  order."""
  cfg = tiny_cfg()            # 128 tokens, holds 0-3 of 16, four chosen
  params, x = _routed_exactly(cfg, live, dtype)
  tokens = x.shape[0]
  assert moe.ladder(tokens, 4, 4, 16) == (256, 512)
  experts = cfg['num_experts_published']
  state = {'bias': jnp.zeros((experts,)),
           'counts': jnp.zeros((experts,), jnp.int32)}
  weight = jax.random.normal(jax.random.PRNGKey(9), x.shape)
  layer = _layer_for(cfg).clone(dtype=dtype)

  def run():
    @jax.jit
    def program(p, x):
      @jax.checkpoint
      def loss(p, x):
        out, stats = layer.apply({'params': p, moe.MOE_STATE: state}, x)
        return jnp.sum(out.astype(jnp.float32) * weight), (out, stats)
      return jax.value_and_grad(loss, (0, 1), has_aux=True)(p, x)
    return program(params, x)

  (loss, (out, stats)), grads = run()
  assert int(stats['rows_routed']) == live
  assert int(stats['rows_room']) == room
  assert int(stats['rows_dropped']) == 0
  monkeypatch.setattr(moe, 'ladder', lambda t, k, held, n: (t * min(k, held),))
  (want_loss, (want, want_stats)), want_grads = run()
  assert int(want_stats['rows_room']) == 512
  assert int(want_stats['rows_routed']) == live
  close(loss, want_loss, 1e-6)
  close(out, want, 1e-6)
  for part in ('experts', 'shared'):
    for name, leaf in grads[0][part].items():
      np.testing.assert_array_equal(np.asarray(leaf),
                                    np.asarray(want_grads[0][part][name]))
  np.testing.assert_array_equal(np.asarray(grads[1]),
                                np.asarray(want_grads[1]))
  close(grads[0]['router'], want_grads[0]['router'], 1e-6)
  assert float(jnp.abs(grads[0]['experts']['gate']).max()) > 0


def test_ladder_is_twice_the_balance_and_the_worst_case():
  # The cell's layer: 8,192 tokens, 8 chosen, 16 of 128 held.
  assert moe.ladder(8192, 8, 16, 128) == (16384, 65536)
  # A share of fewer experts than a token chooses.
  assert moe.ladder(128, 4, 2, 16) == (128, 256)
  # A layer that holds every expert, or half of them, has the one size.
  assert moe.ladder(128, 4, 16, 16) == (512,)
  assert moe.ladder(128, 4, 8, 16) == (512,)
  assert moe.ladder(100, 3, 2, 7) == (172, 200)


def test_bias_update_follows_the_counts():
  cfg = tiny_cfg()
  params, x, bias = _expert_setup(cfg)
  _, _, state = _apply(_layer_for(cfg), params, bias, x, train=True)
  _, counts = ref.moe(params, bias, x, cfg, None, None)
  np.testing.assert_array_equal(np.asarray(state['counts']),
                                np.asarray(counts))
  want = ref.update_state({'layer1/moe/bias': bias}, counts[None], cfg)
  close(state['bias'], want['layer1/moe/bias'], 1e-6)
  assert abs(float(jnp.mean(state['bias']))) < 1e-7
  # An expert chosen more often than the mean loses bias, one chosen
  # less often gains it.
  moved = np.asarray(state['bias'] - (bias - jnp.mean(bias)))
  over = np.asarray(counts) > np.mean(np.asarray(counts))
  assert np.all(moved[over] < 0) and np.all(moved[~over] >= 0)


# ------------------------------------------------------------ the whole model

def _reference_steps(cfg, params, batches):
  """Plain Adam on the reference, the expert bias carried."""
  opt = ref.OPTIMIZER
  state = ref.init_state(cfg)
  zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
  first, second = zeros, zeros
  out = []
  with jax.default_matmul_precision('highest'):
    for count, tokens in enumerate(batches):
      (value, counts), grads = jax.value_and_grad(ref.loss, has_aux=True)(
          params, state, {'tokens': jnp.asarray(tokens)}, cfg)
      t = count + 1
      first = jax.tree_util.tree_map(
          lambda m, g: opt['b1'] * m + (1 - opt['b1']) * g, first, grads)
      second = jax.tree_util.tree_map(
          lambda v, g: opt['b2'] * v + (1 - opt['b2']) * g * g, second, grads)
      params = jax.tree_util.tree_map(
          lambda p, m, v: p - opt['learning_rate'] * (
              m / (1 - opt['b1'] ** t)) / (
                  jnp.sqrt(v / (1 - opt['b2'] ** t)) + opt['eps']),
          params, first, second)
      state = ref.update_state(state, counts, cfg)
      out.append({'loss': float(value), 'grads': grads, 'counts': counts,
                  'params': params, 'state': state})
  return out


def test_model_loss_and_gradients_match_reference():
  cfg = tiny_cfg()
  params = ref.init_params(jax.random.PRNGKey(11), cfg)
  tokens = tokens_for(cfg, 1).astype(np.int32)  # as the device holds them
  model = model_for(cfg)
  variables = model.init_variables(jax.random.PRNGKey(0), {'tokens': tokens})
  tree = to_tree(params, ref.program_path, cfg)
  assert (jax.tree_util.tree_structure(tree) ==
          jax.tree_util.tree_structure(dict(variables['params'])))

  def program(p):
    out, new = model.inference_network_fn(
        {**variables, 'params': p}, {'tokens': tokens}, None, 'train')
    return out['loss'], (out, new)

  (loss, (out, new)), grads = jax.value_and_grad(program, has_aux=True)(tree)
  want = _reference_steps(cfg, params, [tokens])[0]
  assert abs(float(loss) - want['loss']) < 2e-5 * want['loss']
  got = {name: functools.reduce(lambda n, k: n[k],
                                ref.program_path(name, cfg), grads)
         for name in params}
  for name in params:
    close(got[name], want['grads'][name], 2e-4)
  for row, name in enumerate(sorted(ref.init_state(cfg))):
    node = functools.reduce(lambda n, k: n[k],
                            ref.program_state_path(name, cfg)[:-1],
                            new[moe.MOE_STATE])
    np.testing.assert_array_equal(np.asarray(node['counts']),
                                  np.asarray(want['counts'][row]))
    close(node['bias'], want['state'][name], 1e-6)
  assert int(out['moe/rows_dropped']) == 0
  held = np.asarray(cfg['experts_held'])
  assert int(out['moe/rows_routed']) == int(
      np.asarray(want['counts'])[:, held].sum())
  assert int(out['moe/tokens']) == tokens.size * len(ref.init_state(cfg))


# ------------------------------------------------------- the vocabulary loss

LOSS_B, LOSS_S, LOSS_D, LOSS_V = 2, 64, 32, 200


def _loss_inputs(dtype):
  keys = jax.random.split(jax.random.PRNGKey(36), 3)
  h = jax.random.normal(keys[0], (LOSS_B, LOSS_S, LOSS_D)).astype(dtype)
  head = 0.3 * jax.random.normal(keys[1], (LOSS_D, LOSS_V))
  tokens = jax.random.randint(keys[2], (LOSS_B, LOSS_S), 0, LOSS_V)
  return h, head, tokens


def _plain_loss(h, head, tokens, shift):
  """The unchunked float32 log-softmax loss, differentiated by JAX."""
  logits = jnp.einsum('bsd,dv->bsv', h.astype(jnp.float32), head,
                      precision='highest')
  picked = jnp.take_along_axis(
      jax.nn.log_softmax(logits), jnp.roll(tokens, -shift, axis=1)[..., None],
      axis=-1)[..., 0]
  return -jnp.mean(picked[:, :LOSS_S - shift])


def _loss_before_pr36(h, head, tokens, chunk, dtype, shift):
  """``next_token_loss`` as it was before the gradient moved into the
  forward pass (each chunk under ``jax.checkpoint``, differentiated by
  JAX): what the loss's value and the bfloat16 gradients' distance from
  the float32 ones are pinned against."""
  b, s, d = h.shape
  rows = b * s
  chunk = min(chunk, rows)
  weight = head.astype(dtype)

  @jax.checkpoint
  def one_chunk(args):
    hc, lc, mc = args
    logits = jnp.matmul(hc, weight, preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, lc[:, None], axis=-1)[:, 0]
    return -jnp.sum(jnp.where(mc, picked, 0.0))

  parts = jax.lax.map(one_chunk, (
      h.reshape(rows // chunk, chunk, d),
      jnp.roll(tokens, -shift, axis=1).reshape(rows // chunk, chunk),
      jnp.broadcast_to(jnp.arange(s) < s - shift,
                       (b, s)).reshape(rows // chunk, chunk)))
  return jnp.sum(parts) / (b * (s - shift))


def _distance(a, b):
  a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
  return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('chunk', [LOSS_B * LOSS_S, 16],
                         ids=['one_chunk', 'eight_chunks'])
@pytest.mark.parametrize('shift', [1, 2])
def test_loss_gradient_from_the_forward_pass_matches_jax_grad(shift, chunk,
                                                              dtype):
  h, head, tokens = _loss_inputs(dtype)

  # A scalar cotangent that is not 1: the MTP pass's 0.3.
  def now(h, head):
    return 0.3 * afmoe.next_token_loss(h, head, tokens, chunk, dtype,
                                       shift=shift)

  def before_pr36(h, head):
    return 0.3 * _loss_before_pr36(h, head, tokens, chunk, dtype, shift)

  def plain(h, head):
    return 0.3 * _plain_loss(h, head, tokens, shift)

  steps = [jax.jit(jax.value_and_grad(fn, argnums=(0, 1)))
           for fn in (now, before_pr36, plain)]
  (loss, (dh, dhead)), (before, (dh_before, dhead_before)), (
      want, (dh_want, dhead_want)) = [step(h, head) for step in steps]
  evaluate = jax.jit(now)
  evaluated = evaluate(h, head)

  assert dh.dtype == h.dtype and dhead.dtype == head.dtype
  assert float(loss) == float(evaluated)
  # Positions that are not counted take no gradient at all.
  assert not np.asarray(dh, np.float32)[:, LOSS_S - shift:].any()
  assert np.asarray(dh, np.float32)[:, :LOSS_S - shift].all(axis=-1).all()
  if dtype == jnp.float32:
    assert float(loss) == float(before)       # bit for bit
    assert abs(float(loss) - float(want)) < 1e-6 * float(want)
    close(dh, dh_want, 1e-5)
    close(dhead, dhead_want, 1e-5)
    return
  assert abs(float(loss) - float(before)) < 1e-6 * float(before)
  # bfloat16, distance from the float32 gradients as measured here before
  # the change (PR 36): dh 0.0027-0.0028, dhead 0.0018-0.0019 in one chunk
  # and 0.0039-0.0040 in eight (each chunk's float32 product was cast
  # before it was added). Now dhead 0.0016-0.0017 however chunked (the sum
  # is float32) and dh 0.0036: the logits' cotangent enters both products
  # rounded to bfloat16, and 0.3 x dh is rounded once more. The CPU fed the
  # old form's products the float32 cotangent; the chip's default precision
  # rounds it too, and there (PR 36, 8,192 x 25,024 and x 19,360) dh read
  # 0.00287 in both forms at a cotangent of 1 and 0.00383 before, 0.00323
  # now at 0.3; dhead 0.0025-0.0036 before, 0.0001 now.
  assert _distance(dhead, dhead_want) <= _distance(dhead_before, dhead_want)
  assert _distance(dhead, dhead_want) < 0.0019
  assert _distance(dh, dh_want) < 1.4 * _distance(dh_before, dh_want)
  assert _distance(dh, dh_want) < 0.0040


def _vocabulary_wide(jaxpr, found):
  """Every ``dot_general`` and ``add_any`` of ``jaxpr`` and what it
  holds that has a dimension of ``LOSS_V``, as ``(primitive, output)``."""
  for eqn in jaxpr.eqns:
    shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)
              if hasattr(v.aval, 'shape')]
    if (eqn.primitive.name in ('dot_general', 'add_any') and
        any(LOSS_V in shape for shape in shapes)):
      found.append((eqn.primitive.name, eqn.outvars[0].aval))
    for sub in jax.core.jaxprs_in_params(eqn.params):
      _vocabulary_wide(sub, found)
  return found


@pytest.mark.parametrize('shift', [1, 2])
def test_loss_step_holds_three_vocabulary_wide_products(shift):
  # The guard against the fourth product (the logits computed again on the
  # way back) and against a head's gradient summed over chunks in bfloat16.
  h, head, tokens = _loss_inputs(jnp.bfloat16)

  def loss(h, head):
    return afmoe.next_token_loss(h, head, tokens, 16, jnp.bfloat16, shift)

  alone = _vocabulary_wide(jax.make_jaxpr(loss)(h, head).jaxpr, [])
  assert [name for name, _ in alone] == ['dot_general']
  step = _vocabulary_wide(
      jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(h, head).jaxpr, [])
  products = [out for name, out in step if name == 'dot_general']
  assert len(products) == 3, step
  assert all(out.dtype == jnp.float32 for out in products)
  assert sorted(out.shape for out in products) == [
      (16, LOSS_D), (16, LOSS_V), (LOSS_D, LOSS_V)]
  # The one sum over chunks there is is the loop's float32 carry.
  assert not [out for name, out in step if name == 'add_any']
  with pytest.raises(ValueError, match='do not divide'):
    afmoe.next_token_loss(h, head, tokens, 48, jnp.bfloat16, shift)


@pytest.mark.parametrize('sparse', [False, True], ids=['dense', 'sparse'])
@pytest.mark.parametrize('kind', [afmoe.SLIDING, afmoe.FULL])
def test_remat_keeps_named_values_and_runs_forward_kernel_once(
    kind, sparse, monkeypatch):
  """A trunk under its remat policy is the trunk under no remat at all,
  to the last bit, and its gradient runs the attention forward kernel
  once a layer, not twice: the kernel's output and log-sum-exp are kept
  by name. Every name the policy keeps is a name some value has."""
  layers = 2
  cfg = tiny_cfg(layer_types=[kind] * layers, layers_kept=list(range(layers)),
                 num_dense_layers=0 if sparse else layers)
  tokens = tokens_for(cfg, 2).astype(np.int32)

  def traced():
    """(loss and gradients as a function, its parameters, its jaxpr)."""
    model = model_for(cfg)
    variables = model.init_variables(jax.random.PRNGKey(4),
                                     {'tokens': tokens})

    def program(p):
      out, _ = model.inference_network_fn(
          {**variables, 'params': p}, {'tokens': tokens}, None, 'train')
      return out['loss']

    fn, params = jax.value_and_grad(program), variables['params']
    return fn, params, str(jax.make_jaxpr(fn)(params))

  fn, params, text = traced()
  loss, grads = fn(params)
  assert text.count('name=flash_attention_fwd') == layers
  assert text.count('name=flash_attention_bwd') == layers
  assert 'name=flash_attention_dq' not in text
  for name in afmoe.KEPT_NAMES:
    assert (f'name={name}' in text) == (sparse or name[:4] != 'moe_'), name
  # The experts are chosen once a layer: the way back takes the kept
  # choice, the one the kept output was laid out by.
  assert text.count(' top_k[') == (layers if sparse else 0)
  monkeypatch.setattr(afmoe, 'KEPT_IN_LAYER', None)
  nothing_kept = traced()[2]
  assert nothing_kept.count('name=flash_attention_fwd') == 2 * layers
  assert nothing_kept.count(' top_k[') == (2 * layers if sparse else 0)
  monkeypatch.setattr(afmoe.nn, 'remat', lambda cls, **kwargs: cls)
  fn, params, text = traced()
  assert text.count('name=flash_attention_fwd') == layers
  want_loss, want_grads = fn(params)
  assert float(loss) == float(want_loss)
  got, want = (jax.tree_util.tree_leaves_with_path(g)
               for g in (grads, want_grads))
  assert len(got) == len(want)
  for (path, a), (want_path, b) in zip(got, want):
    assert path == want_path
    assert float(jnp.abs(b).max()) > 0, path
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------- records, the Trainer and the counters

def _write_shards(tmp_path, cfg, seed=7, examples=8, shards=2):
  from benchmark.lib import token_traffic

  mix = {'num_examples': examples, 'num_shards': shards,
         'sequence_length': cfg['sequence_length'],
         'tokens': {'feature': 'tokens', 'zipf_exponent': 1.0}}
  pattern, index_of, _ = token_traffic.write_shards(
      str(tmp_path / 'shards'), seed, mix, cfg['vocab_size'])
  drawn = [token_traffic.draw(seed, i, cfg['sequence_length'],
                              cfg['vocab_size'], 1.0)
           for i in range(examples)]
  return pattern, index_of, drawn


def test_int64_tokens_from_spec_to_record_to_batch(tmp_path):
  """The model's int64 ``tokens`` feature, written by the benchmark's
  own codec, comes back through ``NativeRecordInputGenerator`` intact."""
  from benchmark.lib import token_traffic
  from tensor2robot_tpu.data.input_generators import (
      NativeRecordInputGenerator)
  from tensor2robot_tpu.train.trainer import (
      provide_input_generator_with_model_information)

  cfg = tiny_cfg()
  pattern, index_of, drawn = _write_shards(tmp_path, cfg)
  model = model_for(cfg)
  in_spec = model.preprocessor.get_in_feature_specification('train')
  assert in_spec['tokens'].dtype == np.int64
  assert tuple(in_spec['tokens'].shape) == (cfg['sequence_length'],)
  generator = NativeRecordInputGenerator(
      file_patterns=pattern, batch_size=4, shuffle_buffer_size=4, seed=3)
  provide_input_generator_with_model_information(generator, model, 'train')
  features, labels = next(generator.create_iterator('train'))
  assert labels is None or not labels
  tokens = np.asarray(features['tokens'])
  assert tokens.dtype == np.int64 and tokens.shape == (
      4, cfg['sequence_length'])
  for row in tokens:
    index = index_of[token_traffic.digest(row)]
    np.testing.assert_array_equal(row, drawn[index])


def test_two_trainer_steps_match_reference_and_count(tmp_path):
  """``train_eval_model`` from record shards: each step's loss, the
  parameters after two steps, the carried bias and the registry's
  counters are the reference's."""
  from benchmark.lib import token_traffic
  from tensor2robot_tpu.data.input_generators import (
      NativeRecordInputGenerator)
  from tensor2robot_tpu.observability import metrics
  from tensor2robot_tpu.train.trainer import TrainerCallback, train_eval_model

  cfg = tiny_cfg()
  pattern, index_of, drawn = _write_shards(tmp_path, cfg)
  key = jax.random.PRNGKey(21)
  params = ref.init_params(key, cfg)

  def inject(program_params, variables):
    del program_params   # copies: the trainer donates its state
    return to_tree({k: jnp.copy(v) for k, v in params.items()},
                   ref.program_path, cfg), variables

  seen = {'losses': [], 'batches': []}

  class Watch(TrainerCallback):

    def after_step(self, trainer, step, scalars):
      seen['losses'].append(float(scalars['loss']))
      seen['state'] = jax.device_get(trainer.state)

  class Kept:
    """The generator, with the batches it hands out kept."""

    def __init__(self, generator):
      self._generator = generator

    def create_iterator(self, mode):
      for features, labels in self._generator.create_iterator(mode):
        seen['batches'].append(np.asarray(features['tokens']))
        yield features, labels

    def __getattr__(self, name):
      return getattr(self._generator, name)

  before = metrics.snapshot('moe/')
  train_eval_model(
      model=model_for(cfg, init_from_checkpoint_fn=inject), model_dir='',
      train_input_generator=Kept(NativeRecordInputGenerator(
          file_patterns=pattern, batch_size=cfg['batch_size'],
          shuffle_buffer_size=4, seed=5)),
      max_train_steps=2, eval_interval_steps=0, save_interval_steps=0,
      log_interval_steps=0, seed=1, callbacks=[Watch()])
  moved = metrics.delta(before, 'moe/')
  batches = seen['batches'][:2]
  for batch in batches:
    for row in batch:
      assert token_traffic.digest(row) in index_of
  want = _reference_steps(cfg, params, batches)
  for got, step in zip(seen['losses'], want):
    assert abs(got - step['loss']) < 5e-5 * step['loss']
  state = seen['state']
  for name in params:
    leaf = functools.reduce(lambda n, k: n[k], ref.program_path(name, cfg),
                            state.params)
    moved_by = np.asarray(want[-1]['params'][name] - params[name])
    # Adam's first steps move every weight by about the learning rate: by
    # g / (|g| + eps) at the first. Where a gradient of the reference is
    # under float32's rounding of its leaf's largest (and not nothing
    # itself) that ratio follows the program's rounding, not its
    # arithmetic, so those elements are left out: three of a leaf's 2,048
    # at most. ``layer0/attn/q[13, 20]``, a first gradient of 8.4e-9 where
    # the leaf's largest is 0.037 and eps 1e-8, read 0.1% with nothing
    # kept across the layer's remat, 2.2% with the kernel's output and
    # log-sum-exp kept (and with the experts' names besides), 0.5% with
    # q, k, v and the gate kept too; every other element under 0.3%.
    sound = np.all([(g == 0) | (np.abs(g) >= 1e-6 * np.abs(g).max())
                    for g in (np.asarray(step['grads'][name])
                              for step in want)], axis=0)
    assert sound.mean() >= 0.998, name
    close(np.where(sound, np.asarray(leaf) - np.asarray(params[name]), 0),
          np.where(sound, moved_by, 0), 2e-2)
  for name, bias in want[-1]['state'].items():
    node = functools.reduce(lambda n, k: n[k],
                            ref.program_state_path(name, cfg)[:-1],
                            state.model_state[moe.MOE_STATE])
    close(node['bias'], bias, 1e-5)
  held = np.asarray(cfg['experts_held'])
  routed = sum(int(np.asarray(s['counts'])[:, held].sum()) for s in want)
  assert moved['moe/rows_routed'] == routed
  assert moved['moe/rows_dropped'] == 0
  assert moved['moe/tokens'] == 2 * batches[0].size * len(ref.init_state(cfg))
  assert moved['moe/rows_computed'] >= routed
  # Each expert layer took, each step, the lowest rung that held its rows.
  rungs = np.asarray(moe.ladder(batches[0].size, cfg['num_experts_per_tok'],
                                len(held), cfg['num_experts_published']))
  live = np.concatenate([np.asarray(s['counts'])[:, held].sum(axis=1)
                         for s in want])
  assert moved['moe/rows_room'] == int(
      rungs[np.searchsorted(rungs, live)].sum())
  assert moved['moe/rows_room'] < live.size * rungs[-1]


def test_trainer_binary_trains_the_token_policy_from_its_gin(tmp_path):
  """``bin/run_t2r_trainer.py`` on the research config, cut to a tiny
  size by bindings: records in, a loss and a run report out."""
  from tensor2robot_tpu.bin import run_t2r_trainer

  cfg = tiny_cfg()
  pattern, _, _ = _write_shards(tmp_path, cfg)
  config = os.path.join(ROOT, 'tensor2robot_tpu/research/token_policy/'
                        'configs/train_afmoe_token_policy.gin')
  tiny = {
      'sequence_length': cfg['sequence_length'], 'vocab_size': 96,
      'hidden_size': 32, 'num_attention_heads': 4, 'num_key_value_heads': 2,
      'head_dim': 8, 'intermediate_size': 48, 'moe_intermediate_size': 16,
      'num_experts': 16, 'experts_held': (0, 1, 2, 3),
      'num_experts_per_tok': 4, 'sliding_window': 16, 'loss_chunk': 32,
      'device_type': "'cpu'"}
  bindings = [f'AfmoeTokenPolicyModel.{k} = {v}' for k, v in tiny.items()]
  bindings += [
      f"NativeRecordInputGenerator.file_patterns = '{pattern}'",
      'NativeRecordInputGenerator.batch_size = 2',
      f"train_eval_model.model_dir = '{tmp_path}/model'",
      'train_eval_model.max_train_steps = 3',
      'train_eval_model.save_interval_steps = 0',
      'train_eval_model.log_interval_steps = 0']
  args = ['--gin_configs', config, '--no-handle_preemption']
  for binding in bindings:
    args += ['--gin_bindings', binding]
  metrics = run_t2r_trainer.main(args)
  assert np.isfinite(metrics['loss']) and metrics['moe/rows_dropped'] == 0
