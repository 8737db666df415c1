"""The ZAYA token policy against its plain reference
(``benchmark/reference/zaya1_8b.py``), tiny sizes, float32, seeded
weights, a test a mechanism: the CCA block (and each fault planted in
the reference must make the comparison FAIL), the router with a state
coming in, the expert layer given its scores whole and as a share, the
whole model (tied head, remat), the ``Trainer``, the trainer binary.
Helpers that ``tests/test_afmoe.py`` has are taken from there."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import zaya1_8b as ref
from tensor2robot_tpu.layers import moe, zaya
from tensor2robot_tpu.research.token_policy.zaya_model import (
    ZayaTokenPolicyModel)
from test_afmoe import _write_shards, close, to_tree, tokens_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cfg(**over):
  with open(os.path.join(ROOT, 'benchmark/configs/zaya1-8b-ep2.json')) as f:
    cfg = json.load(f)
  rehearsal = cfg.pop('rehearsal')
  rehearsal.pop('program')
  cfg.update(rehearsal)
  cfg.update(over)
  return cfg


def model_for(cfg, **kwargs):
  program = cfg['program']
  keys = {k: cfg[k] for k in program['model_keys']}
  keys.update({arg: cfg[k] for arg, k in program['model_renamed'].items()})
  return ZayaTokenPolicyModel(
      device_type='cpu',
      rope_theta=cfg['rope_parameters']['hybrid']['rope_theta'],
      **keys, **kwargs)


def not_close(a, b, tol=1e-3):
  with pytest.raises(AssertionError):
    close(a, b, tol)


def _layer_params(cfg, part, seed, scale=6.0):
  """Layer 1's leaves under ``part/``, the matrices that start small
  scaled up and every vector away from its start, so that a missing
  scale, bias or temperature would show."""
  key = jax.random.PRNGKey(seed)
  out = {}
  for i, (name, v) in enumerate(ref.init_params(key, cfg).items()):
    if not name.startswith(f'layer1/{part}/'):
      continue
    if v.ndim == 1:
      v = v + 0.3 * jax.random.normal(jax.random.fold_in(key, 1000 + i),
                                      v.shape)
    elif name.rsplit('/', 1)[-1] not in ('conv0_w', 'conv1_w', 'w1', 'w2',
                                         'w3'):
      v = v * scale
    out[name[len(f'layer1/{part}/'):]] = v
  return out


# ---------------------------------------------------------------------- CCA

def _cca(cfg):
  return zaya.CCA(
      cfg['num_attention_heads'], cfg['num_key_value_heads'],
      cfg['head_dim'], cfg['cca_time0'],
      int(cfg['head_dim'] * cfg['partial_rotary_factor']),
      float(cfg['rope_parameters']['hybrid']['rope_theta']))


@pytest.mark.parametrize('fault', [None, 'no_conv', 'no_value_shift',
                                   'no_qk_mean', 'full_rotary'])
def test_cca_block_matches_reference_and_not_a_faulty_one(fault):
  cfg = tiny_cfg()
  params = _layer_params(cfg, 'attn', 3)
  key = jax.random.PRNGKey(4)
  x = jax.random.normal(key, (2, cfg['sequence_length'], cfg['hidden_size']))
  weight = jax.random.normal(jax.random.fold_in(key, 1), x.shape)
  module = _cca(cfg)

  def program(p, x):
    return jnp.sum(module.apply({'params': p}, x) * weight)

  def reference(p, x):
    with jax.default_matmul_precision('highest'):
      out = jax.vmap(lambda row: ref.attention(p, row, cfg, None, fault))(x)
    return jnp.sum(out * weight), out

  (_, want), want_grads = jax.value_and_grad(reference, (0, 1),
                                             has_aux=True)(params, x)
  got = module.apply({'params': params}, x)
  if fault is not None:
    not_close(got, want)
    return
  close(got, want)
  got_grads = jax.grad(program, (0, 1))(params, x)
  close(got_grads[1], want_grads[1], 1e-4)
  for name in params:
    assert float(jnp.abs(want_grads[0][name]).max()) > 0, name
    close(got_grads[0][name], want_grads[0][name], 1e-4)


# ------------------------------------------------------------------- router

def _router(cfg):
  return zaya.Router(cfg['router_hidden_size'], cfg['num_experts_published'],
                     cfg['rms_norm_eps'], cfg['init_std'],
                     cfg['router_init_gain'])


@pytest.mark.parametrize('fault', [None, 'no_eda'])
def test_router_with_a_state_coming_in_matches_reference(fault):
  cfg = tiny_cfg()
  params = _layer_params(cfg, 'router', 6)
  key = jax.random.PRNGKey(7)
  tokens = 2 * cfg['sequence_length']
  y = jax.random.normal(key, (tokens, cfg['hidden_size']))
  r = jax.random.normal(jax.random.fold_in(key, 1),
                        (tokens, cfg['router_hidden_size']))
  weights = [jax.random.normal(jax.random.fold_in(key, 2 + i), shape)
             for i, shape in enumerate([(tokens, cfg['num_experts_published']),
                                        r.shape])]
  module = _router(cfg)

  def program(p, y, r):
    probs, state = module.apply({'params': p}, y, r)
    return jnp.sum(probs * weights[0]) + jnp.sum(state * weights[1])

  def reference(p, y, r):
    with jax.default_matmul_precision('highest'):
      probs, state = ref.router(p, y, r, cfg, fault)
    return jnp.sum(probs * weights[0]) + jnp.sum(state * weights[1])

  got = module.apply({'params': params}, y, r)
  want = ref.router(params, y, r, cfg, fault)
  if fault is not None:
    not_close(got[0], want[0])
    not_close(got[1], want[1])
    return
  close(got[0], want[0])
  close(got[1], want[1])
  np.testing.assert_allclose(np.asarray(got[0]).sum(-1), 1.0, rtol=1e-5)
  got_grads = jax.grad(program, (0, 1, 2))(params, y, r)
  want_grads = jax.grad(reference, (0, 1, 2))(params, y, r)
  for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                  jax.tree_util.tree_leaves(want_grads)):
    assert float(jnp.abs(b).max()) > 0
    close(a, b, 1e-4)


def test_router_down_projection_size_is_nothing_to_the_first_forward_pass():
  # ``router_down_std`` is a choice of how fast Adam rewrites ``Wd``, not
  # of what the seeded router computes: the norm after it takes its size
  # out, in every layer (the state handed on is as much larger as the
  # next layer's own ``u``).
  cfg = tiny_cfg()
  key = jax.random.PRNGKey(8)
  tokens = 2 * cfg['sequence_length']
  y = jax.random.normal(key, (tokens, cfg['hidden_size']))
  small, large = cfg['init_std'], cfg['router_down_std']
  assert large > 10 * small
  probs, states = {}, {}
  for std in (small, large):
    module = zaya.Router(
        cfg['router_hidden_size'], cfg['num_experts_published'],
        cfg['rms_norm_eps'], cfg['init_std'], cfg['router_init_gain'], std)
    r = jnp.zeros((tokens, cfg['router_hidden_size']))
    for layer in range(2):       # the second gets the first's state
      params = module.init(jax.random.fold_in(key, layer), y, r)['params']
      np.testing.assert_allclose(float(jnp.std(params['down_w'])), std,
                                 rtol=0.1)
      probs[std], r = module.apply({'params': params}, y, r)
    states[std] = r
  close(states[large] * (small / large), states[small], 1e-5)
  close(probs[large], probs[small], 1e-3)
  assert float(jnp.mean(jnp.argmax(probs[large], -1) ==
                        jnp.argmax(probs[small], -1))) > 0.99


# ------------------------------------------------------------- expert layer

def _expert_setup(cfg, seed=5):
  """The experts' matrices, probabilities over all published experts
  from the reference's own router, tokens and a bias."""
  key = jax.random.PRNGKey(seed)
  experts = {k[len('experts/'):]: v
             for k, v in _layer_params(cfg, 'moe', seed).items()}
  tokens = 2 * cfg['sequence_length']
  y = jax.random.normal(jax.random.fold_in(key, 1),
                        (tokens, cfg['hidden_size']))
  r = jax.random.normal(jax.random.fold_in(key, 2),
                        (tokens, cfg['router_hidden_size']))
  probs, _ = ref.router(_layer_params(cfg, 'router', seed), y, r, cfg, None)
  bias = 0.02 * jax.random.normal(jax.random.fold_in(key, 3),
                                  (cfg['num_experts_published'],))
  return experts, probs, y, bias


def _layer_for(cfg):
  return moe.ExpertLayer(
      num_experts=cfg['num_experts_published'],
      experts_per_token=cfg['num_experts_per_tok'],
      expert_width=cfg['moe_intermediate_size'],
      experts_held=tuple(cfg['experts_held']), route_norm=False,
      load_balance_coeff=cfg['load_balance_coeff'], shared_expert=False)


def _apply(layer, experts, bias, y, probs, train=False):
  variables = {'params': {'experts': experts}, moe.MOE_STATE: {
      'bias': bias, 'counts': jnp.zeros(bias.shape, jnp.int32)}}
  if train:
    (out, stats), new = layer.apply(variables, y, True, probs,
                                    mutable=[moe.MOE_STATE])
    return out, stats, new[moe.MOE_STATE]
  out, stats = layer.apply(variables, y, False, probs)
  return out, stats, None


def _share(experts, held):
  return {k: v[np.asarray(held)] for k, v in experts.items()}


@pytest.mark.parametrize('held', [tuple(range(8)), (0, 1, 2, 3), (5, 2)])
def test_expert_layer_given_its_scores_whole_and_as_a_share(held):
  whole = tiny_cfg(experts_held=list(range(8)), num_experts=8)
  experts, probs, y, bias = _expert_setup(whole)
  cfg = tiny_cfg(experts_held=list(held), num_experts=len(held))
  experts = _share(experts, held)
  layer = _layer_for(cfg)
  weight = jax.random.normal(jax.random.PRNGKey(9), y.shape)

  def program(e, y, probs):
    return jnp.sum(_apply(layer, e, bias, y, probs)[0] * weight)

  def reference(e, y, probs):
    with jax.default_matmul_precision('highest'):
      return jnp.sum(ref.moe(e, bias, probs, y, cfg, None, None)[0] * weight)

  out, stats, _ = _apply(layer, experts, bias, y, probs)
  want, counts = ref.moe(experts, bias, probs, y, cfg, None, None)
  close(out, want)
  assert int(stats['rows_routed']) == int(counts[np.asarray(held)].sum())
  assert int(stats['rows_dropped']) == 0
  assert int(stats['tokens']) == y.shape[0]
  # One expert a token: its weight is the probability it was chosen by.
  chosen = np.argmax(np.asarray(probs + bias), axis=-1)
  assert int(stats['weight_e6']) == round(1e6 * float(np.mean(
      np.asarray(probs)[np.arange(len(chosen)), chosen])))
  # The layer holds no router and no shared expert of its own.
  assert set(layer.init(jax.random.PRNGKey(0), y, False, probs)['params']
             ) == {'experts'}
  got = jax.grad(program, (0, 1, 2))(experts, y, probs)
  expect = jax.grad(reference, (0, 1, 2))(experts, y, probs)
  for a, b in zip(jax.tree_util.tree_leaves(got),
                  jax.tree_util.tree_leaves(expect)):
    assert float(jnp.abs(b).max()) > 0
    close(a, b, 1e-4)


def test_two_shares_add_up_to_the_uncut_layer():
  """Experts 0-3 and 4-7 of the rehearsal's 8 (0-7 and 8-15 of the
  model's 16): the two chips' partial sums are the uncut reference's
  layer; nothing is computed on both."""
  whole = tiny_cfg(experts_held=list(range(8)), num_experts=8)
  experts, probs, y, bias = _expert_setup(whole)
  want, counts = ref.moe(experts, bias, probs, y, whole, None, None)
  total, routed = 0.0, 0
  for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
    cfg = tiny_cfg(experts_held=list(held), num_experts=len(held))
    out, stats, _ = _apply(_layer_for(cfg), _share(experts, held), bias, y,
                           probs)
    assert int(stats['rows_dropped']) == 0
    # Half the experts held: one rung, the worst case, no conditional.
    assert int(stats['rows_room']) == y.shape[0]
    total, routed = total + out, routed + int(stats['rows_routed'])
  assert routed == y.shape[0] == int(counts.sum())
  close(total, want, 5e-5)


def test_no_row_dropped_when_every_token_chooses_a_held_expert():
  cfg = tiny_cfg()                      # holds 0-3 of 8, one chosen a token
  experts, probs, y, bias = _expert_setup(cfg)
  bias = jnp.where(jnp.arange(bias.shape[0]) < 4, 10.0, 0.0)
  out, stats, state = _apply(_layer_for(cfg), experts, bias, y, probs,
                             train=True)
  want, counts = ref.moe(experts, bias, probs, y, cfg, None, None)
  assert int(stats['rows_routed']) == y.shape[0]       # the worst case
  assert int(np.asarray(counts[:4]).sum()) == y.shape[0]
  assert int(stats['rows_dropped']) == 0
  assert int(stats['rows_room']) == y.shape[0] == moe.ladder(
      y.shape[0], 1, 4, 8)[-1]
  close(out, want)
  np.testing.assert_array_equal(np.asarray(state['counts']),
                                np.asarray(counts))
  close(state['bias'], ref.update_state({'layer0/moe/bias': bias},
                                        counts[None], cfg)['layer0/moe/bias'],
        1e-6)


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
          lambda p, m, v: p - cfg['learning_rate'] * (
              m / (1 - opt['b1'] ** t)) / (
                  jnp.sqrt(v / (1 - opt['b2'] ** t)) + opt['eps']),
          params, first, second)
      state = ref.update_state(state, counts, cfg)
      out.append({'loss': float(value), 'grads': grads, 'counts': counts,
                  'params': params, 'state': state})
  return out


def _at(tree, path):
  return functools.reduce(lambda node, key: node[key], path, tree)


def test_model_loss_and_gradients_match_reference():
  cfg = tiny_cfg()
  params = ref.init_params(jax.random.PRNGKey(11), cfg)
  tokens = tokens_for(cfg, 1).astype(np.int32)  # as the device holds them
  model = model_for(cfg)
  variables = model.init_variables(jax.random.PRNGKey(0), {'tokens': tokens})
  tree = to_tree(params, ref.program_path, cfg)
  assert (jax.tree_util.tree_structure(tree) ==
          jax.tree_util.tree_structure(dict(variables['params'])))
  # The program's own start is the reference's, leaf by leaf in kind:
  # what is a constant there is the same constant here.
  for name, leaf in params.items():
    mine = _at(variables['params'], ref.program_path(name, cfg))
    assert mine.shape == leaf.shape, name
    if leaf.ndim == 1:
      np.testing.assert_array_equal(np.asarray(mine), np.asarray(leaf), name)

  def program(p):
    out, new = model.inference_network_fn(
        {**variables, 'params': p}, {'tokens': tokens}, None, 'train')
    return out['loss'], (out, new)

  (loss, (out, new)), grads = jax.value_and_grad(program, has_aux=True)(tree)
  want = _reference_steps(cfg, params, [tokens])[0]
  assert abs(float(loss) - want['loss']) < 2e-5 * want['loss']
  for name in params:
    close(_at(grads, ref.program_path(name, cfg)), want['grads'][name], 2e-4)
  # The tied embedding: one leaf, no head, and its gradient (held to the
  # reference's above) is the sum of both uses: rows of ids that no
  # position holds are moved, by the head alone.
  assert 'head' not in tree
  fed = np.zeros(cfg['vocab_size'], bool)
  fed[tokens.reshape(-1)] = True
  assert (~fed).any()
  assert np.abs(np.asarray(grads['embed'])[~fed]).max() > 0

  for row, name in enumerate(sorted(ref.init_state(cfg))):
    node = _at(new[moe.MOE_STATE], ref.program_state_path(name, cfg)[:-1])
    np.testing.assert_array_equal(np.asarray(node['counts']),
                                  np.asarray(want['counts'][row]))
    close(node['bias'], want['state'][name], 1e-6)
  assert int(out['moe/rows_dropped']) == 0
  held = np.asarray(cfg['experts_held'])
  assert int(out['moe/rows_routed']) == int(
      np.asarray(want['counts'])[:, held].sum())
  assert int(out['moe/tokens']) == tokens.size * cfg['num_hidden_layers']
  assert 0 < int(out['moe/top1_weight_e6']) <= 1e6 * cfg['num_hidden_layers']


def test_remat_keeps_named_values_and_runs_forward_kernel_once(monkeypatch):
  """The trunk under its remat policy is the trunk under no remat at
  all, to the last bit, and its gradient runs the attention forward
  kernel and the top-1 choice once a layer: what the policy names is
  kept. Every name the policy keeps is a name some value has; a layer
  takes and hands on two streams."""
  cfg = tiny_cfg(num_hidden_layers=2)
  layers = cfg['num_hidden_layers']
  tokens = tokens_for(cfg, 2).astype(np.int32)

  def traced():
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
  for name in zaya.KEPT_NAMES:
    assert f'name={name}' in text, name
  assert text.count(' top_k[') == layers
  monkeypatch.setattr(zaya, 'KEPT_IN_LAYER', None)
  nothing_kept = traced()[2]
  assert nothing_kept.count('name=flash_attention_fwd') == 2 * layers
  assert nothing_kept.count(' top_k[') == 2 * layers
  monkeypatch.setattr(zaya.nn, 'remat', lambda cls, **kwargs: cls)
  fn, params, text = traced()
  assert text.count('name=flash_attention_fwd') == layers
  want_loss, want_grads = fn(params)
  assert float(loss) == float(want_loss)
  got, want = (jax.tree_util.tree_leaves_with_path(g)
               for g in (grads, want_grads))
  assert len(got) == len(want)
  for (path, a), (want_path, b) in zip(got, want):
    assert path == want_path
    # Layer 0's state comes in as zeros: its ``eda`` has nothing to scale.
    assert (float(jnp.abs(b).max()) > 0) != (
        jax.tree_util.keystr(path) == "['layer0']['router']['eda']"), path
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------- records, the Trainer and the counters

def test_two_trainer_steps_match_reference_and_count(tmp_path):
  """``train_eval_model`` from record shards: each step's loss, the
  parameters after two steps, the carried bias and the registry's
  counters are the reference's."""
  from benchmark.lib import token_traffic
  from tensor2robot_tpu.data.input_generators import (
      NativeRecordInputGenerator)
  from tensor2robot_tpu.observability import metrics
  from tensor2robot_tpu.train.trainer import TrainerCallback, train_eval_model

  # At the cell's own 1e-6 a step moves a scale of 1.0 by eight float32
  # roundings: the comparison of the change wants a rate it can resolve.
  cfg = tiny_cfg(learning_rate=1e-4)
  pattern, index_of, _ = _write_shards(tmp_path, cfg)
  params = ref.init_params(jax.random.PRNGKey(21), cfg)

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
    leaf = _at(state.params, ref.program_path(name, cfg))
    moved_by = np.asarray(want[-1]['params'][name] - params[name])
    # Elements whose reference gradient is under float32's rounding of
    # the leaf's largest move by the program's rounding under Adam, not
    # by its arithmetic (tests/test_afmoe.py says how far): left out.
    sound = np.all([(g == 0) | (np.abs(g) >= 1e-6 * np.abs(g).max())
                    for g in (np.asarray(step['grads'][name])
                              for step in want)], axis=0)
    assert sound.mean() >= 0.99, name
    close(np.where(sound, np.asarray(leaf) - np.asarray(params[name]), 0),
          np.where(sound, moved_by, 0), 2e-2)
  for name, bias in want[-1]['state'].items():
    node = _at(state.model_state[moe.MOE_STATE],
               ref.program_state_path(name, cfg)[:-1])
    close(node['bias'], bias, 1e-5)
  held = np.asarray(cfg['experts_held'])
  routed = sum(int(np.asarray(s['counts'])[:, held].sum()) for s in want)
  layers = cfg['num_hidden_layers']
  assert moved['moe/rows_routed'] == routed
  assert moved['moe/rows_dropped'] == 0
  assert moved['moe/tokens'] == 2 * batches[0].size * layers
  assert moved['moe/rows_computed'] >= routed
  # Half the experts held: every layer-step took the one rung there is.
  assert moved['moe/rows_room'] == 2 * batches[0].size * layers
  assert 0 < moved['moe/top1_weight_e6'] <= 2e6 * layers


def test_trainer_binary_trains_the_token_policy_from_its_gin(tmp_path):
  """``bin/run_t2r_trainer.py`` on the research config, cut to a tiny
  size by bindings: records in, a loss and a run report out."""
  from tensor2robot_tpu.bin import run_t2r_trainer

  cfg = tiny_cfg()
  pattern, _, _ = _write_shards(tmp_path, cfg)
  config = os.path.join(ROOT, 'tensor2robot_tpu/research/token_policy/'
                        'configs/train_zaya_token_policy.gin')
  tiny = {
      'sequence_length': cfg['sequence_length'], 'vocab_size': 96,
      'hidden_size': 32, 'num_hidden_layers': 3, 'num_attention_heads': 4,
      'num_key_value_heads': 2, 'head_dim': 8, 'moe_intermediate_size': 16,
      'router_hidden_size': 8, 'num_experts': 8,
      'experts_held': (0, 1, 2, 3), 'loss_chunk': 32,
      'device_type': "'cpu'"}
  bindings = [f'ZayaTokenPolicyModel.{k} = {v}' for k, v in tiny.items()]
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
  assert metrics['moe/top1_weight_e6'] > 0
