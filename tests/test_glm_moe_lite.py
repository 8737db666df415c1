"""The GLM token policy against its plain reference
(``benchmark/reference/glm_4_7_flash.py``), tiny sizes, float32, seeded
weights, a test a mechanism: the MLA block (and each fault planted in
the reference must make the comparison FAIL), the expert layer's eight
shares against the uncut layer, the MTP module (its loss, and the
embedding's and the head's gradients as the sum of two passes'), the
whole model (remat, kept names), the ``Trainer``, the trainer binary.
Helpers that ``tests/test_afmoe.py`` has are taken from there."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import glm_4_7_flash as ref
from tensor2robot_tpu.layers import glm_moe_lite, moe
from tensor2robot_tpu.research.token_policy.glm_model import (
    GlmTokenPolicyModel)
from test_afmoe import _write_shards, close, to_tree, tokens_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cfg(**over):
  with open(os.path.join(ROOT,
                         'benchmark/configs/glm-4.7-flash-ep8.json')) as f:
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
  keys.update(kwargs)
  return GlmTokenPolicyModel(device_type='cpu', **keys)


def not_close(a, b, tol=1e-3):
  with pytest.raises(AssertionError):
    close(a, b, tol)


def _at(tree, path):
  return functools.reduce(lambda node, key: node[key], path, tree)


def _layer_params(cfg, part, seed, scale=6.0):
  """Layer 1's leaves under ``part/``, the matrices that start small
  scaled up and every norm scale away from one, so that a missing norm
  would show."""
  key = jax.random.PRNGKey(seed)
  out = {}
  for i, (name, v) in enumerate(ref.init_params(key, cfg).items()):
    if not name.startswith(f'layer1/{part}/'):
      continue
    if v.ndim == 1:
      v = v + 0.3 * jax.random.normal(jax.random.fold_in(key, 1000 + i),
                                      v.shape)
    elif name.rsplit('/', 1)[-1] not in ('q_b', 'kv_b'):
      v = v * scale
    out[name[len(f'layer1/{part}/'):]] = v
  return out


# ---------------------------------------------------------------------- MLA

def _attn_kwargs(cfg):
  return dict(num_heads=cfg['num_attention_heads'],
              q_rank=cfg['q_lora_rank'], kv_rank=cfg['kv_lora_rank'],
              nope_dim=cfg['qk_nope_head_dim'],
              rope_dim=cfg['qk_rope_head_dim'], v_dim=cfg['v_head_dim'],
              rope_theta=float(cfg['rope_theta']),
              latent_gain=cfg['latent_gain'])


@pytest.mark.parametrize('fault', [None, 'no_key_rotary', 'no_kv_norm'])
def test_mla_block_matches_reference_and_not_a_faulty_one(fault):
  cfg = tiny_cfg()
  params = _layer_params(cfg, 'attn', 3)
  key = jax.random.PRNGKey(4)
  x = jax.random.normal(key, (2, cfg['sequence_length'], cfg['hidden_size']))
  weight = jax.random.normal(jax.random.fold_in(key, 1), x.shape)
  module = glm_moe_lite.MLA(eps=cfg['rms_norm_eps'], **_attn_kwargs(cfg))

  def program(p, x):
    return jnp.sum(module.apply({'params': p}, x) * weight)

  def reference(p, x):
    with jax.default_matmul_precision('highest'):
      out = jax.vmap(lambda row: ref.mla(p, row, cfg, None, fault))(x)
    return jnp.sum(out * weight), out

  (_, want), want_grads = jax.value_and_grad(reference, (0, 1),
                                             has_aux=True)(params, x)
  got = module.apply({'params': params}, x)
  if fault is not None:
    not_close(got, want)
    return
  close(got, want)
  # The module's own start has the reference's leaves.
  made = module.init(jax.random.PRNGKey(0), x)['params']
  assert {k: v.shape for k, v in made.items()} == {
      k: v.shape for k, v in params.items()}
  got_grads = jax.grad(program, (0, 1))(params, x)
  close(got_grads[1], want_grads[1], 1e-4)
  for name in params:
    assert float(jnp.abs(want_grads[0][name]).max()) > 0, name
    close(got_grads[0][name], want_grads[0][name], 1e-4)


def test_rotary_pairs_are_interleaved_and_the_shared_key_is_one():
  cfg = tiny_cfg()
  rot, theta = cfg['qk_rope_head_dim'], float(cfg['rope_theta'])
  x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 3, rot))
  got = glm_moe_lite.rope_interleaved(x, theta)
  close(got[0], ref.rope(x[0], theta))
  # Position 0 is not turned: the pairs' first members, then the second.
  close(got[0, 0], jnp.concatenate([x[0, 0, :, 0::2], x[0, 0, :, 1::2]], -1))
  # A rotation: lengths stay, and a product of two turned vectors knows
  # only how far apart they are.
  close(jnp.sum(got ** 2, -1), jnp.sum(x ** 2, -1))
  same = jnp.broadcast_to(x[:, :1], x.shape)
  turned = glm_moe_lite.rope_interleaved(same, theta)[0, :, 0]
  products = turned @ turned.T
  close(jnp.diagonal(products, 3), jnp.full((13,), products[0, 3]), 1e-4)


# ------------------------------------------------------------- expert layer

def _layer_for(cfg, held):
  return moe.ExpertLayer(
      num_experts=cfg['num_experts_published'],
      experts_per_token=cfg['num_experts_per_tok'],
      expert_width=cfg['moe_intermediate_size'], experts_held=tuple(held),
      route_norm=cfg['norm_topk_prob'],
      route_scale=cfg['routed_scaling_factor'],
      load_balance_coeff=cfg['load_balance_coeff'], shared_expert=True)


def test_eight_shares_add_up_to_the_uncut_layer():
  """Sixteen experts scored, two held a rank: the eight ranks' partial
  sums, the shared expert (which every rank computes alike) counted
  once, are the uncut reference's layer; no routed row is computed on
  two ranks and none is dropped."""
  whole = tiny_cfg(num_experts_published=16, experts_held=list(range(16)),
                   n_routed_experts=16, num_experts_per_tok=4)
  p = ref._nest(_layer_params(whole, 'moe', 5), '')
  key = jax.random.PRNGKey(6)
  tokens = 2 * whole['sequence_length']
  x = jax.random.normal(key, (tokens, whole['hidden_size']))
  bias = 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (16,))
  with jax.default_matmul_precision('highest'):
    want, counts = ref.moe(p, bias, x, whole, None, None)
    shared = ref.swiglu(x, p['shared']['gate'], p['shared']['up'],
                        p['shared']['down'], None)
  total, routed = shared, 0
  for rank in range(8):
    held = (2 * rank, 2 * rank + 1)
    variables = {'params': {
        'router': p['router'], 'shared': p['shared'],
        'experts': {k: v[np.asarray(held)]
                    for k, v in p['experts'].items()}},
                 moe.MOE_STATE: {'bias': bias,
                                 'counts': jnp.zeros((16,), jnp.int32)}}
    out, stats = _layer_for(whole, held).apply(variables, x, False)
    assert int(stats['rows_dropped']) == 0
    assert int(stats['rows_routed']) == int(counts[np.asarray(held)].sum())
    total, routed = total + (out - shared), routed + int(stats['rows_routed'])
  assert routed == tokens * 4 == int(counts.sum())
  close(total, want, 5e-5)


# ------------------------------------------------------------ the whole model

def _reference_steps(cfg, params, batches):
  """Plain Adam on the reference, the expert biases carried."""
  opt = ref.OPTIMIZER
  state = ref.init_state(cfg)
  zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
  first, second = zeros, zeros
  out = []
  with jax.default_matmul_precision('highest'):
    for count, tokens in enumerate(batches):
      inputs = {'tokens': jnp.asarray(tokens)}
      (value, counts), grads = jax.value_and_grad(ref.loss, has_aux=True)(
          params, state, inputs, cfg)
      main, mtp, _ = ref.losses(params, state, inputs, cfg)
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
      out.append({'loss': float(value), 'main': float(main),
                  'mtp': float(mtp), 'grads': grads, 'counts': counts,
                  'params': params, 'state': state})
  return out


def _program(model, variables, tokens):
  def program(p):
    out, new = model.inference_network_fn(
        {**variables, 'params': p}, {'tokens': tokens}, None, 'train')
    return out['loss'], (out, new)

  return program


def test_model_loss_and_gradients_match_reference():
  cfg = tiny_cfg()
  params = ref.init_params(jax.random.PRNGKey(11), cfg)
  tokens = tokens_for(cfg, 1).astype(np.int32)  # as the device holds them
  model = model_for(cfg)
  variables = model.init_variables(jax.random.PRNGKey(0), {'tokens': tokens})
  tree = to_tree(params, ref.program_path, cfg)
  assert (jax.tree_util.tree_structure(tree) ==
          jax.tree_util.tree_structure(dict(variables['params'])))
  # The program's own start is the reference's, leaf by leaf in kind and
  # in size: what is a constant there is the same constant here, and the
  # three deviations (``init_std``, ``embed_std``, ``latent_gain``) land on
  # the same leaves.
  sized = tiny_cfg(vocab_size=512, hidden_size=128, q_lora_rank=64,
                   kv_lora_rank=64)
  big = model_for(sized).init_variables(
      jax.random.PRNGKey(0), {'tokens': tokens})['params']
  for name, leaf in ref.init_params(jax.random.PRNGKey(11), sized).items():
    mine = _at(big, ref.program_path(name, sized))
    assert mine.shape == leaf.shape, name
    if leaf.ndim == 1:
      np.testing.assert_array_equal(np.asarray(mine), np.asarray(leaf), name)
    else:
      np.testing.assert_allclose(float(jnp.std(mine)), float(jnp.std(leaf)),
                                 rtol=0.2, err_msg=name)

  (loss, (out, new)), grads = jax.value_and_grad(
      _program(model, variables, tokens), has_aux=True)(tree)
  want = _reference_steps(cfg, params, [tokens])[0]
  assert abs(float(loss) - want['loss']) < 2e-5 * want['loss']
  assert abs(int(out['glm/loss_main_e6']) - 1e6 * want['main']) < 30
  assert abs(int(out['glm/loss_mtp_e6']) - 1e6 * want['mtp']) < 30
  for name in params:
    assert float(jnp.abs(want['grads'][name]).max()) > 0, name
    close(_at(grads, ref.program_path(name, cfg)), want['grads'][name], 2e-4)

  # Three expert layers of the rehearsal's three + one: layers 1 and 2 and
  # the MTP module's, which the reference's state names ``layer3``.
  names = sorted(ref.init_state(cfg))
  assert names == ['layer1/moe/bias', 'layer2/moe/bias', 'layer3/moe/bias']
  assert ref.program_state_path(names[-1], cfg) == ('mtp', 'layer', 'moe',
                                                    'bias')
  for row, name in enumerate(names):
    node = _at(new[moe.MOE_STATE], ref.program_state_path(name, cfg)[:-1])
    np.testing.assert_array_equal(np.asarray(node['counts']),
                                  np.asarray(want['counts'][row]))
    close(node['bias'], want['state'][name], 1e-6)
  assert int(out['moe/rows_dropped']) == 0
  held = np.asarray(cfg['experts_held'])
  assert int(out['moe/rows_routed']) == int(
      np.asarray(want['counts'])[:, held].sum())
  assert int(out['moe/tokens']) == tokens.size * len(names)


def test_mtp_loss_and_the_two_passes_gradients():
  """The MTP module's loss is the reference's; the embedding and the
  head are one leaf each and take the sum of the two passes' gradients:
  the loss is linear in ``mtp_loss_weight``, so the passes come apart by
  the weight."""
  cfg = tiny_cfg()
  params = ref.init_params(jax.random.PRNGKey(12), cfg)
  tokens = tokens_for(cfg, 2).astype(np.int32)
  tree = to_tree(params, ref.program_path, cfg)
  grads, values = {}, {}
  for weight in (0.0, 1.0, cfg['mtp_loss_weight']):
    model = model_for(cfg, mtp_loss_weight=weight)
    variables = model.init_variables(jax.random.PRNGKey(0),
                                     {'tokens': tokens})
    (values[weight], _), grads[weight] = jax.value_and_grad(
        _program(model, variables, tokens), has_aux=True)(tree)

  inputs, state = {'tokens': jnp.asarray(tokens)}, ref.init_state(cfg)
  with jax.default_matmul_precision('highest'):
    main, mtp, _ = ref.losses(params, state, inputs, cfg)
    want_main = jax.grad(lambda p: ref.losses(p, state, inputs, cfg)[0])(
        params)
    want_mtp = jax.grad(lambda p: ref.losses(p, state, inputs, cfg)[1])(
        params)
  assert abs(float(values[0.0]) - float(main)) < 2e-5 * float(main)
  assert abs(float(values[1.0] - values[0.0]) - float(mtp)) < (
      5e-5 * float(mtp))
  # Untrained, both passes are near the uniform guess.
  assert abs(float(mtp) - np.log(cfg['vocab_size'])) < 1.0
  w = cfg['mtp_loss_weight']
  assert 'head' in tree and tree['head'].shape == (
      cfg['hidden_size'], cfg['vocab_size'])
  for name in ('embed', 'head'):
    first = grads[0.0][name]
    second = grads[1.0][name] - first
    assert float(jnp.abs(first).max()) > 0 and float(
        jnp.abs(second).max()) > 0
    close(first, want_main[name], 2e-4)
    close(second, want_mtp[name], 2e-4)
    close(grads[w][name], first + w * second, 1e-5)
  # The main pass's gradient does not reach the MTP module.
  for leaf in jax.tree_util.tree_leaves(grads[0.0]['mtp']):
    assert float(jnp.abs(leaf).max()) == 0


@pytest.mark.parametrize('fault', ['drop_routed', 'no_mtp_loss',
                                   'mtp_same_token'])
def test_model_is_not_a_faulty_reference(fault):
  cfg = tiny_cfg()
  # The routed experts' matrices and the MTP projection scaled up: at 0.02
  # and these widths what they add is under the comparison's own tolerance.
  params = {name: v * (30.0 if '/moe/experts/' in name or name == 'mtp/proj'
                       else 1.0)
            for name, v in ref.init_params(jax.random.PRNGKey(13),
                                           cfg).items()}
  tokens = tokens_for(cfg, 3).astype(np.int32)
  model = model_for(cfg)
  variables = model.init_variables(jax.random.PRNGKey(0), {'tokens': tokens})
  loss, _ = _program(model, variables, tokens)(
      to_tree(params, ref.program_path, cfg))
  with jax.default_matmul_precision('highest'):
    sound, _ = ref.loss(params, ref.init_state(cfg),
                        {'tokens': jnp.asarray(tokens)}, cfg)
    faulty, _ = ref.loss(params, ref.init_state(cfg),
                         {'tokens': jnp.asarray(tokens)}, cfg, None, fault)
  assert abs(float(loss) - float(sound)) < 2e-5 * float(sound)
  assert abs(float(loss) - float(faulty)) > 1e-4 * float(sound)


def test_remat_keeps_named_values_and_runs_forward_kernel_once(monkeypatch):
  """The trunk under its remat policy is the trunk under no remat at
  all, to the last bit, and its gradient runs the attention forward
  kernel and the top-k once a decoder layer, the MTP module's among
  them: what the policy names is kept."""
  from tensor2robot_tpu.ops import flash_attention as fa

  # The streamed kernels, as at the published sizes (the tiny ones would
  # stage their keys whole: other kernels, with no names).
  monkeypatch.setattr(fa, '_MAX_STAGED_KV_BYTES', 1)
  cfg = tiny_cfg()
  layers = cfg['num_hidden_layers'] + 1
  sparse = layers - cfg['first_k_dense_replace']
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
  for name in glm_moe_lite.KEPT_NAMES:
    assert f'name={name}' in text, name
  assert text.count(' top_k[') == sparse
  monkeypatch.setattr(glm_moe_lite, 'KEPT_IN_LAYER', None)
  nothing_kept = traced()[2]
  assert nothing_kept.count('name=flash_attention_fwd') == 2 * layers
  assert nothing_kept.count(' top_k[') == 2 * sparse
  monkeypatch.setattr(glm_moe_lite.nn, 'remat', lambda cls, **kwargs: cls)
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

def test_two_trainer_steps_match_reference_and_count(tmp_path):
  """``train_eval_model`` from record shards: each step's loss, the
  parameters after two steps, the carried biases and the registry's
  counters (the expert layers' and the two losses') are the
  reference's."""
  from benchmark.lib import token_traffic
  from tensor2robot_tpu.data.input_generators import (
      NativeRecordInputGenerator)
  from tensor2robot_tpu.observability import metrics
  from tensor2robot_tpu.train.trainer import TrainerCallback, train_eval_model

  cfg = tiny_cfg()
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

  before = {prefix: metrics.snapshot(prefix) for prefix in ('moe/', 'glm/')}
  train_eval_model(
      model=model_for(cfg, init_from_checkpoint_fn=inject), model_dir='',
      train_input_generator=Kept(NativeRecordInputGenerator(
          file_patterns=pattern, batch_size=cfg['batch_size'],
          shuffle_buffer_size=4, seed=5)),
      max_train_steps=2, eval_interval_steps=0, save_interval_steps=0,
      log_interval_steps=0, seed=1, callbacks=[Watch()])
  moved = {**metrics.delta(before['moe/'], 'moe/'),
           **metrics.delta(before['glm/'], 'glm/')}
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
  expert_layers = len(want[0]['state'])
  assert moved['moe/rows_routed'] == routed
  assert moved['moe/rows_dropped'] == 0
  assert moved['moe/tokens'] == 2 * batches[0].size * expert_layers
  assert moved['moe/rows_computed'] >= routed
  # Published one dispatch behind, like the counts: both steps' by now.
  assert abs(moved['glm/loss_main_e6'] - 1e6 * sum(
      s['main'] for s in want)) < 1e6 * 1e-4
  assert abs(moved['glm/loss_mtp_e6'] - 1e6 * sum(
      s['mtp'] for s in want)) < 1e6 * 1e-4


def test_trainer_binary_trains_the_token_policy_from_its_gin(tmp_path):
  """``bin/run_t2r_trainer.py`` on the research config, cut to a tiny
  size by bindings: records in, a loss and a run report out."""
  from tensor2robot_tpu.bin import run_t2r_trainer

  cfg = tiny_cfg()
  pattern, _, _ = _write_shards(tmp_path, cfg)
  config = os.path.join(ROOT, 'tensor2robot_tpu/research/token_policy/'
                        'configs/train_glm_token_policy.gin')
  tiny = {k: cfg[k] for k in (
      'sequence_length', 'vocab_size', 'hidden_size', 'num_hidden_layers',
      'num_attention_heads', 'q_lora_rank', 'kv_lora_rank',
      'qk_nope_head_dim', 'qk_rope_head_dim', 'v_head_dim',
      'intermediate_size', 'moe_intermediate_size', 'num_experts_per_tok',
      'loss_chunk')}
  tiny.update(n_routed_experts=cfg['num_experts_published'],
              experts_held=tuple(cfg['experts_held']), device_type="'cpu'")
  bindings = [f'GlmTokenPolicyModel.{k} = {v}' for k, v in tiny.items()]
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
  assert metrics['glm/loss_mtp_e6'] > 0
