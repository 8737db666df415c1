"""AOT-compiles the main path's kernels for a DESCRIBED TPU v5e.

No chip is attached: ``topologies.get_topology_desc`` hands the TPU
compiler installed in this sandbox a described v5e, and it raises what
the chip's compiler would raise. That is the one check interpret mode
cannot make — a lane block Mosaic rejects, a kernel over its scoped
VMEM, a primitive with no TPU lowering (on-chip-measurement guide §2.3).
Each case pins the outcome settled for it: the kernel lowers with at
least one ``tpu_custom_call`` at the real width, or ``is_supported``
says no for the Mosaic target AND an explicit request is refused loudly
(``kernels/refused`` + WARNING) rather than silently running the XLA
reference. A compile that passes here is not a chip run.
"""

import logging
import os
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from tensor2robot_tpu.observability import metrics
from tensor2robot_tpu.ops import _pallas_dispatch as dispatch
from tensor2robot_tpu.ops import (conv_s2d, flash_attention, photometric,
                                  pool)

pytestmark = pytest.mark.kernels

# QT-Opt Grasping44 at its published width, batch 32.
IMAGE = (32, 472, 472, 3)
CONV1_W = (6, 6, 3, 64)
POOL1 = (32, 236, 236, 64)  # after conv1 (6x6 stride 2)
POOL2 = (32, 79, 79, 64)  # after pool1 (3x3 stride 3 SAME)


@pytest.fixture(scope='module')
def chip():
  """``shape, dtype -> ShapeDtypeStruct`` placed on one described v5e
  chip; the module is skipped where the topology cannot be described."""
  os.environ.setdefault('TPU_LOG_DIR', 'disabled')
  try:
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
  except Exception as e:  # pylint: disable=broad-except
    pytest.skip(f'cannot describe a TPU v5e here: {e!r}')
  # A compile for a described chip is written to the persistent cache
  # but can never be read back without the chip (every later run would
  # warn and recompile): keep these out of it.
  was_enabled = jax.config.jax_enable_compilation_cache
  jax.config.update('jax_enable_compilation_cache', False)
  compilation_cache.reset_cache()
  sharding = SingleDeviceSharding(topo.devices[0])
  try:
    with mock.patch.object(dispatch, 'use_interpret', lambda: False):
      yield lambda shape, dtype: jax.ShapeDtypeStruct(
          shape, dtype, sharding=sharding)
  finally:
    jax.config.update('jax_enable_compilation_cache', was_enabled)
    compilation_cache.reset_cache()


def _custom_calls(fn, *args) -> int:
  return jax.jit(fn).lower(*args).compile().as_text().count(
      'tpu_custom_call')


def _refused_loudly(caplog, fn, *args) -> None:
  """Tracing ``fn`` counts one refusal, warns, and emits no kernel."""
  before = metrics.counter('kernels/refused').value
  with caplog.at_level(logging.WARNING), dispatch.force_kernels(True):
    text = jax.jit(fn).lower(*args).as_text()
  assert metrics.counter('kernels/refused').value == before + 1
  assert 'was requested but cannot run here' in caplog.text
  assert 'tpu_custom_call' not in text


def _photometric(chip, caplog):
  del caplog
  assert _custom_calls(
      lambda x, b, c: photometric.fused_brightness_contrast(
          x, b, c, interpret=False),
      chip(IMAGE, jnp.float32), chip(IMAGE[:1], jnp.float32),
      chip(IMAGE[:1], jnp.float32)) == 1


def _flash_attention(chip, caplog):
  del caplog
  qkv = chip((2, 4096, 8, 64), jnp.bfloat16)
  assert flash_attention.is_supported(4096, 64, interpret=False)

  def loss(q, k, v):
    out = flash_attention.flash_attention(q, k, v, causal=True)
    return out.astype(jnp.float32).sum()

  # Forward + the dq and dk/dv backward kernels.
  assert _custom_calls(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv) == 3


def _pool_qtopt_refused(chip, caplog):
  window = (3, 3)
  for shape in (POOL1, POOL2):
    for dtype in (jnp.float32, jnp.bfloat16):
      assert not pool.is_supported(shape, window, window, 'SAME', dtype,
                                   interpret=False), (shape, dtype)
  _refused_loudly(
      caplog, lambda x: pool.max_pool(x, window, window, 'SAME'),
      chip(POOL2, jnp.bfloat16))


def _pool_small_lowers(chip, caplog):
  # The Mosaic rules are not vacuous: a map inside them lowers, forward
  # and routed backward.
  del caplog
  shape, window = (2, 40, 40, 64), (2, 2)
  assert pool.is_supported(shape, window, window, 'SAME', jnp.float32,
                           interpret=False)
  with dispatch.force_kernels(True):
    assert _custom_calls(
        jax.grad(lambda x: pool.max_pool(x, window, window, 'SAME').sum()),
        chip(shape, jnp.float32)) == 2


def _conv_s2d_refused(chip, caplog):
  # Nothing lowers for Mosaic, the first-layer width or a small one.
  for shape in (IMAGE, (2, 32, 32, 3)):
    assert not conv_s2d.is_supported(shape, CONV1_W, (2, 2), 'SAME',
                                     interpret=False)
  _refused_loudly(
      caplog, lambda x, w: conv_s2d.conv2d(x, w, (2, 2), 'SAME'),
      chip(IMAGE, jnp.bfloat16), chip(CONV1_W, jnp.bfloat16))


def _attention_kernels(text):
  """Custom calls of the compiled program by attention kernel."""
  import re

  return {name: len(re.findall(
      rf'custom-call\([^\n]*flash_attention_{name}\b', text))
          for name in ('fwd', 'bwd', 'dq', 'dkv')}


def _flash_attention_window_grouped(chip, caplog):
  # Both token policies' attention at their published widths, 8,192
  # tokens of bfloat16 heads of 128: 32 query heads over 4 key/value
  # heads under a 2,048 window and full causal (afmoe), two sequences of
  # 8 over 2 full causal (the CCA latent). The way back is ONE kernel,
  # under the ``vmem_limit_bytes`` it asks for (a head's float32 dk and
  # dv stay in VMEM: 8 MiB, with out blocks as large again).
  del caplog
  for batch, heads, kv_heads, window in ((1, 32, 4, 2048), (1, 32, 4, None),
                                         (2, 8, 2, None)):
    q = chip((batch, 8192, heads, 128), jnp.bfloat16)
    kv = chip((batch, 8192, kv_heads, 128), jnp.bfloat16)

    def loss(q, k, v, window=window):
      out = flash_attention.flash_attention(q, k, v, True, None, None,
                                            window)
      return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert text.count('tpu_custom_call') == 2
    assert _attention_kernels(text) == dict(fwd=1, bwd=1, dq=0, dkv=0)


def _grouped_product_tiles(chip, caplog):
  # ``ragged_dot`` lowers to a grouped-matmul kernel; its tile table has
  # rows / tile + groups - 1 entries, which is where ``layers/moe.py``
  # has its ``GROUPED_ROW_TILE`` from (the rows the product computes are
  # counted from it).
  del caplog
  import re

  from tensor2robot_tpu.layers import moe

  rows, groups = 65536, 16
  text = jax.jit(lambda x, w, g: jax.lax.ragged_dot(
      x, w, g, preferred_element_type=jnp.bfloat16)).lower(
          chip((rows, 2048), jnp.bfloat16),
          chip((groups, 2048, 1024), jnp.bfloat16),
          chip((groups,), jnp.int32)).compile().as_text()
  assert text.count('tpu_custom_call') >= 2
  table = re.search(r'ragged-dot-metadata[.\d]* = \(s32\[\d+\][^,]*, '
                    r's32\[(\d+)\]', text)
  assert table, text[:2000]
  assert int(table.group(1)) == rows // moe.GROUPED_ROW_TILE + groups - 1


def _expert_layer_ladder(chip, caplog):
  # The token policy's expert layer at the cell's shapes under ``grad``:
  # one conditional forward and one backward, a branch a rung of the
  # routed-row buffer, the grouped products in every branch. No rung's
  # buffer is kept between the two (each backward branch computes its
  # own again): the temporaries stay near the single worst-case
  # buffer's, where keeping them read 3.0x (PERF.md, PR 29).
  del caplog
  import re

  from tensor2robot_tpu.layers import moe

  tokens, hidden, published, held, k = 8192, 2048, 128, 16, 8
  layer = moe.ExpertLayer(
      num_experts=published, experts_per_token=k, expert_width=1024,
      experts_held=tuple(range(held)), route_scale=2.826, dtype=jnp.bfloat16)
  x = chip((tokens, hidden), jnp.bfloat16)
  weight = chip((tokens, hidden), jnp.float32)
  variables = jax.tree_util.tree_map(
      lambda leaf: chip(leaf.shape, leaf.dtype),
      jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                     jnp.zeros((tokens, hidden), jnp.bfloat16)))

  def loss(params, x, state, weight):
    out, _ = layer.apply({'params': params, moe.MOE_STATE: state}, x)
    return jnp.sum(out.astype(jnp.float32) * weight)

  def compiled():
    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        variables['params'], x, variables[moe.MOE_STATE], weight).compile()

  rungs = moe.ladder(tokens, k, held, published)
  assert rungs == (16384, 65536)
  laddered = compiled()
  with mock.patch.object(moe, 'ladder', lambda *shapes: rungs[-1:]):
    single = compiled()
  text = laddered.as_text()
  bodies = dict(re.findall(r'^(%[\w.-]+) [^\n]*\{\n(.*?)^\}', text,
                           flags=re.M | re.S))
  conditionals = re.findall(r'conditional\(.*?branch_computations=\{([^}]*)\}',
                            text)
  assert len(conditionals) == 2, len(conditionals)   # forward, backward
  for branches in conditionals:
    names = [name.strip() for name in branches.split(',')]
    assert len(names) == len(rungs)
    for name in names:
      assert 'tpu_custom_call' in bodies[name], name
  assert 'conditional(' not in single.as_text()
  temporaries = laddered.memory_analysis().temp_size_in_bytes
  assert temporaries < 1.25 * single.memory_analysis().temp_size_in_bytes


def _decoder_layer_keeps_attention_residuals(chip, caplog):
  # One afmoe decoder layer at the token cell's widths (8,192 tokens of
  # 2,048, 32 query heads over 4 of 128, bfloat16, a 2,048 window and
  # full) under the trunk's remat and ``grad``: the forward kernel once,
  # not twice. What the names keep (the kernel's output 64 MiB and
  # log-sum-exp 1 MiB, q and the gate 64 MiB each, k and v 8 MiB each)
  # does not raise the layer's temporaries by more than 80 MiB over
  # keeping nothing: their peak is in the dense MLP's way back, after
  # attention's part is spent.
  del caplog
  import flax.linen as nn

  from tensor2robot_tpu.layers import afmoe

  shape = (1, 8192, 2048)
  h, weight = chip(shape, jnp.bfloat16), chip(shape, jnp.float32)

  def compiled(kind, policy):
    layer = nn.remat(afmoe.DecoderLayer, static_argnums=(2,), policy=policy)(
        kind, False, 32, 4, 128, 2048, 10000.0, 1e-5, 6144, None,
        jnp.bfloat16)
    params = jax.tree_util.tree_map(
        lambda leaf: chip(leaf.shape, leaf.dtype),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                       jnp.zeros(shape, jnp.bfloat16), False))['params']

    def loss(params, h, weight):
      out, _ = layer.apply({'params': params}, h, False)
      return jnp.sum(out.astype(jnp.float32) * weight)

    program = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        params, h, weight).compile()
    return (_attention_kernels(program.as_text())['fwd'],
            program.memory_analysis().temp_size_in_bytes)

  for kind in (afmoe.SLIDING, afmoe.FULL):
    kept_calls, kept_bytes = compiled(kind, afmoe.KEPT_IN_LAYER)
    calls, nothing_kept_bytes = compiled(kind, None)
    assert (kept_calls, calls) == (1, 2), kind
    assert kept_bytes <= nothing_kept_bytes + 80 * 2**20, kind


def _compiled_token_step(chip, trunk, cfg):
  """A token policy's whole step compiled for the described chip: the
  trunk's loss at the cell's batch and sequence, its gradient, Adam, the
  state donated."""
  import optax

  tokens = jnp.zeros((cfg['batch_size'], cfg['sequence_length']), jnp.int32)
  optimizer = optax.adam(cfg['learning_rate'])

  def described(tree):
    return jax.tree_util.tree_map(
        lambda leaf: chip(leaf.shape, leaf.dtype), tree)

  state = described(jax.eval_shape(
      lambda key: trunk.init(key, {'tokens': tokens}, False),
      jax.random.PRNGKey(0)))
  params = state.pop('params')
  moments = described(jax.eval_shape(optimizer.init, params))

  def step(params, moments, state, tokens):
    def loss(p):
      out, new = trunk.apply({'params': p, **state}, {'tokens': tokens},
                             True, mutable=list(state))
      return out['loss'], new

    (value, state), grads = jax.value_and_grad(loss, has_aux=True)(params)
    updates, moments = optimizer.update(grads, moments, params)
    return optax.apply_updates(params, updates), moments, state, value

  return jax.jit(step, donate_argnums=(0, 1, 2)).lower(
      params, moments, state, chip(tokens.shape, jnp.int32)).compile()


def _cell_config(name):
  import json

  with open(os.path.join(os.path.dirname(__file__), os.pardir,
                         f'benchmark/configs/{name}.json')) as f:
    return json.load(f)


def _token_step_holds_what_the_layers_keep(chip, caplog):
  # The whole step of ``trinity-mini.train-packed-8k`` (the trunk from
  # the cell's own configuration, loss, gradient, Adam, the state
  # donated): the forward kernel and the one backward kernel once a layer
  # (no ``flash_attention_dq`` / ``_dkv``) and each expert layer's
  # two conditionals, no recomputed forward of either, and arguments +
  # temporaries at or under 13.5 GB of the chip's 16. What the layers'
  # remat keeps lives in the temporaries (12.85 GB in all with
  # ``KEPT_NAMES`` as of PR 31, 11.04 with nothing kept; 12.99 since the
  # loss keeps ``dh`` and a float32 ``dhead``, PR 36), which the chip's
  # ``memory_stats()`` peak does not show: this is the place a further
  # kept name meets its budget. The vocabulary loss is the step's one
  # loop: its gradient comes from the forward pass's logits, so there is
  # no second loop on the way back.
  del caplog
  import re

  from tensor2robot_tpu.layers import afmoe

  cfg = _cell_config('trinity-mini-ep8')
  kinds = tuple(cfg['layer_types'][i] for i in cfg['layers_kept'])
  trunk = afmoe.Trunk(
      vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
      layer_types=kinds, num_dense_layers=cfg['num_dense_layers'],
      num_heads=cfg['num_attention_heads'],
      num_kv_heads=cfg['num_key_value_heads'], head_dim=cfg['head_dim'],
      sliding_window=cfg['sliding_window'],
      rope_theta=float(cfg['rope_theta']), eps=cfg['rms_norm_eps'],
      dense_width=cfg['intermediate_size'],
      expert_kwargs=dict(
          num_experts=cfg['num_experts_published'],
          experts_held=tuple(cfg['experts_held']),
          experts_per_token=cfg['num_experts_per_tok'],
          expert_width=cfg['moe_intermediate_size'],
          route_norm=cfg['route_norm'], route_scale=cfg['route_scale'],
          load_balance_coeff=cfg['load_balance_coeff']),
      mup_enabled=cfg['mup_enabled'], loss_chunk=cfg['loss_chunk'],
      dtype=jnp.bfloat16, init_std=cfg['init_std'])
  program = _compiled_token_step(chip, trunk, cfg)
  text = program.as_text()
  sparse = len(kinds) - cfg['num_dense_layers']
  assert _attention_kernels(text) == dict(
      fwd=len(kinds), bwd=len(kinds), dq=0, dkv=0)
  assert len(re.findall(r' conditional\(', text)) == 2 * sparse
  assert len(re.findall(r' while\(', text)) == 1
  memory = program.memory_analysis()
  assert (memory.argument_size_in_bytes +
          memory.temp_size_in_bytes) <= 13.5e9


def _zaya_step_fits_and_runs_attention_once(chip, caplog):
  # The whole step of ``zaya1-8b.train-packed-8k`` (the trunk from the
  # cell's own configuration at 2 x 8,192 tokens, loss, gradient, Adam,
  # the state donated): the attention forward kernel and the one backward
  # kernel once a layer inside the CCA latent (8 query heads over 2 of
  # 128, no window), no
  # conditional (half the experts held: the routed-row buffer has one
  # rung), and arguments + temporaries at or under 13.5 GB of the chip's
  # 16 (11.15 GB as of PR 32: 8.50 of state, 2.65 of temporaries; 11.63
  # since PR 36: 3.13 of temporaries), and the tied head's loss as the
  # step's one loop.
  del caplog
  from tensor2robot_tpu.layers import zaya

  cfg = _cell_config('zaya1-8b-ep2')
  trunk = zaya.Trunk(
      vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
      num_layers=cfg['num_hidden_layers'],
      num_heads=cfg['num_attention_heads'],
      num_kv_heads=cfg['num_key_value_heads'], head_dim=cfg['head_dim'],
      conv_taps=cfg['cca_time0'],
      rotary_dim=int(cfg['head_dim'] * cfg['partial_rotary_factor']),
      rope_theta=float(cfg['rope_parameters']['hybrid']['rope_theta']),
      eps=cfg['rms_norm_eps'], router_hidden=cfg['router_hidden_size'],
      expert_kwargs=dict(
          num_experts=cfg['num_experts_published'],
          experts_held=tuple(cfg['experts_held']),
          experts_per_token=cfg['num_experts_per_tok'],
          expert_width=cfg['moe_intermediate_size'],
          load_balance_coeff=cfg['load_balance_coeff']),
      branch_scale=cfg['branch_scale_init'],
      router_init_gain=cfg['router_init_gain'], loss_chunk=cfg['loss_chunk'],
      dtype=jnp.bfloat16, init_std=cfg['init_std'])
  program = _compiled_token_step(chip, trunk, cfg)
  text = program.as_text()
  assert cfg['num_hidden_layers'] == 6
  assert _attention_kernels(text) == dict(fwd=6, bwd=6, dq=0, dkv=0)
  assert ' conditional(' not in text
  assert text.count(' while(') == 1
  memory = program.memory_analysis()
  assert (memory.argument_size_in_bytes +
          memory.temp_size_in_bytes) <= 13.5e9


def _flash_attention_two_lane_tiles(chip, caplog):
  # Latent attention's heads at the third token policy's published widths:
  # 8,192 tokens of 20 bfloat16 heads of 256 (192 content + 64 rotary for
  # q and k, 256 of value), full causal. Streamed at 1,024 x 1,024 blocks
  # under the VMEM limits the two calls ask for; the way back is ONE
  # kernel with a head's float32 dk and dv resident (16 MiB, at the
  # budget's edge with their out blocks).
  del caplog
  assert flash_attention.is_supported(8192, 256, interpret=False)
  qkv = chip((1, 8192, 20, 256), jnp.bfloat16)

  def loss(q, k, v):
    out = flash_attention.flash_attention(q, k, v, True, None, None, None)
    return out.astype(jnp.float32).sum()

  text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
      qkv, qkv, qkv).compile().as_text()
  assert text.count('tpu_custom_call') == 2
  assert _attention_kernels(text) == dict(fwd=1, bwd=1, dq=0, dkv=0)


def _glm_step_fits_and_runs_attention_once(chip, caplog):
  # The whole step of ``glm-4.7-flash.train-packed-8k`` (the trunk from
  # the cell's own configuration at 8,192 tokens, both losses, gradient,
  # Adam, the state donated): the attention forward kernel and the ONE
  # backward kernel once a decoder layer at 20 heads of 256 (five layers
  # and the MTP module's: six of each, no ``flash_attention_dq`` /
  # ``_dkv``), each of the five expert layers' two conditionals (8 held of
  # 64: two rungs), one loop for each of the two vocabulary passes, and
  # arguments + temporaries at or under 13.5 GB of the chip's 16 (12.07 GB
  # as of PR 35, 12.18 since PR 36).
  del caplog
  import re

  from tensor2robot_tpu.research.token_policy import glm_model

  cfg = _cell_config('glm-4.7-flash-ep8')
  program = cfg['program']
  model = glm_model.GlmTokenPolicyModel(
      **{k: cfg[k] for k in program['model_keys']},
      **{arg: cfg[k] for arg, k in program['model_renamed'].items()},
      **program['model_kwargs'])
  assert model.compute_dtype == jnp.bfloat16
  compiled = _compiled_token_step(chip, model.create_module(), cfg)
  text = compiled.as_text()
  layers = cfg['num_hidden_layers'] + cfg['num_nextn_predict_layers']
  assert layers == 6
  assert _attention_kernels(text) == dict(fwd=6, bwd=6, dq=0, dkv=0)
  sparse = layers - cfg['first_k_dense_replace']
  assert len(re.findall(r' conditional\(', text)) == 2 * sparse
  assert len(re.findall(r' while\(', text)) == 2
  memory = compiled.memory_analysis()
  assert (memory.argument_size_in_bytes +
          memory.temp_size_in_bytes) <= 13.5e9


@pytest.mark.parametrize('case', [
    _photometric, _flash_attention, _pool_qtopt_refused, _pool_small_lowers,
    _conv_s2d_refused, _flash_attention_window_grouped,
    _grouped_product_tiles, _expert_layer_ladder,
    _decoder_layer_keeps_attention_residuals,
    _token_step_holds_what_the_layers_keep,
    _zaya_step_fits_and_runs_attention_once,
    _flash_attention_two_lane_tiles,
    _glm_step_fits_and_runs_attention_once,
], ids=lambda fn: fn.__name__.lstrip('_'))
def test_compiles_for_described_v5e(case, chip, caplog):
  case(chip, caplog)
