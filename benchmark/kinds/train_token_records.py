"""The kind ``train_token_records``: a token model trained from record
shards of packed int64 sequences.

Like ``train_records`` it makes ONE call of the program's
``train_eval_model``, which trains through check steps, warm-up and the
measured window (``benchmark/lib/window.py``), and then follows the same
steps with the plain reference on the very sequences the feed delivered.
What differs: the records (``lib/token_traffic.py``), rows matched to
generated examples exactly (a digest of the ids), the operations
counted (``lib/lm_flops.py``), and the reference's loop, which is this
file's own because it carries a non-gradient state (the expert biases)
that ``lib/check.follow`` has no place for.

Memory: the configuration's state is 16 bytes a parameter and fills most
of the chip, so nothing is held twice. The starting weights are handed
to the trainer and not kept; where the check needs them again (the
parameters' change) they are made again from the key, a leaf at a time.
The reference runs after the program's state is freed, its step donating
its state.

``token_gap`` (how many ids fed differ from the ids generated: exact),
``loss_gap``, ``grad_norm_gap``/``grad_median_gap`` (Adam's first moment
after the first step), ``update_norm_gap``/``update_median_gap`` (the
parameters' change after the last check step) as ``lib/check.compare``
defines them, and ``rows_gap``: the worst share, over check steps and
expert layers, of (token, expert) choices that differ between the
program's per-expert counts and the reference's.

Stand-ins (``--stand-in``, never passed by the driver): the float8
control, the bfloat16 witness and the planted faults ``FAULTS``.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import resource
import time

import numpy as np

from benchmark.kinds.train_records import (
    device_times, first_moment_of, from_program_tree, load_symbol,
    to_program_tree)

__all__ = ['run', 'device_times']

QUANTS = ('fp8',)
WITNESSES = ('bf16',)
FAULTS = ('half_batch', 'unchanged_state', 'no_window', 'drop_routed',
          'unnormalised_route')
STAND_INS = QUANTS + WITNESSES + FAULTS

# The model's constructor arguments that are keys of the configuration.
MODEL_KEYS = (
    'sequence_length', 'vocab_size', 'hidden_size', 'num_dense_layers',
    'num_attention_heads', 'num_key_value_heads', 'head_dim',
    'intermediate_size', 'moe_intermediate_size', 'num_experts_per_tok',
    'sliding_window', 'rope_theta', 'rms_norm_eps', 'route_norm',
    'route_scale', 'load_balance_coeff', 'mup_enabled', 'learning_rate',
    'loss_chunk', 'init_std')
COUNTERS = ('moe/tokens', 'moe/rows_routed', 'moe/rows_computed',
            'moe/rows_dropped')


def _reference_steps(ref, cfg, key, batches, quant=None, fault=None):
  """The reference through ``len(batches)`` Adam steps from the weights
  of ``key``, the expert biases carried. Returns per-step losses and
  counts, norms by leaf of the first gradient, of the first moment after
  the first step and of the parameters' change after the last."""
  import jax
  import jax.numpy as jnp

  opt = ref.OPTIMIZER
  ref_fault = fault if fault in ref.FAULTS else None

  def norms_of(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}

  def step(params, first, second, state, tokens, count):
    inputs = ref.preprocess({'features/tokens': tokens}, None, cfg)
    if fault == 'half_batch':
      inputs = {'tokens': inputs['tokens'][:, :tokens.shape[1] // 2]}
    (value, counts), grads = jax.value_and_grad(ref.loss, has_aux=True)(
        params, state, inputs, cfg, quant, ref_fault)
    t = (count + 1).astype(jnp.float32)
    first = {k: opt['b1'] * first[k] + (1 - opt['b1']) * grads[k]
             for k in grads}
    second = {k: opt['b2'] * second[k] + (1 - opt['b2']) * grads[k] ** 2
              for k in grads}
    c1, c2 = 1 - opt['b1'] ** t, 1 - opt['b2'] ** t
    new = {k: params[k] - opt['learning_rate'] * (first[k] / c1) / (
        jnp.sqrt(second[k] / c2) + opt['eps']) for k in params}
    if fault == 'unchanged_state':
      new = params
    return (new, first, second, ref.update_state(state, counts, cfg), value,
            counts, norms_of(grads), norms_of(first))

  step = jax.jit(step, donate_argnums=(0, 1, 2))
  with jax.default_matmul_precision('highest'):
    params = jax.jit(lambda k: ref.init_params(k, cfg))(key)
    first = jax.tree_util.tree_map(jnp.zeros_like, params)
    second = jax.tree_util.tree_map(jnp.zeros_like, params)
    state = ref.init_state(cfg)
    out = {'losses': [], 'counts': []}
    for count, tokens in enumerate(batches):
      params, first, second, state, value, counts, grad_norms, moment = step(
          params, first, second, state, jnp.asarray(tokens),
          jnp.asarray(count, jnp.int32))
      out['losses'].append(float(value))
      out['counts'].append(np.asarray(counts))
      if count == 0:
        out['first_grad'] = {k: float(v) for k, v in grad_norms.items()}
        out['first_moment'] = {k: float(v) for k, v in moment.items()}
    del first, second
    out['change'] = _change_norms(ref, cfg, key, params)
  return out


def _change_norms(ref, cfg, key, params) -> dict:
  """‖now − start‖ by leaf, the start made again from the key."""
  import jax
  import jax.numpy as jnp

  def gaps(k, now):
    return {name: jnp.sqrt(jnp.sum(jnp.square(
        leaf.astype(jnp.float32) - ref.init_leaf(k, name, cfg))))
            for name, leaf in now.items()}

  return {name: float(v) for name, v in jax.jit(gaps)(key, params).items()}


def _rows_gap(program_counts, reference_counts):
  """Worst share of choices that differ, with where."""
  worst, where = 0.0, ''
  for step, (mine, theirs) in enumerate(zip(program_counts,
                                            reference_counts)):
    mine, theirs = np.asarray(mine, np.int64), np.asarray(theirs, np.int64)
    if mine.shape != theirs.shape:
      return 1.0, f'step{step + 1}'  # shapes differ: every choice
    for layer in range(theirs.shape[0]):
      gap = np.abs(mine[layer] - theirs[layer]).sum() / (
          2.0 * max(int(theirs[layer].sum()), 1))
      if not gap <= worst:
        worst, where = float(gap), f'step{step + 1}/expert_layer{layer}'
  return worst, where


def run(job) -> dict:
  cfg, mix, log = job.cfg, job.mix, job.log
  stand_ins = job.stand_ins
  if set(stand_ins) - set(STAND_INS):
    raise SystemExit(f'--stand-in takes {STAND_INS}')
  group = int(cfg['steps_per_dispatch'])
  batch = int(cfg['batch_size'])
  seq = int(cfg['sequence_length'])
  check_steps = int(cfg['check']['steps'])
  if check_steps % group:
    raise SystemExit('check.steps must be whole dispatches')
  if int(mix['sequence_length']) != seq:
    raise SystemExit('the mix and the configuration disagree on the '
                     'sequence length')
  trainer_seed = job.seed % (2 ** 31 - 1)
  setup = {}

  # ----------------------------------------------------------------- shards
  from benchmark.lib import token_traffic

  t = time.perf_counter()
  pattern, index_of, record_bytes = token_traffic.write_shards(
      os.path.join(job.tmp, 'shards'), job.seed, mix, int(cfg['vocab_size']))
  setup['shards_s'] = time.perf_counter() - t

  # -------------------------------------------------- the program, the chip
  import jax
  import jax.numpy as jnp

  from tensor2robot_tpu.observability import metrics as program_metrics
  from tensor2robot_tpu.train.trainer import train_eval_model
  from tensor2robot_tpu.utils.compilation_cache import (
      enable_compilation_cache)

  from benchmark.lib import check, lm_flops, window

  ref = importlib.import_module(f'benchmark.reference.{cfg["reference"]}')
  setup['imports_s'] = time.perf_counter() - job.t0

  devices = job.chips()
  cache_dir = enable_compilation_cache()

  # --------------------------------------------- weights, from the seed
  t = time.perf_counter()
  key = jax.random.fold_in(jax.random.PRNGKey(trainer_seed), job.seed >> 31)
  names = list(ref.param_shapes(cfg))
  state_names = sorted(ref.init_state(cfg), key=lambda n: int(
      n.split('/')[0][len('layer'):]))

  def inject(params, variables):
    # Handed over, not copied and not kept: the trainer donates its state
    # to the step, and a second copy would not fit beside it.
    mine = to_program_tree(
        ref, cfg, jax.jit(lambda k: ref.init_params(k, cfg))(key))

    def shapes(tree):
      return sorted((jax.tree_util.keystr(path), tuple(leaf.shape))
                    for path, leaf in
                    jax.tree_util.tree_leaves_with_path(tree))

    import flax

    if shapes(mine) != shapes(flax.core.unfreeze(params)):
      raise ValueError('the reference\'s weights do not map onto the '
                       'program\'s parameter tree')
    return mine, variables

  program = cfg['program']
  model = load_symbol(program['model'])(
      init_from_checkpoint_fn=inject,
      layer_types=[cfg['layer_types'][i] for i in cfg['layers_kept']],
      num_experts=cfg['num_experts_published'],
      experts_held=cfg['experts_held'],
      **{k: cfg[k] for k in MODEL_KEYS},
      **program.get('model_kwargs', {}))
  setup['model_s'] = time.perf_counter() - t
  generator = load_symbol(program['input_generator'])(
      file_patterns=pattern, batch_size=batch,
      shuffle_buffer_size=mix['shuffle_buffer_size'], seed=trainer_seed)

  # ------------------------------------------------ one call: the window
  shared = window.Shared(keep_batches=check_steps, group=group)
  captured = {'losses': {}, 'counts': []}

  def on_check(index, trainer, scalars):
    step = index * group
    captured['losses'][step] = float(scalars['loss'])
    state = trainer.state
    moe_state = state.model_state['moe_state']
    captured['counts'].append(np.stack([
        np.asarray(_at(moe_state, ref.program_state_path(name, cfg)[:-1])
                   ['counts']) for name in state_names]))
    if index == 1:
      captured['first_moment'] = check.norms(from_program_tree(
          ref, cfg, names, first_moment_of(state.opt_state)))
    if step == check_steps:
      captured['change'] = _change_norms(
          ref, cfg, key, from_program_tree(ref, cfg, names, state.params))

  tracing = {'dir': None}
  compiles, counters = {}, {}

  def on_window_open():
    compiles['open'] = program_metrics.snapshot('compile/')
    counters['open'] = program_metrics.snapshot('moe/')
    if job.trace:
      tracing['dir'] = os.path.join(job.tmp, 'trace')
      options = jax.profiler.ProfileOptions()
      options.python_tracer_level = 0
      options.host_tracer_level = int(mix.get('host_tracer_level', 0))
      options.enable_hlo_proto = False
      jax.profiler.start_trace(tracing['dir'], profiler_options=options)

  seconds = job.seconds
  if job.trace:
    seconds = min(seconds, float(mix['trace_seconds_max']))
  callback = window.WindowCallback(
      shared, check_dispatches=check_steps // group,
      warmup_dispatches=int(mix['warmup_dispatches']), seconds=seconds,
      examples_per_dispatch=batch * group, on_check=on_check,
      on_window_open=on_window_open, skip_window=bool(stand_ins))
  t = time.perf_counter()
  try:
    train_eval_model(
        model=model, model_dir='',
        train_input_generator=window.TimedGenerator(generator, shared),
        max_train_steps=10 ** 9, eval_interval_steps=0,
        save_interval_steps=0, log_interval_steps=0, seed=trainer_seed,
        callbacks=[callback], steps_per_dispatch=group)
  except StopIteration:
    pass  # how an ended stream leaves Trainer.train
  callback.finish()
  if tracing['dir']:
    jax.profiler.stop_trace()
  compiles['close'] = program_metrics.snapshot('compile/')
  counters['close'] = program_metrics.snapshot('moe/')
  setup['train_call_to_first_dispatch_s'] = (
      (callback.first_dispatch_done or time.perf_counter()) - t)
  setup_s = ((callback.t_open or time.perf_counter()) - job.t0)

  memory_peak = max(
      (d.memory_stats() or {}).get('peak_bytes_in_use', 0)
      for d in devices)
  del model, generator
  gc.collect()

  log('setup ' + ' '.join(f'{k}={v:.2f}' for k, v in setup.items()) +
      f' record_bytes={record_bytes:.0f} cache_dir={cache_dir}')
  log('compile at window open: ' + json.dumps(compiles.get('open')) +
      ' at close: ' + json.dumps(compiles['close']))
  gaps = callback.dispatch_gaps_ms()
  moved = {name: (counters['close'].get(name, 0) -
                  counters.get('open', {}).get(name, 0))
           for name in COUNTERS}
  log(f'batch={batch} sequences of {seq} tokens, steps_per_dispatch={group}; '
      f'window: {len(gaps)} dispatches (a boundary to the next: median '
      f'{np.median(gaps) if gaps else 0:.1f} ms, longest '
      f'{max(gaps, default=0):.1f} ms), {len(shared.feed_ms)} batches fed; '
      f'counters in the window {json.dumps(moved)} in all '
      f'{json.dumps(counters["close"])}; host peak rss '
      f'{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20:.1f}'
      ' GiB')

  # -------------------------------------------------------- the check
  t = time.perf_counter()
  feature = mix['tokens']['feature']
  examples = token_traffic.read_examples(pattern, feature)
  ref_batches, token_gap, unmatched = [], 0, False
  for fed in shared.kept:
    rows = fed[f'features/{feature}']
    found = [index_of.get(token_traffic.digest(row)) for row in rows]
    if any(i is None for i in found):
      # A fed row is no generated example: every id of it counts.
      token_gap += sum(row.size for row, i in zip(rows, found) if i is None)
      unmatched = True
      continue
    ref_batch = np.stack([examples[i] for i in found])
    token_gap += int(np.sum(rows != ref_batch))
    ref_batches.append(ref_batch)
  del examples
  shared.kept.clear()
  limits = cfg['check']['limits']
  loss_steps = sorted(captured['losses'])

  if unmatched:   # nothing for the reference to follow
    compared = {'token_gap': {'value': token_gap,
                              'limit': limits.get('token_gap')}}
    correct, stood, reference = False, {}, None
  else:
    reference = _reference_steps(ref, cfg, key, ref_batches)

    def judge(readings):
      """Each number beside its limit; a number with no limit is printed
      and not compared (PERF.md names those)."""
      compared = check.compare(readings, reference, loss_steps)
      compared['token_gap'] = {'value': token_gap}
      worst, where = _rows_gap(readings['counts'], reference['counts'])
      compared['rows_gap'] = {'value': worst, 'at': where}
      correct = True
      for name, entry in compared.items():
        entry['limit'] = limits.get(name)
        if entry['limit'] is not None:
          correct = correct and bool(entry['value'] <= entry['limit'])
      return compared, correct

    compared, correct = judge(captured)
    stood = {}
    for name in stand_ins:
      stand = _reference_steps(
          ref, cfg, key, ref_batches,
          quant=name if name in QUANTS + WITNESSES else None,
          fault=name if name in FAULTS else None)
      stood[name] = judge({
          'losses': {s: stand['losses'][s - 1] for s in loss_steps},
          'first_moment': stand['first_moment'], 'change': stand['change'],
          'counts': stand['counts']})
    log(f'check: {time.perf_counter() - t:.1f}s, reference losses '
        f'{[round(x, 6) for x in reference["losses"]]}, program losses '
        f'{captured["losses"]}')

  dropped = counters['close'].get('moe/rows_dropped', 0)
  if dropped:
    log(f'moe/rows_dropped is {dropped}: the expert layer lost rows')
    correct = False
  rate = callback.examples_per_s()
  if not (job.rehearse or stand_ins) and rate is None:
    raise SystemExit('the window closed fewer than two dispatches')
  layers = ref.layers(cfg)
  # The whole step's required operations count the rows the window
  # routed, as the grouped product's roofline does: from random weights
  # the held experts lose rows as training goes (PERF.md, section 6).
  rows_per_token = (moved['moe/rows_routed'] / moved['moe/tokens']
                    if moved['moe/tokens'] else None)
  return {
      'end_to_end': {
          'setup_s': setup_s,
          'train_examples_per_s': rate,
      },
      'attempted': len(gaps), 'failed': 0,
      'memory_peak_bytes': int(memory_peak),
      'compared': compared, 'correct': correct, 'stand_ins': stood,
      'trace_dir': tracing['dir'],
      # What the per-layer readers find beside the trace.
      'context': {
          'flops_per_example': lm_flops.train_flops_per_sequence(
              layers, seq, rows_per_token),
          'examples_per_dispatch': batch * group,
          'steps_per_dispatch': group,
          'feed_ms': list(shared.feed_ms), 'dispatch_gaps_ms': gaps,
          'memory_peak_bytes': memory_peak,
          'own_spans': list(shared.spans),
          'attention_flops_per_example':
              lm_flops.attention_train_flops_per_sequence(layers, seq),
          'routed_row_flops': lm_flops.routed_row_train_flops(layers),
          'moe_counters': moved,
          'tokens_per_example': seq,
          'expert_layers': sum(1 for l in layers if 'expert_width' in l),
          'route_shapes': {
              'tokens': batch * seq, 'k': int(cfg['num_experts_per_tok']),
              'experts': int(cfg['num_experts_published']),
              'room': batch * seq * min(int(cfg['num_experts_per_tok']),
                                        len(cfg['experts_held']))},
      },
  }


def _at(tree, path):
  for part in path:
    tree = tree[part]
  return tree
