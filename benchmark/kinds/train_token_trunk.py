"""The kind ``train_token_trunk``: a decoder trunk of a token policy
trained from record shards of packed int64 sequences, whichever trunk
it is.

The set-up, the window and the check are ``train_token_records``'s (ONE
call of the program's ``train_eval_model`` through check steps, warm-up
and the measured window; then the same steps with the plain reference
on the very sequences the feed delivered, its loop carrying the expert
biases), and its helpers are imported from there and from
``train_records``. What that kind fixes for one model this one reads
from the configuration:

* the model's constructor arguments: ``program.model_keys`` (keys of the
  configuration passed under their own names), ``program.model_renamed``
  (argument: key) and ``program.model_kwargs``;
* the module that counts required operations from the reference's
  ``layers(cfg)``: ``flops`` (the three totals of ``lib/lm_flops.py``);
* the planted faults: ``half_batch``, ``unchanged_state`` and the
  reference's own ``FAULTS``.

The program's model class is looked up before anything is made, so a
checkout whose program lacks it fails at once.

``token_gap``, ``loss_gap``, ``grad_norm_gap``/``grad_median_gap``,
``update_norm_gap``/``update_median_gap`` and ``rows_gap`` are what
``train_token_records`` says they are, but that ``loss_gap`` and
``rows_gap`` are the worst of the first ``check.compared_steps`` steps
(default: all ``check.steps``; the log gives every step's). Where one
expert is chosen a token, the optimizer's first steps move the choice of
whole layers on either side of a rounding; the first step is taken from
the seeded weights on both sides, and the parameters' change
(``update_*``) is still read after ``check.steps``.
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
from benchmark.kinds.train_token_records import (
    QUANTS, WITNESSES, _at, _change_norms, _reference_steps, _rows_gap)

__all__ = ['run', 'device_times']

SHARED_FAULTS = ('half_batch', 'unchanged_state')


def run(job) -> dict:
  cfg, mix, log = job.cfg, job.mix, job.log
  stand_ins = job.stand_ins
  program = cfg['program']
  model_cls = load_symbol(program['model'])   # a parent without it: at once
  ref = importlib.import_module(f'benchmark.reference.{cfg["reference"]}')
  flops = importlib.import_module(cfg['flops'])
  faults = SHARED_FAULTS + tuple(ref.FAULTS)
  if set(stand_ins) - set(QUANTS + WITNESSES + faults):
    raise SystemExit(f'--stand-in takes {QUANTS + WITNESSES + faults}')
  group = int(cfg['steps_per_dispatch'])
  batch = int(cfg['batch_size'])
  seq = int(cfg['sequence_length'])
  check_steps = int(cfg['check']['steps'])
  compared_steps = int(cfg['check'].get('compared_steps', check_steps))
  if check_steps % group or compared_steps % group:
    raise SystemExit('check.steps and check.compared_steps must be whole '
                     'dispatches')
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

  from tensor2robot_tpu.observability import metrics as program_metrics
  from tensor2robot_tpu.train.trainer import train_eval_model
  from tensor2robot_tpu.utils.compilation_cache import (
      enable_compilation_cache)

  from benchmark.lib import check, window

  setup['imports_s'] = time.perf_counter() - job.t0

  devices = job.chips()
  cache_dir = enable_compilation_cache()

  # --------------------------------------------- weights, from the seed
  t = time.perf_counter()
  key = jax.random.fold_in(jax.random.PRNGKey(trainer_seed), job.seed >> 31)
  names = list(ref.param_shapes(cfg))
  state_names = sorted(ref.init_state(cfg), key=lambda n: int(
      n.split('/')[0][len('layer'):]))

  def start(k):
    return ref.init_params(k, cfg)

  make_weights = jax.jit(start)

  def inject(params, variables):
    # Handed over, not copied and not kept: the trainer donates its state
    # to the step, and a second copy would not fit beside it.
    mine = to_program_tree(ref, cfg, make_weights(key))

    def shapes(tree):
      return sorted((jax.tree_util.keystr(path), tuple(leaf.shape))
                    for path, leaf in
                    jax.tree_util.tree_leaves_with_path(tree))

    import flax

    if shapes(mine) != shapes(flax.core.unfreeze(params)):
      raise ValueError('the reference\'s weights do not map onto the '
                       'program\'s parameter tree')
    return mine, variables

  model = model_cls(
      init_from_checkpoint_fn=inject,
      **{k: cfg[k] for k in program['model_keys']},
      **{arg: cfg[k] for arg, k in program.get('model_renamed', {}).items()},
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
  moved = {name: (total - counters.get('open', {}).get(name, 0))
           for name, total in counters['close'].items()}
  log(f'batch={batch} sequences of {seq} tokens, steps_per_dispatch={group}; '
      f'window: {len(gaps)} dispatches (a boundary to the next: median '
      f'{np.median(gaps) if gaps else 0:.1f} ms, longest '
      f'{max(gaps, default=0):.1f} ms), {len(shared.feed_ms)} batches fed; '
      f'counters in the window {json.dumps(moved)} in all '
      f'{json.dumps(counters["close"])}; host peak rss '
      f'{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20:.1f}'
      ' GiB')

  if moved.get('moe/top1_weight_e6') and moved.get('moe/tokens'):
    layer_steps = moved['moe/tokens'] / (batch * seq)
    log('mean probability of the chosen expert in the window '
        f'{moved["moe/top1_weight_e6"] / layer_steps / 1e6:.4f}')

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
      dispatches = compared_steps // group
      compared = check.compare(readings, reference, loss_steps[:dispatches])
      compared['token_gap'] = {'value': token_gap}
      worst, where = _rows_gap(readings['counts'][:dispatches],
                               reference['counts'][:dispatches])
      compared['rows_gap'] = {'value': worst, 'at': where}
      correct = True
      for name, entry in compared.items():
        entry['limit'] = limits.get(name)
        if entry['limit'] is not None:
          correct = correct and bool(entry['value'] <= entry['limit'])
      return compared, correct

    compared, correct = judge(captured)
    log('by check step, compared or not: loss_gap ' + json.dumps([
        round(abs(captured['losses'][s] - reference['losses'][s - 1]) /
              max(abs(reference['losses'][s - 1]), 1e-30), 7)
        for s in loss_steps]) + ' rows_gap ' + json.dumps([
            round(_rows_gap([mine], [theirs])[0], 5) for mine, theirs in
            zip(captured['counts'], reference['counts'])]))
    stood = {}
    for name in stand_ins:
      stand = _reference_steps(
          ref, cfg, key, ref_batches,
          quant=name if name in QUANTS + WITNESSES else None,
          fault=name if name in faults else None)
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
                    if moved.get('moe/tokens') else None)
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
          'flops_per_example': flops.train_flops_per_sequence(
              layers, seq, rows_per_token),
          'examples_per_dispatch': batch * group,
          'steps_per_dispatch': group,
          'feed_ms': list(shared.feed_ms), 'dispatch_gaps_ms': gaps,
          'memory_peak_bytes': memory_peak,
          'own_spans': list(shared.spans),
          'attention_flops_per_example':
              flops.attention_train_flops_per_sequence(layers, seq),
          'routed_row_flops': flops.routed_row_train_flops(layers),
          'moe_counters': moved,
          'tokens_per_example': seq,
          'expert_layers': sum(1 for l in layers if 'expert_width' in l),
          # Sizes the readers tell ops by (result shapes).
          'trunk_shapes': {
              'batch': batch, 'sequence': seq,
              'hidden': int(cfg['hidden_size']),
              'heads': int(cfg['num_attention_heads']),
              'kv_heads': int(cfg['num_key_value_heads']),
              'head_dim': int(cfg['head_dim']),
              'router_hidden': int(cfg.get('router_hidden_size', 0)),
              'experts': int(cfg['num_experts_published']),
              'experts_per_token': int(cfg['num_experts_per_tok'])},
      },
  }
