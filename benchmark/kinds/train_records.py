"""The kind ``train_records``: training fed from record shards.

A traffic mix names its kind (``"kind"`` in ``benchmark/workloads/
<traffic>.json``) and ``benchmark/run.py`` hands the run to the module of
that name here. A kind is one file: ``run(job)`` drives the system under
test through set-up, the measured window and the check and returns what
``run.py`` prints; ``device_times(ctx)`` says which part of a trace is
the window. A serving kind is another file beside this one.

This kind: shards from the seed (worker processes, while the parent
imports the program), weights from the seed on the device, then ONE call
of the program's ``train_eval_model``, which trains through check steps,
warm-up and the measured window (``benchmark/lib/window.py``). After the
window: peak memory, the program's state freed, the reference's steps
(``benchmark/lib/check.py``).

Stand-ins (``--stand-in``, never passed by the driver) read the controls
and planted faults that the limits were set from (PERF.md, section 4).
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import resource
import statistics
import time

QUANTS = ('fp8',)
FAULTS = ('half_batch', 'unchanged_state')
# The reference with operands rounded to the configuration's own bfloat16:
# no control, a second witness of what that precision alone does.
WITNESSES = ('bf16',)
STAND_INS = QUANTS + FAULTS + WITNESSES


def load_symbol(path: str):
  module, name = path.rsplit('.', 1)
  return getattr(importlib.import_module(module), name)


def to_program_tree(ref, cfg, flat: dict) -> dict:
  tree: dict = {}
  for name, value in flat.items():
    node = tree
    *parents, leaf = ref.program_path(name, cfg)
    for part in parents:
      node = node.setdefault(part, {})
    node[leaf] = value
  return tree


def from_program_tree(ref, cfg, names, tree) -> dict:
  out = {}
  for name in names:
    node = tree
    for part in ref.program_path(name, cfg):
      node = node[part]
    out[name] = node
  return out


def first_moment_of(opt_state):
  """Adam's ``mu`` or the momentum ``trace``, wherever optax nests it."""
  for attr in ('mu', 'trace'):
    if hasattr(opt_state, attr):
      return getattr(opt_state, attr)
  if isinstance(opt_state, (tuple, list)):
    for part in opt_state:
      found = first_moment_of(part)
      if found is not None:
        return found
  return None


def device_times(ctx):
  """The traced window (whole dispatches of the train step's program),
  the device's busy seconds in it and the breakdown."""
  from benchmark.metrics import _traced

  t = _traced.traced(ctx)
  return t['busy_s'], t['window_s'], t['breakdown']


def run(job) -> dict:
  cfg, mix, cell, log = job.cfg, job.mix, job.cell, job.log
  stand_ins = job.stand_ins
  if set(stand_ins) - set(STAND_INS):
    raise SystemExit(f'--stand-in takes {STAND_INS}')
  group = int(cfg['steps_per_dispatch'])
  batch = int(cfg['batch_size'])
  check_steps = int(cfg['check']['steps'])
  if check_steps % group:
    raise SystemExit('check.steps must be whole dispatches')
  trainer_seed = job.seed % (2 ** 31 - 1)
  setup = {}

  # ------------------------------------------------------ shards, in workers
  from benchmark.lib import traffic

  workers = max(1, (os.cpu_count() or 2) - 2)
  shards = traffic.ShardJob(os.path.join(job.tmp, 'shards'), job.seed, mix,
                            cfg['record_features'], workers)
  job.on_exit(shards.close)

  # -------------------------------------------------- the program, the chip
  import jax
  import jax.numpy as jnp

  from tensor2robot_tpu.observability import metrics as program_metrics
  from tensor2robot_tpu.train.trainer import train_eval_model
  from tensor2robot_tpu.utils.compilation_cache import (
      enable_compilation_cache)

  from benchmark.lib import check, flops, window

  ref = importlib.import_module(f'benchmark.reference.{cfg["reference"]}')
  setup['imports_s'] = time.perf_counter() - job.t0

  devices = job.chips()
  cache_dir = enable_compilation_cache()

  # --------------------------------------------- weights, from the seed
  t = time.perf_counter()
  key = jax.random.fold_in(jax.random.PRNGKey(trainer_seed), job.seed >> 31)
  params0 = jax.jit(lambda k: ref.init_params(k, cfg))(key)
  jax.block_until_ready(params0)
  names = list(params0)

  def inject(params, variables):
    # Copies: the trainer donates its state to the step.
    mine = to_program_tree(
        ref, cfg, {k: jnp.copy(v) for k, v in params0.items()})

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
  model_kwargs = dict(program.get('model_kwargs', {}))
  if 'preprocessor' in program:
    import functools

    model_kwargs['preprocessor_cls'] = functools.partial(
        load_symbol(program['preprocessor']['cls']),
        **{k: tuple(v) for k, v in
           program['preprocessor']['kwargs'].items()})
  model = load_symbol(program['model'])(
      init_from_checkpoint_fn=inject,
      **{k: tuple(v) if isinstance(v, list) else v
         for k, v in model_kwargs.items()})
  setup['weights_s'] = time.perf_counter() - t

  signatures, frame_bytes = shards.result()
  setup['shards_ready_s'] = time.perf_counter() - job.t0
  generator = load_symbol(program['input_generator'])(
      file_patterns=shards.pattern, batch_size=batch,
      shuffle_buffer_size=mix['shuffle_buffer_size'], seed=trainer_seed)

  # ------------------------------------------------ one call: the window
  shared = window.Shared(keep_batches=check_steps, group=group)
  captured = {'losses': {}}

  def on_check(index, trainer, scalars):
    step = index * group
    captured['losses'][step] = float(scalars['loss'])
    state = trainer.state
    if index == 1:
      captured['first_moment'] = check.norms(from_program_tree(
          ref, cfg, names, first_moment_of(state.opt_state)))
    if step == check_steps:
      now = from_program_tree(ref, cfg, names, state.params)
      captured['change'] = check.norms(
          {k: now[k] - params0[k] for k in names})

  tracing = {'dir': None}
  compiles = {}

  def on_window_open():
    compiles['open'] = program_metrics.snapshot('compile/')
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
      on_window_open=on_window_open,
      skip_window=bool(stand_ins))
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
  setup['train_call_to_first_dispatch_s'] = (
      (callback.first_dispatch_done or time.perf_counter()) - t)
  setup_s = ((callback.t_open or time.perf_counter()) - job.t0)

  memory_peak = max(
      (d.memory_stats() or {}).get('peak_bytes_in_use', 0)
      for d in devices)
  del model, generator
  gc.collect()

  log('setup ' + ' '.join(f'{k}={v:.2f}' for k, v in setup.items()) +
      f' frame_bytes={frame_bytes:.0f} cache_dir={cache_dir}')
  log('compile at window open: ' + json.dumps(compiles.get('open')) +
      ' at close: ' + json.dumps(compiles['close']))
  gaps = callback.dispatch_gaps_ms()
  log(f'batch={batch} steps_per_dispatch={group} window: '
      f'{len(gaps)} dispatches, {len(gaps) * batch * group} examples, '
      f'{len(shared.feed_ms)} batches fed; host peak rss '
      f'{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20:.1f}'
      ' GiB')

  # -------------------------------------------------------- the check
  t = time.perf_counter()
  from concurrent.futures import ThreadPoolExecutor

  examples = check.read_examples(shards.pattern)
  first_frame = next(f['key'] for f in cfg['record_features']
                     if f['kind'] == 'jpeg')
  ref_batches, worst_pixel = [], 0.0
  with ThreadPoolExecutor(max(2, workers)) as pool:
    for fed in shared.kept:
      rows = check.match_rows(fed, first_frame, signatures)
      ref_batch = check.reference_batch(
          examples, rows, cfg['record_features'], pool)
      worst_pixel = max(worst_pixel, check.pixel_gap(
          fed, ref_batch, cfg['record_features']))
      ref_batches.append(ref_batch)
  del examples
  shared.kept.clear()
  reference = check.follow(ref, cfg, params0, ref_batches, trainer_seed)
  limits = cfg['check']['limits']
  loss_steps = sorted(captured['losses'])

  def judge(readings):
    """Each number beside its limit; a number with no limit is printed
    and not compared (PERF.md names those)."""
    compared = check.compare(readings, reference, loss_steps)
    compared['pixel_gap'] = {'value': worst_pixel}
    correct = True
    for name, entry in compared.items():
      entry['limit'] = limits.get(name)
      if entry['limit'] is not None:
        correct = correct and bool(entry['value'] <= entry['limit'])
    return compared, correct

  compared, correct = judge(captured)
  stood = {}
  for name in stand_ins:
    stand = check.follow(ref, cfg, params0, ref_batches, trainer_seed,
                         quant=name if name in QUANTS + WITNESSES else None,
                         fault=name if name in FAULTS else None)
    stood[name] = judge({
        'losses': {s: stand['losses'][s - 1] for s in loss_steps},
        'first_moment': stand['first_moment'], 'change': stand['change']})
  log(f'check: {time.perf_counter() - t:.1f}s, reference losses '
      f'{[round(x, 6) for x in reference["losses"]]}, program losses '
      f'{captured["losses"]}')

  rate = callback.examples_per_s()
  if not (job.rehearse or stand_ins) and rate is None:
    raise SystemExit('the window closed fewer than two dispatches')
  return {
      'end_to_end': {
          'setup_s': setup_s,
          'train_examples_per_s': rate,
          'step_wall_p95_ms': (
              statistics.quantiles(gaps, n=20)[18] / group
              if len(gaps) >= 20 else None),
      },
      'attempted': len(gaps), 'failed': 0,
      'memory_peak_bytes': int(memory_peak),
      'compared': compared, 'correct': correct, 'stand_ins': stood,
      'trace_dir': tracing['dir'],
      # What the per-layer readers find beside the trace.
      'context': {
          'flops_per_example': flops.train_flops_per_example(
              ref.layers(cfg)),
          'examples_per_dispatch': batch * group,
          'steps_per_dispatch': group,
          'feed_ms': list(shared.feed_ms), 'dispatch_gaps_ms': gaps,
          'memory_peak_bytes': memory_peak,
          'own_spans': list(shared.spans),
      },
  }
