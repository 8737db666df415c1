"""Measures BASELINE.md's target numbers and records them in BASELINE.json.

The reference publishes no benchmarks (BASELINE.md), so the measurable
targets come from running its testable workloads in THIS framework on one
chip:

1. pose_env regression on tests/test_data/pose_env_test_data.tfrecord —
   converged eval pose_mse.
2. QT-Opt grasping critic — steps/sec/chip (bench.py's headline; recorded
   there).
3. Grasp2Vec — steps/sec/chip.
4. WTL vision trial model — steps/sec/chip.
5. MAML over pose_env tasks — steps/sec/chip + adaptation eval loss.

Run: python tools/measure_baselines.py  (on the TPU box; ~minutes)
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
TEST_DATA = os.path.join(REPO, 'tests', 'test_data',
                         'pose_env_test_data.tfrecord')


def _time_train_step(model, batch_size: int, steps: int = 50,
                     generator=None, trace: bool = False,
                     grad_accum: int = 1):
  """(wall steps/s, trace-measured device ms/step or None) for the
  jitted train step over device-resident random batches.

  ``grad_accum=M`` compiles the microbatch-accumulation step
  (``TrainerConfig.grad_accum_microbatches``): ``batch_size`` is the
  EFFECTIVE batch, sliced into M microbatches inside the program — the
  configuration the accum batch curve measures against the HBM cliff.
  """
  import jax

  from tensor2robot_tpu.data.input_generators import (
      DefaultRandomInputGenerator)
  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.train import Trainer, TrainerConfig

  generator = generator or DefaultRandomInputGenerator(
      batch_size=batch_size)
  generator.batch_size = batch_size
  generator.set_specification_from_model(model, ModeKeys.TRAIN)
  config = TrainerConfig(model_dir='', max_train_steps=1,
                         eval_interval_steps=0, log_interval_steps=0,
                         grad_accum_microbatches=grad_accum)
  trainer = Trainer(model, config)
  it = generator.create_iterator(ModeKeys.TRAIN)
  trainer.train(it, None)
  state = trainer.state
  # Measure the PRODUCTION dispatch path: the auto-input-layout
  # executable when the backend supports it (what Trainer.train runs),
  # else the default jitted step. Formats flow into batch placement so
  # the step never re-lays inputs out (the WTL episode batch pays
  # 1.5 ms/step for that copy on the default path).
  host_batches = [next(it) for _ in range(4)]
  auto = trainer._maybe_build_auto_step(  # pylint: disable=protected-access
      host_batches[0][0], host_batches[0][1])
  step_fn = (trainer._auto_step if auto else  # pylint: disable=protected-access
             trainer._train_step_fn)  # pylint: disable=protected-access
  formats = trainer._batch_formats if auto else None  # pylint: disable=protected-access
  batches = [
      mesh_lib.shard_batch(b, trainer.mesh, formats) for b in host_batches
  ]
  for i in range(3):
    state, _ = step_fn(state, *batches[i % 4])
  jax.block_until_ready(state)
  t0 = time.perf_counter()
  for i in range(steps):
    state, _ = step_fn(state, *batches[i % 4])
  jax.block_until_ready(state)
  wall = steps / (time.perf_counter() - t0)
  device_ms = None
  if trace and jax.default_backend() != 'cpu':
    from tools.trace_profile import (device_ms_per_iter,
                                     device_ms_per_step_loop)

    if auto:  # Compiled objects cannot ride the chained-jit harness.
      device_ms, _ = device_ms_per_step_loop(step_fn, state, batches, n=10)
    else:
      device_ms, _ = device_ms_per_iter(step_fn, (state, *batches[0]), n=10)
  return wall, device_ms


def measure_pose_env_convergence(max_train_steps: int = 400) -> dict:
  from tensor2robot_tpu.data.input_generators import (
      DefaultRecordInputGenerator)
  from tensor2robot_tpu.research.pose_env import PoseEnvRegressionModel
  from tensor2robot_tpu.train import train_eval_model

  import tempfile

  model = PoseEnvRegressionModel(device_type='tpu')
  with tempfile.TemporaryDirectory() as tmp:
    metrics = train_eval_model(
        model=model,
        model_dir=tmp,
        train_input_generator=DefaultRecordInputGenerator(
            file_patterns=TEST_DATA, batch_size=32),
        eval_input_generator=DefaultRecordInputGenerator(
            file_patterns=TEST_DATA, batch_size=32),
        max_train_steps=max_train_steps,
        eval_steps=4,
        eval_interval_steps=0,
        save_interval_steps=max_train_steps,
        log_interval_steps=0)
  return {
      'pose_env_eval_mse': round(float(metrics['pose_mse']), 6),
      'pose_env_eval_loss': round(float(metrics['loss']), 6),
      'pose_env_train_steps': max_train_steps,
  }


def measure_grasp2vec():
  """(wall steps/s, trace-measured device ms/step) at batch 16.

  The r4 wall-only anchor (11.7 steps/s = 85 ms) read slightly FASTER
  than the step's own device time (~88 ms) — the block_until_ready
  sync error, marginal here because the step is deep. Anchored on the
  traced device ms like the other workloads."""
  from tensor2robot_tpu.research.grasp2vec import Grasp2VecModel

  return _time_train_step(Grasp2VecModel(device_type='tpu'),
                          batch_size=16, steps=30, trace=True)


def measure_wtl_vision(batch_size: int = 32):
  """WTL vision trial at a COMPUTE-BOUND configuration (r4 verdict #3).

  The original batch-4 anchor measured 37-43 steps/s across runs/boxes
  (dispatch-latency noise straddling the recorded 55.7) — not
  reproducible, so useless as a regression gate. Batch 32 is ~37 ms of
  device time per step (rooflined in PERF_NOTES), so the recorded
  number tracks compute. Returns (wall steps/s, device ms/step)."""
  from tensor2robot_tpu.research.vrgripper import (
      VRGripperEnvVisionTrialModel)

  model = VRGripperEnvVisionTrialModel(
      device_type='tpu', episode_length=40)
  return _time_train_step(model, batch_size=batch_size, steps=30,
                          trace=True)


def measure_pose_env_maml(batch_size: int = 64):
  """MAML (wall steps/s, TRACE-measured device ms/step) at batch 64.

  The original batch-4 anchor was sub-millisecond device time — a
  measure of host dispatch latency, useless for regression detection.
  Batch 64 helps but is not enough: the step is ~4 ms of device time,
  so WALL still carries more dispatch overhead than compute. The
  regression anchor is therefore the xplane-traced DEVICE ms, like
  WTL's, with wall recorded as context only.
  """
  from tensor2robot_tpu.research.pose_env import PoseEnvRegressionModelMAML
  from tensor2robot_tpu.research.pose_env.pose_env_models import (
      PoseEnvRegressionModel)

  model = PoseEnvRegressionModelMAML(
      base_model=PoseEnvRegressionModel(device_type='tpu'),
      num_inner_loop_steps=1)
  return _time_train_step(model, batch_size=batch_size, trace=True)


def measure_qtopt_batch(batch_size: int, steps: int = 30,
                        grad_accum: int = 1, remat: str = 'none',
                        kernel_policy: str = 'none',
                        matmul_precision: str = 'bf16'):
  """One QT-Opt batch-size point: (wall steps/s, device ms/step).

  ``kernel_policy``/``matmul_precision`` select the Pallas pool/conv
  kernels and the fp8 contraction path (the PR-15 A/B axes; the bench's
  ``qtopt_kernel_step_ms`` / ``qtopt_fp8_step_ms`` lines run this in a
  subprocess per arm)."""
  from tensor2robot_tpu.research.qtopt import GraspingModelWrapper

  return _time_train_step(
      GraspingModelWrapper(device_type='tpu', remat_policy=remat,
                           kernel_policy=kernel_policy,
                           matmul_precision=matmul_precision),
      batch_size=batch_size, steps=steps, trace=True,
      grad_accum=grad_accum)


def measure_qtopt_loop(batch_size: int, steps: int = 48,
                       steps_per_dispatch: int = 1,
                       device_feed: bool = False,
                       fused_update: bool = False):
  """QT-Opt wall ms/step through the REAL dispatch loop.

  ``_time_train_step`` times the raw jitted step — correct for kernel
  arms, blind to dispatch/H2D overhead, which is exactly what the
  device-feed knob attacks. This point runs ``Trainer.train`` itself
  (prefetcher, placement stage, K-step dispatch), warmed by a first
  segment that pays all compiles, then timed over ``steps`` more steps
  by extending ``max_train_steps`` on the same trainer (the built
  executables carry over; no recompile — the ledger's sentinel would
  show it). Returns ``(ms_per_step, h2d_puts_per_step,
  dispatches_per_step)`` — the latter two from the registry counters,
  which is where the "exactly 1/K" acceptance line comes from.
  """
  from tensor2robot_tpu.data.input_generators import (
      DefaultRandomInputGenerator)
  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.observability import metrics as metrics_lib
  from tensor2robot_tpu.research.qtopt import GraspingModelWrapper
  from tensor2robot_tpu.train import Trainer, TrainerConfig

  model = GraspingModelWrapper(device_type='tpu')
  generator = DefaultRandomInputGenerator(batch_size=batch_size)
  generator.set_specification_from_model(model, ModeKeys.TRAIN)
  warm = 2 * steps_per_dispatch
  config = TrainerConfig(
      model_dir='', max_train_steps=warm, eval_interval_steps=0,
      log_interval_steps=0, prefetch_batches=2,
      steps_per_dispatch=steps_per_dispatch, device_feed=device_feed,
      fused_update=fused_update)
  trainer = Trainer(model, config)
  trainer.train(generator.create_iterator(ModeKeys.TRAIN), None)

  puts0 = metrics_lib.counter('trainer/h2d/device_puts').value
  disp0 = metrics_lib.counter('trainer/dispatches').value
  config.max_train_steps = warm + steps
  t0 = time.perf_counter()
  trainer.train(generator.create_iterator(ModeKeys.TRAIN), None)
  wall = time.perf_counter() - t0
  puts = metrics_lib.counter('trainer/h2d/device_puts').value - puts0
  disp = metrics_lib.counter('trainer/dispatches').value - disp0
  return (wall * 1e3 / steps, puts / steps, disp / steps)


def measure_qtopt_batch_curve(batches=(32, 48, 64, 96, 128),
                              accums=(1,)) -> dict:
  """Per-example throughput curve (r4 verdict #2), memory-annotated.

  Each (batch, accum) point runs in its OWN subprocess, so its
  ``device_memory_peak_mb`` — the allocator's own ``memory_stats()``
  peak — is that point's alone, and the HBM cliff is pinned to
  bytes in the artifact rather than inferred from a throughput collapse.
  ``accums``: grad_accum_microbatches values per batch size (M > 1 only
  where M divides the batch) — the accum curve BENCH_r06 records.
  Returns {(batch, accum) or batch: point dict}.
  """
  import subprocess
  import sys

  curve = {}
  for b in batches:
    for m in accums:
      if b % m:
        continue
      args = [sys.executable, os.path.abspath(__file__),
              '--qtopt-batch', str(b)]
      if m > 1:
        args += ['--accum', str(m)]
      proc = subprocess.run(args, capture_output=True, text=True)
      line = None
      for out_line in proc.stdout.splitlines():
        if out_line.startswith('{'):
          line = out_line
      key = b if m == 1 else (b, m)
      if line is None:
        print(f'  batch {b} M={m} FAILED:\n{proc.stdout[-500:]}\n'
              f'{proc.stderr[-800:]}')
        continue
      curve[key] = json.loads(line)
      print(f'  batch {b} M={m}: {curve[key]}', flush=True)
  return curve


RETIRED_KEYS = (
    # batch-4 WTL: box-variance noise, replaced by the batch-32 anchor.
    'wtl_vision_steps_per_sec_per_chip',
    # subsumed by the measured batch curve.
    'qtopt_steps_per_sec_per_chip_batch128',
)


def main(argv=None):
  import argparse

  parser = argparse.ArgumentParser()
  parser.add_argument('--qtopt-batch', type=int, default=None,
                      help='measure ONE qtopt batch point and print one '
                           'JSON line (subprocess mode for the curve)')
  parser.add_argument('--accum', type=int, default=1,
                      help='grad_accum_microbatches for the --qtopt-batch '
                           'point (batch is the EFFECTIVE batch)')
  parser.add_argument('--remat', default='none',
                      choices=('none', 'conv_towers', 'full'),
                      help='activation remat policy for the --qtopt-batch '
                           'point')
  parser.add_argument('--kernel-policy', default='none',
                      choices=('none', 'pool', 'pool_conv'),
                      help='Pallas kernel routing for the --qtopt-batch '
                           'point (ops/pool.py + ops/conv_s2d.py)')
  parser.add_argument('--matmul-precision', default='bf16',
                      choices=('bf16', 'fp8'),
                      help='Dense/Conv contraction precision for the '
                           '--qtopt-batch point (quantize/fp8_training.py)')
  parser.add_argument('--loop', action='store_true',
                      help='time the --qtopt-batch point through the REAL '
                           'Trainer.train dispatch loop (prefetcher + '
                           'placement + K-step dispatch) instead of the '
                           'raw jitted step; implied by --device-feed / '
                           '--fused-update / --steps-per-dispatch > 1')
  parser.add_argument('--device-feed', action='store_true',
                      help='TrainerConfig.device_feed for the --qtopt-batch '
                           'loop point (one device_put + one dispatch per '
                           'K steps)')
  parser.add_argument('--fused-update', action='store_true',
                      help='TrainerConfig.fused_update for the '
                           '--qtopt-batch loop point (ops/fused_update.py '
                           'Pallas optimizer+EMA pass)')
  parser.add_argument('--steps-per-dispatch', type=int, default=1,
                      help='TrainerConfig.steps_per_dispatch (K) for the '
                           '--qtopt-batch loop point')
  parser.add_argument('--only', default=None,
                      help='comma list of: pose_env, grasp2vec, wtl, '
                           'maml, qtopt_curve, qtopt_accum_curve '
                           '(default: all but qtopt_accum_curve)')
  args = parser.parse_args(argv)

  import jax

  on_tpu = jax.default_backend() != 'cpu'

  if args.qtopt_batch is not None and (
      args.loop or args.device_feed or args.fused_update
      or args.steps_per_dispatch > 1):
    ms_per_step, puts_per_step, disp_per_step = measure_qtopt_loop(
        args.qtopt_batch, steps_per_dispatch=args.steps_per_dispatch,
        device_feed=args.device_feed, fused_update=args.fused_update)
    print(json.dumps({
        'loop_ms_per_step': round(ms_per_step, 3),
        'h2d_puts_per_step': round(puts_per_step, 4),
        'dispatches_per_step': round(disp_per_step, 4),
        'steps_per_dispatch': args.steps_per_dispatch,
        'device_feed': args.device_feed,
        'fused_update': args.fused_update,
    }))
    return

  if args.qtopt_batch is not None:
    from tensor2robot_tpu.observability import memory as memory_lib

    wall, device_ms = measure_qtopt_batch(
        args.qtopt_batch, grad_accum=args.accum, remat=args.remat,
        kernel_policy=args.kernel_policy,
        matmul_precision=args.matmul_precision)
    # Allocator high-water mark AFTER the timed loop: with the whole
    # point in its own subprocess, the peak IS this configuration's —
    # the number that says on which side of the HBM cliff it ran.
    peak_mb = memory_lib.device_memory_peak_mb()
    print(json.dumps({
        'steps_per_sec': round(wall, 3),
        'device_ms': round(device_ms, 2) if device_ms else None,
        'examples_per_sec': round(wall * args.qtopt_batch, 1),
        'device_examples_per_sec': (
            round(1000.0 / device_ms * args.qtopt_batch, 1)
            if device_ms else None),
        'device_memory_peak_mb': (round(peak_mb, 1)
                                  if peak_mb is not None else None),
        'grad_accum_microbatches': args.accum,
        'remat_policy': args.remat,
        'kernel_policy': args.kernel_policy,
        'matmul_precision': args.matmul_precision,
    }))
    return

  if not on_tpu:
    print('WARNING: not on TPU; numbers will not be recorded.')
  want = set(args.only.split(',')) if args.only else {
      'pose_env', 'grasp2vec', 'wtl', 'maml', 'qtopt_curve'}
  if 'qtopt_accum_curve' in want:
    # The accum curve: effective batches past the measured cliff, M
    # sized so the MICRObatch stays at the known-good 64 (plus the M=1
    # cliff points for the same-session A/B). BENCH_r06's headline
    # acceptance: effective batch 128 = 2×64 holds ≥90% of batch-64
    # per-example device throughput.
    print('qtopt ACCUM batch curve (each point in its own subprocess) ...',
          flush=True)
    accum_curve = measure_qtopt_batch_curve(
        batches=(64, 96, 128, 192, 256), accums=(1, 2, 3, 4))
    for key, point in sorted(accum_curve.items(), key=str):
      b, m = key if isinstance(key, tuple) else (key, 1)
      if point.get('device_examples_per_sec'):
        print(f'  effective batch {b} (M={m}): '
              f"{point['device_examples_per_sec']} ex/s device, "
              f"peak {point.get('device_memory_peak_mb')} MB", flush=True)

  measured = {}
  if 'pose_env' in want:
    print('pose_env convergence ...', flush=True)
    measured.update(measure_pose_env_convergence())
    print(f"  pose_env_eval_mse={measured['pose_env_eval_mse']}", flush=True)
  if 'grasp2vec' in want:
    print('grasp2vec (batch 16, trace-anchored) ...', flush=True)
    wall, device_ms = measure_grasp2vec()
    if device_ms:
      measured['grasp2vec_steps_per_sec_per_chip'] = round(wall, 3)
      measured['grasp2vec_device_ms_per_step_batch16'] = round(device_ms, 2)
      print(f'  {wall:.2f} steps/s wall, {device_ms} ms device', flush=True)
    else:
      print('  TRACE FAILED: refusing to record a wall number without '
            'the device-ms anchor.', flush=True)
  if 'wtl' in want:
    print('wtl vision steps/sec (batch 32, compute-bound) ...', flush=True)
    wall, device_ms = measure_wtl_vision()
    measured['wtl_vision_steps_per_sec_per_chip_batch32'] = round(wall, 3)
    if device_ms:
      measured['wtl_vision_device_ms_per_step_batch32'] = round(device_ms, 2)
    print(f'  {wall:.2f} steps/s wall, {device_ms} ms device', flush=True)
  if 'maml' in want:
    print('pose_env maml (batch 64, trace-anchored) ...', flush=True)
    wall, device_ms = measure_pose_env_maml()
    if device_ms:
      measured['pose_env_maml_steps_per_sec_per_chip_batch64'] = round(
          wall, 3)
      measured['pose_env_maml_device_ms_per_step_batch64'] = round(
          device_ms, 2)
      print(f'  {wall:.2f} steps/s wall, {device_ms} ms device', flush=True)
    else:
      # The device ms IS the regression anchor; recording a fresh wall
      # next to a stale anchor would look coherent while gating nothing.
      print('  TRACE FAILED: refusing to record a wall number without '
            'the device-ms anchor.', flush=True)
  if 'qtopt_curve' in want:
    print('qtopt batch curve (each point in its own subprocess) ...',
          flush=True)
    curve = measure_qtopt_batch_curve()
    # DEVICE examples/s is the recorded curve, like every other anchor;
    # wall examples/s also carries the host. A point whose trace failed is
    # refused outright — recording its wall number under the
    # device-labeled key would mix units and could mis-pick the optimum.
    device_curve = {
        b: point['device_examples_per_sec']
        for b, point in curve.items()
        if point.get('device_examples_per_sec')
    }
    for b in sorted(set(curve) - set(device_curve)):
      print(f'  batch {b}: TRACE FAILED — refusing to record its wall '
            'number under the device-anchored key.', flush=True)
    for b, value in device_curve.items():
      measured[f'qtopt_examples_per_sec_per_chip_batch{b}'] = value
      peak = curve[b].get('device_memory_peak_mb')
      if peak is not None:
        # Bytes beside the throughput: the cliff's location is
        # self-describing in the recorded curve.
        measured[f'qtopt_device_memory_peak_mb_batch{b}'] = peak
    if device_curve:
      measured['qtopt_optimal_batch'] = int(
          max(device_curve, key=device_curve.get))

  print(json.dumps(measured, indent=2))
  if on_tpu:
    path = os.path.join(REPO, 'BASELINE.json')
    with open(path) as f:
      record = json.load(f)
    recorded = record.setdefault('measured', {})
    recorded.update(measured)
    for key in RETIRED_KEYS:
      recorded.pop(key, None)
    with open(path, 'w') as f:
      json.dump(record, f, indent=2)
    print(f'recorded into {path}')


if __name__ == '__main__':
  main()
