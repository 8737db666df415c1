"""End-to-end profile of record-fed training (VERDICT r2 #6).

Trains Grasp2Vec from GENERATED tfrecord shards through
``NativeRecordInputGenerator`` (native C++ reader + wire parser + PIL
jpeg decode — no TF in the loop) and reports, per configuration:

* wall ms/step of the real Trainer.train loop (prefetch 0 and 2),
* the device-resident step floor (same compiled executable),
* input overhead = wall − device, i.e. the unhidden host cost,

so the bounded-device-prefetch win and any remaining host-boundedness
are measured, not asserted. All three windows reuse ONE compiled step,
so the record-fed windows and the floor they are compared with run the
same program.

Usage: ``python tools/profile_record_train.py [--steps 12] [--batch 16]``
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
  sys.path.insert(0, REPO)


def generate_shards(model, out_dir: str, num_examples: int = 64,
                    num_shards: int = 4) -> str:
  """Writes spec-shaped examples (jpeg images, random scalars) with the
  native record writer; features AND labels share one example, as the
  reference's recorded episodes do."""
  import numpy as np

  from tensor2robot_tpu.data import example_codec, native_io
  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.specs import SpecStruct, algebra

  merged = {}
  for getter in (model.preprocessor.get_in_feature_specification,
                 model.preprocessor.get_in_label_specification):
    spec = getter(ModeKeys.TRAIN)
    if spec is not None:
      merged.update(algebra.flatten_spec_structure(spec).items())
  rng = np.random.RandomState(0)
  per_shard = num_examples // num_shards
  for s in range(num_shards):
    path = os.path.join(out_dir, f'data-{s:05d}.tfrecord')
    with native_io.NativeRecordWriter(path) as writer:
      for _ in range(per_shard):
        example = SpecStruct()
        for key, spec in merged.items():
          dtype = np.dtype(spec.dtype)
          if dtype == np.uint8 and len(spec.shape) == 3:
            # Smooth random images: noise jpegs are pathologically large.
            base = rng.randint(0, 255, (8, 10, 3)).astype(np.uint8)
            import PIL.Image

            img = np.asarray(
                PIL.Image.fromarray(base).resize(
                    (spec.shape[1], spec.shape[0]), PIL.Image.BILINEAR))
            example[key] = img.astype(dtype)
          elif np.issubdtype(dtype, np.floating):
            example[key] = rng.randn(*spec.shape).astype(dtype)
          else:
            example[key] = rng.randint(
                0, 2, spec.shape).astype(dtype)
        writer.write(example_codec.encode_example(merged, example))
  return os.path.join(out_dir, 'data-*.tfrecord')


def make_model(workload: str):
  if workload == 'grasp2vec':
    from tensor2robot_tpu.research.grasp2vec import Grasp2VecModel

    return Grasp2VecModel(device_type='tpu')
  if workload == 'qtopt':
    from tensor2robot_tpu.research.qtopt import GraspingModelWrapper

    return GraspingModelWrapper(device_type='tpu')
  raise ValueError(f'unknown workload {workload!r}')


def run_profiles(pattern: str, batch: int, steps: int,
                 per_step: bool = False, workload: str = 'grasp2vec'):
  """One Trainer, one compiled executable, three measurements.

  The record-fed windows (prefetch 0/2) and the device-resident window
  all reuse the SAME compiled step, so their difference is the input
  path's alone.
  """
  import jax

  from tensor2robot_tpu.data.input_generators import (
      NativeRecordInputGenerator)
  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.train import Trainer, TrainerConfig

  def cfg(max_steps, prefetch):
    return TrainerConfig(model_dir='', max_train_steps=max_steps,
                         eval_interval_steps=0, log_interval_steps=0,
                         prefetch_batches=prefetch)

  import time as _time

  from tensor2robot_tpu.train.trainer import TrainerCallback

  class _StepTimer(TrainerCallback):

    def __init__(self):
      self.last = _time.perf_counter()
      self.samples = []

    def reset(self):
      self.last = _time.perf_counter()
      self.samples = []

    def after_step(self, trainer, step, scalars):
      now = _time.perf_counter()
      self.samples.append(1e3 * (now - self.last))
      if per_step:
        print(f'    step {step}: {1e3 * (now - self.last):7.0f} ms',
              flush=True)
      self.last = now

  timer = _StepTimer()
  model = make_model(workload)
  trainer = Trainer(model, cfg(3, 0), callbacks=[timer])
  gen = NativeRecordInputGenerator(file_patterns=pattern, batch_size=batch,
                                   shuffle_buffer_size=8, seed=0)
  gen.set_specification_from_model(model, ModeKeys.TRAIN)
  trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)  # compile
  jax.block_until_ready(trainer.state.params)
  # A second short run before the timed windows: warm-up.
  trainer._config = cfg(8, 0)  # pylint: disable=protected-access
  trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)
  jax.block_until_ready(trainer.state.params)

  done = 8
  results = {}
  for prefetch in (0, 2):
    trainer._config = cfg(done + steps, prefetch)  # pylint: disable=protected-access
    it = gen.create_iterator(ModeKeys.TRAIN)
    timer.reset()
    trainer.train(it, None)
    jax.block_until_ready(trainer.state.params)
    # Drop each window's FIRST step: it pays the iterator's start-up
    # (reader threads, first decode), not the steady input path.
    samples = sorted(timer.samples[1:])
    results[prefetch] = {
        'median': samples[len(samples) // 2],
        'p90': samples[int(len(samples) * 0.9)],
        'mean': sum(samples) / len(samples),
    }
    done += steps

  # Device-resident floor with the same executable.
  state = trainer.state
  step_fn = trainer._train_step_fn  # pylint: disable=protected-access
  it = gen.create_iterator(ModeKeys.TRAIN)
  batches = []
  for _ in range(2):
    f, l = next(it)
    batches.append((mesh_lib.shard_batch(f, trainer.mesh),
                    mesh_lib.shard_batch(l, trainer.mesh)))
  for i in range(3):
    state, _ = step_fn(state, *batches[i % 2])
  jax.block_until_ready(state.params)
  t0 = time.perf_counter()
  for i in range(10):
    state, _ = step_fn(state, *batches[i % 2])
  jax.block_until_ready(state.params)
  device_ms = (time.perf_counter() - t0) / 10 * 1e3
  return results, device_ms


def main():
  parser = argparse.ArgumentParser()
  parser.add_argument('--steps', type=int, default=12,
                      help='timed steps per window; must be >= 2 (the '
                           'first step of each window is dropped)')
  parser.add_argument('--batch', type=int, default=16)
  parser.add_argument('--examples', type=int, default=64)
  parser.add_argument('--per_step', action='store_true')
  parser.add_argument('--workload', default='grasp2vec',
                      choices=('grasp2vec', 'qtopt'))
  parser.add_argument('--json', action='store_true',
                      help='emit ONE machine-readable summary line '
                           '(bench.py subprocess mode); the TUNED config '
                           '(engine autotune + autotuned prefetch) is the '
                           'headline — the A/B across bench rounds must '
                           'compare the shipped pipeline, not whichever '
                           'window happened to win (ISSUE 13 satellite); '
                           'both windows ride along under "windows"')
  args = parser.parse_args()
  if args.steps < 2:
    parser.error('--steps must be >= 2 (first step per window is dropped)')

  data_dir = tempfile.mkdtemp(prefix='t2r_recdata_')
  pattern = generate_shards(
      make_model(args.workload), data_dir, num_examples=args.examples)
  if not args.json:
    print(f'generated shards: {pattern}')
  results, device_ms = run_profiles(pattern, args.batch, args.steps,
                                    per_step=args.per_step,
                                    workload=args.workload)
  if args.json:
    import json

    from tensor2robot_tpu.data import engine as engine_lib

    # The headline is the TUNED path — the prefetch depth the core
    # heuristic would ship (trainer `prefetch auto`), with the engine
    # autotuned — not min() over windows: BENCH_r05's grasp2vec line
    # reported the prefetch-0 serial window, so round-over-round A/Bs
    # compared a configuration nobody runs.
    tuned_prefetch = engine_lib.autotune_prefetch()
    tuned = results.get(tuned_prefetch) or results[max(results)]
    decision = engine_lib.last_decision()
    print(json.dumps({
        'workload': args.workload,
        'batch_size': args.batch,
        'median_ms_per_step': round(tuned['median'], 1),
        'p90_ms_per_step': round(tuned['p90'], 1),
        'steps_per_sec': round(1000.0 / tuned['median'], 3),
        'device_ms_per_step': round(device_ms, 1),
        'fraction_of_device_floor': round(device_ms / tuned['median'], 3),
        'prefetch': tuned_prefetch,
        'windows': {
            f'prefetch_{p}': {
                'median_ms_per_step': round(r['median'], 1),
                'steps_per_sec': round(1000.0 / r['median'], 3),
            } for p, r in sorted(results.items())
        },
        # The input engine's autotune outcome for this run (workers /
        # ring depth), so BENCH artifacts record the pipeline shape
        # beside the throughput it produced.
        'engine_autotune': decision.as_dict() if decision else None,
    }))
    return
  print(f'device-resident step: {device_ms:.1f} ms')
  for prefetch, r in results.items():
    print(f"prefetch={prefetch}: median {r['median']:.0f} ms/step "
          f"(p90 {r['p90']:.0f}, mean {r['mean']:.0f}); input overhead "
          f"{r['median'] - device_ms:.0f} ms/step, device busy "
          f"{device_ms / r['median']:.0%} at the median")


if __name__ == '__main__':
  main()
