"""Trainer binary: parse config files, call train_eval_model.

Shape-for-shape equivalent of ``/root/reference/bin/run_t2r_trainer.py:
32-39``: all wiring lives in config files; the binary parses
``--gin_configs`` / ``--gin_bindings`` and calls one function.

Usage:
  python -m tensor2robot_tpu.bin.run_t2r_trainer \
      --gin_configs path/to/experiment.gin \
      --gin_bindings 'train_eval_model.max_train_steps = 100'

A run that completes leaves ``run_report.json`` in the model dir: the
metrics registry's end-of-run report, whose ``device`` section says what
the run actually ran on, ``programs`` what it compiled (hand-kernel
count included) and ``metrics`` its ``compile/*`` and ``trainer/*``
counters. chip_smoke.py reads it.
"""

from __future__ import annotations

import argparse
import logging
import sys

from tensor2robot_tpu import config as t2r_config

RUN_REPORT_FILENAME = 'run_report.json'


def main(argv=None):
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument('--gin_configs', action='append', default=[],
                      help='Path to a gin config file (repeatable).')
  parser.add_argument('--gin_bindings', action='append', default=[],
                      help='Individual gin bindings (repeatable).')
  parser.add_argument(
      '--handle_preemption', action=argparse.BooleanOptionalAction,
      default=True,
      help='Convert SIGTERM/SIGINT into a forced checkpoint and a '
           'distinct resumable exit status (42).')
  args = parser.parse_args(argv)

  # Install the preemption handler BEFORE any work: a SIGTERM during
  # config parsing or state init should still exit resumable, and the
  # trainer honors the process-global handler at every dispatch boundary.
  from tensor2robot_tpu.train import resilience

  shutdown = None
  if args.handle_preemption:
    shutdown = resilience.install_graceful_shutdown()

  try:
    return _run(args, resilience)
  finally:
    # Restore signal dispositions on the way out: once training is over
    # a SIGTERM should kill normally, and embedding callers (tests, or
    # programs invoking main() directly) must not inherit a process-
    # global handler as a side effect.
    if shutdown is not None:
      shutdown.uninstall()


def _run(args, resilience):
  t2r_config.register_framework_configurables()
  t2r_config.parse_config_files_and_bindings(
      config_files=args.gin_configs, bindings=args.gin_bindings)

  # Persist the config next to the checkpoints, like the reference's
  # GinConfigSaverHook (train_eval.py:540-541): the FULL parsed config at
  # startup (so crashed/preempted runs are still reproducible from the
  # model dir), refined to the operative (actually-consumed) config on
  # successful completion.
  import os

  try:
    model_dir = t2r_config.query_parameter('train_eval_model.model_dir',
                                           resolve=True)
  except t2r_config.ConfigError:
    model_dir = None
  if not isinstance(model_dir, str):
    model_dir = None

  local_model_dir = model_dir if model_dir and '://' not in model_dir else None

  def save_config(text, filename):
    if local_model_dir is None:
      return
    os.makedirs(local_model_dir, exist_ok=True)
    with open(os.path.join(local_model_dir, filename), 'w') as f:
      f.write(text)

  # The startup snapshot is the FULL parsed config (the run may crash
  # before an operative config exists) — named distinctly so
  # operative_config-0.gin never misrepresents un-consumed bindings.
  save_config(t2r_config.config_str(), 'config-0.gin')
  train_eval_model = t2r_config.get_configurable('train_eval_model')
  try:
    result = train_eval_model()
  except resilience.PreemptedError as e:
    # The trainer already forced a checkpoint (+ input state). Exit with
    # the DISTINCT resumable status so schedulers restart rather than
    # fail the job; the restarted run restores and continues.
    logging.warning('%s; exiting with resumable status %d.', e, e.exit_code)
    sys.exit(e.exit_code)
  except Exception as e:
    # Liveness failures (train/distributed_resilience.DeadHostError and
    # kin) carry their own exit status (43): a peer process died, the
    # scheduler should restart the WHOLE job from the last committed
    # checkpoint rather than treat this worker as an ordinary crash.
    code = getattr(e, 'exit_code', None)
    if code is not None:
      logging.error('%s; exiting with status %d.', e, code)
      sys.exit(code)
    raise
  operative = t2r_config.operative_config_str()
  logging.info('Operative config:\n%s', operative)
  save_config(operative, 'operative_config-0.gin')
  if local_model_dir is not None:
    from tensor2robot_tpu.observability import metrics as metrics_lib

    final = {k: float(v) for k, v in (result or {}).items()}
    metrics_lib.register_report_provider('result', lambda: final)
    metrics_lib.dump_report(
        os.path.join(local_model_dir, RUN_REPORT_FILENAME))
  return result


if __name__ == '__main__':
  logging.basicConfig(level=logging.INFO)
  main()
