"""Distributed fault-tolerance drills: commit protocol, coordinated
preemption, liveness — unit level AND over the real 2-process
``jax.distributed`` harness.

The 2-process drills (bounded < 60 s each, NOT marked slow — they are
the acceptance surface of the subsystem) run each worker with its own
per-host mesh: this jaxlib's CPU backend cannot execute cross-process
XLA programs, which is exactly the regime the control-plane design is
for — coordination must not depend on the data plane.

  (a) SIGTERM delivered to exactly ONE process → BOTH processes agree on
      a stop step, write the same COMMITTED checkpoint — via the SHARDED
      multi-host payload path (both hosts' Orbax writers) — and exit 42;
      restarting both resumes bit-exact (train-state hash equal to an
      uninterrupted 2-process run), and a checkpoint directory missing
      its commit marker is never restored.
  (b) kill one host mid-step (SIGKILL) → the surviving host exits with a
      clear liveness error (status 43), not a hang.
  (c) elastic topology: the drill's 2-host sharded checkpoint restores
      onto THIS single-device mesh with reshape=True, sha256-equal to
      the 2-host state; strict mode (reshape off) still fails loudly.
  (d) kill one host INSIDE the sharded payload write → the step stays
      torn/invisible, the survivor's exit is bounded, and the restart
      resumes from the last committed step (re-saving cleanly into the
      dirty step dir).
  (e) completed-host vs late-proposal SIGTERM race → converges on the
      completed host's published final boundary instead of DeadHostError;
      the truly-exited variant retries once against surviving hosts
      (unit-level, fake 2-host fabric).
"""

import hashlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import textwrap
import threading
import time

import jax
import numpy as np
import pytest

from tensor2robot_tpu.observability import metrics as metrics_lib
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.train import (CheckpointManager, TopologyMismatchError,
                                    latest_checkpoint_step)
from tensor2robot_tpu.train import checkpoints as ckpt_lib
from tensor2robot_tpu.train import distributed_resilience as dist_lib
from tensor2robot_tpu.utils import faults

pytestmark = pytest.mark.multihost_faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ===================================================== unit: aggregation


def test_aggregate_snapshots_counters_summed_gauges_labeled():
  merged = dist_lib.aggregate_snapshots({
      0: {'data/records': 10, 'trainer/queue_depth': 2.0,
          'wait_ms': {'count': 4, 'sum': 8.0, 'mean': 2.0}},
      1: {'data/records': 32, 'trainer/queue_depth': 0.0,
          'wait_ms': {'count': 1, 'sum': 4.0, 'mean': 4.0}},
  })
  assert merged['data/records'] == 42                 # counters: summed
  assert merged['trainer/queue_depth/host0'] == 2.0   # gauges: per host
  assert merged['trainer/queue_depth/host1'] == 0.0
  assert merged['wait_ms'] == {'count': 5, 'sum': 12.0, 'mean': 12.0 / 5}


def test_report_provider_sections_ride_metricsz_report():
  metrics_lib.register_report_provider('cluster', lambda: {'hosts': 2})
  try:
    report = metrics_lib.report()
    assert report['cluster'] == {'hosts': 2}
  finally:
    metrics_lib.unregister_report_provider('cluster')
  assert 'cluster' not in metrics_lib.report()
  # A broken provider degrades in-band instead of killing /metricsz.
  metrics_lib.register_report_provider('bad', lambda: 1 / 0)
  try:
    assert 'error' in metrics_lib.report()['bad']
  finally:
    metrics_lib.unregister_report_provider('bad')


# ============================================ unit: commit marker protocol


def _save_two_checkpoints(model_dir):
  """Trains 20 tiny steps saving at 10 and 20; returns the ckpt dir."""
  from tensor2robot_tpu.train import train_eval_model
  from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

  train_eval_model(
      model=MockT2RModel(device_type='tpu'),
      model_dir=model_dir,
      train_input_generator=MockInputGenerator(batch_size=8),
      max_train_steps=20,
      save_interval_steps=10,
      eval_interval_steps=0,
      log_interval_steps=0)
  return os.path.join(model_dir, 'checkpoints')


def test_commit_markers_written_and_torn_step_skipped(tmp_path):
  ckpt_dir = _save_two_checkpoints(str(tmp_path / 'm'))
  for step in (10, 20):
    marker = ckpt_lib.read_commit_marker(ckpt_dir, step)
    assert marker is not None and marker['step'] == step
    assert marker['topology']['process_count'] == 1
  assert latest_checkpoint_step(ckpt_dir) == 20

  # Un-commit the latest (the exact signature of a job that died between
  # the payload write and the commit): it must vanish from every
  # consumer and count as torn exactly once.
  before = metrics_lib.counter('checkpoint/torn_skipped').value
  faults.remove_commit_marker(ckpt_dir, 20)
  assert latest_checkpoint_step(ckpt_dir) == 10
  assert latest_checkpoint_step(ckpt_dir) == 10  # second poll: no recount
  assert metrics_lib.counter('checkpoint/torn_skipped').value == before + 1

  # restore() never touches the torn step — even explicitly.
  from tensor2robot_tpu.utils.mocks import MockT2RModel
  from tensor2robot_tpu.specs import numpy_gen
  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.train import Trainer, TrainerConfig

  model = MockT2RModel(device_type='tpu')
  trainer = Trainer(model, TrainerConfig(model_dir=str(tmp_path / 'm'),
                                         prefetch_batches=0))
  features = numpy_gen.make_random_numpy(
      model.preprocessor.get_in_feature_specification(ModeKeys.TRAIN),
      batch_size=8)
  trainer.initialize(features)
  assert trainer.step == 10  # restored the committed step, not the torn one
  with pytest.raises(RuntimeError, match='no commit marker'):
    trainer.checkpoint_manager.restore(trainer.state, step=20)


def test_legacy_directories_without_markers_stay_visible(tmp_path):
  # A pre-protocol directory (no markers anywhere) keeps PR-1 semantics.
  ckpt_dir = str(tmp_path / 'checkpoints')
  for step in (5, 7):
    os.makedirs(os.path.join(ckpt_dir, f'ckpt_{step}'))
  assert latest_checkpoint_step(ckpt_dir) == 7


def test_topology_mismatch_fails_loudly(tmp_path):
  ckpt_dir = _save_two_checkpoints(str(tmp_path / 'm'))

  # Same directory, different claimed topology: restore must refuse with
  # the recorded-vs-current detail, not silently misread the state.
  wrong = dict(mesh_lib.describe_topology(mesh_lib.single_device_mesh()))
  wrong['process_count'] = 4
  manager = CheckpointManager(ckpt_dir, topology=wrong)
  with pytest.raises(TopologyMismatchError, match='process_count'):
    manager.restore({'step': np.zeros(())})
  # topology=None (robot-host predictors, manual surgery) skips the check
  # at the manager level; the payload itself still restores.
  permissive = CheckpointManager(ckpt_dir, topology=None)
  assert permissive.latest_step() == 20


# ==================================================== unit: heartbeats


def _write_heartbeat(directory, host, age_sec, step=0, done=False):
  os.makedirs(directory, exist_ok=True)
  with open(os.path.join(directory, f'host_{host}.json'), 'w') as f:
    json.dump({'time': time.time() - age_sec, 'step': step, 'pid': 1,
               'process_index': host, 'done': done}, f)


def test_heartbeat_straggler_then_dead_flagging(tmp_path):
  hb_dir = str(tmp_path / 'hb')
  dead = []
  service = dist_lib.HeartbeatService(
      hb_dir, process_index=0, process_count=2,
      straggler_after_secs=5.0, dead_after_secs=60.0, action='flag',
      include_metrics=False, on_dead=lambda hosts: dead.extend(hosts))
  service.beat()
  before = metrics_lib.counter(
      'distributed/heartbeat/stragglers_flagged').value

  _write_heartbeat(hb_dir, host=1, age_sec=10.0, step=3)  # straggling
  ages = service.check_peers()
  assert 10.0 <= ages[1] < 12.0
  assert not service.dead_hosts
  assert metrics_lib.counter(
      'distributed/heartbeat/stragglers_flagged').value == before + 1
  service.check_peers()  # still straggling: no double count
  assert metrics_lib.counter(
      'distributed/heartbeat/stragglers_flagged').value == before + 1

  _write_heartbeat(hb_dir, host=1, age_sec=120.0, step=3)  # dead
  service.check_peers()
  assert service.dead_hosts == {1} and dead == [1]

  # A host that said goodbye (done) is never declared dead.
  _write_heartbeat(hb_dir, host=1, age_sec=120.0, step=9, done=True)
  service.dead_hosts.clear()
  service.check_peers()
  assert not service.dead_hosts


def test_heartbeat_aggregation_feeds_scalars_and_report(tmp_path):
  hb_dir = str(tmp_path / 'hb')
  os.makedirs(hb_dir)
  with open(os.path.join(hb_dir, 'host_1.json'), 'w') as f:
    json.dump({'time': time.time(), 'step': 7, 'pid': 2, 'process_index': 1,
               'metrics': {'data/records_read': 5,
                           'trainer/prefetch/queue_depth': 1.0}}, f)
  service = dist_lib.HeartbeatService(
      hb_dir, process_index=0, process_count=2, action='flag')
  marker = metrics_lib.counter('data/records_read')
  base = marker.value
  marker.inc(3)
  service.beat()
  merged = service.aggregate()
  # Our live registry + the peer's snapshot: counters summed.
  assert merged['data/records_read'] == base + 3 + 5
  assert merged['trainer/prefetch/queue_depth/host1'] == 1.0
  scalars = service.aggregated_scalars()
  assert scalars['cluster/data/records_read'] == float(base + 3 + 5)
  assert scalars['cluster/host1/step'] == 7.0
  report = service.cluster_report()
  assert report['hosts']['1']['step'] == 7
  assert report['process_count'] == 2


# ================================================ unit: export hardening


def test_export_commit_marker_and_torn_version_skipped(tmp_path):
  from tensor2robot_tpu.export import exporters as exporters_lib
  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.specs import numpy_gen
  from tensor2robot_tpu.train import Trainer, TrainerConfig
  from tensor2robot_tpu.utils.mocks import MockT2RModel

  model = MockT2RModel(device_type='tpu')
  trainer = Trainer(model, TrainerConfig(prefetch_batches=0))
  features = numpy_gen.make_random_numpy(
      model.preprocessor.get_in_feature_specification(ModeKeys.TRAIN),
      batch_size=2)
  trainer.initialize(features)
  root = str(tmp_path / 'export')
  exporter = exporters_lib.ModelExporter(serialize_serving=False)
  good = exporter.export(model, trainer.state, root, version=1000)
  assert os.path.exists(
      os.path.join(good, exporters_lib.EXPORT_COMMIT_FILENAME))

  # A NEWER version whose commit marker is missing (a replication that
  # died mid-flight) must be invisible to hot-reloading consumers.
  torn = os.path.join(root, '2000')
  shutil.copytree(good, torn)
  os.remove(os.path.join(torn, exporters_lib.EXPORT_COMMIT_FILENAME))
  before = metrics_lib.counter('export/uncommitted_skipped').value
  committed = exporters_lib.committed_export_dirs(root)
  assert committed == [good]
  assert metrics_lib.counter(
      'export/uncommitted_skipped').value == before + 1

  from tensor2robot_tpu.predictors.predictors import ExportedModelPredictor

  predictor = ExportedModelPredictor(export_dir=root, t2r_model=model)
  assert predictor.restore()
  assert predictor.model_path == good  # never the torn version


def test_predictor_falls_back_to_last_good_on_broken_reload(tmp_path):
  from tensor2robot_tpu.export import exporters as exporters_lib
  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.specs import numpy_gen
  from tensor2robot_tpu.train import Trainer, TrainerConfig
  from tensor2robot_tpu.predictors.predictors import ExportedModelPredictor
  from tensor2robot_tpu.utils.mocks import MockT2RModel

  model = MockT2RModel(device_type='tpu')
  trainer = Trainer(model, TrainerConfig(prefetch_batches=0))
  features = numpy_gen.make_random_numpy(
      model.preprocessor.get_in_feature_specification(ModeKeys.TRAIN),
      batch_size=2)
  trainer.initialize(features)
  root = str(tmp_path / 'export')
  exporter = exporters_lib.ModelExporter(serialize_serving=False)
  good = exporter.export(model, trainer.state, root, version=1000)

  predictor = ExportedModelPredictor(export_dir=root, t2r_model=model)
  assert predictor.restore()
  step_before = predictor.global_step

  # A newer version that LOOKS committed but whose payload is destroyed
  # (marker intact, state gutted): the reload fails, the predictor keeps
  # serving the last-good model and counts the fallback.
  broken = os.path.join(root, '2000')
  shutil.copytree(good, broken)
  shutil.rmtree(os.path.join(broken, exporters_lib.STATE_DIRNAME))
  os.makedirs(os.path.join(broken, exporters_lib.STATE_DIRNAME))
  before = metrics_lib.counter('predictor/load_fallbacks').value
  assert predictor.restore()  # no raise
  assert predictor.model_path == good
  assert predictor.global_step == step_before
  assert metrics_lib.counter('predictor/load_fallbacks').value == before + 1


def test_async_export_skips_already_exported_after_restart(tmp_path):
  from tensor2robot_tpu.export import exporters as exporters_lib
  from tensor2robot_tpu.export.async_export import AsyncExportCallback
  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.train import Trainer, TrainerConfig
  from tensor2robot_tpu.utils.mocks import MockInputGenerator, MockT2RModel

  model_dir = str(tmp_path / 'm')
  root = os.path.join(model_dir, 'export', 'latest_exporter_numpy')

  def run(max_steps):
    model = MockT2RModel(device_type='tpu')
    callback = AsyncExportCallback(asynchronous=False)
    config = TrainerConfig(
        model_dir=model_dir, max_train_steps=max_steps,
        save_interval_steps=1000, eval_interval_steps=0,
        log_interval_steps=0, prefetch_batches=0, async_checkpoints=False)
    trainer = Trainer(model, config, callbacks=[callback])
    gen = MockInputGenerator(batch_size=8)
    gen.set_specification_from_model(model, ModeKeys.TRAIN)
    trainer.train(gen.create_iterator(ModeKeys.TRAIN), None)

  run(4)
  assert exporters_lib.read_export_state(root)['last_exported_step'] == 4
  versions = exporters_lib.valid_export_dirs(root)
  assert len(versions) == 1

  # Training further exports the new step and advances the state.
  run(8)
  assert exporters_lib.read_export_state(root)['last_exported_step'] == 8
  assert len(exporters_lib.valid_export_dirs(root)) == 2

  # A restarted incarnation replaying an already-exported checkpoint
  # (after_checkpoint for a step at/below the persisted position) must
  # skip, count it, and leave the versions untouched.
  model = MockT2RModel(device_type='tpu')
  callback = AsyncExportCallback(asynchronous=False)
  config = TrainerConfig(model_dir=model_dir, prefetch_batches=0,
                         async_checkpoints=False)
  trainer = Trainer(model, config)
  from tensor2robot_tpu.specs import numpy_gen

  features = numpy_gen.make_random_numpy(
      model.preprocessor.get_in_feature_specification(ModeKeys.TRAIN),
      batch_size=8)
  trainer.initialize(features)
  versions = exporters_lib.valid_export_dirs(root)
  before = metrics_lib.counter('export/skipped_already_exported').value
  callback.after_checkpoint(trainer, step=4)
  assert exporters_lib.valid_export_dirs(root) == versions
  assert metrics_lib.counter(
      'export/skipped_already_exported').value == before + 1


# ======================================== real 2-process drills (bounded)

_WORKER = textwrap.dedent("""
    import json, os, sys
    os.environ['JAX_PLATFORMS'] = 'cpu'
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=2'

    import jax

    coordinator = sys.argv[1]
    pid = int(sys.argv[2])
    mode = sys.argv[3]   # 'preempt' | 'run' | 'kill' | 'race' | 'killsave'
    model_dir = sys.argv[4]
    max_steps = int(sys.argv[5])
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=2, process_id=pid,
                               local_device_ids=[0, 1])

    import hashlib
    import signal
    import numpy as np

    from tensor2robot_tpu.modes import ModeKeys
    from tensor2robot_tpu.models import optimizers as opt_lib
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    from tensor2robot_tpu.specs import SpecStruct
    from tensor2robot_tpu.train import (PreemptedError, Trainer,
                                        TrainerConfig,
                                        latest_checkpoint_step)
    from tensor2robot_tpu.train.distributed_resilience import DeadHostError
    from tensor2robot_tpu.utils import faults
    from tensor2robot_tpu.utils.mocks import MockT2RModel

    def make_batches(n, batch_size=8, seed=0):
      rng = np.random.RandomState(seed)
      batches = []
      for _ in range(n):
        points = rng.uniform(-1., 1., (batch_size, 2)).astype(np.float32)
        labels = (points.sum(axis=1) > 0).astype(np.float32)
        f = SpecStruct(); f['measured_position'] = points
        l = SpecStruct(); l['valid_position'] = labels
        batches.append((f, l))
      return batches

    mesh = mesh_lib.create_local_mesh(data=-1)
    model = MockT2RModel(
        device_type='tpu',
        create_optimizer_fn=lambda: opt_lib.create_adam_optimizer(1e-2))
    start = latest_checkpoint_step(
        os.path.join(model_dir, 'checkpoints')) or 0
    batches = make_batches(max_steps)[start:]
    if start:
      # On resume the trainer pulls one batch as a shape probe and DROPS
      # it (an InputStateCallback would rewind under it); sacrifice a
      # copy so training still consumes exactly batches[start:].
      batches = [batches[0]] + batches

    callbacks = []
    if mode == 'preempt':
      # Throttle BOTH hosts so neither can race to completion before the
      # proposal lands — the drill must exercise the mid-run stop path.
      callbacks.append(
          faults.DelayDispatchCallback(at_step=1, delay_secs=0.1))
      if pid == 0:
        # Real OS SIGTERM to exactly ONE process, mid-run.
        callbacks.append(
            faults.PreemptionCallback(at_step=start + 3,
                                      signum=signal.SIGTERM))
    if mode == 'kill':
      if pid == 1:
        callbacks.append(faults.KillSelfCallback(at_step=3))
      else:
        # Keep the survivor busy so death is detected mid-training.
        callbacks.append(
            faults.DelayDispatchCallback(at_step=1, delay_secs=0.25))
    if mode == 'race':
      # Completed-host vs late-proposal race: host 1 runs full speed and
      # COMPLETES (publishing its final boundary, then waiting in the
      # final-save barriers) while throttled host 0 is still mid-run;
      # host 0's SIGTERM then lands as a LATE proposal against a host
      # that will never poll again. The negotiation must converge on the
      # completed host's published final step — not time out as a
      # DeadHostError.
      if pid == 0:
        callbacks.append(
            faults.DelayDispatchCallback(at_step=1, delay_secs=0.15))
        callbacks.append(
            faults.PreemptionCallback(at_step=start + 10,
                                      signum=signal.SIGTERM))
    if mode == 'killsave' and pid == 1:
      # SIGKILL INSIDE the sharded payload write of the step-12 save:
      # the write started on both hosts, no ack was ever written.
      faults.install_kill_during_save(at_step=12)

    fast_liveness = mode in ('kill', 'killsave')
    config = TrainerConfig(
        model_dir=model_dir,
        max_train_steps=max_steps,
        save_interval_steps=6 if mode in ('killsave', 'run_saves')
                            else 10 ** 6,  # forced/final saves only
        eval_interval_steps=0,
        log_interval_steps=0,
        prefetch_batches=0,
        handle_preemption=True,
        checkpoint_sharded_payloads='on',
        checkpoint_barrier_timeout_secs=8.0 if mode == 'killsave'
                                        else 600.0,
        heartbeat_interval_secs=0.25 if fast_liveness else 1.0,
        heartbeat_straggler_secs=0.8 if fast_liveness else 10.0,
        liveness_timeout_secs=2.5 if fast_liveness else 60.0)
    trainer = Trainer(model, config, mesh=mesh, callbacks=callbacks)
    # Align the two hosts' training starts (process spawn + import skew
    # would otherwise let one host get steps ahead before the other
    # begins), so the fault schedules below hit mid-run on both.
    jax._src.distributed.global_state.client.wait_at_barrier(
        't2r_drill_start', 60000)
    try:
      trainer.train(iter(batches), None)
    except PreemptedError as e:
      print(json.dumps({'pid': pid, 'mode': mode, 'preempted_at': e.step,
                        'start': start}), flush=True)
      sys.exit(e.exit_code)
    except DeadHostError as e:
      print(json.dumps({'pid': pid, 'mode': mode, 'dead_host': str(e),
                        'start': start}), flush=True)
      sys.exit(e.exit_code)
    state = jax.device_get(trainer.state)
    digest = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(state.params):
      digest.update(np.ascontiguousarray(leaf).tobytes())
    print(json.dumps({'pid': pid, 'mode': mode, 'step': trainer.step,
                      'start': start, 'hash': digest.hexdigest()}),
          flush=True)
""")


def _run_two_workers(mode, model_dir, max_steps, timeout=90):
  """Launches the 2-process jax.distributed harness; returns (rc, out)."""
  port = socket.socket()
  port.bind(('127.0.0.1', 0))
  coordinator = f'127.0.0.1:{port.getsockname()[1]}'
  port.close()
  env = dict(os.environ)
  env['PYTHONPATH'] = REPO + os.pathsep + env.get('PYTHONPATH', '')
  env.pop('JAX_PLATFORMS', None)
  env.pop('XLA_FLAGS', None)
  procs = [
      subprocess.Popen(
          [sys.executable, '-c', _WORKER, coordinator, str(pid), mode,
           model_dir, str(max_steps)],
          stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env)
      for pid in (0, 1)
  ]
  outputs = []
  deadline = time.time() + timeout
  for proc in procs:
    try:
      out, _ = proc.communicate(timeout=max(1.0, deadline - time.time()))
    except subprocess.TimeoutExpired:
      proc.kill()
      out, _ = proc.communicate()
      pytest.fail(f'worker hung past {timeout}s (the one failure mode the '
                  f'subsystem exists to prevent): {out.decode()[-2000:]}')
    outputs.append(out.decode())
  return [p.returncode for p in procs], outputs


def _last_json(output):
  for line in reversed(output.strip().splitlines()):
    try:
      return json.loads(line)
    except ValueError:
      continue
  raise AssertionError(f'no JSON line in worker output:\n{output[-2000:]}')


@pytest.fixture(scope='module')
def sigterm_drill(tmp_path_factory):
  """Runs the coordinated-SIGTERM drill once: interrupt, resume, reference.

  Returns everything the assertions below need, so the (expensive)
  2-process phases run a single time for the whole module.
  """
  base = tmp_path_factory.mktemp('sigterm_drill')
  interrupted_dir = str(base / 'interrupted')
  reference_dir = str(base / 'reference')

  # Phase 1: SIGTERM to process 0 only → both must exit 42 together.
  rcs, outs = _run_two_workers('preempt', interrupted_dir, max_steps=30)
  phase1 = [_last_json(o) for o in outs]
  ckpt_dir = os.path.join(interrupted_dir, 'checkpoints')
  stop_step = phase1[0].get('preempted_at')

  # Inject a NEWER uncommitted checkpoint before the restart: the torn
  # step must never be restored (acceptance criterion).
  if stop_step is not None and os.path.isdir(
      os.path.join(ckpt_dir, f'ckpt_{stop_step}')):
    torn = os.path.join(ckpt_dir, f'ckpt_{stop_step + 5}')
    shutil.copytree(os.path.join(ckpt_dir, f'ckpt_{stop_step}'), torn)
    os.remove(os.path.join(torn, ckpt_lib.COMMIT_FILENAME))

  # Phase 2: restart both processes; they resume and run to completion.
  rcs2, outs2 = _run_two_workers('run', interrupted_dir, max_steps=30)
  phase2 = [_last_json(o) for o in outs2]

  # Phase 3: uninterrupted 2-process reference run.
  rcs3, outs3 = _run_two_workers('run', reference_dir, max_steps=30)
  phase3 = [_last_json(o) for o in outs3]

  return {
      'rcs': (rcs, rcs2, rcs3),
      'outs': (outs, outs2, outs3),
      'phases': (phase1, phase2, phase3),
      'ckpt_dir': ckpt_dir,
      'stop_step': stop_step,
  }


def test_coordinated_sigterm_both_hosts_commit_same_step(sigterm_drill):
  rcs, _, _ = sigterm_drill['rcs']
  phase1, _, _ = sigterm_drill['phases']
  outs1 = sigterm_drill['outs'][0]
  assert rcs == [42, 42], outs1  # BOTH exit resumable, not just the signaled one
  steps = {p['preempted_at'] for p in phase1}
  assert len(steps) == 1, phase1  # the SAME agreed stop step on both hosts
  stop_step = steps.pop()
  # The forced checkpoint is COMMITTED with both hosts acked.
  marker = ckpt_lib.read_commit_marker(sigterm_drill['ckpt_dir'], stop_step)
  assert marker is not None, os.listdir(sigterm_drill['ckpt_dir'])
  assert marker['hosts'] == [0, 1]
  assert marker['topology']['process_count'] == 2


def test_coordinated_resume_is_bit_exact_and_skips_torn_step(sigterm_drill):
  _, rcs2, rcs3 = sigterm_drill['rcs']
  _, phase2, phase3 = sigterm_drill['phases']
  assert rcs2 == [0, 0] and rcs3 == [0, 0], sigterm_drill['outs']
  stop_step = sigterm_drill['stop_step']
  for p in phase2:
    assert p['start'] == stop_step  # resumed from the committed step —
    # NOT from the newer uncommitted directory injected before restart
    assert p['step'] == 30
  # Bit-exact: interrupted+resumed === uninterrupted, on every host.
  for resumed, reference in zip(phase2, phase3):
    assert resumed['hash'] == reference['hash'], (phase2, phase3)


def test_kill_one_host_survivor_exits_with_liveness_error(tmp_path):
  rcs, outs = _run_two_workers('kill', str(tmp_path / 'm'), max_steps=400,
                               timeout=75)
  # Host 1 died by SIGKILL; host 0 must exit with the liveness status and
  # a clear error — within the bounded timeout, never a hang.
  assert rcs[1] == -signal.SIGKILL, outs[1]
  assert rcs[0] == dist_lib.LIVENESS_EXIT_CODE, (rcs, outs[0][-2000:])
  assert 'LIVENESS' in outs[0] and 'host 1' in outs[0]


def test_two_host_checkpoint_refuses_single_host_restore_strict(
    sigterm_drill):
  # Restore the drill's committed 2-host checkpoint from THIS (single)
  # process in STRICT mode (reshape off): the topology mismatch must
  # fail loudly and actionably — and name the elastic escape hatch.
  topology = mesh_lib.describe_topology(
      mesh_lib.single_device_mesh(), grad_accum_microbatches=1,
      steps_per_dispatch=1)
  assert topology['process_count'] == 1
  manager = CheckpointManager(sigterm_drill['ckpt_dir'], topology=topology)
  with pytest.raises(TopologyMismatchError) as excinfo:
    manager.restore({'step': np.zeros(())})
  message = str(excinfo.value)
  assert 'process_count' in message and 'checkpoint has 2' in message
  assert 'checkpoint_topology_check' in message  # actionable override
  assert 'reshape' in message  # the elastic path is advertised


# ======================================= elastic topology: sharded + reshape


def _drill_state_template():
  """A TrainState structurally identical to the drill workers' (same
  model + optimizer), for restoring their checkpoints in-process."""
  from tensor2robot_tpu.models import optimizers as opt_lib
  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.specs import numpy_gen
  from tensor2robot_tpu.train import Trainer, TrainerConfig
  from tensor2robot_tpu.utils.mocks import MockT2RModel

  model = MockT2RModel(
      device_type='tpu',
      create_optimizer_fn=lambda: opt_lib.create_adam_optimizer(1e-2))
  trainer = Trainer(model, TrainerConfig(prefetch_batches=0))
  features = numpy_gen.make_random_numpy(
      model.preprocessor.get_in_feature_specification(ModeKeys.TRAIN),
      batch_size=8)
  trainer.initialize(features)
  return trainer.state


def _params_hash(state) -> str:
  digest = hashlib.sha256()
  for leaf in jax.tree_util.tree_leaves(jax.device_get(state.params)):
    digest.update(np.ascontiguousarray(leaf).tobytes())
  return digest.hexdigest()


def test_sharded_payload_written_by_all_hosts(sigterm_drill):
  """The drill checkpoints are genuinely multi-writer: the commit marker
  records the sharded format + both hosts' shards, and the Orbax payload
  carries both processes' ocdbt stores."""
  ckpt_dir = sigterm_drill['ckpt_dir']
  step = sigterm_drill['stop_step']
  marker = ckpt_lib.read_commit_marker(ckpt_dir, step)
  assert marker is not None
  assert marker['format'] == ckpt_lib.FORMAT_SHARDED
  assert sorted(marker['shards']) == ['0', '1']
  payload = os.path.join(ckpt_dir, f'ckpt_{step}', 'default')
  assert os.path.isdir(os.path.join(payload, 'ocdbt.process_0')), (
      os.listdir(payload))
  assert os.path.isdir(os.path.join(payload, 'ocdbt.process_1')), (
      os.listdir(payload))


def test_two_host_sharded_checkpoint_reshards_onto_one_host(sigterm_drill):
  """The acceptance drill for resharding restore: a checkpoint written
  by TWO hosts' sharded writers restores onto THIS single-process,
  single-device mesh with reshape=True, bit-exact — sha256 of the
  restored params equals the 2-host run's own final-state hash."""
  _, phase2, _ = sigterm_drill['phases']
  final_step = phase2[0]['step']
  two_host_hash = phase2[0]['hash']
  assert phase2[1]['hash'] == two_host_hash  # both hosts agreed already

  mesh = mesh_lib.single_device_mesh()
  topology = mesh_lib.describe_topology(
      mesh, grad_accum_microbatches=1, steps_per_dispatch=1)
  before = metrics_lib.counter('checkpoint/reshaped_restores').value
  manager = CheckpointManager(
      sigterm_drill['ckpt_dir'], topology=topology, reshape=True, mesh=mesh)
  restored = manager.restore(_drill_state_template(), step=final_step)
  assert int(jax.device_get(restored.step)) == final_step
  assert _params_hash(restored) == two_host_hash
  assert metrics_lib.counter(
      'checkpoint/reshaped_restores').value == before + 1


def test_inspect_checkpoint_tool_reports_topology_and_shards(sigterm_drill):
  """tools/inspect_checkpoint.py — the operator half of resharding
  restore: topology, ack set, shard layout and verdicts, as JSON."""
  proc = subprocess.run(
      [sys.executable, os.path.join(REPO, 'tools', 'inspect_checkpoint.py'),
       sigterm_drill['ckpt_dir'], '--json'],
      capture_output=True, text=True, timeout=60)
  assert proc.returncode == 0, proc.stdout + proc.stderr
  report = json.loads(proc.stdout)
  assert report['protocol_active']
  by_step = {s['step']: s for s in report['steps']}
  stop = by_step[sigterm_drill['stop_step']]
  assert stop['verdict'] == 'committed'
  assert stop['format'] == ckpt_lib.FORMAT_SHARDED
  assert stop['topology']['process_count'] == 2
  assert sorted(stop['shard_layout']['process_stores']) == ['0', '1']
  assert sorted(a['process_index'] for a in stop['acks']
                if not a.get('stale')) == [0, 1]
  # The torn step injected before the restart reads as TORN.
  torn_step = sigterm_drill['stop_step'] + 5
  if torn_step in by_step:
    assert by_step[torn_step]['verdict'] == 'torn'
    assert torn_step in report['torn_steps']
  assert report['latest_restorable_step'] == max(by_step)


def test_reshape_still_raises_on_semantic_mismatch(sigterm_drill):
  # reshape demotes ONLY the host/mesh-layout keys: a microbatch-config
  # mismatch changes what the state means and must still fail loudly.
  topology = mesh_lib.describe_topology(
      mesh_lib.single_device_mesh(), grad_accum_microbatches=2,
      steps_per_dispatch=1)
  manager = CheckpointManager(
      sigterm_drill['ckpt_dir'], topology=topology, reshape=True,
      mesh=mesh_lib.single_device_mesh())
  with pytest.raises(TopologyMismatchError, match='grad_accum'):
    manager.restore({'step': np.zeros(())})


@pytest.fixture(scope='module')
def killsave_drill(tmp_path_factory):
  """Kill one host INSIDE the sharded payload write, then restart.

  Phase 1 ('killsave'): interval saves every 6 steps; step 6 commits
  normally, and host 1 SIGKILLs itself inside the step-12 write. Phase 2
  ('run_saves'): both processes restart against the same directory.
  """
  model_dir = str(tmp_path_factory.mktemp('killsave') / 'm')
  rcs, outs = _run_two_workers('killsave', model_dir, max_steps=30,
                               timeout=75)
  ckpt_dir = os.path.join(model_dir, 'checkpoints')
  # Snapshot the torn state BEFORE the restart rewrites the step dir.
  phase1 = {
      'rcs': rcs,
      'outs': outs,
      'committed_6': ckpt_lib.read_commit_marker(ckpt_dir, 6),
      'step12_exists': os.path.isdir(os.path.join(ckpt_dir, 'ckpt_12')),
      'step12_marker': ckpt_lib.read_commit_marker(ckpt_dir, 12),
      'latest_committed': latest_checkpoint_step(ckpt_dir),
  }
  rcs2, outs2 = _run_two_workers('run_saves', model_dir, max_steps=30,
                                 timeout=90)
  return {
      'phase1': phase1,
      'rcs2': rcs2,
      'outs2': outs2,
      'phase2': [_last_json(o) for o in outs2],
      'ckpt_dir': ckpt_dir,
  }


def test_kill_during_sharded_save_leaves_step_invisible(killsave_drill):
  p1 = killsave_drill['phase1']
  rcs = p1['rcs']
  # Host 1 died by SIGKILL inside the payload write; host 0's exit is
  # BOUNDED and loud (barrier-timeout DeadHostError or heartbeat
  # liveness — both status 43), never a hang or a committed torn step.
  assert rcs[1] == -signal.SIGKILL, p1['outs'][1][-2000:]
  assert rcs[0] == dist_lib.LIVENESS_EXIT_CODE, (rcs, p1['outs'][0][-2000:])
  assert p1['committed_6'] is not None            # the prior save committed
  assert p1['committed_6']['format'] == ckpt_lib.FORMAT_SHARDED
  assert p1['step12_exists']                      # the write STARTED...
  assert p1['step12_marker'] is None              # ...but never committed
  assert p1['latest_committed'] == 6              # torn step invisible


def test_restart_after_killed_save_resumes_from_last_committed(
    killsave_drill):
  rcs2 = killsave_drill['rcs2']
  phase2 = killsave_drill['phase2']
  assert rcs2 == [0, 0], killsave_drill['outs2']
  for p in phase2:
    assert p['start'] == 6    # resumed from the COMMITTED step, not 12
    assert p['step'] == 30
  # The restart re-saved into the dirty step-12 dir (stale orbax tmp
  # dirs, no stale acks can satisfy the fresh incarnation) and committed
  # it cleanly this time.
  marker12 = ckpt_lib.read_commit_marker(killsave_drill['ckpt_dir'], 12)
  assert marker12 is not None and marker12['hosts'] == [0, 1]
  assert latest_checkpoint_step(killsave_drill['ckpt_dir']) == 30


def test_completed_host_late_proposal_converges(tmp_path):
  """The completed-host vs late-proposal SIGTERM race (ROADMAP carried
  follow-up): host 1 finishes and waits in its final-save barriers while
  throttled host 0 is still mid-run; host 0's SIGTERM then proposes a
  stop that host 1 will never poll for. The published-final-boundary fix
  converges the negotiation on host 1's final step — both hosts commit
  the SAME final checkpoint and exit cleanly, instead of the pre-fix
  bounded DeadHostError + liveness exit."""
  model_dir = str(tmp_path / 'm')
  rcs, outs = _run_two_workers('race', model_dir, max_steps=20, timeout=75)
  payloads = [_last_json(o) for o in outs]
  assert rcs == [0, 0], (rcs, outs)
  for p in payloads:
    assert p['step'] == 20, payloads
  assert 'Coordinated stop agreed' in outs[0]
  assert 'DeadHostError' not in outs[0] and 'LIVENESS' not in outs[0]
  marker = ckpt_lib.read_commit_marker(
      os.path.join(model_dir, 'checkpoints'), 20)
  assert marker is not None and marker['hosts'] == [0, 1]


# ===================== unit: async commit + survivors (fake 2-host fabric)


class _FakeContext:
  """An in-process 2-"host" coordination fabric (threads, not processes)
  compatible with everything CheckpointManager / CoordinatedShutdown use:
  first-wins KV store, blocking get, prefix listing, paired barriers."""

  class _Shared:

    def __init__(self, process_count):
      self.process_count = process_count
      self.kv = {}
      self.lock = threading.Lock()
      self.barriers = {}

  def __init__(self, shared, process_index):
    self._shared = shared
    self.process_index = int(process_index)
    self.process_count = shared.process_count

  @classmethod
  def pair(cls):
    shared = cls._Shared(2)
    return cls(shared, 0), cls(shared, 1)

  @property
  def is_primary(self):
    return self.process_index == 0

  def put(self, key, value):
    with self._shared.lock:
      if key in self._shared.kv:
        return False
      self._shared.kv[key] = str(value)
      return True

  def get(self, key, timeout_secs):
    deadline = time.monotonic() + timeout_secs
    while time.monotonic() < deadline:
      with self._shared.lock:
        if key in self._shared.kv:
          return self._shared.kv[key]
      time.sleep(0.005)
    return None

  def get_dir(self, prefix):
    with self._shared.lock:
      return {k: v for k, v in self._shared.kv.items()
              if k.startswith(prefix)}

  def barrier(self, name, timeout_secs, participants=None):
    parties = len(participants) if participants else self.process_count
    key = (name, tuple(participants or ()))
    with self._shared.lock:
      bar = self._shared.barriers.setdefault(
          key, threading.Barrier(parties))
    try:
      bar.wait(timeout=timeout_secs)
    except threading.BrokenBarrierError as e:
      raise dist_lib.DeadHostError(
          f'fake barrier {name!r} timed out') from e


class _FakeShutdown:

  def __init__(self, requested=False):
    self.requested = requested

  def request(self):
    self.requested = True


def _run_on_hosts(*fns):
  """Runs one callable per fake host on parallel threads; re-raises."""
  errors = []

  def wrap(fn):
    try:
      fn()
    except BaseException as e:  # pylint: disable=broad-except
      errors.append(e)

  threads = [threading.Thread(target=wrap, args=(fn,)) for fn in fns]
  for t in threads:
    t.start()
  for t in threads:
    t.join(timeout=60)
  if errors:
    raise errors[0]


def _fake_state():
  return {'w': np.arange(8, dtype=np.float32), 'b': np.float32(0.5) * 0}


def test_async_commit_marker_rides_later_poll(tmp_path):
  ckpt_dir = str(tmp_path / 'ckpts')
  ctx0, ctx1 = _FakeContext.pair()
  m0 = CheckpointManager(ckpt_dir, async_save=False, distributed=ctx0,
                         async_commit=True, barrier_timeout_secs=20.0)
  m1 = CheckpointManager(ckpt_dir, async_save=False, distributed=ctx1,
                         async_commit=True, barrier_timeout_secs=20.0)
  state = _fake_state()

  # A first SYNC save activates the commit protocol in the directory
  # (so the async in-flight step below is invisible, not legacy).
  _run_on_hosts(lambda: m0.save(5, state, force=True, sync=True),
                lambda: m1.save(5, state, force=True, sync=True))
  assert latest_checkpoint_step(ckpt_dir) == 5

  # Async save: both hosts return immediately; the marker is NOT yet
  # published and the in-flight step stays invisible...
  assert m0.save(10, state, force=True)
  assert m1.save(10, state, force=True)
  overlap_before = metrics_lib.histogram(
      'checkpoint/save_overlap_ms').count
  # ...until the primary's boundary polls observe every ack durable.
  deadline = time.monotonic() + 20
  committed = False
  while time.monotonic() < deadline and not committed:
    committed = m0.poll_async_commit()
    time.sleep(0.01)
  assert committed, 'async commit never completed'
  marker = ckpt_lib.read_commit_marker(ckpt_dir, 10)
  assert marker is not None and marker['hosts'] == [0, 1]
  assert latest_checkpoint_step(ckpt_dir) == 10
  assert metrics_lib.histogram(
      'checkpoint/save_overlap_ms').count == overlap_before + 1
  # The forced sync path (shutdown) is a no-op once committed, and the
  # barriers still pair up on both hosts.
  _run_on_hosts(m0.wait_until_finished, m1.wait_until_finished)
  _run_on_hosts(m0.close, m1.close)


def test_async_commit_stale_acks_never_commit_early(tmp_path):
  """The satellite edge case: a previous incarnation's host_ack files in
  the same step dir must not let the async commit publish a marker
  before THIS incarnation's writes are durable."""
  ckpt_dir = str(tmp_path / 'ckpts')
  ctx0, ctx1 = _FakeContext.pair()
  m0 = CheckpointManager(ckpt_dir, async_save=False, distributed=ctx0,
                         async_commit=True, barrier_timeout_secs=20.0)
  m1 = CheckpointManager(ckpt_dir, async_save=False, distributed=ctx1,
                         async_commit=True, barrier_timeout_secs=20.0)
  state = _fake_state()
  _run_on_hosts(lambda: m0.save(5, state, force=True, sync=True),
                lambda: m1.save(5, state, force=True, sync=True))

  # Plant a full set of STALE acks (previous incarnation) for step 10.
  step_dir = os.path.join(ckpt_dir, 'ckpt_10')
  os.makedirs(step_dir)
  for host in (0, 1):
    with open(os.path.join(step_dir, f'host_ack_{host}.json'), 'w') as f:
      json.dump({'process_index': host, 'step': 10, 'pid': 1,
                 'incarnation': 'dead-previous-attempt'}, f)

  # Only host 0 saves: its fresh ack lands, host 1's stale one must NOT
  # count — no marker, the step stays invisible.
  assert m0.save(10, state, force=True)
  deadline = time.monotonic() + 3
  while time.monotonic() < deadline:
    assert not m0.poll_async_commit()
    time.sleep(0.05)
  assert ckpt_lib.read_commit_marker(ckpt_dir, 10) is None
  assert latest_checkpoint_step(ckpt_dir) == 5

  # Host 1's real save completes the set; the poll commits with BOTH
  # fresh acks (stale ones replaced/ignored).
  assert m1.save(10, state, force=True)
  deadline = time.monotonic() + 20
  committed = False
  while time.monotonic() < deadline and not committed:
    committed = m0.poll_async_commit()
    time.sleep(0.01)
  assert committed
  assert ckpt_lib.read_commit_marker(ckpt_dir, 10)['hosts'] == [0, 1]
  _run_on_hosts(m0.wait_until_finished, m1.wait_until_finished)
  _run_on_hosts(m0.close, m1.close)


def test_sync_commit_ignores_stale_acks_from_previous_incarnation(tmp_path):
  ckpt_dir = str(tmp_path / 'ckpts')
  ctx0, ctx1 = _FakeContext.pair()
  m0 = CheckpointManager(ckpt_dir, async_save=False, distributed=ctx0)
  m1 = CheckpointManager(ckpt_dir, async_save=False, distributed=ctx1)
  # Stale leftovers: an ack from a dead attempt AND one naming a host
  # that does not even exist in this 2-process incarnation.
  step_dir = os.path.join(ckpt_dir, 'ckpt_7')
  os.makedirs(step_dir)
  for host in (1, 5):
    with open(os.path.join(step_dir, f'host_ack_{host}.json'), 'w') as f:
      json.dump({'process_index': host, 'step': 7, 'pid': 1,
                 'incarnation': 'dead-previous-attempt'}, f)
  state = _fake_state()
  _run_on_hosts(lambda: m0.save(7, state, force=True, sync=True),
                lambda: m1.save(7, state, force=True, sync=True))
  marker = ckpt_lib.read_commit_marker(ckpt_dir, 7)
  # Committed over exactly this incarnation's acks: the ghost host 5
  # never appears, and host 1's entry is the fresh ack, not the stale.
  assert marker is not None and marker['hosts'] == [0, 1]
  assert sorted(marker['shards']) == ['0', '1']
  assert m0._read_acks(7, incarnation='dead-previous-attempt').keys() <= {
      1, 5}
  _run_on_hosts(m0.close, m1.close)


def test_survivor_commit_after_peer_completed(tmp_path):
  """set_participants([survivor]) lets the still-running host commit its
  preemption checkpoint after the peer completed and exited — including
  taking over the payload-writer role from the departed primary."""
  ckpt_dir = str(tmp_path / 'ckpts')
  _, ctx1 = _FakeContext.pair()
  m1 = CheckpointManager(ckpt_dir, async_save=False, distributed=ctx1)
  m1.set_participants([1])
  assert m1.save(9, _fake_state(), force=True, sync=True)
  marker = ckpt_lib.read_commit_marker(ckpt_dir, 9)
  assert marker is not None and marker['hosts'] == [1]
  assert latest_checkpoint_step(ckpt_dir) == 9
  m1.close()


def test_negotiation_uses_completed_hosts_published_boundary():
  ctx0, ctx1 = _FakeContext.pair()
  # Host 0 completed at step 30 and published unconditionally (the
  # trainer's completion path); it will never poll again.
  done = dist_lib.CoordinatedShutdown(ctx0, _FakeShutdown())
  done.publish_boundary(30)
  # Host 1's late SIGTERM at step 25 converges on 30 without host 0.
  cs = dist_lib.CoordinatedShutdown(ctx1, _FakeShutdown(requested=True))
  assert cs.poll(25) == 30
  assert cs.participants == [0, 1]


def test_negotiation_retries_once_against_surviving_hosts():
  _, ctx1 = _FakeContext.pair()
  before = metrics_lib.counter('distributed/negotiation_retries').value
  cs = dist_lib.CoordinatedShutdown(
      ctx1, _FakeShutdown(requested=True), negotiate_timeout_secs=5.0,
      peer_heartbeats=lambda: {0: {'done': True, 'step': 30}})
  # Host 0 exited before the proposal, never published — but its goodbye
  # heartbeat proves an orderly completion, so the negotiation retries
  # against the survivors instead of escalating.
  assert cs.poll(25) == 25
  assert cs.participants == [1]
  assert metrics_lib.counter(
      'distributed/negotiation_retries').value == before + 1


def test_negotiation_escalates_when_missing_host_not_done():
  _, ctx1 = _FakeContext.pair()
  cs = dist_lib.CoordinatedShutdown(
      ctx1, _FakeShutdown(requested=True), negotiate_timeout_secs=0.4,
      peer_heartbeats=lambda: {0: {'done': False, 'step': 3}})
  with pytest.raises(dist_lib.DeadHostError, match='negotiation'):
    cs.poll(25)


# =============================== unit: commit-marker edge cases (satellite)


def test_latest_checkpoint_step_mixed_sharded_and_legacy_dirs(tmp_path):
  d = str(tmp_path / 'ckpts')
  os.makedirs(os.path.join(d, 'ckpt_3'))   # legacy marker-less dir
  os.makedirs(os.path.join(d, 'ckpt_7'))   # single-writer, committed
  ckpt_lib.write_commit_marker(
      d, 7, extra={'format': ckpt_lib.FORMAT_SINGLE_WRITER})
  os.makedirs(os.path.join(d, 'ckpt_9'))   # sharded, committed
  ckpt_lib.write_commit_marker(
      d, 9, hosts=[0, 1], extra={'format': ckpt_lib.FORMAT_SHARDED})
  # Both marker formats are first-class; the marker-less dir is torn
  # (markers exist in the directory, so the legacy rule is off).
  assert latest_checkpoint_step(d) == 9
  faults.remove_commit_marker(d, 9)
  assert latest_checkpoint_step(d) == 7
  before = metrics_lib.counter('checkpoint/torn_skipped').value
  assert latest_checkpoint_step(d) == 7  # re-polling never recounts
  assert metrics_lib.counter('checkpoint/torn_skipped').value == before


def test_restore_unaffected_by_stale_acks_next_to_marker(tmp_path):
  """A committed step dir can accumulate stale acks from a previous
  incarnation of the SAME step (crash between payload and commit, then a
  successful retry): restore and visibility only consult the marker."""
  model_dir = str(tmp_path / 'm')
  ckpt_dir = _save_two_checkpoints(model_dir)
  stale = os.path.join(ckpt_dir, 'ckpt_20', 'host_ack_3.json')
  with open(stale, 'w') as f:
    json.dump({'process_index': 3, 'step': 20, 'pid': 1,
               'incarnation': 'dead-previous-attempt'}, f)
  assert latest_checkpoint_step(ckpt_dir) == 20
  marker = ckpt_lib.read_commit_marker(ckpt_dir, 20)
  assert marker is not None and 3 not in marker['hosts']

  # An end-to-end restore (trainer resume) is untouched by the stray ack.
  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.specs import numpy_gen
  from tensor2robot_tpu.train import Trainer, TrainerConfig
  from tensor2robot_tpu.utils.mocks import MockT2RModel

  model = MockT2RModel(device_type='tpu')
  trainer = Trainer(model, TrainerConfig(model_dir=model_dir,
                                         prefetch_batches=0))
  features = numpy_gen.make_random_numpy(
      model.preprocessor.get_in_feature_specification(ModeKeys.TRAIN),
      batch_size=8)
  trainer.initialize(features)
  assert trainer.step == 20
