#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once through the binaries a user would call, at
the full published width of the flagship model (the QT-Opt Grasping44
critic exactly as ``research/qtopt/configs/train_qtopt.gin`` wires it:
472x472 crops of 512x640 frames, 64-channel towers, bf16 compute, batch
32, 8 steps per dispatch, weights random from the config's seed):

  native  builds and loads the C++ record reader and JPEG decoder
  train   ``python -m tensor2robot_tpu.bin.run_t2r_trainer`` for a few
          dispatches, one checkpoint, one export
  serve   ``python -m tensor2robot_tpu.bin.run_serving`` on that export;
          a few ``/v1/predict`` requests over stdlib HTTP, then SIGTERM

and checks what came out by the repo's own means: the trainer's
``run_report.json``, the committed checkpoint and export, the server's
``/statz``. One JSON line per phase, then the contract's last line:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as the child that ran the steps reported it. Any phase
that fails, overruns its time limit, reports another platform than the
chip's, or ran a degraded path makes the script exit non-zero without
that line.

A chip belongs to one process at a time, so THIS process imports no jax
and nothing that imports jax: each phase is a child, one after another.
Children are told ``JAX_PLATFORMS=tpu``; where there is no chip jax
fails in them at start-up, and so does this script.

  python chip_smoke.py               one chip (as the driver runs it)
  python chip_smoke.py --multichip   four chips: the sharded train step
                                     against one device, nothing else
  python chip_smoke.py --rehearse    tiny shapes on the CPU, to find
                                     wrong paths before chip time is
                                     spent; can never report a tpu

The numbers in the phase lines are observations of one run (host clocks,
one sample), not metrics: the benchmark is where metrics are defined.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
GIN_CONFIG = 'tensor2robot_tpu/research/qtopt/configs/train_qtopt.gin'
# Child commands (module-level so tests/test_chip_smoke.py can put stub
# children in their place).
TRAIN_CMD = [sys.executable, '-m', 'tensor2robot_tpu.bin.run_t2r_trainer']
SERVE_CMD = [sys.executable, '-m', 'tensor2robot_tpu.bin.run_serving']
MULTICHIP_CMD = [sys.executable, os.path.abspath(__file__),
                 '--child-multichip']
# The whole script must end inside the contract's 1200 s, compilation
# included; each phase gets its own limit or what is left, if less.
DEADLINE_S = 1150.0
TRAIN_LIMIT_S = 800.0
SERVE_START_LIMIT_S = 400.0
SERVE_DRAIN_LIMIT_S = 60.0
MULTICHIP_LIMIT_S = 1100.0

STEPS_PER_DISPATCH = 8  # train_qtopt.gin's; checked against the report
DISPATCHES = 5
BATCH = 32
# --rehearse: the model sizes __graft_entry__ rehearses with.
TINY_MODEL = {'input_shape': (96, 112, 3), 'target_shape': (80, 80),
              'num_convs': (2, 2, 1)}
TINY_BATCH = 4
REQUEST_EXAMPLES = (1, 1, 2, 1)  # examples per /v1/predict request
SEED = 0
FEW_DISPATCHES_LOSS_RTOL = 5e-2  # --multichip; multichip_child says why


class PhaseFailed(Exception):
  """A phase failed, timed out, or ran somewhere or somehow it should not."""


class Run:
  """One invocation: where it writes, what its children inherit, and
  the children it must not leave behind."""

  def __init__(self, out_dir: str, rehearse: bool, chips: int):
    self.out_dir = out_dir
    self.rehearse = rehearse
    self.platform = 'cpu' if rehearse else 'tpu'
    self.chips = chips
    self.model_dir = os.path.join(out_dir, 'model')
    self.started = time.monotonic()
    self.children = []
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = self.platform
    env['PYTHONPATH'] = HERE + os.pathsep + env.get('PYTHONPATH', '')
    env['PYTHONUNBUFFERED'] = '1'
    if rehearse and chips > 1:
      env['XLA_FLAGS'] = (env.get('XLA_FLAGS', '') +
                          f' --xla_force_host_platform_device_count={chips}')
    self.env = env

  def limit(self, own: float) -> float:
    left = DEADLINE_S - (time.monotonic() - self.started)
    if left <= 0:
      raise PhaseFailed(f'no time left inside the {DEADLINE_S:.0f}s deadline')
    return min(own, left)

  def spawn(self, cmd, log_name: str) -> subprocess.Popen:
    log = open(os.path.join(self.out_dir, log_name), 'w')
    try:
      child = subprocess.Popen(
          cmd, cwd=HERE, env=self.env, stdout=log,
          stderr=subprocess.STDOUT, start_new_session=True)
    finally:
      log.close()  # the child holds its own descriptor
    child.log_path = log.name
    self.children.append(child)
    return child

  def kill_children(self) -> None:
    for child in self.children:
      if child.poll() is None:
        try:
          os.killpg(child.pid, signal.SIGKILL)
        except ProcessLookupError:
          pass
        child.wait()


def _tail(path: str, n: int = 4000) -> str:
  with open(path, errors='replace') as f:
    return f.read()[-n:]


def _wait(child: subprocess.Popen, limit_s: float, what: str) -> None:
  try:
    rc = child.wait(timeout=limit_s)
  except subprocess.TimeoutExpired:
    raise PhaseFailed(
        f'{what} overran its {limit_s:.0f}s limit; its log ends:\n'
        f'{_tail(child.log_path)}') from None
  if rc != 0:
    raise PhaseFailed(
        f'{what} exited {rc}; its log ends:\n{_tail(child.log_path)}')


def _require(condition, message: str) -> None:
  if not condition:
    raise PhaseFailed(message)


def _load_json(path: str, what: str):
  try:
    with open(path) as f:
      return json.load(f)
  except (OSError, ValueError) as e:
    raise PhaseFailed(f'{what} is missing or unreadable: {e}') from None


def _check_device(run: Run, device, who: str) -> dict:
  """The child ran where this run was meant to run, on as many devices."""
  _require(isinstance(device, dict), f'{who} reported no device')
  device = {k: device.get(k) for k in ('platform', 'kind', 'count')}
  _require(device['platform'] == run.platform,
           f'{who} ran on {device}, not on a {run.platform!r} platform')
  _require(isinstance(device['count'], int) and
           device['count'] >= run.chips,
           f'{who} saw {device["count"]} device(s); this run needs '
           f'{run.chips}')
  return device


def _compile_observations(section: dict) -> dict:
  """The ``compile`` report section (utils/compilation_cache.report)."""
  cache_dir = section.get('dir')
  entries = (len(os.listdir(cache_dir))
             if cache_dir and os.path.isdir(cache_dir) else 0)
  return {
      'cache_dir': cache_dir,
      'cache_entries_on_disk': entries,
      'backend_compiles': section.get('compile/backend_compiles', 0),
      'compile_seconds': round(section.get('compile/compile_seconds', 0.0), 2),
      'cache_hits': section.get('compile/cache_hits', 0),
      'cache_misses': section.get('compile/cache_misses', 0),
  }


# ------------------------------------------------------------------ native


def native_phase(run: Run) -> dict:
  """The C++ record reader and JPEG decoder build and load here. No
  phase below reads records (the gin's generator is random), but the
  record-fed path does, and a machine that cannot build them must be
  found out now, with the compiler's output — not by a slow run later."""
  del run
  from tensor2robot_tpu import native  # ctypes + stdlib: no jax

  t0 = time.monotonic()
  loaded = {'record_io': native.load_record_io() is not None,
            'jpeg_decode': native.load_jpeg_decode() is not None}
  errors = native.build_errors()
  _require(all(loaded.values()) and not errors,
           f'native libraries did not build and load: {loaded}\n' +
           '\n'.join(f'--- {k}:\n{v}' for k, v in errors.items()))
  return {'loaded': loaded, 'wall_s': round(time.monotonic() - t0, 2)}


# ------------------------------------------------------------------- train


def _newest_export(export_root: str) -> str:
  versions = [d for d in glob.glob(os.path.join(export_root, '*'))
              if os.path.basename(d).isdigit()]
  _require(versions, f'no export version under {export_root}')
  return max(versions, key=lambda d: int(os.path.basename(d)))


def train_phase(run: Run) -> dict:
  steps = STEPS_PER_DISPATCH * DISPATCHES
  bindings = [
      f"train_eval_model.model_dir = '{run.model_dir}'",
      f'train_eval_model.max_train_steps = {steps}',
      'train_eval_model.eval_steps = 2',
      f'train_eval_model.save_interval_steps = {steps}',
      'train_eval_model.create_exporters_fn = @create_default_exporters()',
  ]
  batch = BATCH
  if run.rehearse:
    batch = TINY_BATCH
    bindings += [f'GraspingModelWrapper.{k} = {v}'
                 for k, v in TINY_MODEL.items()]
    bindings.append(f'DefaultRandomInputGenerator.batch_size = {batch}')
  cmd = TRAIN_CMD + ['--gin_configs', GIN_CONFIG]
  for binding in bindings:
    cmd += ['--gin_bindings', binding]
  t0 = time.monotonic()
  _wait(run.spawn(cmd, 'train.log'), run.limit(TRAIN_LIMIT_S), 'the trainer')
  wall_s = time.monotonic() - t0

  report = _load_json(os.path.join(run.model_dir, 'run_report.json'),
                      "the trainer's run_report.json")
  device = _check_device(run, report.get('device'), 'the trainer')
  metrics = report.get('metrics', {})
  counted = {k: metrics.get(f'trainer/{k}')
             for k in ('steps', 'dispatches', 'examples')}
  _require(counted == {'steps': steps, 'dispatches': DISPATCHES,
                       'examples': steps * batch},
           f'expected {DISPATCHES} dispatches of {STEPS_PER_DISPATCH} steps '
           f'at batch {batch}; the report counts {counted}')
  result = report.get('result', {})
  loss = result.get('loss')
  _require(isinstance(loss, float) and math.isfinite(loss) and
           all(math.isfinite(v) for v in result.values()),
           f'the final metrics are not finite: {result}')

  checkpoint = os.path.join(run.model_dir, 'checkpoints', f'ckpt_{steps}')
  _require(os.path.exists(os.path.join(checkpoint, 'commit.json')),
           f'no committed checkpoint at {checkpoint}')
  export_root = os.path.join(run.model_dir, 'export', 'latest_exporter_numpy')
  export = _newest_export(export_root)
  _require(os.path.exists(os.path.join(export, 'export_commit.json')),
           f'export {export} has no commit marker')
  meta = _load_json(os.path.join(export, 'export_meta.json'), 'export meta')
  _require(meta.get('self_contained_serving_fn') is True and
           meta.get('global_step') == steps,
           f'export is not the self-contained step-{steps} artifact: {meta}')

  # Degraded paths say so in the report; none may have been taken.
  degraded = {k: metrics.get(k, 0) for k in (
      'kernels/refused', 'export/serving_fn_single_platform',
      'resilience/nonfinite_skipped_steps')}
  _require(not any(degraded.values()), f'degraded paths were taken: {degraded}')
  tpu_branches = {'trainer/prefetch/place_stage': metrics.get(
      'trainer/prefetch/place_stage')}
  if not run.rehearse:
    # The defaults that switch on only on a TPU backend.
    _require(all(v == 1.0 for v in tpu_branches.values()),
             f'TPU-only default branches were not taken: {tpu_branches}')
  # The ledger's record of the step, taken at the first dispatch.
  program = report.get('programs', {}).get('train/step')
  dispatch_wall = metrics.get('trainer/step_wall_ms', {})
  return {
      'device': device,
      'jax': report['device'].get('jax'),
      'wall_s': round(wall_s, 1),
      'steps': steps, 'steps_per_dispatch': STEPS_PER_DISPATCH,
      'batch': batch, 'loss': loss,
      'compile': _compile_observations(report.get('compile', {})),
      # From asking for the first batch to the first K steps being ready
      # (block_until_ready), compile included; then dispatch-to-dispatch
      # wall time with the device one dispatch behind (random batches are
      # made on the host: this is not a device step time).
      'first_dispatch_s': round(
          metrics.get('trainer/first_dispatch_seconds', 0.0), 2),
      'process_start_to_first_step_s': round(
          metrics.get('trainer/restart_to_first_step_seconds', 0.0), 2),
      'steady_dispatch_wall_ms': {
          k: round(dispatch_wall.get(k, 0.0), 1)
          for k in ('count', 'min', 'mean', 'max')},
      # Every hand kernel is opt-in (kernel_policy, use_fused_kernel),
      # so the default step has none.
      'tpu_custom_calls_in_train_step': (program or {}).get('custom_calls'),
      'tpu_default_branches': tpu_branches,
      'checkpoint': os.path.relpath(checkpoint, run.out_dir),
      'export': os.path.relpath(export, run.out_dir),
  }


# ------------------------------------------------------------------- serve


def _free_port() -> int:
  with socket.socket() as s:
    s.bind(('127.0.0.1', 0))
    return s.getsockname()[1]


def _get(url: str, timeout: float = 10.0):
  with urllib.request.urlopen(url, timeout=timeout) as r:
    return r.status, json.loads(r.read())


def _random_features(spec: dict, examples: int, rng: random.Random) -> dict:
  """Spec-shaped nested lists from the export's own assets."""
  def tensor(shape, draw):
    if not shape:
      return draw()
    return [tensor(shape[1:], draw) for _ in range(shape[0])]

  features = {}
  for name, entry in spec.items():
    shape = [examples] + list(entry['shape'])
    if entry['dtype'] == 'uint8':
      flat = iter(rng.randbytes(math.prod(shape)))
      features[name] = tensor(shape, lambda: next(flat))
    else:
      features[name] = tensor(shape, lambda: rng.uniform(-1.0, 1.0))
  return features


def _shape(value) -> list:
  return [len(value)] + _shape(value[0]) if isinstance(value, list) else []


def _all_finite(value) -> bool:
  if isinstance(value, list):
    return all(_all_finite(v) for v in value)
  return isinstance(value, (int, float)) and math.isfinite(value)


def serve_phase(run: Run, export_root: str, train_device: dict) -> dict:
  export = _newest_export(export_root)
  assets = _load_json(
      os.path.join(export, 'assets.extra', 't2r_assets.json'),
      "the export's t2r_assets.json")
  spec = assets['feature_spec']
  port = _free_port()
  url = f'http://127.0.0.1:{port}'
  cmd = SERVE_CMD + ['--export_dir', export_root, '--port', str(port),
                     '--reload-interval-secs', '0']
  if run.rehearse:
    cmd += ['--max-batch', '4']
  t0 = time.monotonic()
  server = run.spawn(cmd, 'serve.log')
  deadline = t0 + run.limit(SERVE_START_LIMIT_S)
  while True:
    _require(server.poll() is None,
             f'the server exited {server.returncode} before /healthz '
             f'answered; its log ends:\n{_tail(server.log_path)}')
    _require(time.monotonic() < deadline,
             f'/healthz did not answer within {SERVE_START_LIMIT_S:.0f}s; '
             f'the log ends:\n{_tail(server.log_path)}')
    try:
      status, health = _get(url + '/healthz', timeout=2.0)
      if status == 200 and health.get('status') == 'ok':
        break
    except (urllib.error.URLError, OSError, ValueError):
      pass
    time.sleep(0.5)
  startup_s = time.monotonic() - t0

  rng = random.Random(SEED)
  round_trips_ms = []
  output_shapes = None
  for i, examples in enumerate(REQUEST_EXAMPLES):
    body = json.dumps(
        {'features': _random_features(spec, examples, rng)}).encode()
    request = urllib.request.Request(
        url + '/v1/predict', data=body,
        headers={'Content-Type': 'application/json'})
    t_req = time.monotonic()
    try:
      with urllib.request.urlopen(request, timeout=120.0) as r:
        status, reply = r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
      raise PhaseFailed(
          f'request {i} got {e.code}: {e.read()[:500]!r}') from None
    round_trips_ms.append(round(1e3 * (time.monotonic() - t_req), 1))
    outputs = reply.get('outputs')
    _require(status == 200 and isinstance(outputs, dict) and outputs and
             reply.get('examples') == examples,
             f'request {i}: status {status}, reply keys {sorted(reply)}')
    _require(all(len(v) == examples and _all_finite(v)
                 for v in outputs.values()),
             f'request {i}: outputs are not {examples} finite rows each')
    # Per-example output shapes are one fact of the export: every reply
    # declares the same ones.
    shapes = {k: _shape(v)[1:] for k, v in outputs.items()}
    _require(output_shapes in (None, shapes),
             f'request {i}: output shapes {shapes} != {output_shapes}')
    output_shapes = shapes

  _, statz = _get(url + '/statz')
  device = _check_device(run, statz.get('device'), 'the server')
  _require(device == train_device,
           f'the server ran on {device}, the trainer on {train_device}')
  _require(statz.get('executor') == 'JitBucketExecutor',
           f"requests were served by {statz.get('executor')!r}, not the "
           'jitted bucket executor')
  _require(statz.get('requests') == len(REQUEST_EXAMPLES) and
           statz.get('request_errors') == 0 and
           statz.get('actions') == sum(REQUEST_EXAMPLES) and
           statz.get('bucket_compiles') == len(statz.get('buckets', ())),
           'the server counts differ from what was sent: ' + json.dumps(
               {k: statz.get(k) for k in (
                   'requests', 'request_errors', 'actions', 'bucket_compiles',
                   'buckets')}))

  server.send_signal(signal.SIGTERM)
  try:
    rc = server.wait(timeout=run.limit(SERVE_DRAIN_LIMIT_S))
  except subprocess.TimeoutExpired:
    raise PhaseFailed('the server did not drain after SIGTERM') from None
  _require(rc == 0, f'the server exited {rc} after SIGTERM; its log ends:\n'
                    f'{_tail(server.log_path)}')
  return {
      'device': device,
      'wall_s': round(time.monotonic() - t0, 1),
      'spawn_to_healthz_s': round(startup_s, 1),
      'buckets': statz.get('buckets'),
      'executor': statz.get('executor'),
      'compile': _compile_observations(statz.get('compile', {})),
      'requests': len(REQUEST_EXAMPLES),
      'output_shapes': output_shapes,
      # This client's clock around each HTTP round trip (JSON of a
      # 512x640x3 frame both ways included), and the plane's own p50.
      'request_round_trip_ms': round_trips_ms,
      'plane_request_latency_ms_p50': statz.get('request_latency_ms_p50'),
      'drain_rc': rc,
  }


# --------------------------------------------------------------- multichip


def multichip_phase(run: Run) -> dict:
  """One child drives all four chips: the sharded QT-Opt step on the
  dp, fsdp and dp x fsdp meshes against one device of the same host."""
  cmd = MULTICHIP_CMD + (['--rehearse'] if run.rehearse else [])
  child = run.spawn(cmd, 'multichip.log')
  t0 = time.monotonic()
  _wait(child, run.limit(MULTICHIP_LIMIT_S), 'the multichip child')
  lines = [l for l in _tail(child.log_path, 1 << 20).splitlines()
           if l.startswith('{')]
  _require(lines, 'the multichip child printed no result')
  result = json.loads(lines[-1])
  result['device'] = _check_device(run, result.get('device'),
                                   'the multichip child')
  result['wall_s'] = round(time.monotonic() - t0, 1)
  return result


def multichip_child(rehearse: bool) -> int:
  """Runs IN THE CHILD (imports jax). Prints its result as the last
  line of its output; any failed assertion is a non-zero exit."""
  import jax
  import numpy as np

  from tensor2robot_tpu.data.input_generators import (
      DefaultRandomInputGenerator)
  from tensor2robot_tpu.modes import ModeKeys
  from tensor2robot_tpu.observability import device as device_lib
  from tensor2robot_tpu.observability import programs
  from tensor2robot_tpu.parallel import create_mesh, equivalence
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.research.qtopt import GraspingModelWrapper
  from tensor2robot_tpu.train import TrainerConfig

  device = device_lib.announce('chip_smoke --multichip')
  devices = jax.devices()
  assert len(devices) == 4, device
  tiny = TINY_MODEL if rehearse else {}
  # device_type='cpu' is the model's float32 flavour: the arms compare
  # in f32 (parallel/equivalence.py says why), on whatever device.
  make_model = lambda: GraspingModelWrapper(device_type='cpu', **tiny)
  generator = DefaultRandomInputGenerator(
      batch_size=2 * TINY_BATCH if rehearse else BATCH)
  generator.set_specification_from_model(make_model(), ModeKeys.TRAIN)
  batch = next(generator.create_iterator(ModeKeys.TRAIN))

  def config(steps):
    # The ledger records the step ('train/step') at each arm's first
    # dispatch.
    return TrainerConfig(
        model_dir='', max_train_steps=steps, seed=SEED,
        steps_per_dispatch=STEPS_PER_DISPATCH, eval_interval_steps=0,
        log_interval_steps=0, prefetch_batches=0)

  def placement(mesh):
    """Every device of ``mesh`` holds its own shard of a placed batch
    and of an fsdp-sharded state leaf (nothing piled on device 0)."""
    placed = mesh_lib.shard_batch(batch, mesh)
    image = placed[0]['state/image']
    per_device = {s.device.id: list(s.data.shape)
                  for s in image.addressable_shards}
    assert len(per_device) == mesh.devices.size, per_device
    n = mesh_lib.global_batch_size(1, mesh)
    assert all(shape[0] == image.shape[0] // n
               for shape in per_device.values()), (per_device, n)
    return {'batch_shard_shape_by_device': per_device}

  def run_arm(mesh, steps):
    # The ledger is the process's: the 'train/step' on record is the step
    # of the arm that ran last.
    return equivalence.run_arm(make_model, mesh, batch, config(steps))

  def sharded_arm(mesh, steps):
    arm = run_arm(mesh, steps)
    record = programs.get('train/step')
    assert record is not None, 'no train/step program was recorded'
    return arm, dict(record.collectives)

  one_device = create_mesh(devices=devices[:1], data=1)
  meshes = {
      'dp4': create_mesh(devices=devices, data=4),
      'fsdp4': create_mesh(devices=devices, data=1, fsdp=4),
      'dp2_fsdp2': create_mesh(devices=devices, data=2, fsdp=2),
  }
  comparisons = {}
  # f32 matmuls at default precision are bf16 passes on the MXU: the
  # strict band needs the full-precision passes in BOTH arms.
  with jax.default_matmul_precision('highest'):
    reference = run_arm(one_device, 1)
    for name, mesh in meshes.items():
      arm, collectives = sharded_arm(mesh, 1)
      seen = equivalence.compare_arms(arm, reference, f'{name} one step')
      # The expected collectives are in the compiled step: the gradient
      # all-reduce under dp, the parameter all-gather under fsdp.
      assert collectives.get('all-reduce', 0) + collectives.get(
          'reduce-scatter', 0) >= 1, (name, collectives)
      assert name == 'dp4' or collectives.get('all-gather', 0) >= 1, (
          name, collectives)
      comparisons[f'{name}/1_step'] = dict(
          seen, collectives=collectives, **placement(mesh))
      print(json.dumps({name: comparisons[f'{name}/1_step']}), flush=True)
    # A few dispatches of K steps on the mesh the Trainer's users run
    # (data x fsdp), same seed and steps on one device: donated sharded
    # state carried across dispatches, the scanned K-step program. One
    # device and a partitioned program already differ by ~7e-6 in one
    # step's deltas (above), and an untrained BatchNorm tower under SGD
    # amplifies that with every step — on the CPU rehearsal the losses
    # were 3e-4 apart after 8 steps and 1.5e-2 after 24 — so this band
    # says "finite and the same run", no more; the one-step comparisons
    # are the strict ones.
    steps = 3 * STEPS_PER_DISPATCH
    reference = run_arm(one_device, steps)
    arm, collectives = sharded_arm(meshes['dp2_fsdp2'], steps)
    comparisons[f'dp2_fsdp2/{steps}_steps'] = dict(
        equivalence.compare_arms(
            arm, reference, f'dp2_fsdp2 {steps} steps',
            loss_rtol=FEW_DISPATCHES_LOSS_RTOL, check_deltas=False),
        collectives=collectives)

  # The fsdp-sharded state really is spread over the four devices.
  from tensor2robot_tpu.train import Trainer

  trainer = Trainer(make_model(), config(1), mesh=meshes['fsdp4'])
  state = trainer.initialize(batch[0])
  kernels = [leaf for leaf in jax.tree_util.tree_leaves(state.params)
             if leaf.ndim == 4]
  for leaf in kernels:
    shards = {s.device.id: s.data.shape for s in leaf.addressable_shards}
    assert len(shards) == 4 and all(
        int(np.prod(shape)) * 4 == leaf.size for shape in shards.values()), (
            leaf.shape, shards)
  print(json.dumps({
      'device': device,
      'matmul_precision': 'highest',
      'bands': {'one_step': {'loss_rtol': 1e-5, 'delta_rtol': 0.02},
                'few_dispatches': {'loss_rtol': FEW_DISPATCHES_LOSS_RTOL}},
      'fsdp_sharded_conv_kernels_on_4_devices': len(kernels),
      'comparisons': comparisons,
  }), flush=True)
  return 0


# -------------------------------------------------------------------- main


def _phase_line(name: str, observations: dict) -> None:
  print(json.dumps({'phase': name, 'ok': True,
                    'observations': observations}), flush=True)


def main(argv=None) -> int:
  parser = argparse.ArgumentParser(
      description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
  parser.add_argument('--multichip', action='store_true',
                      help='four chips: the sharded step against one '
                           'device, and no other phase')
  parser.add_argument('--rehearse', action='store_true',
                      help='tiny shapes on the CPU (virtual devices for '
                           '--multichip); never reports a tpu')
  parser.add_argument('--out', default=os.path.join(
      HERE, 'chiprun_out', 'chip_smoke'),
                      help='logs and the run directory (default: %(default)s)')
  parser.add_argument('--keep', action='store_true',
                      help='keep the model directory (checkpoints, exports)')
  parser.add_argument('--child-multichip', action='store_true',
                      help=argparse.SUPPRESS)
  args = parser.parse_args(argv)
  if args.child_multichip:
    return multichip_child(args.rehearse)

  run = Run(args.out, args.rehearse, chips=4 if args.multichip else 1)
  os.makedirs(run.out_dir, exist_ok=True)
  # A model dir left by an earlier run would make the trainer resume at
  # its last step and take none.
  shutil.rmtree(run.model_dir, ignore_errors=True)

  def on_term(signum, frame):
    del frame
    raise SystemExit(128 + signum)  # unwinds through the finally below

  signal.signal(signal.SIGTERM, on_term)
  try:
    if args.multichip:
      result = multichip_phase(run)
      _phase_line('multichip', result)
      device = result['device']
    else:
      _phase_line('native', native_phase(run))
      trained = train_phase(run)
      _phase_line('train', trained)
      device = trained['device']
      _phase_line('serve', serve_phase(
          run, os.path.join(run.model_dir, 'export',
                            'latest_exporter_numpy'), device))
  except PhaseFailed as e:
    print(f'chip_smoke FAILED: {e}', file=sys.stderr, flush=True)
    return 1
  finally:
    run.kill_children()
    if not args.keep:
      shutil.rmtree(run.model_dir, ignore_errors=True)
  final = {'ok': True, 'device': device}
  if run.rehearse:
    final['rehearsal'] = True  # and device.platform is 'cpu', checked above
  print(json.dumps(final), flush=True)
  return 0


if __name__ == '__main__':
  sys.exit(main())
