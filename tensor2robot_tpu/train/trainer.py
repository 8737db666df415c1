"""Train/eval driver: one generic jitted step over a device mesh.

TPU-native replacement of ``utils/train_eval.py:394-587``. The reference
drives ``tf.estimator.train_and_evaluate`` with Estimator/TPUEstimator,
wrapper models, and SessionRunHooks. Here a single SPMD program owns the
step: host-side input generators yield numpy batches; the jitted step runs
preprocess → forward → loss → grad → update entirely on device, sharded
over a ``jax.sharding.Mesh`` (data/fsdp axes shard the batch, XLA inserts
the gradient all-reduce the reference got from ``CrossShardOptimizer``).

Composition mirrors ``abstract_model.py:683-821``:

  preprocess (device, bf16 cast) → inference_network_fn → model_train_fn
  → optax update [→ EMA update]           (TRAIN, donated state)
  preprocess → inference_network_fn → model_eval_fn      (EVAL, averaged)

Checkpoints are Orbax (``train/checkpoints.py``); export and hooks attach
through the callback protocol (the reference's HookBuilder surface).
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.observability import device as device_lib
from tensor2robot_tpu.observability import flight
from tensor2robot_tpu.observability import memory as memory_lib
from tensor2robot_tpu.observability import metrics as metrics_lib
from tensor2robot_tpu.observability import postmortem as postmortem_lib
from tensor2robot_tpu.observability import programs as programs_lib
from tensor2robot_tpu.observability import tracing
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.specs import SpecStruct
from tensor2robot_tpu.train import checkpoints as ckpt_lib
from tensor2robot_tpu.train import distributed_resilience as dist_lib
from tensor2robot_tpu.train import resilience
from tensor2robot_tpu.train.train_state import (TrainState,
                                                accumulate_grads, apply_ema,
                                                create_train_state,
                                                finalize_accumulated_grads,
                                                init_grad_accumulators)

Batch = Tuple[Any, Any]
# What the train loop's place() returns and the prefetch queue carries:
# (features, labels) on the device, as host-to-device copies.
PlacedBatch = Batch
MetricDict = Dict[str, float]


def _process_start_time() -> float:
  """Epoch seconds this PROCESS started (not this module's import).

  /proc-derived on Linux so the restart-goodput gauge charges python
  startup + imports to the restart, which is what an operator's restart
  budget pays; falls back to this module's import time elsewhere.
  """
  try:
    with open('/proc/self/stat') as f:
      stat = f.read()
    # Fields after the parenthesized comm (which may contain spaces):
    # index 19 is starttime, in clock ticks since boot.
    ticks = float(stat[stat.rindex(')') + 2:].split()[19])
    with open('/proc/uptime') as f:
      uptime = float(f.read().split()[0])
    return time.time() - (uptime - ticks / os.sysconf('SC_CLK_TCK'))
  except Exception:  # pylint: disable=broad-except
    return time.time()


_PROCESS_START_TIME = _process_start_time()
# restart_to_first_step_seconds is a per-PROCESS number: only the first
# completed dispatch after a (re)start is a restart measurement.
_restart_recorded = False


def _record_restart_to_first_step() -> None:
  global _restart_recorded
  if _restart_recorded:
    return
  _restart_recorded = True
  elapsed = time.time() - _PROCESS_START_TIME
  metrics_lib.gauge('trainer/restart_to_first_step_seconds').set(elapsed)
  from tensor2robot_tpu.utils import compilation_cache as cache_lib

  logging.info(
      'First train step completed %.2fs after process start '
      '(compilation cache: %s).', elapsed, cache_lib.enabled_dir() or 'off')


# Whole-loop restart accounting (ROADMAP direction 5): the preemption
# branch persists the SIGTERM receipt time beside the checkpoints, and
# the restarted process's first completed dispatch turns it into the
# `trainer/sigterm_to_resumed_step_seconds` gauge — signal receipt →
# in-flight dispatch drain → forced checkpoint → scheduler restart →
# python/jax startup → restore → first post-restore dispatch, the number
# an operator's preemption budget actually pays. Measured across a REAL
# subprocess restart by tests/test_collect_loop.py, which reads the
# measurement back from `loop_restart.json`.
PREEMPT_STATE_FILENAME = 'preempt_state.json'
LOOP_RESTART_FILENAME = 'loop_restart.json'


def _write_preempt_state(model_dir: str, shutdown, step: int) -> None:
  """Persists the SIGTERM receipt mark (atomic, never raises)."""
  if not model_dir:
    return
  import json

  sigterm_time = getattr(shutdown, 'signal_time', None) if shutdown else None
  path = os.path.join(model_dir, PREEMPT_STATE_FILENAME)
  try:
    tmp = f'{path}.tmp{os.getpid()}'
    with open(tmp, 'w') as f:
      json.dump({'sigterm_time': float(sigterm_time or time.time()),
                 'step': int(step), 'pid': os.getpid()}, f)
    os.replace(tmp, path)
  except OSError as e:
    logging.warning('Cannot persist preempt state under %r: %r',
                    model_dir, e)


def _record_sigterm_to_resumed(model_dir: str, step: int) -> None:
  """First-post-restore-dispatch mark: closes the restart measurement.

  A no-op unless a preemption left its receipt mark; the mark is
  CONSUMED (one measurement per preemption) and the result persisted to
  ``loop_restart.json`` for the test that reads it.
  """
  if not model_dir:
    return
  import json

  path = os.path.join(model_dir, PREEMPT_STATE_FILENAME)
  try:
    with open(path) as f:
      state = json.load(f)
    sigterm_time = float(state['sigterm_time'])
  except (OSError, ValueError, KeyError, TypeError):
    return
  elapsed = time.time() - sigterm_time
  metrics_lib.gauge('trainer/sigterm_to_resumed_step_seconds').set(elapsed)
  flight.event('shutdown', 'trainer/sigterm_to_resumed',
               f'seconds={elapsed:.3f} step={step}')
  logging.info(
      'Whole-loop restart: %.2fs from SIGTERM receipt (pre-restart step '
      '%s) to the first post-restore completed dispatch (step %d).',
      elapsed, state.get('step'), step)
  try:
    os.remove(path)
    out = os.path.join(model_dir, LOOP_RESTART_FILENAME)
    tmp = f'{out}.tmp{os.getpid()}'
    with open(tmp, 'w') as f:
      json.dump({'sigterm_to_resumed_step_seconds': elapsed,
                 'resumed_step': int(step),
                 'preempted_step': state.get('step')}, f)
    os.replace(tmp, out)
  except OSError as e:
    logging.warning('Cannot persist loop-restart measurement: %r', e)


def _place_batch(place: Callable[[Batch], 'PlacedBatch'],
                 release: Optional[Callable[[], None]],
                 batch: Batch, key: int,
                 wait_transfer: bool = False) -> 'PlacedBatch':
  """Places batch ``key``, waits for the copy where that is this
  thread's to wait for, and returns its ring-buffer lease
  (data/engine.py) if it holds one.

  ``place(batch)`` returns the batch on the device as plain
  host-to-device copies (``device_put`` / ``shard_batch`` with the batch
  shardings): no program runs on the device, so a copy is ready when its
  bytes have arrived, whatever step the device is running (PERF.md, PR
  25 and 26: a placement that waited on the compute queue ran the feed
  in series with the step).

  The one placement routine of every path (placement stage, consumer
  thread, no prefetch), so each leaves the same spans under the batch's
  key: ``trainer/place_stage`` round the whole and, inside it,
  ``trainer/place/put`` round the ``place`` call (host staging, returns
  before the bytes have moved) and ``trainer/place/transfer`` round the
  block on the copies.

  Who blocks: the placement stage always (``wait_transfer``: it runs
  off the loop thread with a placed batch queued ahead), so that the
  moment a batch's bytes are on the device is on record; and any thread
  that holds a lease, whose release point depends on the backend:

  * Accelerators: ``device_put`` COPIES to device memory, so place,
    block on the copies, then release: the host bytes are not read again.
  * XLA-CPU: ``device_put`` may ZERO-COPY alias the host numpy buffer,
    and releasing would let the engine overwrite the live batch under
    the step (observed as corrupted training). Take an explicit host
    copy of the ring views, release, then place the copy.
  """
  clock = time.perf_counter_ns
  t_start = clock()
  on_cpu = jax.default_backend() == 'cpu'
  if release is not None and on_cpu:
    batch = jax.tree_util.tree_map(lambda x: np.array(x, copy=True), batch)
    release()
    release = None
  t_put = clock()
  placed = place(batch)
  t_placed = clock()
  tracing.record('trainer/place/put', t_put, t_placed, key)
  if wait_transfer or release is not None:
    jax.block_until_ready(placed)
    tracing.record('trainer/place/transfer', t_placed, clock(), key)
  if release is not None:
    release()
  tracing.record('trainer/place_stage', t_start, clock(), key)
  return placed


def crossed_interval(interval: int, step_before: int, step_after: int) -> bool:
  """Did the step counter cross a multiple of ``interval``?

  The ONE interval test for the trainer loop and every logging callback
  (via ``Trainer.crossed``), so the cadence can't drift. ``interval == 0``
  disables. With ``steps_per_dispatch > 1`` the counter advances K at a
  time and may jump over exact multiples; an interval fires at the first
  dispatch boundary on or after each multiple. For K == 1 this reduces
  exactly to ``step_after % interval == 0``.
  """
  return bool(interval) and (step_after // interval) > (step_before // interval)


class TrainerCallback:
  """Hook surface, replacing SessionRunHooks/HookBuilders (hooks/*.py)."""

  def begin(self, trainer: 'Trainer') -> None:
    ...

  def after_step(self, trainer: 'Trainer', step: int,
                 scalars: MetricDict) -> None:
    ...

  def after_checkpoint(self, trainer: 'Trainer', step: int) -> None:
    ...

  def after_eval(self, trainer: 'Trainer', step: int,
                 metrics: MetricDict) -> None:
    ...

  def end(self, trainer: 'Trainer') -> None:
    ...


@dataclasses.dataclass
class TrainerConfig:
  """Run configuration (the reference's RunConfig + TrainSpec/EvalSpec)."""

  model_dir: str = ''
  max_train_steps: int = 1000
  eval_steps: int = 10          # batches per eval pass
  eval_interval_steps: int = 500  # train steps between eval passes
  save_interval_steps: int = 500
  max_checkpoints_to_keep: Optional[int] = 5
  keep_checkpoint_period: Optional[int] = None
  log_interval_steps: int = 100
  seed: int = 0
  async_checkpoints: bool = True
  # Bounded device prefetch: a background thread pulls batches from the
  # input iterator and stages them on device (shard_batch) up to this
  # many ahead, overlapping host parse/decode + h2d with the device step
  # (the role tf.data prefetch + infeed play for the reference's
  # TPUEstimator). 0 disables (batches fetched inline). Batch order is
  # preserved, so training is bit-identical either way. None = auto:
  # 2 on multi-core hosts, 0 on single-core ones — profiled on a 1-CPU
  # host, the worker thread CONTENDS with dispatch instead of
  # overlapping it (record-fed grasp2vec: 297 → 663 ms/step median).
  prefetch_batches: Optional[int] = None
  # Non-finite update guard (train/resilience.py). 'off' compiles the
  # historical step (bitwise status quo). 'skip_update' / 'raise' fold a
  # device-side all_finite(loss, grads) check into the jitted step and
  # guard the whole state update with where(ok, new, old): a NaN/Inf
  # batch can never corrupt params, opt state, EMA, or the rng stream
  # (state.step only advances on applied updates, so the skipped slot's
  # fold_in key is reused — training equals a run that never drew the
  # bad batch). The host evaluates the flag one dispatch behind (no
  # added sync) and either raises immediately ('raise') or counts skips
  # and halts after nonfinite_halt_after consecutive bad dispatches.
  nonfinite_mode: str = 'off'
  nonfinite_halt_after: int = 10
  # Honor SIGTERM/SIGINT at dispatch boundaries: finish the in-flight
  # dispatch, force a checkpoint (+ input-state save via the normal
  # after_checkpoint callbacks), and raise resilience.PreemptedError —
  # the preemptible-fleet contract. False leaves signal handling alone
  # (library embedders own their signals); an already-installed global
  # handler (resilience.install_graceful_shutdown) is honored either way.
  handle_preemption: bool = False
  # Train steps folded into ONE device dispatch (TPUEstimator's
  # iterations_per_loop, tpu_config.py in the reference's stack): the
  # loop stacks K host batches and a lax.scan runs K optimizer steps
  # per XLA program, so per-dispatch host overhead (python dispatch,
  # argument handling) amortizes K×.
  # Training math is IDENTICAL to K single dispatches (same rng stream:
  # the per-step fold_in keys off state.step). Logging, checkpointing
  # and eval quantize to dispatch boundaries — intervals fire at the
  # first boundary ON OR AFTER each multiple, exactly like
  # iterations_per_loop; callbacks see only boundary steps.
  steps_per_dispatch: int = 1
  # Device-resident multi-step feeding: with steps_per_dispatch=K, the
  # K-batch step-group moves to device as ONE ``jax.device_put`` of the
  # whole (features, labels) pytree — one H2D burst per dispatch instead
  # of one per leaf — into a double-buffered input ring (prefetch depth
  # >= 2, so the burst for superbatch N+1 overlaps the scanned compute
  # of N), and on accelerator backends the batch arguments are DONATED
  # to the K-step executable, letting XLA reuse the superbatch's device
  # buffers as scratch. The grouping path assembles batches in place
  # into preallocated contiguous superbatch buffers (no np.stack copy;
  # see _SuperbatchAssembler). Training math is bitwise identical to
  # the default feed (same executable on CPU; pinned by
  # tests/test_device_feed.py). Ignored (off) when the mesh spans
  # processes — multi-host feeding assembles per-process shards, which
  # has no single-put form. Default OFF: no cell of the benchmark has
  # measured it on the chip (PERF.md §7).
  device_feed: bool = False
  # Microbatch gradient accumulation (GPipe-style): the jitted step runs
  # a lax.scan over M slices of the host batch — [B, ...] reshaped to
  # [M, B/M, ...] — accumulating gradients in donated float32 carries,
  # then applies ONE optimizer update on the microbatch-mean gradient.
  # Peak activation memory follows the MICRObatch (B/M), so effective
  # batches past the HBM cliff train at near-optimal per-example
  # throughput (the qtopt curve collapses 8.6× at batch 96; M=2×64 keeps
  # batch-64 activations). For mean-reduced losses the update equals the
  # full-batch step exactly (f32 accumulators; pinned by
  # tests/test_memory_scaling.py), with one caveat: batch-coupled ops
  # (BatchNorm batch statistics, batch-shaped dropout masks) see the
  # microbatch — "ghost batch norm" semantics, B/M-sized stats.
  # Preprocessing runs ONCE over the full host batch (same rng draws as
  # the unsliced step); the per-step rng fold_in, EMA update, and the
  # non-finite guard (evaluated over the ACCUMULATED gradients) all
  # advance once per effective batch. Composes with steps_per_dispatch:
  # K host batches × M microbatches nest as one XLA program. B % M == 0.
  grad_accum_microbatches: int = 1
  # Dense/Conv contraction precision for the training step
  # (quantize/fp8_training.py). None leaves the model's own
  # ``matmul_precision`` untouched; 'bf16' forces the historical
  # program; 'fp8' routes every Dense/Conv contraction through
  # delayed-amax-scaled float8_e4m3fn quantize-dequantize — the chip's
  # 2×-bf16 MXU path, the only lever on the 22% MFU ceiling itself.
  # Master weights stay float32 in the optimizer state (params are
  # never cast); per-op gradients leave the injected ops unscaled in
  # full precision before any accumulation; amax histories ride the
  # 'fp8_stats' collection through model_state like BatchNorm
  # statistics. Gated on quantization.fp8_supported(); accepted by a
  # parity band vs. the bf16 run (tests/test_kernels.py), the same
  # certificate discipline as the grasp2vec bf16 soak.
  matmul_precision: Optional[str] = None
  # Per-dispatch step-time breakdown (observability/): decomposes each
  # dispatch's wall time into host wait-for-batch, H2D placement,
  # dispatch/enqueue, device step, and callback overhead, and merges
  # examples_per_sec / input_bound_fraction / goodput into the scalars
  # dict at log intervals (so MetricsLogger/TensorBoard publish them
  # with zero API change). The device-step measurement blocks on the
  # PREVIOUS dispatch's outputs only after enqueueing the current one —
  # one dispatch behind, so the device pipeline never drains and no
  # sync is added to the in-flight dispatch (host run-ahead caps at one
  # dispatch, which the bounded prefetch queue effectively imposed
  # already). Costs a handful of perf_counter reads + registry updates
  # per dispatch; False restores the uninstrumented loop exactly.
  step_breakdown: bool = True
  # Compiled-program ledger (observability/programs.py): record the
  # train step's executable — cost_analysis FLOPs/bytes, memory
  # analysis, fingerprint, donation map, the compiled HLO whose
  # ``op_scopes()`` map every instruction to the scope that issued it —
  # once, at the first dispatch, from the executable that dispatch ran
  # (a cache hit: no second backend compile), and watch the jit cache
  # for steady-state recompiles (flagged as 'program' flight events
  # within the dispatch that paid them). Per-dispatch cost is one C++
  # cache-size probe + an int compare.
  program_ledger: bool = True
  # Live metrics endpoint (observability/metricsz.py): serve
  # ``registry.report()`` JSON at http://127.0.0.1:<port>/metricsz from a
  # stdlib http.server daemon thread, for fleet scraping without touching
  # the training process. None = off (the default; the T2R_METRICSZ_PORT
  # env var also opts in); 0 = an ephemeral port (logged, and readable
  # from ``observability.metricsz.global_server().port``).
  metricsz_port: Optional[int] = None
  # Metrics time-series history (observability/timeseries.py): snapshot
  # the whole registry every this-many seconds into a bounded ring,
  # served at /metricsz?history=1 and embedded in postmortem bundles so
  # an incident shows how every series MOVED over the final minutes,
  # not just where it ended. 0 disables; the process-global recorder is
  # started once (first cadence wins).
  timeseries_interval_secs: float = 10.0
  # Distributed resilience (train/distributed_resilience.py), the
  # multi-process extension of handle_preemption: coordinated preemption
  # (any host's SIGTERM → ALL hosts checkpoint the same step and exit
  # resumable together), the atomic multi-host checkpoint commit
  # protocol, per-host heartbeats with a liveness monitor, and process-0
  # metric aggregation. None = auto: on iff jax.process_count() > 1 and
  # the jax.distributed coordination service is available; False forces
  # it off (each process then behaves like PR 1's single-process layer —
  # NOT safe on real pods).
  distributed_coordination: Optional[bool] = None
  # Heartbeat cadence and liveness thresholds (multi-process only). A
  # host whose heartbeat is older than straggler_after is flagged; older
  # than liveness_timeout is DEAD: with liveness_action='exit' the
  # monitor logs a loud liveness error and exits
  # distributed_resilience.LIVENESS_EXIT_CODE instead of letting the
  # survivors hang forever in a collective/barrier ('flag' only records
  # it — embedders that own their own death handling).
  heartbeat_interval_secs: float = 5.0
  heartbeat_straggler_secs: float = 15.0
  liveness_timeout_secs: float = 60.0
  liveness_action: str = 'exit'
  # Validate a checkpoint's recorded topology (process count, mesh
  # shape, microbatch config) against this run on restore; a mismatch is
  # a loud TopologyMismatchError instead of silently misread state.
  checkpoint_topology_check: bool = True
  # Elastic topology resume: with reshape on (the default), a mismatch
  # on the PURE-LAYOUT topology keys (process_count, device_count, mesh
  # shape) becomes a resharding restore — target shardings are rebuilt
  # from the CURRENT mesh and Orbax reshards the payload on read — so a
  # preempted 2-host job resumes on 1 host (or 4) instead of dying on
  # TopologyMismatchError. Semantic keys (grad_accum_microbatches,
  # steps_per_dispatch) still fail loudly: they change what the state
  # MEANS, not where it lives. False restores the strict PR-5 behavior.
  checkpoint_reshape: bool = True
  # Sharded multi-host checkpoint payloads: 'auto' shards whenever the
  # state's arrays span processes (a true FSDP/pod mesh — each host then
  # writes exactly the shards it owns); 'on' additionally stripes
  # per-host replica-group state across hosts (requires replicas in
  # lockstep, which deterministic same-stream training guarantees — the
  # 2-process drills run this); 'off' keeps the single-writer path
  # (process 0 writes everything).
  checkpoint_sharded_payloads: str = 'auto'
  # Async multi-host commit: unforced interval saves start their payload
  # write at the save point but run the ack/marker agreement at LATER
  # dispatch boundaries instead of blocking the loop on commit barriers
  # (checkpoint/save_overlap_ms records the hidden write time). Forced
  # saves — preemption, the final save — always commit synchronously, so
  # shutdown never leaves a durable payload without its marker.
  checkpoint_async_commit: bool = False
  # Deadline for every cross-host wait in the commit protocol; a peer
  # that misses it surfaces as a bounded DeadHostError, never a hang.
  checkpoint_barrier_timeout_secs: float = 600.0

  def resolved_distributed_coordination(self) -> bool:
    if self.distributed_coordination is not None:
      return self.distributed_coordination
    return jax.process_count() > 1

  def resolved_sharded_payloads(self, mesh) -> bool:
    if self.checkpoint_sharded_payloads == 'on':
      return True
    if self.checkpoint_sharded_payloads == 'off':
      return False
    if self.checkpoint_sharded_payloads != 'auto':
      raise ValueError(
          f"checkpoint_sharded_payloads must be 'auto', 'on' or 'off'; "
          f'got {self.checkpoint_sharded_payloads!r}')
    return mesh is not None and mesh_lib.mesh_spans_processes(mesh)

  def resolved_prefetch_batches(self) -> int:
    if self.prefetch_batches is not None:
      return self.prefetch_batches
    # The data layer's autotuner owns the core heuristic (it also sizes
    # the input engine's workers off the same affinity-aware count and
    # breakdown signals): 2 on multi-core hosts, 0 on single-core ones,
    # where the worker thread CONTENDS with dispatch instead of
    # overlapping it (record-fed grasp2vec: 297 → 663 ms/step median).
    from tensor2robot_tpu.data import engine as engine_lib

    return engine_lib.autotune_prefetch()


class _DevicePrefetcher:
  """Background pipeline staging upcoming batches ahead of the step.

  Pulls ``(features, labels)`` from ``it`` and keeps up to ``depth``
  staged batches in a bounded queue, so host parse/decode overlaps the
  device step instead of serializing with it. Two shapes, by backend:

  * Real TPU backends run a THREE-stage pipeline: a fetch worker pulls
    host batches from ``it`` (with the parallel input engine upstream
    this is mostly dequeueing — the engine's own workers do the decode),
    and a DEDICATED placement worker applies ``place`` (the H2D copy of
    ``shard_batch``), so the decode of batch N+2, the placement of N+1
    and the device step of N all overlap across batches instead of
    serializing behind one thread. They do overlap because a placement
    is a copy and nothing on the device's compute queue
    (``_place_batch``): the batch is handed on when its bytes have
    arrived, while step N still runs. The loop then finds batch N+1
    waiting, enqueues step N+1 a whole step early and blocks in
    ``trainer/device_wait``, as it was written to.
  * On the forced-host CPU platform placement happens on the consumer
    thread and a single fetch worker is the only stage — XLA CPU runs an
    N-device mesh's collectives as N in-process threads, and a
    concurrent device_put can starve one participant into a rendezvous
    deadlock (observed as an all-reduce termination timeout → SIGABRT).

  FIFO through every stage: batch order — and therefore training — is
  unchanged in either shape.
  """

  _DONE = object()

  def __init__(self, it: Iterator[Batch],
               place: Callable[[Batch], 'PlacedBatch'], depth: int,
               place_stage: Optional[bool] = None,
               release: Optional[Callable[[], None]] = None):
    import queue
    import threading

    self._q: 'queue.Queue' = queue.Queue(maxsize=depth)
    self._host_q: Optional['queue.Queue'] = None
    self._err: Optional[BaseException] = None
    self._stop = threading.Event()
    # Ring-buffer lease release (data/engine.py reuse_buffers): called
    # once per batch AFTER its H2D transfer completes (``_place_batch``:
    # place() → block on the copies → release(), on the place/consumer
    # thread, off the dispatch critical path), so the engine may recycle
    # the host buffers the batch's arrays were views of.
    self._release = release
    # Queue telemetry: a depth gauge pinned near 0 plus a climbing
    # starvation counter is the registry's signature of an input-bound
    # run (the breakdown's host_wait_ms says the same from the loop
    # side); starved_wait_ms is how long each starvation stalled.
    prefetch_metrics = metrics_lib.scope('trainer/prefetch')
    self._m_depth = prefetch_metrics.gauge('queue_depth')
    self._m_starved = prefetch_metrics.counter('starvation')
    self._m_starve_ms = prefetch_metrics.histogram('starved_wait_ms')
    self._m_batches = prefetch_metrics.counter('batches')
    if place_stage is None:
      place_stage = jax.default_backend() == 'tpu'
    prefetch_metrics.gauge('place_stage').set(float(place_stage))
    self._consumer_place = None if place_stage else place
    self._threads = []

    if place_stage:
      host_q: 'queue.Queue' = queue.Queue(maxsize=depth)
      self._host_q = host_q
      m_host_depth = prefetch_metrics.gauge('host_queue_depth')

      def placer():
        try:
          while not self._stop.is_set():
            item = host_q.get()
            if item is self._DONE:
              return
            # Placement overlaps the device step and the upstream
            # decode; its time shows up as placement_overlapped_ms in
            # the breakdown (off the dispatch critical path).
            key, batch = item
            self._q.put(_place_batch(place, self._release, batch, key,
                                     wait_transfer=True))
        except BaseException as e:
          if self._err is None:
            self._err = e
        finally:
          self._q.put(self._DONE)

      self._threads = [
          threading.Thread(target=self._fetch_into,
                           args=(it, host_q, m_host_depth), daemon=True,
                           name='t2r-prefetch-fetch'),
          threading.Thread(target=placer, daemon=True,
                           name='t2r-prefetch-place'),
      ]
    else:
      self._threads = [
          threading.Thread(target=self._fetch_into, args=(it, self._q),
                           daemon=True, name='t2r-prefetch')
      ]
    for thread in self._threads:
      thread.start()

  def _fetch_into(self, it: Iterator[Batch], out_q: 'queue.Queue',
                  depth_gauge=None) -> None:
    """The fetch stage: numbers the host batches as it takes them (the
    n-th batch is the n-th dispatch: FIFO through every stage) and
    hands ``(n, batch)`` on. ``trainer/fetch`` is the time blocked in
    ``next(it)`` for batch n; ``trainer/fetch_put`` the time blocked
    handing it on: the feed is ahead (back-pressure)."""
    clock = time.perf_counter_ns
    it = iter(it)
    try:
      for key in itertools.count():
        t_fetch = clock()
        try:
          batch = next(it)
        except StopIteration:
          return
        t_fetched = clock()
        tracing.record('trainer/fetch', t_fetch, t_fetched, key)
        if self._stop.is_set():
          return
        out_q.put((key, batch))
        tracing.record('trainer/fetch_put', t_fetched, clock(), key)
        if depth_gauge is not None:
          depth_gauge.set(out_q.qsize())
    except BaseException as e:  # surfaced on the consumer side
      self._err = e
    finally:
      out_q.put(self._DONE)

  def __iter__(self):
    return self

  def __next__(self) -> 'PlacedBatch':
    import queue

    if self._err is not None:
      # Deliver worker failures PROMPTLY: staged batches behind the
      # sentinel are not drained first — a dead pipeline must not feed
      # up to `depth` more steps before the loop learns about it.
      raise self._err
    try:
      item = self._q.get_nowait()
    except queue.Empty:
      # Starvation: the consumer outran the staging worker.
      self._m_starved.inc()
      t0 = time.perf_counter()
      item = self._q.get()
      self._m_starve_ms.observe((time.perf_counter() - t0) * 1e3)
    self._m_depth.set(self._q.qsize())
    if item is self._DONE:
      if self._err is not None:
        raise self._err
      raise StopIteration
    if self._consumer_place is not None:
      key, batch = item
      item = _place_batch(self._consumer_place, self._release, batch, key)
    self._m_batches.inc()
    return item

  def close(self, timeout: float = 10.0) -> None:
    import queue
    import time

    self._stop.set()
    # Keep draining until the workers exit: a single drain is not enough
    # (a worker's blocked put() refills the slot, and its final
    # put(_DONE) could block forever on a depth-1 queue). Both queues
    # drain — the fetch stage can be blocked on the host queue just as
    # the placement stage can be on the placed queue. Bounded: a worker
    # stuck inside the input iterator's next() (stalled producer) can
    # never observe the stop event — abandon the daemon thread rather
    # than hang end-of-training shutdown.
    deadline = time.monotonic() + timeout
    while any(t.is_alive() for t in self._threads):
      if time.monotonic() > deadline:
        logging.warning(
            'Prefetch worker did not exit within %.1fs (input iterator '
            'blocked?); abandoning the daemon thread(s).', timeout)
        break
      for q in (self._q, self._host_q):
        if q is None:
          continue
        try:
          q.get(timeout=0.025)
        except queue.Empty:
          pass
    for q in (self._q, self._host_q):
      if q is None:
        continue
      try:
        while True:
          q.get_nowait()
      except queue.Empty:
        pass


class _SuperbatchAssembler:
  """Assembles K host batches into contiguous ``[K, batch, ...]`` groups.

  Replaces the PR-4 ``np.stack`` grouping copy: each source batch is
  copied exactly once, directly into its slice of a preallocated
  contiguous superbatch buffer, and its source ring lease
  (``data/engine.py`` ``release()``) is returned the moment its bytes
  are copied in — per batch, instead of per group.

  Two buffer modes:

  * ``reuse=False`` (default, and the CPU path): every group gets fresh
    buffers and :meth:`release` is a no-op. Required wherever a
    zero-copy ``device_put`` may alias the host buffer for the
    dispatch's lifetime (XLA-CPU — see ``_place_batch``).
  * ``reuse=True`` (device feed on accelerators): ``slots``
    preallocated buffer sets are recycled as a ring, mirroring the
    input engine's lease contract — the consumer calls
    :meth:`release` once per delivered superbatch when its H2D
    transfer completes, freeing the OLDEST outstanding slot (FIFO,
    exactly like engine ``release()``). Two slots double-buffer: the
    assembly of group N+1 proceeds while group N's burst is in flight,
    and assembly blocks only when both slots are outstanding.

  Grouping semantics are unchanged from the old ``_grouped_batches``:
  groups clip so the train loop never overshoots ``max_steps``; a batch
  whose leaf shapes differ from the open group's closes that group
  early (the odd batch starts its own group); short/ragged groups get
  fresh buffers (never ring slots — their shapes differ) and just
  retrace the scan executable. Emitted steps are tracked here so
  grouping stays correct when a prefetcher pulls groups ahead.
  """

  def __init__(self, it: Iterator[Batch], k: int, start_step: int,
               max_steps: int,
               release: Optional[Callable[[], None]] = None,
               reuse: bool = False, slots: int = 2):
    import collections
    import queue

    self._it = iter(it)
    self._k = max(1, int(k))
    self._max_steps = max_steps
    self._emitted = start_step
    self._release_source = release
    self._reuse = bool(reuse)
    self._slots = max(1, int(slots))
    self._free: Optional['queue.Queue'] = queue.Queue() if reuse else None
    self._ring: List[Batch] = []
    self._ring_sig = None
    # FIFO of outstanding superbatch leases: ring slot index, or None
    # for fresh buffers (whose release is a no-op entry).
    self._leases = collections.deque()  # GUARDED_BY(self._lease_lock)
    self._lease_lock = threading.Lock()
    self._gen = self._generate()

  def release(self) -> None:
    """Frees the OLDEST outstanding superbatch lease (engine contract).

    Called by the placement stage once a superbatch's H2D transfer has
    completed; returns its ring slot (if any) for reuse.
    """
    with self._lease_lock:
      if not self._leases:
        raise RuntimeError('release() without an outstanding superbatch')
      slot = self._leases.popleft()
    if slot is not None:
      self._free.put(slot)

  @staticmethod
  def _leaf_shapes(batch):
    return [np.shape(x) for x in jax.tree_util.tree_leaves(batch)]

  @staticmethod
  def _alloc(batch: Batch, k: int) -> Batch:
    return jax.tree_util.tree_map(
        lambda x: np.empty((k,) + np.shape(x),
                           dtype=np.asarray(x).dtype), batch)

  def _assemble(self, group) -> Batch:
    k = len(group)
    slot = None
    if self._reuse and k == self._k:
      sig = (k, self._leaf_shapes(group[0]),
             [np.asarray(x).dtype
              for x in jax.tree_util.tree_leaves(group[0])])
      if self._ring_sig is None:
        self._ring_sig = sig
        for i in range(self._slots):
          self._ring.append(self._alloc(group[0], k))
          self._free.put(i)
      if sig == self._ring_sig:
        # Blocks until the consumer releases a slot: bounds assembly
        # run-ahead to the ring depth (the double buffer).
        slot = self._free.get()
    buffers = self._ring[slot] if slot is not None else self._alloc(
        group[0], k)
    dst_leaves = jax.tree_util.tree_leaves(buffers)
    for i, batch in enumerate(group):
      for dst, src in zip(dst_leaves, jax.tree_util.tree_leaves(batch)):
        np.copyto(dst[i], src)
      if self._release_source is not None:
        # This batch's bytes now live in the superbatch buffer; its
        # source ring slot can be recycled immediately.
        self._release_source()
    with self._lease_lock:
      self._leases.append(slot)
    return buffers

  def _generate(self):
    group: List[Batch] = []
    for batch in self._it:
      if group and self._leaf_shapes(batch) != self._leaf_shapes(group[0]):
        yield self._assemble(group)
        self._emitted += len(group)
        group = []
        if self._emitted >= self._max_steps:
          return
      group.append(batch)
      if len(group) >= min(self._k, self._max_steps - self._emitted):
        yield self._assemble(group)
        self._emitted += len(group)
        group = []
        if self._emitted >= self._max_steps:
          return
    if group:
      yield self._assemble(group)

  def __iter__(self):
    return self

  def __next__(self) -> Batch:
    return next(self._gen)


def _grouped_batches(it: Iterator[Batch], k: int, start_step: int,
                     max_steps: int,
                     release: Optional[Callable[[], None]] = None
                     ) -> Iterator[Batch]:
  """K-batch ``[K, batch, ...]`` step-groups (fresh-buffer assembly).

  Compatibility wrapper over :class:`_SuperbatchAssembler` in its
  fresh-allocation mode — the historical grouping semantics, minus the
  intermediate ``np.stack`` list-of-views copy.
  """
  return _SuperbatchAssembler(it, k, start_step, max_steps, release=release)


def _mean_metrics(metric_batches: List[MetricDict]) -> MetricDict:
  if not metric_batches:
    return {}
  keys = metric_batches[0].keys()
  return {
      k: float(np.mean([float(m[k]) for m in metric_batches])) for k in keys
  }


class _DispatchBreakdown:
  """Per-dispatch wall-time decomposition for the train loop.

  A *boundary* is the instant right after a dispatch's one-behind
  device block. ``wall(i) = boundary(i) - boundary(i-1)`` then
  decomposes EXACTLY (no untracked residue — every interval between
  the five timestamps is attributed). The timestamps are the loop's one
  set of ``perf_counter_ns`` reads, which it also hands to the span ring
  (``trainer/after_dispatch``, ``wait_batch``, ``dispatch``,
  ``device_wait`` tile the same intervals, keyed by dispatch):

    callback_ms   boundary(i-1) → start of wait: callbacks, logging,
                  checkpoint saves, interleaved eval — everything the
                  host does between dispatches besides feeding.
    host_wait_ms  blocked in ``next(batches)`` (minus consumer-thread
                  placement, carved out below) — input-bound time.
    placement_ms  ``shard_batch`` H2D placement on the LOOP thread
                  (worker-thread placement overlaps the device step and
                  is recorded separately as placement_overlapped_ms).
    dispatch_ms   the async ``step_fn`` enqueue call.
    device_step_ms  blocked on the PREVIOUS dispatch's outputs after
                  enqueueing this one: the device compute not hidden by
                  host work. Compute-bound runs see the true step time
                  here; input-bound runs see ~0 — which is the answer.

  The first dispatch is excluded from windows (it pays jit compile).
  ``window_scalars`` drains the accumulation into the scalar dict the
  existing logging callbacks already publish.
  """

  _WINDOW_KEYS = ('callback', 'wait', 'place', 'dispatch', 'device')

  def __init__(self, enabled: bool):
    self.enabled = enabled
    # Written by place() when it runs on the loop thread; drained by
    # record(). A plain list cell: single producer+consumer (the loop).
    self.place_ms = [0.0]
    self._boundary: Optional[int] = None
    self._dispatches = metrics_lib.counter('trainer/dispatches')
    self._steps = metrics_lib.counter('trainer/steps')
    self._examples = metrics_lib.counter('trainer/examples')
    self._wall_hist = metrics_lib.histogram('trainer/step_wall_ms')
    self._place_hist = metrics_lib.histogram('trainer/placement_ms')
    self._callback_hist = metrics_lib.histogram('trainer/callback_ms')
    # Closed log windows: the input engine's mid-run re-autotune keys off
    # this counter (one re-evaluation per window, data/engine.py).
    self._windows = metrics_lib.counter('trainer/breakdown_windows')
    self._skipped_counter = metrics_lib.counter(
        'resilience/nonfinite_skipped_steps')
    self._reset_window()

  def _reset_window(self) -> None:
    self._win = {k: 0.0 for k in self._WINDOW_KEYS}
    self._win_wall = 0.0
    self._win_dispatches = 0
    self._win_steps = 0
    self._win_examples = 0
    self._win_skipped0 = self._skipped_counter.value

  def record(self, t_wait0: int, t_wait1: int, t_disp: int,
             t_boundary: int, steps: int, examples: int) -> None:
    """Closes one dispatch given its four loop timestamps
    (``perf_counter_ns``): start-of-wait, batch-in-hand,
    dispatch-enqueued, after-device-block."""
    self._dispatches.inc()
    self._steps.inc(steps)
    self._examples.inc(examples)
    place_ms, self.place_ms[0] = self.place_ms[0], 0.0
    prev_boundary, self._boundary = self._boundary, t_boundary
    if not self.enabled:
      return  # counters only: without the device block the timestamps
              # measure dispatch enqueues, not where the time went
    self._place_hist.observe(place_ms)
    if prev_boundary is None:
      return  # first dispatch: jit compile dominates; not a steady-state sample
    callback_ms = (t_wait0 - prev_boundary) / 1e6
    wall_ms = (t_boundary - prev_boundary) / 1e6
    self._callback_hist.observe(callback_ms)
    self._wall_hist.observe(wall_ms)
    self._win['callback'] += callback_ms
    self._win['wait'] += max(0.0, (t_wait1 - t_wait0) / 1e6 - place_ms)
    self._win['place'] += place_ms
    self._win['dispatch'] += (t_disp - t_wait1) / 1e6
    self._win['device'] += (t_boundary - t_disp) / 1e6
    self._win_wall += wall_ms
    self._win_dispatches += 1
    self._win_steps += steps
    self._win_examples += examples

  def window_scalars(self) -> MetricDict:
    """Drains the current log window into publishable scalars.

    ``goodput_examples_per_sec`` discounts examples whose updates the
    non-finite guard skipped on device — throughput that moved bytes
    but trained nothing.
    """
    if not self.enabled or self._win_dispatches == 0:
      return {}
    n = self._win_dispatches
    wall_ms = self._win_wall
    wall_s = wall_ms / 1e3
    skipped = self._skipped_counter.value - self._win_skipped0
    eps = self._win_examples / wall_s if wall_s > 0 else 0.0
    out = {
        'examples_per_sec': eps,
        'input_bound_fraction':
            (self._win['wait'] + self._win['place']) / wall_ms
            if wall_ms > 0 else 0.0,
        'goodput_examples_per_sec':
            eps * max(0.0, 1.0 - skipped / max(1, self._win_steps)),
        'breakdown/wall_ms': wall_ms / n,
        'breakdown/host_wait_ms': self._win['wait'] / n,
        'breakdown/placement_ms': self._win['place'] / n,
        'breakdown/dispatch_ms': self._win['dispatch'] / n,
        'breakdown/device_step_ms': self._win['device'] / n,
        'breakdown/callback_ms': self._win['callback'] / n,
    }
    for key, value in out.items():
      metrics_lib.gauge(f'trainer/{key}').set(value)
    self._windows.inc()
    # Postmortem retention: the last K closed windows ride every
    # incident bundle (bounded ring in observability/postmortem.py).
    postmortem_lib.note_breakdown_window(out)
    self._reset_window()
    return out


def _resilience_scalars(start_snapshot, policy) -> MetricDict:
  """Train-scalar view of the resilience registry counters.

  Deltas against the run-start snapshot (the registry is process-global;
  a second trainer in the same process must not inherit the first one's
  counts). Zero-valued entries are elided except the two non-finite
  counters, which stay in the schema whenever the guard is on so their
  TensorBoard series exist from step one.
  """
  always = ()
  if policy is not None:
    always = ('resilience/nonfinite_skipped_steps',
              'resilience/consecutive_bad_dispatches')
  out: MetricDict = {}
  for name, value in metrics_lib.delta(start_snapshot, 'resilience/').items():
    if isinstance(value, dict):  # histogram: not a publishable scalar
      continue
    if value or name in always:
      out[name] = float(value)
  return out


class Trainer:
  """Owns the jitted step functions, state, and checkpoint manager."""

  def __init__(self,
               model,
               config: TrainerConfig,
               mesh: Optional[jax.sharding.Mesh] = None,
               callbacks: Sequence[TrainerCallback] = (),
               shutdown: Optional[resilience.GracefulShutdown] = None):
    self._model = model
    self._config = config
    device_lib.announce('Trainer')
    if config.matmul_precision is not None:
      # Before any module build: modules bake the precision in at
      # construction (the Dense/Conv injection classes).
      if hasattr(model, 'set_matmul_precision'):
        model.set_matmul_precision(config.matmul_precision)
      else:
        from tensor2robot_tpu.quantize import fp8_training as fp8_lib

        fp8_lib.require_fp8_support(config.matmul_precision)
    self._nonfinite_policy = (
        resilience.NonFinitePolicy(config.nonfinite_mode,
                                   config.nonfinite_halt_after)
        if config.nonfinite_mode != 'off' else None)
    if shutdown is None and config.handle_preemption:
      shutdown = resilience.install_graceful_shutdown()
    self._shutdown = shutdown
    self._mesh = mesh if mesh is not None else mesh_lib.single_device_mesh()
    if hasattr(model, 'set_mesh'):
      # Mesh-aware models (e.g. sequence-parallel attention layouts) get
      # the mesh the jitted step will run over before any module build.
      model.set_mesh(self._mesh)
    self._callbacks = list(callbacks)
    self._preprocessor = model.preprocessor
    self._optimizer = model.create_optimizer()
    self._loop_k = max(1, int(config.steps_per_dispatch))
    self._accum_m = max(1, int(config.grad_accum_microbatches))
    # Device-resident feeding (one device_put + one dispatch per K
    # steps). Off when the mesh spans processes: multi-host placement
    # assembles per-process shards leaf by leaf, which has no
    # single-put form. Batch-argument donation rides only accelerator
    # backends — on XLA-CPU device_put may zero-copy alias host numpy,
    # and donating an aliased buffer would let XLA scribble on the host
    # batch (it also keeps the CPU executable identical to the
    # default-feed one, the bitwise on/off equivalence tests pin).
    self._feed_enabled = (bool(config.device_feed) and
                          not mesh_lib.mesh_spans_processes(self._mesh))
    self._feed_donate_batch = (self._feed_enabled and
                               jax.default_backend() != 'cpu')
    self._state: Optional[TrainState] = None
    self._train_step_fn = None
    self._eval_step_fn = None
    # Whether 'train/step' landed in the program ledger (set on the loop
    # thread at a loop's first dispatch).
    self._program_recorded = False
    # Step the current dispatch started from; callbacks use crossed() so
    # their interval semantics survive steps_per_dispatch > 1.
    self._dispatch_start_step = 0
    # Distributed control plane (multi-process runs only): coordinated
    # preemption, the multi-host checkpoint commit protocol, heartbeats.
    self._dist_ctx: Optional[dist_lib.DistributedContext] = None
    if config.resolved_distributed_coordination():
      self._dist_ctx = dist_lib.DistributedContext.create()
    self._heartbeat: Optional[dist_lib.HeartbeatService] = None
    topology = None
    if config.checkpoint_topology_check:
      topology = mesh_lib.describe_topology(
          self._mesh,
          grad_accum_microbatches=self._accum_m,
          steps_per_dispatch=self._loop_k)
    self._manager: Optional[ckpt_lib.CheckpointManager] = None
    if config.model_dir:
      sharding_rules = ()
      if hasattr(model, 'param_sharding_rules'):
        sharding_rules = tuple(
            model.param_sharding_rules(self._mesh) or ())
      self._manager = ckpt_lib.CheckpointManager(
          os.path.join(config.model_dir, 'checkpoints'),
          max_to_keep=config.max_checkpoints_to_keep,
          keep_period=config.keep_checkpoint_period,
          save_interval_steps=config.save_interval_steps,
          async_save=config.async_checkpoints,
          topology=topology,
          distributed=self._dist_ctx,
          barrier_timeout_secs=config.checkpoint_barrier_timeout_secs,
          sharded=config.resolved_sharded_payloads(self._mesh),
          async_commit=config.checkpoint_async_commit,
          reshape=config.checkpoint_reshape,
          mesh=self._mesh,
          sharding_rules=sharding_rules)
    # Opt-in live metrics endpoint (config port or T2R_METRICSZ_PORT
    # env); process-global and idempotent, so a second Trainer in the
    # same process reuses the running server.
    from tensor2robot_tpu.observability import metricsz, timeseries

    metricsz.maybe_start(config.metricsz_port)
    # Metrics history ring: feeds /metricsz?history=1 and the postmortem
    # bundle's time-series window (idempotent, first cadence wins).
    timeseries.maybe_start(config.timeseries_interval_secs or None)
    # Before the first lowering: executables compiled by a previous
    # incarnation load from disk instead of recompiling (measured by
    # restart_to_first_step_seconds; the compile/* counters it installs
    # are the cause line). Where the cache lives is not this config's
    # business: utils/compilation_cache.py has the one rule.
    from tensor2robot_tpu.utils.compilation_cache import (
        enable_compilation_cache)

    enable_compilation_cache()

  # ------------------------------------------------------------- properties

  @property
  def model(self):
    return self._model

  @property
  def config(self) -> TrainerConfig:
    return self._config

  @property
  def mesh(self) -> jax.sharding.Mesh:
    return self._mesh

  @property
  def state(self) -> Optional[TrainState]:
    return self._state

  @property
  def step(self) -> int:
    return 0 if self._state is None else int(self._state.step)

  @property
  def checkpoint_manager(self) -> Optional[ckpt_lib.CheckpointManager]:
    return self._manager

  @property
  def dispatch_start_step(self) -> int:
    """The step the dispatch that just reported began from (callbacks)."""
    return self._dispatch_start_step

  @property
  def nonfinite_policy(self) -> Optional['resilience.NonFinitePolicy']:
    """Host-side non-finite accounting (None when the guard is off)."""
    return self._nonfinite_policy

  @property
  def distributed_context(self) -> Optional['dist_lib.DistributedContext']:
    """The multi-process control plane (None in single-process runs)."""
    return self._dist_ctx

  @property
  def is_primary_process(self) -> bool:
    """Whether this process owns job-wide side effects (exports,
    checkpoint payloads, aggregation). True in single-process runs."""
    return self._dist_ctx is None or self._dist_ctx.is_primary

  def crossed(self, interval: int, step: int) -> bool:
    """Whether the dispatch that just reported ``step`` crossed a multiple
    of ``interval`` — the interval test callbacks must use instead of
    ``step % interval == 0``, which boundary steps (multiples of
    ``steps_per_dispatch``) rarely satisfy."""
    return crossed_interval(interval, self._dispatch_start_step, step)

  # ------------------------------------------------------------ step builds

  def _train_step_body(self):
    model = self._model
    preprocessor = self._preprocessor
    optimizer = self._optimizer
    decay = model.avg_model_params_decay
    guard_nonfinite = self._config.nonfinite_mode != 'off'
    accum_m = self._accum_m

    def all_finite(loss, grads):
      # Device-side guard flag: ok == all_finite(loss, grads). With
      # grad_accum_microbatches > 1, `grads` is the ACCUMULATED
      # (microbatch-mean) tree — one bad microbatch poisons the whole
      # effective batch's update, which is the correct granularity: the
      # optimizer only ever sees the accumulated gradient.
      checks = [jnp.all(jnp.isfinite(loss))]
      for g in jax.tree_util.tree_leaves(grads):
        if jnp.issubdtype(jnp.asarray(g).dtype, jnp.inexact):
          checks.append(jnp.all(jnp.isfinite(g)))
      return jnp.stack(checks).all()

    def train_step(state: TrainState, features, labels):
      step_rng = jax.random.fold_in(state.rng, state.step)
      pre_rng, net_rng = jax.random.split(step_rng)
      # Preprocessing covers the FULL host batch in one call — with
      # microbatching this keeps every rng draw (crop offsets,
      # photometric distortions) identical to the unsliced step; only
      # the network forward/backward is sliced.
      features_p, labels_p = preprocessor.preprocess(
          features, labels, ModeKeys.TRAIN, pre_rng)

      def loss_fn(params, model_state, f, l):
        variables = dict(model_state)
        variables['params'] = params
        outputs, new_variables = model.inference_network_fn(
            variables, f, l, ModeKeys.TRAIN, net_rng)
        loss, scalars = model.model_train_fn(f, l, outputs, ModeKeys.TRAIN)
        new_model_state = {
            k: v for k, v in dict(new_variables).items() if k != 'params'
        }
        return loss, (scalars, new_model_state)

      grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
      if accum_m == 1:
        (loss, (scalars, new_model_state)), grads = grad_fn(
            state.params, state.model_state, features_p, labels_p)
      else:
        # Microbatch accumulation: scan over [M, B/M, ...] slices with
        # f32 accumulators in the (donated) carry; ONE update per
        # effective batch. model_state threads through the scan, so
        # BatchNorm running averages advance per microbatch (their
        # values never feed the TRAIN-mode forward, so loss/grads are
        # unaffected by the threading order).
        micro_f = mesh_lib.microbatch_split(features_p, accum_m)
        micro_l = (None if labels_p is None else
                   mesh_lib.microbatch_split(labels_p, accum_m))

        def micro_body(carry, mb):
          model_state, grad_acc, loss_acc = carry
          f, l = mb
          (mb_loss, (mb_scalars, new_ms)), mb_grads = grad_fn(
              state.params, model_state, f, l)
          carry = (new_ms, accumulate_grads(grad_acc, mb_grads),
                   loss_acc + mb_loss.astype(jnp.float32))
          return carry, mb_scalars

        (new_model_state, grad_acc, loss_acc), scalars_m = jax.lax.scan(
            micro_body,
            (state.model_state, init_grad_accumulators(state.params),
             jnp.zeros((), jnp.float32)),
            (micro_f, micro_l))
        grads = finalize_accumulated_grads(grad_acc, state.params, accum_m)
        loss = loss_acc / accum_m
        # Mean-reduced scalars: the microbatch mean IS the full-batch
        # value; reduced in f32 for the same reason the accumulators are.
        scalars = jax.tree_util.tree_map(
            lambda s: jnp.mean(jnp.asarray(s).astype(jnp.float32), axis=0),
            scalars_m)
      # Named for the device trace's readers (``ProgramRecord.op_scopes``);
      # where XLA fuses the update into a gradient's product, the product
      # keeps its layer's scope.
      with jax.named_scope('train/optimizer'):
        updates, new_opt_state = optimizer.update(
            grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            model_state=new_model_state,
            opt_state=new_opt_state,
            ema_params=apply_ema(state, new_params, decay))
      scalars = dict(scalars)
      scalars['loss'] = loss
      if guard_nonfinite:
        # The ENTIRE
        # state transition is selected through where(ok, new, old), so a
        # non-finite batch leaves params/opt-state/EMA/step untouched —
        # no host sync, no extra dispatch; the host policy reads the
        # count from the scalars one dispatch behind. Leaves the replace
        # kept by reference (rng) skip the select via identity.
        with jax.named_scope('train/optimizer'):
          ok = all_finite(loss, grads)
          new_state = jax.tree_util.tree_map(
              lambda n, o: n if n is o else jnp.where(ok, n, o),
              new_state, state)
        scalars['nonfinite_count'] = jnp.where(ok, 0, 1).astype(jnp.int32)
      return new_state, scalars

    return train_step

  def _multi_step_body(self):
    """K optimizer steps per XLA program over ``[K, batch, ...]`` groups.

    A ``lax.scan`` of the single-step body: same math and the same rng
    stream as K separate dispatches (the per-step ``fold_in`` keys off
    ``state.step``, which the scan carry advances). Returns the LAST
    step's scalars — the value per-step logging would have reported at
    the dispatch boundary.
    """
    step = self._train_step_body()

    def multi_step(state: TrainState, features_k, labels_k):
      def body(carry, batch):
        return step(carry, batch[0], batch[1])

      state, scalars_k = jax.lax.scan(body, state, (features_k, labels_k))
      out = jax.tree_util.tree_map(lambda x: x[-1], scalars_k)
      if 'nonfinite_count' in out:
        # The guard flag aggregates over the WHOLE group (a bad step in
        # the middle must not be masked by a clean last step).
        out['nonfinite_count'] = jnp.sum(scalars_k['nonfinite_count'])
      for name in self._model.counter_scalars:
        if name in out:  # a count is of the whole group, like the flag
          out[name] = jnp.sum(scalars_k[name], axis=0)
      return state, out

    return multi_step

  def _loop_step_body(self):
    """The body the train loop dispatches (single- or K-step)."""
    return (self._multi_step_body() if self._loop_k > 1
            else self._train_step_body())

  def _loop_batch_sharding(self):
    return (mesh_lib.stacked_batch_sharding(self._mesh)
            if self._loop_k > 1 else mesh_lib.batch_sharding(self._mesh))

  def _donate_argnums(self) -> Tuple[int, ...]:
    """(state,) — plus the batch args under accelerator device feed,
    where the superbatch's device buffers become the step's scratch (the
    donated input ring; the host copy already lives in the assembler)."""
    return (0, 1, 2) if self._feed_donate_batch else (0,)

  def _build_train_step(self):
    state_sharding = self._state_sharding()
    batch_sharding = self._loop_batch_sharding()
    return jax.jit(
        self._loop_step_body(),
        in_shardings=(state_sharding, batch_sharding, batch_sharding),
        out_shardings=(state_sharding, None),
        donate_argnums=self._donate_argnums())

  def _record_step_program(self, features, labels) -> None:
    """'train/step' in the program ledger, from the executable that the
    first dispatch ran.

    ``lower`` at that dispatch's own arguments (the state it returned,
    the batch it took: donated buffers lower by their avals) finds the
    trace, the lowering and the executable in jax's caches, so no
    backend compile is paid (``compile/backend_compiles`` stands still;
    avals rebuilt as ``ShapeDtypeStruct`` miss them and compile again).
    The record keeps the compiled HLO, whose instruction names are the
    op names of a device trace of this step.
    """
    compiles = metrics_lib.counter('compile/backend_compiles')
    before, t0 = compiles.value, time.perf_counter()
    if programs_lib.record_jitted(
        'train/step', self._train_step_fn, (self._state, features, labels),
        donate_argnums=self._donate_argnums(),
        donated_params=len(jax.tree_util.tree_leaves(self._state)),
        source='trainer/first_dispatch', steps_per_execution=self._loop_k,
        # A trainer's first program, not a steady state's recompile (the
        # dispatch probe watches for those).
        flag_steady_state=False):
      self._program_recorded = True
    # What the record cost the loop, and the proof that it compiled nothing.
    metrics_lib.gauge('trainer/program_record_seconds').set(
        time.perf_counter() - t0)
    metrics_lib.gauge('trainer/program_record_backend_compiles').set(
        compiles.value - before)

  def _build_eval_step(self):
    model = self._model
    preprocessor = self._preprocessor

    def eval_step(state: TrainState, features, labels):
      features_p, labels_p = preprocessor.preprocess(
          features, labels, ModeKeys.EVAL, None)
      outputs, _ = model.inference_network_fn(
          dict(state.eval_variables), features_p, labels_p, ModeKeys.EVAL)
      return model.model_eval_fn(features_p, labels_p, outputs)

    state_sharding = self._state_sharding()
    batch_sharding = mesh_lib.batch_sharding(self._mesh)
    return jax.jit(
        eval_step,
        in_shardings=(state_sharding, batch_sharding, batch_sharding))

  def _state_sharding(self):
    if self._state is None:
      raise ValueError('State must be initialized before building steps.')
    rules = ()
    if hasattr(self._model, 'param_sharding_rules'):
      rules = tuple(self._model.param_sharding_rules(self._mesh) or ())
    return mesh_lib.state_shardings_for(self._mesh, self._state,
                                        rules=rules)

  # ------------------------------------------------------- state lifecycle

  def initialize(self, features, labels=None) -> TrainState:
    """Creates (or restores) the train state from spec-shaped features."""
    del labels
    rng = jax.random.PRNGKey(self._config.seed)
    pre_rng, init_rng = jax.random.split(rng)
    # Initialize from *preprocessed* features: the device-side contract.
    features_p, _ = self._preprocessor.preprocess(
        features, None, ModeKeys.TRAIN, pre_rng)
    self._state = create_train_state(
        self._model, self._optimizer, init_rng, features_p, ModeKeys.TRAIN)
    if self._manager is not None and self._manager.latest_step() is not None:
      restored = self._manager.restore(self._state)
      if restored is not None:
        self._state = restored
    # Place the state according to mesh rules (replicated or fsdp-sharded).
    sharding = self._state_sharding()
    self._state = jax.tree_util.tree_map(
        lambda x, s: x if x is None else jax.device_put(x, s),
        self._state, sharding, is_leaf=lambda x: x is None)
    self._train_step_fn = self._build_train_step()
    self._eval_step_fn = self._build_eval_step()
    return self._state

  def save_checkpoint(self, force: bool = False,
                      sync: Optional[bool] = None) -> None:
    """Saves the current state; ``sync=True`` (preemption/final saves)
    forces the barriered commit even under checkpoint_async_commit."""
    if self._manager is None or self._state is None:
      return
    if self._manager.save(self.step, self._state, force=force, sync=sync):
      for cb in self._callbacks:
        cb.after_checkpoint(self, self.step)

  # ------------------------------------------------------------------ loops

  def train(self,
            train_iter: Iterator[Batch],
            eval_iter_fn: Optional[Callable[[], Iterator[Batch]]] = None
            ) -> MetricDict:
    """Interleaved train/eval loop (train_and_evaluate semantics).

    Every abnormal exit — preemption (:class:`~tensor2robot_tpu.train.
    resilience.PreemptedError`, 42), a liveness/barrier failure
    (``DeadHostError``, 43), a non-finite raise, or any uncaught
    exception — writes a postmortem bundle into
    ``<model_dir>/postmortem/`` (flight-ring events, metrics report,
    time-series window, breakdown windows, topology) before the error
    propagates; render it with ``tools/postmortem.py``.
    """
    try:
      return self._train_loop(train_iter, eval_iter_fn)
    except BaseException as e:
      self._note_abnormal_exit(e)
      raise

  def _note_abnormal_exit(self, error: BaseException) -> None:
    """Classifies a terminal error and dumps the postmortem bundle.

    Bounded and non-raising (postmortem.dump's contract): runs between
    the terminal error and its propagation to the exit path.
    """
    if isinstance(error, (GeneratorExit, StopIteration)):
      return
    if isinstance(error, resilience.PreemptedError):
      reason = 'preempted'
    elif isinstance(error, resilience.NonFiniteError):
      reason = 'nonfinite'
    elif isinstance(error, dist_lib.DeadHostError):
      reason = 'dead_host'
    elif isinstance(error, KeyboardInterrupt):
      reason = 'keyboard_interrupt'
    else:
      reason = 'trainer_exception'
    flight.event('error', f'trainer/{reason}',
                 f'{type(error).__name__}: {str(error)[:180]}')
    exit_code = getattr(error, 'exit_code', None)
    try:
      topology = mesh_lib.describe_topology(
          self._mesh,
          grad_accum_microbatches=self._accum_m,
          steps_per_dispatch=self._loop_k)
    except Exception:  # pylint: disable=broad-except
      topology = None
    postmortem_lib.dump(self._config.model_dir, reason,
                        exit_code=exit_code, error=error,
                        topology=topology,
                        extra={'step': self.step})

  def _train_loop(self,
                  train_iter: Iterator[Batch],
                  eval_iter_fn: Optional[Callable[[], Iterator[Batch]]] = None
                  ) -> MetricDict:
    config = self._config
    # Ring-buffer lease hook (data/engine.py reuse_buffers): present on
    # engine-backed iterators; None otherwise. Called once per consumed
    # batch at the point its bytes stop being needed (H2D transfer
    # completion, or the np.stack copy in the K>1 grouping path).
    release_fn = getattr(train_iter, 'release', None)
    if self._state is None:
      resuming = (self._manager is not None and
                  self._manager.latest_step() is not None)
      features, labels = next(train_iter)
      self.initialize(features)
      # On resume the pulled batch served only as the shape probe: the
      # restored run must not train on it — an InputStateCallback's
      # begin() rewinds the stream UNDER it, and without one the
      # restarted stream repeats examples anyway, so dropping it is
      # never a loss.
      first_batch: Optional[Batch] = None if resuming else (features, labels)
      if resuming and release_fn is not None:
        # The dropped probe batch still holds its ring lease; block
        # until initialization consumed its values (async dispatches
        # may still be reading the slot buffers) before releasing.
        jax.block_until_ready(self._state)
        release_fn()
    else:
      first_batch = None

    for cb in self._callbacks:
      cb.begin(self)

    scalars: MetricDict = {}
    eval_metrics: MetricDict = {}
    last_log = time.time()
    # Host-side step mirror: reading self.step would force a device sync
    # (int(state.step)) after every dispatch, serializing the pipeline.
    step = self.step
    last_log_step = step
    breakdown = _DispatchBreakdown(config.step_breakdown)
    # Compiled-program plane (observability/programs.py): one ledger
    # record at the first dispatch and a cache-size probe per dispatch
    # (the steady-state recompile sentinel).
    programs_on = config.program_ledger and programs_lib.enabled()
    program_record_pending = programs_on and not self._program_recorded
    recompile_probe = (
        programs_lib.dispatch_probe(self._train_step_fn, 'train/step')
        if programs_on else None)
    # Resilience counters are published as deltas against this run's
    # starting point (the registry is process-global).
    resilience_snap = metrics_lib.snapshot('resilience/')
    loop_ident = threading.get_ident()
    overlap_place_hist = metrics_lib.histogram(
        'trainer/placement_overlapped_ms')
    device_feed = self._feed_enabled
    feed_sharding = self._loop_batch_sharding() if device_feed else None
    # One increment per device-feed placement call: with the dispatch
    # counter, the registry pins "exactly ONE device_put and ONE
    # dispatch per K steps" (tests/test_device_feed.py).
    h2d_puts = metrics_lib.counter('trainer/h2d/device_puts')
    # Bytes handed to the put, on every placement path: over the time in
    # ``trainer/place/transfer`` (which ends when the copy does) they
    # give the H2D rate.
    h2d_bytes = metrics_lib.counter('trainer/h2d/bytes')

    def place(batch: Batch) -> PlacedBatch:
      """The copy: every leaf with its batch sharding, so that nothing
      here reaches the device's compute queue."""
      t0 = time.perf_counter()
      h2d_bytes.inc(sum(getattr(leaf, 'nbytes', 0)
                        for leaf in jax.tree_util.tree_leaves(batch)))
      if device_feed:
        # The whole (features, labels) group moves in ONE device_put
        # call (one H2D burst per dispatch, not shard_batch's per-leaf
        # puts), with the loop sharding replicated over its structure.
        h2d_puts.inc()
        placed = jax.device_put(
            batch, jax.tree_util.tree_map(lambda _: feed_sharding, batch))
      else:
        placed = mesh_lib.shard_batch(
            batch, self._mesh, stacked=self._loop_k > 1)
      place_ms = (time.perf_counter() - t0) * 1e3
      if threading.get_ident() == loop_ident:
        # Critical-path placement: carved out of host_wait in the
        # breakdown (the no-prefetch path and the CPU consumer-place
        # path both run here, inside the loop's next(batches)).
        breakdown.place_ms[0] += place_ms
      else:
        # Prefetch-worker placement overlaps the device step: real H2D
        # cost, but not on the dispatch critical path.
        overlap_place_hist.observe(place_ms)
      return placed

    if first_batch is not None:
      train_iter = itertools.chain([first_batch], train_iter)
    host_iter: Iterator[Batch] = train_iter
    place_release = release_fn
    if self._loop_k > 1:
      # Group assembly copies batches out of their SOURCE ring slots
      # into the superbatch buffers, so source leases are released
      # there; downstream stages see only the assembled buffers. Under
      # accelerator device feed the superbatch buffers are themselves a
      # two-slot ring: the assembler leases a slot per group and the
      # placement stage frees it once the H2D burst completes
      # (``_place_batch`` blocks on the copies, then calls
      # ``assembler.release``) — the host half of the double-buffered
      # donated input ring. On CPU ``device_put`` aliases host memory
      # (zero copy), so reusing buffers would corrupt in-flight
      # batches: keep fresh allocations there.
      feed_reuse = device_feed and jax.default_backend() != 'cpu'
      assembler = _SuperbatchAssembler(
          train_iter, self._loop_k, step, config.max_train_steps,
          release=release_fn, reuse=feed_reuse)
      host_iter = assembler
      place_release = assembler.release if feed_reuse else None

    prefetcher: Optional[_DevicePrefetcher] = None
    prefetch_depth = config.resolved_prefetch_batches()
    if device_feed and prefetch_depth > 0:
      # Double-buffered device input ring: keep at least two placed
      # superbatches in flight so the H2D burst for group N+1 overlaps
      # the scanned compute of group N.
      prefetch_depth = max(2, prefetch_depth)
    if prefetch_depth > 0:
      prefetcher = _DevicePrefetcher(host_iter, place, prefetch_depth,
                                     release=place_release)
      batches: Iterator[PlacedBatch] = iter(prefetcher)
    else:
      batches = (_place_batch(place, place_release, b, key)
                 for key, b in enumerate(host_iter))
    # Previous dispatch's device-side non-finite count, evaluated one
    # dispatch behind so policy enforcement adds no sync (the update was
    # already guarded on device; the lagged dispatch ran on clean state).
    pending_nonfinite: Optional[Tuple[Any, int]] = None
    # The previous dispatch's device outputs: the one-behind readiness
    # probe the breakdown blocks on AFTER enqueueing the next dispatch.
    prev_out: Optional[MetricDict] = None
    shutdown = (self._shutdown if self._shutdown is not None
                else resilience.active_shutdown())
    # Multi-process control plane: coordinated preemption agreement and
    # the per-host heartbeat/liveness monitor (model_dir is the shared
    # medium — without one, liveness degrades to barrier timeouts only).
    coordinated: Optional[dist_lib.CoordinatedShutdown] = None
    if self._dist_ctx is not None:
      if config.model_dir:
        self._heartbeat = dist_lib.HeartbeatService(
            os.path.join(config.model_dir,
                         dist_lib.HEARTBEAT_DIRNAME),
            process_index=self._dist_ctx.process_index,
            process_count=self._dist_ctx.process_count,
            interval_secs=config.heartbeat_interval_secs,
            straggler_after_secs=config.heartbeat_straggler_secs,
            dead_after_secs=config.liveness_timeout_secs,
            action=config.liveness_action)
        self._heartbeat.set_step(step)
        self._heartbeat.start()
      # Goodbye heartbeats let the negotiation retry against surviving
      # hosts when a peer completed and exited before a late proposal.
      coordinated = dist_lib.CoordinatedShutdown(
          self._dist_ctx, shutdown,
          peer_heartbeats=(self._heartbeat.read_peers
                           if self._heartbeat is not None else None))
    # The step ALL processes agreed to stop at (or this process's own
    # boundary for a single-process shutdown). The loop keeps training
    # until it reaches it, so every host's forced checkpoint lands on
    # one common step.
    stop_step: Optional[int] = None
    # Scalars the model declares as counts (``counter_scalars``) go to the
    # registry under their own names, read one dispatch behind like the
    # non-finite flag: by then the values are on the host's side of a
    # boundary the loop already took.
    counted = tuple(self._model.counter_scalars)
    pending_counts: Optional[MetricDict] = None

    def publish_counts(values: MetricDict) -> None:
      for name in counted:
        if name in values:
          metrics_lib.counter(name).inc(int(values[name]))

    # The loop reads the clock once a boundary; the same reads feed the
    # breakdown and the span ring. Four spans tile the loop thread's time
    # between boundaries, keyed by the dispatch ordinal (= the batch
    # ordinal the fetch stage counts: FIFO): ``trainer/wait_batch`` and
    # ``trainer/dispatch`` of dispatch n, ``trainer/device_wait`` on the
    # outputs of n-1, and ``trainer/after_dispatch``, the tail from n's
    # boundary to the next wait (breakdown, probes, callbacks, saves,
    # eval), closed when the next wait opens or the loop ends.
    clock = time.perf_counter_ns
    key = 0  # of the next dispatch
    t_tail: Optional[int] = None  # boundary of dispatch key-1, tail open
    try:
      while step < config.max_train_steps:
        if stop_step is None:
          if coordinated is not None:
            # One boundary's coordination round: propagates any host's
            # local SIGTERM to every process and agrees on the common
            # stop step (max of all published boundaries).
            stop_step = coordinated.poll(step)
            if (stop_step is not None and self._manager is not None and
                coordinated.participants is not None):
              # Hosts that completed and said goodbye before the
              # proposal are excluded from the remaining commits.
              self._manager.set_participants(coordinated.participants)
          elif shutdown is not None and shutdown.requested:
            stop_step = step
            # First boundary that OBSERVES the flag: safe (non-signal)
            # context for the flight record the handler could not take.
            signum = getattr(shutdown, '_signal_observed', None)
            flight.event(
                'shutdown', 'resilience/shutdown_observed',
                f'step={step} ' + (f'signum={signum}' if signum is not None
                                   else 'source=programmatic'))
        if stop_step is not None and step >= stop_step:
          # Preemption: the in-flight dispatch finished (we are at a
          # boundary); force a checkpoint + input-state save and exit
          # with the distinct resumable status. In a multi-process run
          # every host takes this branch at the SAME step and the save
          # below runs the atomic commit protocol.
          logging.warning(
              'Graceful shutdown requested; checkpointing step %d and '
              'raising PreemptedError (resumable).', self.step)
          self.save_checkpoint(force=True, sync=True)
          if self._manager is not None:
            self._manager.wait_until_finished()
          if getattr(self, 'is_primary_process', True):
            # Start mark of the whole-loop restart number: the restarted
            # process's first post-restore dispatch consumes it into
            # trainer/sigterm_to_resumed_step_seconds.
            _write_preempt_state(config.model_dir, shutdown, step)
          for cb in self._callbacks:
            cb.end(self)
          raise resilience.PreemptedError(self.step)
        t_wait0 = clock()
        if t_tail is not None:
          tracing.record('trainer/after_dispatch', t_tail, t_wait0, key - 1)
          t_tail = None
        try:
          features, labels = next(batches)
        finally:  # an ended stream leaves here: its wait is on record
          t_wait1 = clock()
          tracing.record('trainer/wait_batch', t_wait0, t_wait1, key)
        self._state, scalars = self._train_step_fn(
            self._state, features, labels)
        t_disp = t_boundary = clock()
        tracing.record('trainer/dispatch', t_wait1, t_disp, key)
        if breakdown.enabled and prev_out is not None:
          # One dispatch behind: the current dispatch is already on
          # device, so this block never drains the pipeline — it
          # measures the device compute not hidden by host work.
          jax.block_until_ready(prev_out)
          t_boundary = clock()
          tracing.record('trainer/device_wait', t_disp, t_boundary, key - 1)
        prev_out = scalars
        t_tail = t_boundary
        key += 1
        if not _restart_recorded:
          # Restart-goodput mark: the first dispatch's outputs becoming
          # ready means compile + restore + warmup are all paid. The
          # one-off block adds no steady-state sync (first dispatch is
          # excluded from the breakdown as compile anyway).
          jax.block_until_ready(scalars)
          # Wait for the batch + compile + first K steps, to readiness.
          metrics_lib.gauge('trainer/first_dispatch_seconds').set(
              (clock() - t_wait0) / 1e9)
          _record_restart_to_first_step()
          _record_sigterm_to_resumed(config.model_dir, step)
        before = step
        self._dispatch_start_step = before
        batch_leaves = jax.tree_util.tree_leaves(features)
        if self._loop_k > 1:
          # Group size travels as the leading (scan) dim; the final
          # group may be short (max_train_steps or an exhausted input).
          step += batch_leaves[0].shape[0]
        else:
          step += 1
        breakdown.record(
            t_wait0, t_wait1, t_disp, t_boundary, steps=step - before,
            examples=int(np.prod(batch_leaves[0].shape[:2]))
            if self._loop_k > 1 and batch_leaves
            else (batch_leaves[0].shape[0] if batch_leaves else 0))
        if program_record_pending:
          # First dispatch done: its executable is the step that runs.
          program_record_pending = False
          self._record_step_program(features, labels)
        if recompile_probe is not None:
          # One C++ cache-size read + int compare per dispatch: growth
          # after warmup means steady state just paid a trace+compile.
          recompile_probe()
        if flight.enabled():
          # One flight event per dispatch boundary: the incident ring's
          # backbone timeline (~1 µs; the ring is bounded, so even
          # sub-ms steps only shorten the window it covers).
          flight.event(
              'dispatch', 'trainer/boundary',
              f'step={step} wall_ms={(t_boundary - t_wait0) / 1e6:.3f}')
        if self._heartbeat is not None:
          # Liveness payload: peers (and post-mortem tooling) see the
          # last COMPLETED dispatch boundary, not a wall-clock guess.
          self._heartbeat.set_step(step)
        if self._manager is not None and self._dist_ctx is not None:
          # Async-commit progress (checkpoint_async_commit): the commit
          # primary publishes the marker for an in-flight save once every
          # participant's payload is durable — no barrier on the loop.
          self._manager.poll_async_commit()
        if self._nonfinite_policy is not None:
          prev, pending_nonfinite = pending_nonfinite, (
              scalars.get('nonfinite_count'), step)
          if prev is not None and prev[0] is not None:
            self._nonfinite_policy.observe(prev[0], prev[1])
        if counted:
          if pending_counts is not None:
            publish_counts(pending_counts)
          pending_counts = scalars
        if crossed_interval(config.log_interval_steps, before, step):
          scalars = {k: float(v) for k, v in scalars.items()}
          dt = time.time() - last_log
          last_log = time.time()
          scalars['steps_per_sec'] = (step - last_log_step) / max(dt, 1e-9)
          last_log_step = step
          # Step-time breakdown + resilience counters ride the normal
          # scalars dict, so MetricsLogger/TensorBoard publish them with
          # zero call-site changes.
          scalars.update(breakdown.window_scalars())
          # HBM gauges (peak/live bytes) ride the same scalar merge, so
          # TensorBoard shows memory beside throughput; no-op (empty) on
          # backends without allocator stats (CPU).
          scalars.update(memory_lib.memory_scalars())
          scalars.update(
              _resilience_scalars(resilience_snap, self._nonfinite_policy))
          if (self._heartbeat is not None and self._dist_ctx is not None
              and self._dist_ctx.is_primary):
            # Whole-job view (PR-2 follow-up): process 0 merges every
            # host's registry snapshot riding the heartbeats — counters
            # summed, per-host step/age gauges — into the same scalars
            # dict TensorBoard already publishes.
            scalars.update(self._heartbeat.aggregated_scalars())
        with tracing.span('trainer/callbacks', key=key - 1, annotate=False):
          for cb in self._callbacks:
            cb.after_step(self, step, scalars)
        if (self._manager is not None and
            crossed_interval(config.save_interval_steps, before, step)):
          # K > 1 boundary steps are rarely exact interval multiples;
          # the crossing above is the interval authority, so force past
          # orbax's own multiple-of-interval should_save.
          self.save_checkpoint(force=self._loop_k > 1)
        if (eval_iter_fn is not None and config.eval_interval_steps and
            (crossed_interval(config.eval_interval_steps, before, step) or
             step >= config.max_train_steps)):
          eval_metrics = self.evaluate(eval_iter_fn())
    finally:
      if t_tail is not None:
        tracing.record('trainer/after_dispatch', t_tail, clock(), key - 1)
      if prefetcher is not None:
        prefetcher.close()
      if self._heartbeat is not None:
        self._heartbeat.stop()
        self._heartbeat = None
    if (self._nonfinite_policy is not None and
        pending_nonfinite is not None and pending_nonfinite[0] is not None):
      # Flush the final dispatch's flag before declaring success.
      self._nonfinite_policy.observe(*pending_nonfinite)
    if pending_counts is not None:
      publish_counts(pending_counts)
    if coordinated is not None and stop_step is None:
      # Completion: publish this host's final boundary UNCONDITIONALLY —
      # a peer whose SIGTERM lands after this moment (the completed-host
      # vs late-proposal race) finds it in the KV store and converges on
      # it, even though this host will never poll again. Then join any
      # already-in-flight negotiation so the peer is not stranded: the
      # agreed target includes this host's completed boundary in its
      # max, so completion proceeds normally and the final save's commit
      # barriers align across hosts (every host saves the same final
      # step).
      coordinated.publish_boundary(step)
      coordinated.poll(step)
      if (self._manager is not None and
          coordinated.participants is not None):
        self._manager.set_participants(coordinated.participants)
    self.save_checkpoint(force=True, sync=True)
    if self._manager is not None:
      self._manager.wait_until_finished()
    if eval_iter_fn is not None and not eval_metrics:
      eval_metrics = self.evaluate(eval_iter_fn())
    for cb in self._callbacks:
      cb.end(self)
    return eval_metrics or scalars

  def evaluate(self, eval_iter: Iterator[Batch]) -> MetricDict:
    config = self._config
    if self._state is None:
      features, labels = next(eval_iter)
      self.initialize(features)
      batches: List[Batch] = [(features, labels)]
    else:
      batches = []
    metric_batches: List[MetricDict] = []
    for _ in range(config.eval_steps):
      if batches:
        features, labels = batches.pop()
      else:
        try:
          features, labels = next(eval_iter)
        except StopIteration:
          break
      features = mesh_lib.shard_batch(features, self._mesh)
      labels = mesh_lib.shard_batch(labels, self._mesh)
      # Keep per-batch metrics on device; a float() here would force a
      # device sync every eval step. One sync happens in _mean_metrics.
      metric_batches.append(self._eval_step_fn(self._state, features, labels))
    metrics = _mean_metrics(jax.device_get(metric_batches))
    for cb in self._callbacks:
      cb.after_eval(self, self.step, metrics)
    return metrics

  def predict(self, features) -> SpecStruct:
    """Single PREDICT-mode forward pass on numpy features."""
    if self._state is None:
      self.initialize(features)
    features_p, _ = self._preprocessor.preprocess(
        features, None, ModeKeys.PREDICT, None)
    outputs, _ = self._model.inference_network_fn(
        dict(self._state.eval_variables), features_p, None, ModeKeys.PREDICT)
    return self._model.create_export_outputs_fn(features_p, outputs)

  def close(self) -> None:
    if self._manager is not None:
      self._manager.wait_until_finished()
      self._manager.close()


# ------------------------------------------------------------ driver entry


EVAL_STATE_FILENAME = 'eval_state.json'


def _read_continuous_eval_state(model_dir: str) -> Optional[int]:
  """Last step the continuous evaluator finished, or None."""
  if not model_dir:
    return None
  import json

  try:
    with open(os.path.join(model_dir, EVAL_STATE_FILENAME)) as f:
      return int(json.load(f)['last_evaluated_step'])
  except (OSError, ValueError, KeyError, TypeError):
    return None


def _write_continuous_eval_state(model_dir: str, step: int) -> None:
  """Atomically persists the evaluator's position (crash/preempt-safe)."""
  if not model_dir:
    return
  import json

  path = os.path.join(model_dir, EVAL_STATE_FILENAME)
  tmp = path + f'.tmp{os.getpid()}'
  with open(tmp, 'w') as f:
    json.dump({'last_evaluated_step': int(step)}, f)
  os.replace(tmp, path)


def provide_input_generator_with_model_information(input_generator, model,
                                                   mode: str):
  """Spec handshake (utils/train_eval.py:101-129)."""
  input_generator.set_specification_from_model(model, mode)
  return input_generator


def train_eval_model(model=None,
                     model_dir: str = '',
                     train_input_generator=None,
                     eval_input_generator=None,
                     max_train_steps: int = 1000,
                     eval_steps: int = 10,
                     eval_interval_steps: int = 500,
                     save_interval_steps: int = 500,
                     max_checkpoints_to_keep: Optional[int] = 5,
                     log_interval_steps: int = 100,
                     seed: int = 0,
                     mesh: Optional[jax.sharding.Mesh] = None,
                     callbacks: Sequence[TrainerCallback] = (),
                     create_exporters_fn=None,
                     use_continuous_eval: bool = False,
                     eval_timeout_secs: Optional[float] = 30.0,
                     steps_per_dispatch: int = 1,
                     checkpoint_input_state: bool = False,
                     nonfinite_mode: str = 'off',
                     nonfinite_halt_after: int = 10,
                     handle_preemption: bool = False,
                     ) -> MetricDict:
  """The reference's `train_eval_model` entry (utils/train_eval.py:394-587).

  * train + eval generators → interleaved train/eval (+ export on eval).
  * train generator only → train-only job.
  * eval generator only + ``use_continuous_eval`` → watch ``model_dir`` for
    new checkpoints, evaluate each, and run exporters.
  """
  if model is None:
    raise ValueError('train_eval_model requires a model.')
  config = TrainerConfig(
      model_dir=model_dir,
      max_train_steps=max_train_steps,
      eval_steps=eval_steps,
      eval_interval_steps=eval_interval_steps,
      save_interval_steps=save_interval_steps,
      max_checkpoints_to_keep=max_checkpoints_to_keep,
      log_interval_steps=log_interval_steps,
      seed=seed,
      steps_per_dispatch=steps_per_dispatch,
      nonfinite_mode=nonfinite_mode,
      nonfinite_halt_after=nonfinite_halt_after,
      handle_preemption=handle_preemption)
  callbacks = list(callbacks)
  exporters = []
  if create_exporters_fn is not None:
    exporters = list(create_exporters_fn(model))

  if train_input_generator is not None:
    provide_input_generator_with_model_information(
        train_input_generator, model, ModeKeys.TRAIN)
  if eval_input_generator is not None:
    provide_input_generator_with_model_information(
        eval_input_generator, model, ModeKeys.EVAL)

  train_iter = None
  if train_input_generator is not None:
    if checkpoint_input_state:
      # Resumable stream (train/input_state.py): save the pipeline
      # position with every checkpoint and restore it on resume. The
      # generator must support it (record-backed generators do); a
      # config asking for it on one that doesn't should fail loudly,
      # not silently restart streams on every preemption.
      from tensor2robot_tpu.train.input_state import InputStateCallback

      if not hasattr(train_input_generator,
                     'create_checkpointable_iterator'):
        raise ValueError(
            'checkpoint_input_state=True needs a generator with '
            'create_checkpointable_iterator (e.g. '
            'DefaultRecordInputGenerator); got '
            f'{type(train_input_generator).__name__}.')
      train_iter = train_input_generator.create_checkpointable_iterator(
          ModeKeys.TRAIN)
      callbacks.append(InputStateCallback(train_iter))
    else:
      train_iter = train_input_generator.create_iterator(ModeKeys.TRAIN)

  trainer = Trainer(model, config, mesh=mesh, callbacks=callbacks)

  # Spec dump at startup (the reference logs the full in/out spec contract
  # before training, utils/train_eval.py:65-98).
  preprocessor = model.preprocessor
  for kind, getter in (
      ('feature', preprocessor.get_in_feature_specification),
      ('label', preprocessor.get_in_label_specification)):
    spec = getter(ModeKeys.TRAIN)
    if spec is not None:
      logging.info('train %s specs:\n%s', kind,
                   '\n'.join(f'  {k}: {v}'
                             for k, v in sorted(spec.items())))

  def run_exporters(metrics: MetricDict) -> None:
    for exporter in exporters:
      exporter.export(trainer, metrics)

  try:
    if train_iter is not None:
      eval_iter_fn = None
      if eval_input_generator is not None:
        eval_iter_fn = lambda: eval_input_generator.create_iterator(
            ModeKeys.EVAL)
      metrics = trainer.train(train_iter, eval_iter_fn)
      if exporters:
        run_exporters(metrics)
      return metrics
    if eval_input_generator is None:
      raise ValueError('Need a train or eval input generator.')
    # Continuous-eval job over appearing checkpoints
    # (utils/train_eval.py:550-585). Each step is BACKED UP into the
    # evaluator's own directory before restore so the trainer's retention
    # GC cannot delete it mid-eval (utils/train_eval.py:590-707).
    #
    # Preemption-aware (PR-1 follow-up): the loop persists its last
    # evaluated step to <model_dir>/eval_state.json after every eval, and
    # a graceful-shutdown request (SIGTERM on a preemptible evaluator —
    # installed by the Trainer when handle_preemption is on) raises
    # PreemptedError BETWEEN checkpoints, which the trainer binary
    # converts to the resumable exit status 42. The restarted evaluator
    # reads the state and skips already-evaluated checkpoints instead of
    # re-running (or worse, re-exporting) them.
    metrics = {}
    ckpt_dir = os.path.join(model_dir, 'checkpoints')
    backup_dir = os.path.join(model_dir, ckpt_lib.EVAL_BACKUP_DIRNAME)
    last_evaluated: Optional[int] = None
    if use_continuous_eval:
      last_evaluated = _read_continuous_eval_state(model_dir)
      if last_evaluated is not None:
        logging.info(
            'Continuous eval resuming: checkpoints up to step %d were '
            'already evaluated.', last_evaluated)
    shutdown = (trainer._shutdown if trainer._shutdown is not None  # pylint: disable=protected-access
                else resilience.active_shutdown())
    for step in ckpt_lib.checkpoints_iterator(
        ckpt_dir,
        timeout=eval_timeout_secs,
        stop_after_step=max_train_steps if use_continuous_eval else None):
      if last_evaluated is not None and step <= last_evaluated:
        logging.info(
            'Continuous eval: skipping step %d (already evaluated before '
            'the restart).', step)
        continue
      if shutdown is not None and shutdown.requested:
        logging.warning(
            'Graceful shutdown requested; continuous eval exiting '
            'resumable after step %s.', last_evaluated)
        if use_continuous_eval and last_evaluated is not None:
          _write_continuous_eval_state(model_dir, last_evaluated)
        raise resilience.PreemptedError(last_evaluated or 0)
      backup = ckpt_lib.create_backup_checkpoint_for_eval(
          ckpt_dir, step, backup_dir)
      if backup is None:
        # GC won the race; wait for the next checkpoint. If this was the
        # final checkpoint the iterator will terminate, so say loudly
        # that the returned metrics are from an earlier step.
        logging.warning(
            'Continuous eval: checkpoint %d disappeared before it could '
            'be backed up; skipping its eval.', step)
        if use_continuous_eval and step >= max_train_steps:
          logging.warning(
              'Continuous eval: the FINAL checkpoint (step %d) was never '
              'evaluated; returning metrics from the last evaluated '
              'checkpoint%s.', step, '' if metrics else ' (none: empty)')
        continue
      eval_iter = eval_input_generator.create_iterator(ModeKeys.EVAL)
      if trainer.state is None:
        features, _ = next(eval_input_generator.create_iterator(ModeKeys.EVAL))
        trainer.initialize(features)
      restored = ckpt_lib.restore_from_backup(trainer.state, backup)
      if restored is not None:
        trainer._state = restored  # pylint: disable=protected-access
      metrics = trainer.evaluate(eval_iter)
      if exporters:
        run_exporters(metrics)
      last_evaluated = step
      if use_continuous_eval:
        _write_continuous_eval_state(model_dir, step)
      if not use_continuous_eval:
        break
    return metrics
  finally:
    trainer.close()


def predict_from_model(model=None,
                       input_generator=None,
                       model_dir: str = '',
                       mesh: Optional[jax.sharding.Mesh] = None):
  """Streams predictions batch-by-batch (utils/train_eval.py:364-391)."""
  if model is None or input_generator is None:
    raise ValueError('predict_from_model requires model and input generator.')
  config = TrainerConfig(model_dir=model_dir, async_checkpoints=False)
  trainer = Trainer(model, config, mesh=mesh)
  provide_input_generator_with_model_information(
      input_generator, model, ModeKeys.PREDICT)
  for features, _ in input_generator.create_iterator(ModeKeys.PREDICT):
    yield trainer.predict(features)
