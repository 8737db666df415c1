"""Parallel host input engine: stage-overlapped, deterministic batching.

The native record path (``NativeRecordInputGenerator``) historically ran
read → parse → decode → batch as ONE serial chain per batch behind the
trainer's single prefetch thread: on multi-core hosts, decode of batch
N+1 never overlapped parse of N+2, and the host side capped record-fed
training far below the device floor on shallow-step workloads (PERF_NOTES
"Record-fed training"). The reference hid this problem inside tf.data's
C++ multi-threaded runtime; this module is the JAX-native equivalent for
the TF-free path.

Stages (each its own thread(s), connected by bounded queues):

  ticket issuer   ONE thread walks the interleaved/shuffled record
                  stream in its deterministic order and slices it into
                  numbered batch tickets ``(seq, [records])``. All
                  ordering authority lives here.
  workers (N)     each pulls a ticket and runs parse + image decode for
                  its WHOLE batch (the expensive, GIL-releasing work),
                  concurrently across DIFFERENT batches.
  reorder         delivers parsed batches strictly in ticket order, so
                  the output stream is byte-identical to the serial path
                  for ANY worker count — and errors surface at exactly
                  the batch index where the serial path would have
                  raised them.

Because delivery order equals ticket order equals the serial record
order, the engine's stream position is well-defined (delivered batch
count), which is what makes the native path's mid-epoch resumable input
state possible (``NativeRecordInputGenerator.create_checkpointable_
iterator``).

Backpressure: at most ``ring_depth`` tickets are outstanding (issued but
not yet delivered/released), bounding memory to a ring of batch buffers.
With ``reuse_buffers=True`` the ring is literal: each slot owns
preallocated contiguous per-feature image buffers (``parse_fn.
make_image_buffers``) that workers decode straight into — no per-batch
allocation, no ``np.stack`` copy — and a slot recycles only after the
consumer calls :meth:`release` (delivered arrays are VIEWS of slot
buffers; release declares them dead). Default ``False`` allocates fresh
buffers per ticket, so delivered batches are plainly owned by the caller
— the right mode for the trainer, whose prefetch queue holds batches
with no release point.

Sizing is core-aware and self-tuning: :func:`autotune` generalizes the
trainer's ``prefetch auto`` heuristic — it reads the AVAILABLE core
count (affinity/cgroup-aware) plus the PR-2 observability signals
(``trainer/input_bound_fraction``, prefetch starvation counters) when a
measured window exists, and collapses to the serial path on single-core
hosts, where PERF_NOTES measured extra pipeline threads as a net loss
(they contend with dispatch instead of overlapping it).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import queue as queue_lib
import threading
import time
from typing import Any, Callable, Iterable, Iterator, List, Optional

from tensor2robot_tpu.observability import metrics as metrics_lib
from tensor2robot_tpu.observability import tracing

# Autotune defaults. Workers beyond ~4 stop paying off for JPEG-decode
# batches (the native decoder already fans one batch across cores); the
# input-bound escalation may go to 8 when the breakdown proves the run
# is starved anyway.
_DEFAULT_MAX_WORKERS = 4
_INPUT_BOUND_MAX_WORKERS = 8
# A tuning window is trusted only after this many measured dispatches.
_MIN_DISPATCHES_FOR_SIGNALS = 32
# input_bound_fraction thresholds: below the floor the run is compute-
# bound and pipeline threads would only contend; above the ceiling the
# host is the bottleneck and deserves every core.
_COMPUTE_BOUND_FRACTION = 0.05
_INPUT_BOUND_FRACTION = 0.5


def available_cpus() -> int:
  """CPUs AVAILABLE to this process (affinity/cgroup-aware):
  ``os.cpu_count`` lies under taskset/containers."""
  try:
    return len(os.sched_getaffinity(0))
  except (AttributeError, OSError):
    return os.cpu_count() or 1


@dataclasses.dataclass(frozen=True)
class EngineDecision:
  """One autotune outcome — recorded beside bench metrics (bench.py)."""

  num_workers: int
  ring_depth: int
  prefetch_depth: int
  cpus: int
  reason: str

  @property
  def serial(self) -> bool:
    return self.num_workers == 0

  def as_dict(self) -> dict:
    return dataclasses.asdict(self)


_LAST_DECISION: Optional[EngineDecision] = None


def last_decision() -> Optional[EngineDecision]:
  """The most recent :func:`autotune` outcome in this process."""
  return _LAST_DECISION


def _signal_window():
  """(input_bound_fraction, starvation, dispatches) from the registry,
  or None when no trustworthy measured window exists yet."""
  dispatches = metrics_lib.counter('trainer/dispatches').value
  if dispatches < _MIN_DISPATCHES_FOR_SIGNALS:
    return None
  if 'trainer/input_bound_fraction' not in metrics_lib.registry.names(
      'trainer/input_bound_fraction'):
    return None
  return (metrics_lib.gauge('trainer/input_bound_fraction').value,
          metrics_lib.counter('trainer/prefetch/starvation').value,
          dispatches)


def autotune(num_workers: Optional[int] = None,
             ring_depth: Optional[int] = None,
             cpus: Optional[int] = None) -> EngineDecision:
  """Core-aware worker/ring sizing; explicit arguments always win.

  ``num_workers=None`` asks for the heuristic: 0 (serial) on single-core
  hosts; else ``min(cpus - 1, 4)``, refined by the step-time breakdown's
  signals when a prior window measured this process as compute-bound
  (shrink to 1) or input-bound (grow toward ``cpus - 1``). The decision
  is published as ``data/engine/*`` gauges and kept for
  :func:`last_decision`.
  """
  global _LAST_DECISION
  cpus = available_cpus() if cpus is None else int(cpus)
  if num_workers is not None:
    workers = max(0, int(num_workers))
    reason = f'explicit num_workers={workers}'
  elif cpus <= 1:
    workers = 0
    reason = ('single-core host: serial path (pipeline threads contend '
              'with dispatch instead of overlapping it)')
  else:
    workers = min(cpus - 1, _DEFAULT_MAX_WORKERS)
    reason = f'{cpus} cpus: default min(cpus-1, {_DEFAULT_MAX_WORKERS})'
    signals = _signal_window()
    if signals is not None:
      input_bound, starvation, dispatches = signals
      if input_bound < _COMPUTE_BOUND_FRACTION and starvation == 0:
        workers = min(workers, 1)
        reason = (f'measured compute-bound (input_bound_fraction='
                  f'{input_bound:.3f} over {dispatches} dispatches): '
                  f'1 worker suffices')
      elif input_bound >= _INPUT_BOUND_FRACTION or starvation > 0:
        workers = min(cpus - 1, _INPUT_BOUND_MAX_WORKERS)
        reason = (f'measured input-bound (input_bound_fraction='
                  f'{input_bound:.3f}, starvation={starvation}): '
                  f'all spare cores')
  if ring_depth is None:
    ring_depth = 2 * workers if workers else 0
  ring_depth = max(ring_depth, workers + 1) if workers else 0
  prefetch_depth = 0 if cpus <= 1 else 2
  decision = EngineDecision(
      num_workers=workers, ring_depth=ring_depth,
      prefetch_depth=prefetch_depth, cpus=cpus, reason=reason)
  scope = metrics_lib.scope('data/engine')
  scope.gauge('workers').set(decision.num_workers)
  scope.gauge('ring_depth').set(decision.ring_depth)
  _LAST_DECISION = decision
  return decision


def autotune_prefetch(cpus: Optional[int] = None) -> int:
  """The trainer's ``prefetch auto`` depth — same core heuristic."""
  cpus = available_cpus() if cpus is None else int(cpus)
  return 0 if cpus <= 1 else 2


class _Failure:
  """A ticket whose production raised: delivered in order, then raised."""

  __slots__ = ('exc',)

  def __init__(self, exc: BaseException):
    self.exc = exc


class ParallelBatchEngine:
  """Ticket-ordered parallel read→parse→decode over a record stream.

  ``records``: the raw serialized-record iterator (the generator's
  interleaved + shuffled stream) — consumed by ONE issuer thread, so its
  deterministic order is preserved exactly. ``parse_fn(records) ->
  batch`` runs in the workers (it must be thread-safe across DIFFERENT
  record lists, which the native parser and decode pools are).
  ``num_workers == 0`` degrades to a fully serial inline loop (no
  threads at all) — the reference stream every parallel configuration is
  byte-compared against.

  Iteration yields exactly what the serial loop would: one parsed batch
  per ``batch_size`` records, final short batch dropped
  (``drop_remainder`` parity). ``delivered`` counts yielded batches —
  the engine's checkpointable stream position.

  ``reautotune=True`` re-evaluates the worker count MID-RUN: at every
  trainer log-window crossing (``trainer/breakdown_windows``) the engine
  re-reads the live breakdown signals — ``trainer/input_bound_fraction``
  and the prefetch-starvation delta over the window — and grows/shrinks
  its worker pool, at most one change per window. The stream stays
  byte-identical through any resize (ticket order is the only ordering
  authority); bounds are [1, ring_depth - 1], so a running pipeline
  never collapses to the thread-less serial path nor outgrows its ring.
  Decision history is published as ``data/engine/reautotune/*`` and kept
  on :attr:`decision_history`.
  """

  _DONE = object()
  _RETIRE = object()  # poison pill retiring exactly one worker (resize)

  def __init__(self,
               records: Iterable[bytes],
               parse_fn: Callable[[List[bytes]], Any],
               batch_size: int,
               num_workers: int,
               ring_depth: Optional[int] = None,
               reuse_buffers: bool = False,
               reautotune: bool = False,
               cpus: Optional[int] = None,
               lease_timeout: float = 5.0,
               start_delivered: int = 0):
    if batch_size <= 0:
      raise ValueError(f'batch_size must be positive, got {batch_size}')
    self._records = iter(records)
    self._parse_fn = parse_fn
    self._batch_size = int(batch_size)
    # Serial-vs-pipeline is a MODE, fixed at construction; the mutable
    # worker-pool size below never crosses it (re-autotune bounds are
    # [1, ring_depth-1]), so mode checks need no lock.
    self._serial = max(0, int(num_workers)) == 0
    self._num_workers = max(0, int(num_workers))  # GUARDED_BY(self._workers_lock)
    # ``start_delivered``: a resumed pipeline's record iterator begins
    # mid-stream (seek or replay restore), so ``delivered`` — the
    # engine's checkpointable stream position — continues from the
    # restored batch count instead of restarting at 0.
    self.delivered = int(start_delivered)
    self._workers_lock = threading.Lock()
    self._closed = False  # GUARDED_BY(self._workers_lock)
    self._metrics = metrics_lib.scope('data/engine')
    self._m_tickets = self._metrics.counter('tickets')
    self._m_batches = self._metrics.counter('batches')
    self._m_reorder_depth = self._metrics.gauge('reorder_depth')
    self._m_wait = self._metrics.histogram('reorder_wait_ms')
    if self._serial:
      self._pending: List[bytes] = []
      return

    if ring_depth is None:
      ring_depth = 2 * self._num_workers
    self._ring_depth = max(int(ring_depth), self._num_workers + 1)
    # Mid-run re-autotune state: one evaluation per closed breakdown
    # window, keyed off the trainer's window counter; starvation is read
    # as a per-window delta (the counter is cumulative — an incident an
    # hour ago must not pin the pool grown forever).
    self._cpus = cpus
    self._reautotune_enabled = bool(reautotune)
    self._max_workers = self._ring_depth - 1
    self._worker_seq = self._num_workers  # GUARDED_BY(self._workers_lock)
    self._lease_lock = threading.Lock()
    self._lease_cond = threading.Condition(self._lease_lock)
    self._lease_timeout = float(lease_timeout)
    self._m_windows = metrics_lib.counter('trainer/breakdown_windows')
    self._last_window = self._m_windows.value
    self._starve_counter = metrics_lib.counter('trainer/prefetch/starvation')
    self._last_starvation = self._starve_counter.value
    self._m_workers = self._metrics.gauge('workers')
    self._m_reauto_windows = self._metrics.counter('reautotune/windows')
    self._m_reauto_changes = self._metrics.counter('reautotune/changes')
    self._m_reauto_target = self._metrics.gauge('reautotune/target_workers')
    self.decision_history: List[dict] = []  # GUARDED_BY(self._workers_lock)
    # Outstanding-ticket bound: acquired per issued ticket, released when
    # the consumer is done with the batch (delivery, or — in ring mode —
    # the explicit release that frees the slot for reuse).
    self._sem = threading.Semaphore(self._ring_depth)
    self._ticket_q: 'queue_lib.Queue' = queue_lib.Queue()
    self._cond = threading.Condition()
    self._results: dict = {}  # seq -> batch | _Failure  # GUARDED_BY(self._cond)
    self._next_seq = 0  # GUARDED_BY(self._cond)
    self._end_seq: Optional[int] = None  # first seq never produced  # GUARDED_BY(self._cond)
    self._stop = threading.Event()

    self._reuse = bool(reuse_buffers)
    self._free_slots: 'queue_lib.Queue' = queue_lib.Queue()
    self._slot_of: dict = {}  # seq -> slot id (ring mode)  # GUARDED_BY(self._cond)
    self._lease_order: List[int] = []  # delivered-not-released slots, FIFO  # GUARDED_BY(self._lease_cond)
    if self._reuse:
      make_buffers = getattr(parse_fn, 'make_image_buffers', None)
      if make_buffers is None:
        logging.warning(
            'reuse_buffers=True but parse_fn has no make_image_buffers; '
            'falling back to per-ticket allocation.')
        self._reuse = False
      else:
        self._slots = [make_buffers(self._batch_size)
                       for _ in range(self._ring_depth)]
        for i in range(self._ring_depth):
          self._free_slots.put(i)

    self._threads = [  # GUARDED_BY(self._workers_lock)
        threading.Thread(target=self._issue_tickets, daemon=True,
                         name='t2r-engine-tickets')
    ]
    for i in range(self._num_workers):
      self._threads.append(
          threading.Thread(target=self._worker, daemon=True,
                           name=f't2r-engine-worker-{i}'))
    for t in self._threads:
      t.start()

  # ------------------------------------------------------------- threads

  def _issue_tickets(self) -> None:
    """The ordering authority: slices the record stream into numbered
    tickets. A stream error occupies the seq at which the serial path
    would have raised it, so error position is order-preserved too."""
    seq = 0
    try:
      pending: List[bytes] = []
      for record in self._records:
        pending.append(record)
        if len(pending) < self._batch_size:
          continue
        while not self._sem.acquire(timeout=0.1):
          if self._stop.is_set():
            return
        if self._stop.is_set():
          return
        self._m_tickets.inc()
        self._ticket_q.put((seq, pending))
        seq += 1
        pending = []
      # Final short batch dropped: drop_remainder parity with the
      # serial loop and the tf.data path.
    except BaseException as e:  # delivered, in order, at seq
      with self._cond:
        self._results[seq] = _Failure(e)
        self._end_seq = seq + 1
        self._cond.notify_all()
    else:
      with self._cond:
        self._end_seq = seq
        self._cond.notify_all()
    finally:
      # One sentinel; workers re-put it as they exit (the pool may have
      # been resized since these tickets were issued).
      self._ticket_q.put(self._DONE)

  def _worker(self) -> None:
    while True:
      item = self._ticket_q.get()
      if item is self._RETIRE:
        return  # mid-run shrink: exactly one worker exits
      if item is self._DONE:
        # Propagate end-of-stream to sibling workers: the issuer puts
        # ONE sentinel, so shutdown is correct for any worker count the
        # pool was resized to since tickets started.
        self._ticket_q.put(self._DONE)
        return
      if self._stop.is_set():
        return
      seq, records = item
      slot = None
      if self._reuse:
        slot = self._free_slots.get()  # never blocks long: slots ≥ the
        # outstanding-ticket bound, and a ticket only exists with its
        # semaphore permit held.
      try:
        # Keyed by the ticket: the engine's own sequence (it restarts
        # with every engine and counts batches the trainer may drop), so
        # not the trainer's batch ordinal.
        with tracing.span('data/engine/parse_decode', key=seq,
                          annotate=False):
          if slot is None:
            batch = self._parse_fn(records)
          else:
            batch = self._parse_fn(records, image_out=self._slots[slot])
      except BaseException as e:  # surfaced at this seq, in order
        if slot is not None:
          self._free_slots.put(slot)
          slot = None
        batch = _Failure(e)
      with self._cond:
        self._results[seq] = batch
        if slot is not None:
          self._slot_of[seq] = slot
        self._m_reorder_depth.set(len(self._results))
        self._cond.notify_all()

  # ----------------------------------------------------- mid-run autotune

  def _maybe_reautotune(self) -> None:
    """One worker-count re-evaluation per closed breakdown window."""
    if not self._reautotune_enabled:
      return
    windows = self._m_windows.value
    if windows == self._last_window:
      return
    self._last_window = windows
    self._m_reauto_windows.inc()
    starvation = self._starve_counter.value
    starve_delta = starvation - self._last_starvation
    self._last_starvation = starvation
    if (metrics_lib.counter('trainer/dispatches').value <
        _MIN_DISPATCHES_FOR_SIGNALS):
      return
    input_bound = metrics_lib.gauge('trainer/input_bound_fraction').value
    cpus = available_cpus() if self._cpus is None else int(self._cpus)
    with self._workers_lock:
      current = self._num_workers
    if input_bound < _COMPUTE_BOUND_FRACTION and starve_delta == 0:
      target = 1  # compute-bound: extra pipeline threads only contend
    elif input_bound >= _INPUT_BOUND_FRACTION or starve_delta > 0:
      target = min(max(cpus - 1, 1), _INPUT_BOUND_MAX_WORKERS)
    else:
      target = current
    target = max(1, min(target, self._max_workers))
    self._m_reauto_target.set(target)
    if target != current:
      self._set_num_workers(target, input_bound, starve_delta)

  def _set_num_workers(self, target: int, input_bound: float,
                       starvation: int) -> None:
    """Grows (spawn) or shrinks (retire pills) the worker pool in place.

    Safe mid-stream: tickets/reorder carry all ordering state, so the
    delivered stream is byte-identical across any resize. Retire pills
    queue FIFO behind outstanding tickets — a shrinking pool finishes
    the work it already accepted.
    """
    with self._workers_lock:
      if self._closed:
        return  # close() already snapshotted the pool: no new threads
      old = self._num_workers
      if target == old:
        return
      if target > old:
        for _ in range(target - old):
          t = threading.Thread(target=self._worker, daemon=True,
                               name=f't2r-engine-worker-{self._worker_seq}')
          self._worker_seq += 1
          self._threads.append(t)
          t.start()
      else:
        for _ in range(old - target):
          self._ticket_q.put(self._RETIRE)
      self._num_workers = target
      decision = {'window': self._last_window, 'from': old, 'to': target,
                  'input_bound_fraction': round(float(input_bound), 4),
                  'starvation': int(starvation)}
      self.decision_history.append(decision)
    self._m_workers.set(target)
    self._m_reauto_changes.inc()
    logging.info('Input engine re-autotune: %s', decision)

  # ------------------------------------------------------------ consumer

  def __iter__(self) -> Iterator[Any]:
    return self

  def __next__(self) -> Any:
    if self._serial:
      return self._serial_next()
    self._maybe_reautotune()
    if self._reuse:
      # A full ring is TRANSIENT when someone releases asynchronously
      # (the trainer's placement stage frees each lease at transfer
      # completion, from its own thread) — wait briefly for that. Only a
      # ring nobody will ever release (a consumer ignoring the lease
      # contract) stays full: fail loudly then, deadlocking never.
      deadline = time.monotonic() + self._lease_timeout
      with self._lease_cond:
        while len(self._lease_order) >= self._ring_depth:
          remaining = deadline - time.monotonic()
          if remaining <= 0:
            raise RuntimeError(
                f'all {self._ring_depth} ring slots are leased (no '
                f'release() for {self._lease_timeout:.1f}s); call '
                f'release() once per consumed batch before requesting '
                f'the next one')
          self._lease_cond.wait(timeout=remaining)
    t0 = time.perf_counter()
    with self._cond:
      while (self._next_seq not in self._results and
             (self._end_seq is None or self._next_seq < self._end_seq)):
        self._cond.wait()
      if self._next_seq not in self._results:
        raise StopIteration
      seq = self._next_seq
      self._next_seq += 1
      result = self._results.pop(seq)
      self._m_reorder_depth.set(len(self._results))
      slot = self._slot_of.pop(seq, None)
    self._m_wait.observe((time.perf_counter() - t0) * 1e3)
    if isinstance(result, _Failure):
      self.close()
      raise result.exc
    if slot is not None:
      # Ring mode: the permit (and the slot) stay held until release().
      with self._lease_lock:
        self._lease_order.append(slot)
    else:
      self._sem.release()
    self.delivered += 1
    self._m_batches.inc()
    return result

  def _serial_next(self) -> Any:
    """The reference path: one batch, produced inline, no threads."""
    pending = self._pending
    self._pending = []
    for record in self._records:
      pending.append(record)
      if len(pending) >= self._batch_size:
        with tracing.span('data/engine/parse_decode', key=self.delivered,
                          annotate=False):
          batch = self._parse_fn(pending)
        self.delivered += 1
        self._m_batches.inc()
        return batch
    raise StopIteration  # final short batch dropped (drop_remainder)

  def release(self) -> None:
    """Ring mode: declares the OLDEST still-leased batch's arrays dead.

    Delivered batches are views of ring-slot buffers; releasing returns
    the slot to the worker pool (and its backpressure permit), after
    which those arrays WILL be overwritten. Call once per consumed batch,
    after its contents are copied/placed. No-op without
    ``reuse_buffers``. Thread-safe: the trainer's placement stage
    releases from its own thread while the fetch stage consumes.
    """
    if self._serial or not self._reuse:
      return
    with self._lease_cond:
      if not self._lease_order:
        return
      slot = self._lease_order.pop(0)
      self._lease_cond.notify_all()
    self._free_slots.put(slot)
    self._sem.release()

  # ------------------------------------------------------------ lifecycle

  def close(self, timeout: float = 5.0) -> None:
    """Stops the pipeline threads (idempotent)."""
    with self._workers_lock:
      if self._serial or self._closed:
        self._closed = True
        return
      self._closed = True
      # Snapshot pool state under the lock: a concurrent mid-run grow
      # (_set_num_workers, driven from the consumer thread) appends to
      # _threads while this method would otherwise iterate it — a
      # RuntimeError plus unjoined workers (found by the lock-discipline
      # checker, PR 8). After _closed flips, _set_num_workers is a
      # no-op, so the snapshot is complete.
      threads = list(self._threads)
      workers = self._num_workers
    self._stop.set()
    with self._cond:
      # A next() after close must observe end-of-stream, not block
      # forever waiting for a ticket no worker will ever produce.
      if self._end_seq is None:
        self._end_seq = self._next_seq
      self._cond.notify_all()
    # Unblock workers waiting on tickets/slots and the issuer waiting on
    # the semaphore (it polls with a timeout).
    for _ in range(workers):
      self._ticket_q.put(self._DONE)
    if self._reuse:
      for _ in range(workers):
        self._free_slots.put(0)
    deadline = time.monotonic() + timeout
    for t in threads:
      t.join(max(0.0, deadline - time.monotonic()))
      if t.is_alive():
        logging.warning(
            'Engine thread %s did not exit within %.1fs (record stream '
            'blocked?); abandoning the daemon thread.', t.name, timeout)

  def __enter__(self) -> 'ParallelBatchEngine':
    return self

  def __exit__(self, *exc) -> None:
    self.close()

  def __del__(self):
    try:
      self.close(timeout=0.1)
    except Exception:  # interpreter shutdown
      pass
