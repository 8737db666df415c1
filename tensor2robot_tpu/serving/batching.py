"""Dynamic cross-client batching over a stateless predictor core.

The throughput half of the serving plane (``server.py`` is the transport
half): concurrent per-client action requests are queued, assembled into
ONE padded device dispatch (collect until ``max_batch`` examples or
``batch_deadline_ms`` elapse, whichever first), executed against the
predictor's :class:`~tensor2robot_tpu.predictors.predictors.
StatelessServingFn`, and split back per request. The device-resident CEM
loop already sustains ~94.5 actions/s per chip at batch 64×3 (BENCH_r05
``cem_action_device_ms``) with ONE client; aggregating N clients into one
dispatch multiplies per-chip throughput near-linearly up to the batch-64
optimum instead of serializing N single-sample dispatches.

Design points:

* **Bucketed batch shapes, compiled once.** Totals are padded up to
  power-of-two buckets (≤ ``max_batch``), each bucket AOT-compiled at
  startup via ``jit(fn).lower(...).compile()`` — so a varying client
  count (1 → N → 1) NEVER triggers an XLA recompile in steady state.
  Every compile increments ``serving/bucket_compiles``; tier-1 pins the
  counter flat across varying load (the zero-recompile guarantee is
  structural: the dispatch path only looks up executables).
* **Padding is replication.** Short batches repeat their last example up
  to the bucket edge — shape-stable AND numerically inert for any model
  (zero-fill can manufacture NaNs in normalizing preprocessors). Padded
  rows are sliced off before the split (``serving/padded_examples``).
* **Hot swap between dispatches.** A reload thread polls
  ``predictor.restore()`` (riding the export commit-marker /
  last-good-fallback path from ``export/exporters.py``); a new model
  generation is prepared OFF-thread — params placed, new program's
  buckets warmed — and adopted by the dispatcher atomically between two
  dispatches. In-flight and queued requests are never dropped
  (``serving/model_swaps``); a torn or broken export leaves the last
  good generation serving.
* **One dispatcher thread** owns all device work. Client threads only
  queue and wait, so the GIL-heavy JSON/HTTP edges scale with threads
  while the compute path stays single-file (no executor lock needed).

* **Quantized serving behind a parity gate.** ``quantize='int8'`` (or
  ``'fp8'``) serves the weight-only quantized twin of the stateless fn
  (``tensor2robot_tpu/quantize/``): int8 payload + per-output-channel
  scales streamed from HBM, dequantized inline in the jitted program.
  Batch-1 predict on robot-scale critics is weight-streaming-bound
  (PERF_NOTES r6), so the ~4× byte cut is the serving plane's highest-
  leverage optimisation. Adoption is GATED: the quantized fn must match
  the full-precision fn within ``quant_parity_atol/rtol`` on
  calibration batches, else the plane refuses it and serves full
  precision (``serving/quant_parity_rejects``). Quantization +
  parity checks run off-thread (startup / reload prep, like bucket
  warmup); executable caches key on ``('quant', mode, program_key)``
  so weights-only hot swaps still reuse compiled buckets and the
  zero-recompile guarantee is preserved.

SLO metrics live in the process registry under ``metrics_prefix``
(default ``serving/`` — under a :class:`~tensor2robot_tpu.serving.router.
ModelRouter` each model's batcher scopes to ``serving/model/<name>/``)
and are published through ``/metricsz`` via ``register_report_provider``:
request/action counters, batch-size + request-latency histograms
(p50/p99), a rolling ``actions_per_sec`` gauge, queue depth,
swap/compile counters, and the quantization block (``param_bytes``
gauge, ``quant/*`` parity + compression gauges).

Fleet hooks (ROADMAP direction 2): ``queue_depth`` and ``submit(...,
on_done=...)`` feed the router's admission control and per-class SLOs;
the executor's ``page_out()``/``page_in()`` pair implements HBM-budgeted
model paging — host params and compiled bucket executables are KEPT
across a page-out, so page-in is a ``device_put``, never a recompile.
"""

from __future__ import annotations

import collections
import heapq
import itertools
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from tensor2robot_tpu.observability import flight
from tensor2robot_tpu.observability import metrics as metrics_lib
from tensor2robot_tpu.observability import programs as programs_lib
from tensor2robot_tpu.observability import tracing


class ServingError(Exception):
  """Base class for serving-plane failures."""


class OverloadedError(ServingError):
  """The request queue is full (or the plane is shutting down)."""


class SheddedError(OverloadedError):
  """Admission control rejected this request (priority-class shedding).

  Carries ``retry_after_secs`` so the HTTP edge can reply 503 with a
  ``Retry-After`` header — the client contract is *back off and retry*,
  not *fail*: shedding best-effort traffic is how the interactive robot
  tier keeps its latency SLO under overload.
  """

  def __init__(self, message: str, retry_after_secs: float = 1.0):
    super().__init__(message)
    self.retry_after_secs = float(retry_after_secs)


class RequestError(ServingError):
  """This request failed (bad features, dispatch error)."""


def default_buckets(max_batch: int) -> Tuple[int, ...]:
  """Powers of two up to ``max_batch`` (plus ``max_batch`` if not one)."""
  if max_batch < 1:
    raise ValueError(f'max_batch must be >= 1, got {max_batch}')
  buckets = []
  b = 1
  while b < max_batch:
    buckets.append(b)
    b *= 2
  buckets.append(max_batch)
  return tuple(buckets)


def bucket_for(n: int, buckets: Sequence[int]) -> int:
  """Smallest bucket >= n (buckets are sorted ascending)."""
  for b in buckets:
    if b >= n:
      return b
  raise ValueError(f'batch of {n} exceeds largest bucket {buckets[-1]}')


def pad_to_bucket(features: Dict[str, np.ndarray], total: int,
                  bucket: int) -> Dict[str, np.ndarray]:
  """Pads the batch dim from ``total`` to ``bucket`` by repeating the
  last example (numerically inert for any model, unlike zero fill)."""
  if total == bucket:
    return features
  out = {}
  for key, value in features.items():
    pad = np.repeat(value[-1:], bucket - total, axis=0)
    out[key] = np.concatenate([value, pad], axis=0)
  return out


class _Request:
  """One client's queued examples + completion signal."""

  __slots__ = ('features', 'n', 'enqueue_time', 'event', 'outputs', 'error',
               'model_version', 'request_id', 'traced', 'queued_wall',
               'on_done', 'trace')

  def __init__(self, features: Dict[str, np.ndarray], n: int,
               enqueue_time: float, request_id: str = '',
               traced: bool = False,
               on_done: Optional[Callable[['_Request'], None]] = None,
               trace: Optional[tracing.TraceContext] = None):
    self.features = features
    self.n = n
    self.enqueue_time = enqueue_time
    self.event = threading.Event()
    self.outputs: Optional[Dict[str, np.ndarray]] = None
    self.error: Optional[BaseException] = None
    self.model_version: int = -1
    self.request_id = request_id
    self.traced = traced
    # Cross-process trace context (trace id + the upstream hop's span
    # id): a request carrying one records request/queued/dispatch spans
    # into the process span index (/tracez) under the fleet-wide trace.
    self.trace = trace
    # Completion hook (router SLO accounting): invoked on the dispatcher
    # thread after the result is published, holding no batcher lock.
    self.on_done = on_done
    # Wall-clock submit time for traced requests: the dispatcher records
    # the 'queued' flight event retroactively with this timestamp, so
    # client threads never touch the ring (no lock contention at the
    # submit edge).
    self.queued_wall: float = 0.0


class ServingFuture:
  """Handle returned by :meth:`DynamicBatcher.submit`."""

  def __init__(self, request: _Request):
    self._request = request

  def result(self, timeout: Optional[float] = None) -> Dict[str, np.ndarray]:
    """Blocks for the batched dispatch; raises on failure/timeout."""
    if not self._request.event.wait(timeout):
      raise TimeoutError(
          f'serving request not completed within {timeout}s '
          f'(queued {time.monotonic() - self._request.enqueue_time:.3f}s '
          'ago)')
    if self._request.error is not None:
      raise self._request.error
    return self._request.outputs

  @property
  def model_version(self) -> int:
    return self._request.model_version

  @property
  def request_id(self) -> str:
    """The ID assigned at submit (client-provided or generated)."""
    return self._request.request_id


class JitBucketExecutor:
  """Bucket-shaped AOT executables over a stateless serving fn.

  One executable per batch bucket, compiled via
  ``jax.jit(fn).lower(params_shapes, batch_shapes).compile()`` — the
  dispatch path is a dict lookup, so steady-state serving can never
  re-trace or re-compile. On hot swap, a generation with the SAME
  ``program_key`` and param shapes inherits the executable cache (only
  the placed params change); a new program recompiles its buckets
  off-thread before adoption.
  """

  def __init__(self, serving: 'StatelessServingFn',
               buckets: Sequence[int],
               compiled: Optional[Dict[int, Any]] = None,
               label: str = 'serving'):
    import jax

    from tensor2robot_tpu.export.exporters import to_plain_tree

    self._fn = serving.fn
    self._feature_spec = serving.feature_spec
    self._buckets = tuple(buckets)
    self._label = label
    self.program_key = serving.program_key
    self.version = serving.version
    self.params_ref = serving.params  # identity marker for swap detection
    # Under quantization the served params are a DERIVED tree; the
    # batcher re-points these at the predictor's source generation so
    # reload polling compares against what restore() actually produces.
    self.source_params_ref = serving.params
    self.source_program_key = serving.program_key
    host_params = to_plain_tree(serving.params)
    # HBM bytes streamed per dispatch (the quantization target metric;
    # QuantizedTensor nodes count payload + scales).
    self.param_bytes = int(sum(
        np.asarray(leaf).size * np.asarray(leaf).dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(host_params)))
    self._param_shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        host_params)
    # The host tree is KEPT across the executor's lifetime: it is what
    # makes model paging (router.py) a `device_put`, never a reload or a
    # recompile — compiled bucket executables survive a page-out.
    self._host_params = host_params
    # Weights live on device across dispatches: re-uploading them per
    # batch would dominate the dispatch at robot-scale models. The page
    # lock serializes paging decisions against in-flight dispatches (a
    # page-out waits for the current dispatch, never tears one).
    self._page_lock = threading.Lock()
    self._device_params = jax.device_put(host_params)  # GUARDED_BY(self._page_lock)
    self._compiled: Dict[int, Any] = dict(compiled or {})

  def compatible_cache(self, serving: 'StatelessServingFn'
                       ) -> Optional[Dict[int, Any]]:
    """The executable cache, iff ``serving`` runs the same program over
    the same param shapes (the weights-only hot-swap case)."""
    import jax

    if serving.program_key != self.program_key:
      return None
    from tensor2robot_tpu.export.exporters import to_plain_tree

    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        to_plain_tree(serving.params))
    try:
      equal = (jax.tree_util.tree_structure(shapes) ==
               jax.tree_util.tree_structure(self._param_shapes) and
               all(a.shape == b.shape and a.dtype == b.dtype
                   for a, b in zip(jax.tree_util.tree_leaves(shapes),
                                   jax.tree_util.tree_leaves(
                                       self._param_shapes))))
    except Exception:  # pylint: disable=broad-except
      equal = False
    return dict(self._compiled) if equal else None

  def _feature_shapes(self, bucket: int):
    import jax

    return {
        key: jax.ShapeDtypeStruct((bucket,) + tuple(spec.shape), spec.dtype)
        for key, spec in self._feature_spec.items()
    }

  def ensure_bucket(self, bucket: int):
    """Compile-or-get the bucket's executable (counted: a steady-state
    serving plane must show a FLAT ``serving/bucket_compiles``)."""
    exe = self._compiled.get(bucket)
    if exe is None:
      import jax

      t0 = time.perf_counter()
      lowered = jax.jit(self._fn).lower(
          self._param_shapes, self._feature_shapes(bucket))
      exe = lowered.compile()
      compile_seconds = time.perf_counter() - t0
      self._compiled[bucket] = exe
      metrics_lib.counter('serving/bucket_compiles').inc()
      metrics_lib.histogram('serving/bucket_compile_ms').observe(
          1e3 * compile_seconds)
      # Program ledger: every serving bucket lands with its FLOPs/
      # bytes/fingerprint, so /programz (and program_report.py --diff)
      # can say whether e.g. a quantized arm actually shrank the
      # program, and the per-model MFU gauge has its numerator.
      programs_lib.record_compiled(
          f'{self._label}/bucket/{bucket}', exe, lowered=lowered,
          compile_seconds=compile_seconds, source='serving')
    return exe

  def warm(self) -> None:
    for bucket in self._buckets:
      self.ensure_bucket(bucket)

  def dispatch_utilization(self, bucket: int,
                           device_seconds: float) -> Dict[str, float]:
    """Ledger-derived roofline numbers for ONE dispatch of ``bucket``
    ({} until the bucket compiled, or with the ledger disabled)."""
    return programs_lib.utilization(
        f'{self._label}/bucket/{bucket}', 1, device_seconds)

  # ------------------------------------------------------------- HBM paging

  @property
  def resident(self) -> bool:
    """Whether the params are currently device-resident (HBM)."""
    with self._page_lock:
      return self._device_params is not None

  def page_out(self) -> int:
    """Releases the device-resident params (LRU eviction under an HBM
    budget). Host params and every compiled bucket executable are KEPT,
    so the matching page-in is a ``device_put`` — never a recompile.
    Returns the HBM bytes released (0 when already paged out)."""
    with self._page_lock:
      if self._device_params is None:
        return 0
      self._device_params = None
      metrics_lib.counter('serving/page_outs').inc()
      flight.event('router', f'{self._label}/page_out',
                   f'version={self.version} bytes={self.param_bytes}')
      return self.param_bytes

  def page_in(self) -> bool:
    """Re-places host params on device; True iff a transfer happened."""
    with self._page_lock:
      if self._device_params is not None:
        return False
      self._page_in_locked()
      return True

  def _page_in_locked(self) -> None:  # HOLDS(self._page_lock)
    import jax

    t0 = time.perf_counter()
    self._device_params = jax.device_put(self._host_params)
    metrics_lib.counter('serving/page_ins').inc()
    metrics_lib.histogram('serving/page_in_ms').observe(
        1e3 * (time.perf_counter() - t0))
    flight.event('router', f'{self._label}/page_in',
                 f'version={self.version} bytes={self.param_bytes}')

  def execute(self, features: Dict[str, np.ndarray],
              bucket: int) -> Dict[str, np.ndarray]:
    exe = self.ensure_bucket(bucket)
    with self._page_lock:
      # Auto page-in: a request queued for a model the router paged out
      # after admission must never fail — correctness over budget (the
      # router's accounting converges on the next submit).
      if self._device_params is None:
        self._page_in_locked()
      outputs = exe(self._device_params, features)
    return {k: np.asarray(v) for k, v in outputs.items()}


class PredictCallableExecutor:
  """Degraded executor for predictors without a stateless jax core
  (e.g. ``SavedModelPredictor``): one ``predict()`` per assembled batch.

  Cross-client batching still pays (one signature run per batch instead
  of per request); bucketing/padding is skipped — the backend owns its
  own shape handling — so the zero-recompile guarantee does not apply.
  """

  # Callable executors own no device-resident params: they are always
  # "resident" and never pageable (router paging skips them).
  resident = True

  def __init__(self, predictor):
    self._predictor = predictor
    self.program_key = ('predict_callable', id(predictor))
    self.version = predictor.model_version
    self.params_ref = None
    self.param_bytes = 0

  def warm(self) -> None:
    pass

  def page_out(self) -> int:
    return 0

  def page_in(self) -> bool:
    return False

  def compatible_cache(self, serving) -> Optional[Dict[int, Any]]:
    del serving
    return None

  def execute(self, features: Dict[str, np.ndarray],
              bucket: int) -> Dict[str, np.ndarray]:
    del bucket
    return self._predictor.predict(features)


class DynamicBatcher:
  """Deadline-aware cross-client batch assembly + single-file dispatch.

  Thread roles: N client threads ``submit()``; ONE dispatcher thread
  assembles/executes; an optional reload thread prepares new model
  generations. ``close()`` drains — queued requests complete, new
  submits raise :class:`OverloadedError`.
  """

  def __init__(self,
               predictor,
               max_batch: int = 64,
               batch_deadline_ms: float = 5.0,
               max_queue: int = 1024,
               buckets: Optional[Sequence[int]] = None,
               reload_interval_secs: Optional[float] = None,
               quantize: str = 'off',
               quant_parity_atol: float = 0.05,
               quant_parity_rtol: float = 0.05,
               quant_calibration_batches: int = 2,
               quant_calibration_batch_size: int = 4,
               quant_skip_patterns: Sequence[str] = (),
               request_trace_sample: float = 0.0,
               slow_request_log_size: int = 10,
               postmortem_dir: Optional[str] = None,
               metrics_prefix: str = 'serving',
               register_report: bool = True,
               clock: Callable[[], float] = time.monotonic):
    if max_batch < 1:
      raise ValueError(f'max_batch must be >= 1, got {max_batch}')
    if quantize not in (None, '', 'off', 'int8', 'fp8'):
      raise ValueError(f"quantize must be one of 'off'/'int8'/'fp8', "
                       f'got {quantize!r}')
    self._predictor = predictor
    self._quantize = quantize if quantize not in (None, '') else 'off'
    self._quant_parity_atol = float(quant_parity_atol)
    self._quant_parity_rtol = float(quant_parity_rtol)
    self._quant_calibration_batches = int(quant_calibration_batches)
    self._quant_calibration_batch_size = int(quant_calibration_batch_size)
    self._quant_skip_patterns = tuple(quant_skip_patterns)
    self._max_batch = int(max_batch)
    self._deadline_s = float(batch_deadline_ms) / 1e3
    self._max_queue = int(max_queue)
    self._buckets = tuple(sorted(buckets)) if buckets else default_buckets(
        self._max_batch)
    if self._buckets[-1] < self._max_batch:
      raise ValueError(
          f'largest bucket {self._buckets[-1]} < max_batch '
          f'{self._max_batch}: full batches could not dispatch')
    self._reload_interval = reload_interval_secs
    self._clock = clock
    # Per-request tracing (the incident path): every request gets an ID
    # at submit (echoed as X-Request-Id by the HTTP edge and attached to
    # the latency histogram as a bucket exemplar); lifecycle events
    # (queued → assembled → dispatched → returned) flow into the flight
    # ring only for SAMPLED requests — off by default, overhead pinned
    # by bench.py's serving_flight_overhead line.
    if not 0.0 <= float(request_trace_sample) <= 1.0:
      raise ValueError(f'request_trace_sample must be in [0, 1], got '
                       f'{request_trace_sample!r}')
    self._trace_sample = float(request_trace_sample)
    self._trace_every = (int(round(1.0 / self._trace_sample))
                         if self._trace_sample > 0 else 0)
    # CPython-atomic sequence (itertools.count.__next__ holds the GIL);
    # pid-tagged so IDs stay unique across a fleet's logs.
    self._req_seq = itertools.count(1)
    self._id_prefix = f'r{os.getpid():x}'
    self._postmortem_dir = postmortem_dir
    # Fleet-timeline label for this batcher's spans (the serving server
    # stamps 'replica-<port>' / the model name at start); None falls
    # back to the process-wide tracing.service().
    self.service_label: Optional[str] = None
    # Bounded sampled slow-request log: top-k completed requests by
    # latency, surfaced in /statz so a p99 outlier names its request.
    self._slow_k = max(0, int(slow_request_log_size))
    self._slow_lock = threading.Lock()
    self._slow_log: List[Tuple[float, int, Dict[str, Any]]] = []  # GUARDED_BY(self._slow_lock)

    self._cond = threading.Condition()
    self._pending: collections.deque = collections.deque()  # GUARDED_BY(self._cond)
    self._closed = False  # GUARDED_BY(self._cond)
    # Model-generation handoff state. Three threads touch these: the
    # reload poller stages, the dispatcher adopts, clients read the
    # live version — all under the one condition lock (uncontended in
    # steady state: the dispatcher touches it once per dispatch).
    self._model = None  # GUARDED_BY(self._cond)
    self._pending_model = None  # GUARDED_BY(self._cond)
    self._feature_spec = None
    self._dispatcher: Optional[threading.Thread] = None
    self._reloader: Optional[threading.Thread] = None
    self._reload_stop = threading.Event()
    # Rolling actions/s window: (completion_time, n_actions) pairs.
    self._rate_window: collections.deque = collections.deque()
    self._rate_span_s = 5.0

    # Per-instance metric scope: a standalone plane keeps the historical
    # 'serving' prefix; under a ModelRouter each model's batcher scopes
    # to 'serving/model/<name>' so per-model SLOs are first-class (and N
    # batchers in one process never clobber each other's gauges).
    self._metrics_prefix = metrics_prefix.rstrip('/')
    self._register_report = bool(register_report)
    s = metrics_lib.scope(self._metrics_prefix)
    self._m_requests = s.counter('requests')
    self._m_actions = s.counter('actions')
    self._m_errors = s.counter('request_errors')
    self._m_batch_size = s.histogram('batch_size')
    self._m_latency = s.histogram('request_latency_ms')
    self._m_dispatch = s.histogram('dispatch_ms')
    self._m_padded = s.counter('padded_examples')
    self._m_dispatches = s.counter('dispatches')
    self._m_swaps = s.counter('model_swaps')
    self._m_reload_errors = s.counter('reload_errors')
    self._m_queue_depth = s.gauge('queue_depth')
    self._m_actions_per_sec = s.gauge('actions_per_sec')
    self._m_version = s.gauge('model_version')
    self._m_param_bytes = s.gauge('param_bytes')
    self._m_quant_rejects = s.counter('quant_parity_rejects')
    self._m_quant_errors = s.counter('quant_errors')
    qs = metrics_lib.scope(self._metrics_prefix + '/quant')
    self._m_quant_active = qs.gauge('active')
    self._m_quant_bytes_full = qs.gauge('param_bytes_full')
    self._m_quant_bytes_ratio = qs.gauge('param_bytes_ratio')
    self._m_quant_abs_err = qs.gauge('parity_max_abs_err')
    self._m_quant_rel_err = qs.gauge('parity_max_rel_err')
    # Watched across reload polls: the predictor absorbs a committed-
    # but-broken export INTERNALLY (keeps last-good, counts here, never
    # raises) — still an incident worth a bundle.
    self._m_predictor_fallbacks = metrics_lib.counter(
        'predictor/load_fallbacks')

  # ------------------------------------------------------------- lifecycle

  def start(self) -> 'DynamicBatcher':
    """Loads the executor, warms every bucket, starts the dispatcher
    (and the reload poller when ``reload_interval_secs`` is set)."""
    if self._dispatcher is not None:
      return self
    self._predictor.assert_is_loaded()
    if self._quantize == 'off':
      self._m_quant_active.set(0.0)  # registry is process-global
    model = self._build_executor(reuse_from=None)
    model.warm()
    with self._cond:
      self._model = model
    self._feature_spec = self._predictor.get_feature_specification()
    self._m_version.set(float(model.version))
    self._m_param_bytes.set(float(model.param_bytes))
    self._dispatcher = threading.Thread(
        target=self._dispatch_loop, daemon=True, name='t2r-serving-dispatch')
    self._dispatcher.start()
    if self._reload_interval is not None:
      self._reloader = threading.Thread(
          target=self._reload_loop, daemon=True, name='t2r-serving-reload')
      self._reloader.start()
    if self._register_report:
      metrics_lib.register_report_provider(self._metrics_prefix, self.report)
    return self

  def close(self) -> None:
    """Orderly drain: completes queued requests, then stops threads."""
    with self._cond:
      if self._closed:
        return
      self._closed = True
      self._cond.notify_all()
    self._reload_stop.set()
    if self._reloader is not None:
      self._reloader.join(timeout=30.0)
    if self._dispatcher is not None:
      self._dispatcher.join(timeout=60.0)
      # Only a STARTED batcher owns the provider slot; closing a
      # never-started one must not unregister a live sibling's report.
      if self._register_report:
        metrics_lib.unregister_report_provider(self._metrics_prefix)

  def __enter__(self) -> 'DynamicBatcher':
    return self.start()

  def __exit__(self, *exc) -> None:
    self.close()

  # --------------------------------------------------------------- clients

  @property
  def feature_spec(self):
    return self._feature_spec

  @property
  def model_version(self) -> int:
    with self._cond:
      model = self._model
    return -1 if model is None else int(model.version)

  @property
  def buckets(self) -> Tuple[int, ...]:
    return self._buckets

  @property
  def max_queue(self) -> int:
    return self._max_queue

  @property
  def metrics_prefix(self) -> str:
    return self._metrics_prefix

  @property
  def queue_depth(self) -> int:
    """Live pending-request count (the router's admission signal)."""
    with self._cond:
      return len(self._pending)

  def current_executor(self):
    """The live model generation (router paging/accounting hook)."""
    with self._cond:
      return self._model

  def submit(self, features: Dict[str, np.ndarray],
             request_id: Optional[str] = None,
             on_done: Optional[Callable[['_Request'], None]] = None,
             trace: Optional[tracing.TraceContext] = None
             ) -> ServingFuture:
    """Queues one client's examples; returns a future for the batched
    dispatch. ``features`` values carry a leading batch dim and share
    it (a single example may omit it — the predictor's dim-expansion
    contract); a request larger than ``max_batch`` is rejected (split
    client-side — it could never ride one dispatch).

    ``request_id`` (e.g. an ingress ``X-Request-Id``) labels the request
    through the latency exemplars, the slow-request log, and — for
    sampled requests — its flight-ring lifecycle trace; omitted, a
    process-unique one is generated (``ServingFuture.request_id``).
    ``trace`` (a :class:`~tensor2robot_tpu.observability.tracing.
    TraceContext` from an ingress ``traceparent`` header) additionally
    records the request's spans into the process ``/tracez`` index
    under the fleet-wide trace id — and implies a full lifecycle trace
    regardless of ``request_trace_sample`` (the client asked)."""
    features = self._validate(features)
    sizes = {np.shape(v)[0] if np.ndim(v) else 1 for v in features.values()}
    if len(sizes) != 1:
      raise RequestError(f'inconsistent per-feature batch sizes: {sizes}')
    (n,) = sizes
    if n < 1 or n > self._max_batch:
      raise RequestError(
          f'request batch {n} outside [1, max_batch={self._max_batch}]')
    seq = next(self._req_seq)
    rid = request_id if request_id else f'{self._id_prefix}-{seq}'
    traced = (trace is not None or
              (bool(self._trace_every) and seq % self._trace_every == 0))
    request = _Request(features, int(n), self._clock(), request_id=rid,
                       traced=traced, on_done=on_done, trace=trace)
    if traced:
      request.queued_wall = time.time()
    with self._cond:
      if self._closed:
        raise OverloadedError('serving plane is shut down')
      if len(self._pending) >= self._max_queue:
        raise OverloadedError(
            f'request queue full ({self._max_queue} requests)')
      self._pending.append(request)
      self._m_queue_depth.set(float(len(self._pending)))
      self._cond.notify_all()
    self._m_requests.inc()
    return ServingFuture(request)

  def _validate(self, features: Dict[str, np.ndarray]
                ) -> Dict[str, np.ndarray]:
    """Spec-coerces a request at the API edge: exact key set, spec
    dtypes, per-example shapes, batch dim added if omitted. The AOT
    bucket executables are shape/dtype-strict by design — a loose
    request must fail HERE as a 400, not poison a whole batch."""
    spec = self._feature_spec
    if spec is None:
      return features  # pre-start submit is rejected later anyway
    missing = [k for k in spec if k not in features]
    if missing:
      raise RequestError(f'missing features: {sorted(missing)}')
    out = {}
    for key, tensor_spec in spec.items():
      try:
        value = np.asarray(features[key], dtype=tensor_spec.dtype)
      except (TypeError, ValueError) as e:
        raise RequestError(
            f'feature {key!r} not coercible to {tensor_spec.dtype}: '
            f'{e}') from e
      expected = tuple(tensor_spec.shape)
      while value.ndim < len(expected) + 1:
        value = value[None]
      if value.shape[1:] != expected:
        raise RequestError(
            f'feature {key!r} has per-example shape {value.shape[1:]}, '
            f'spec requires {expected}')
      out[key] = value
    return out

  # ------------------------------------------------------------ dispatcher

  def _assemble(self) -> Optional[List[_Request]]:
    """Collects the next batch: waits for a first request, then fills
    until ``max_batch`` examples or ``batch_deadline_ms`` after
    assembly began — whichever comes first. Backlog drains without
    waiting (a busy dispatcher returns to a full queue and leaves with
    a full batch immediately). Returns None on shutdown-and-drained,
    and an EMPTY batch when a staged model generation is waiting on an
    otherwise idle plane — so a rolling deploy is adopted (and visible
    in ``model_version``/healthz) without requiring traffic."""
    with self._cond:
      while (not self._pending and not self._closed
             and self._pending_model is None):
        self._cond.wait()
      if not self._pending:
        if self._closed:
          return None  # closed and drained
        return []  # idle adoption: swap now, assemble later
      batch: List[_Request] = []
      total = 0
      deadline = self._clock() + self._deadline_s
      while True:
        while self._pending:
          nxt = self._pending[0]
          if total + nxt.n > self._max_batch:
            break
          self._pending.popleft()
          batch.append(nxt)
          total += nxt.n
          if total == self._max_batch:
            break
        if total >= self._max_batch or self._closed:
          break
        if self._pending and total + self._pending[0].n > self._max_batch:
          break  # next request only fits in the following batch
        remaining = deadline - self._clock()
        if remaining <= 0:
          break
        self._cond.wait(timeout=remaining)
      self._m_queue_depth.set(float(len(self._pending)))
      return batch

  def _adopt_pending_model(self):
    """Atomically takes a staged generation and makes it live.

    Read-and-clear MUST be one critical section: the reload poller can
    stage a newer generation between a bare read and a later clear, and
    that staging would be silently dropped (the plane then serves the
    old model until the next poll happens to catch the version skew —
    found by the lock-discipline checker, PR 8).
    """
    with self._cond:
      pending = self._pending_model
      if pending is None:
        return None
      self._pending_model = None
      self._model = pending
    return pending

  def _dispatch_loop(self) -> None:
    while True:
      batch = self._assemble()
      if batch is None:
        return
      # Hot swap point: strictly BETWEEN dispatches, never under one.
      pending = self._adopt_pending_model()
      if pending is not None:
        self._m_swaps.inc()
        self._m_version.set(float(pending.version))
        self._m_param_bytes.set(float(pending.param_bytes))
        flight.event('swap', f'{self._metrics_prefix}/model_swap',
                     f'version={pending.version}')
        logging.info('Serving hot-swapped to model version %d',
                     pending.version)
      if batch:
        self._execute(batch)

  def _execute(self, batch: List[_Request]) -> None:
    total = sum(r.n for r in batch)
    with self._cond:
      model = self._model
    # Traced subset computed once: the lifecycle phases below batch
    # their ring writes (flight.events_many — one lock per phase per
    # dispatch, not per request), keeping full-sample tracing within
    # the bench-pinned 3% overhead budget.
    traced = [r for r in batch if r.traced]
    ctx_traced = [r for r in batch if r.trace is not None]
    prefix = self._metrics_prefix
    assembled_wall = time.time() if traced else 0.0
    if traced:
      assembled = f' batch={len(batch)} total={total}'
      entries = [('request', f'{prefix}/queued',
                  f'id={r.request_id} n={r.n}'
                  + (f' trace={r.trace.trace_id}' if r.trace else ''),
                  r.queued_wall)
                 for r in traced]
      entries.extend(('request', f'{prefix}/assembled',
                      'id=' + r.request_id + assembled) for r in traced)
      flight.events_many(entries)
    t0 = self._clock()
    bucket = total  # refined below; pre-bound for the error path
    try:
      if len(batch) == 1:
        features = batch[0].features
      else:
        keys = batch[0].features.keys()
        features = {
            k: np.concatenate([np.asarray(r.features[k]) for r in batch],
                              axis=0) for k in keys
        }
      if isinstance(model, JitBucketExecutor):
        bucket = bucket_for(total, self._buckets)
        features = pad_to_bucket(features, total, bucket)
        self._m_padded.inc(bucket - total)
      else:
        bucket = total
      if traced:
        dispatched = f' bucket={bucket}'
        flight.events_many([
            ('request', f'{prefix}/dispatched',
             'id=' + r.request_id + dispatched) for r in traced])
      t_exec0 = time.perf_counter()
      outputs = model.execute(features, bucket)
      exec_seconds = time.perf_counter() - t_exec0
      if isinstance(model, JitBucketExecutor) and exec_seconds > 0:
        # Per-model roofline gauges (scoped 'serving/model/<name>/mfu'
        # under the router): execute() blocks on the device→host output
        # reads, so this wall is a lower bound on device utilization.
        # Explicit key set keeps the gauge names config-bounded.
        util = model.dispatch_utilization(bucket, exec_seconds)
        for key in ('mfu', 'hbm_gbps', 'tflops', 'roofline_fraction'):
          if key in util:
            metrics_lib.gauge(f'{prefix}/{key}').set(util[key])
      offset = 0
      for request in batch:
        request.outputs = {
            k: v[offset:offset + request.n] for k, v in outputs.items()
        }
        request.model_version = int(model.version)
        offset += request.n
    except BaseException as e:  # pylint: disable=broad-except
      for request in batch:
        request.error = RequestError(f'batched dispatch failed: {e!r}')
      self._m_errors.inc(len(batch))
    finally:
      now = self._clock()
      self._m_dispatches.inc()
      self._m_dispatch.observe(1e3 * (now - t0))
      self._m_batch_size.observe(total)
      self._m_actions.inc(total)
      self._note_rate(now, total)
      returned_events = []
      for request in batch:
        latency_ms = 1e3 * (now - request.enqueue_time)
        # The request ID rides the latency histogram as a bucket
        # exemplar: a p99 outlier bucket names a concrete request whose
        # flight trace / slow-log entry can be pulled.
        self._m_latency.observe(latency_ms, exemplar=request.request_id)
        self._note_slow(request, latency_ms, now)
        if request.traced:
          returned_events.append(
              ('request', f'{prefix}/returned',
               f'id={request.request_id} latency_ms={latency_ms:.3f} '
               f'error={int(request.error is not None)}'))
      flight.events_many(returned_events)
      if ctx_traced:
        # Spans under the fleet-wide trace id, batched into the process
        # span index with ONE ring lock (flight-events discipline): the
        # request span parents on the upstream hop's span id, its
        # queued/dispatch children decompose where the time went.
        now_wall = time.time()
        span_dicts = []
        for request in ctx_traced:
          trace_id = request.trace.trace_id
          request_span = tracing.mint_span_id()
          error = int(request.error is not None)
          span_dicts.append({
              'trace_id': trace_id, 'span_id': request_span,
              'parent_id': request.trace.span_id,
              'name': f'{prefix}/request', 'kind': 'serving',
              'start': request.queued_wall, 'end': now_wall,
              'request_id': request.request_id,
              'detail': (f'n={request.n} version={request.model_version} '
                         f'error={error}')})
          span_dicts.append({
              'trace_id': trace_id, 'span_id': tracing.mint_span_id(),
              'parent_id': request_span,
              'name': f'{prefix}/queued', 'kind': 'serving',
              'start': request.queued_wall, 'end': assembled_wall,
              'request_id': request.request_id,
              'detail': f'batch={len(batch)} total={total}'})
          span_dicts.append({
              'trace_id': trace_id, 'span_id': tracing.mint_span_id(),
              'parent_id': request_span,
              'name': f'{prefix}/dispatch', 'kind': 'serving',
              'start': assembled_wall, 'end': now_wall,
              'request_id': request.request_id,
              'detail': f'bucket={bucket}'})
        tracing.record_spans(span_dicts, service_label=self.service_label)
      for request in batch:
        request.event.set()
        if request.on_done is not None:
          try:
            request.on_done(request)
          except Exception:  # pylint: disable=broad-except
            logging.exception('serving on_done callback failed')

  def _note_slow(self, request: _Request, latency_ms: float,
                 now: float) -> None:
    """Maintains the bounded top-k-by-latency request log (dispatcher
    thread writes, ``report()`` readers snapshot under the lock)."""
    del now
    if not self._slow_k:
      return
    entry = (latency_ms, id(request), {
        'request_id': request.request_id,
        'latency_ms': round(latency_ms, 3),
        'examples': request.n,
        'model_version': request.model_version,
        'error': request.error is not None,
        'time': time.time(),
    })
    with self._slow_lock:
      log = self._slow_log
      if len(log) < self._slow_k:
        heapq.heappush(log, entry)
      elif latency_ms > log[0][0]:
        heapq.heapreplace(log, entry)

  def slow_requests(self) -> List[Dict[str, Any]]:
    """Top-k completed requests by latency, slowest first."""
    with self._slow_lock:
      entries = [info for _, _, info in self._slow_log]
    return sorted(entries, key=lambda e: -e['latency_ms'])

  def _note_rate(self, now: float, n: int) -> None:
    window = self._rate_window
    window.append((now, n))
    cutoff = now - self._rate_span_s
    while window and window[0][0] < cutoff:
      window.popleft()
    span = max(now - window[0][0], 1e-3) if len(window) > 1 else None
    if span:
      self._m_actions_per_sec.set(
          sum(c for _, c in window) / span)

  # ---------------------------------------------------------------- reload

  def _build_executor(self, reuse_from):
    try:
      source = self._predictor.stateless_serving_fn()
    except NotImplementedError:
      return PredictCallableExecutor(self._predictor)
    serving = self._quantize_gate(source)
    compiled = (reuse_from.compatible_cache(serving)
                if reuse_from is not None else None)
    executor = JitBucketExecutor(serving, self._buckets, compiled=compiled,
                                 label=self._metrics_prefix)
    # Reload polling compares against the predictor's OWN generation,
    # not the derived quantized tree (see _same_generation).
    executor.source_params_ref = source.params
    executor.source_program_key = source.program_key
    return executor

  def _quantize_gate(self, serving):
    """Weight-only quantization behind the parity gate.

    Runs on the PREPARING thread (startup or reload poller, never the
    dispatcher): quantize the snapshot, check it against the full-
    precision fn on calibration batches, and only then let it near the
    executor. A band violation refuses the quantized generation
    (``serving/quant_parity_rejects``) and serves full precision; a
    prep failure (e.g. fp8 on a jaxlib without the dtype) does the same
    via ``serving/quant_errors``. Either way serving NEVER degrades
    below the full-precision path.
    """
    mode = self._quantize
    if mode == 'off':
      return serving
    from tensor2robot_tpu import quantize as quant_lib

    try:
      quantized = quant_lib.quantize_serving_fn(
          serving, mode=mode, skip_patterns=self._quant_skip_patterns)
      report = quant_lib.check_parity(
          serving, quantized,
          atol=self._quant_parity_atol, rtol=self._quant_parity_rtol,
          calibration_batches=self._quant_calibration_batches,
          calibration_batch_size=self._quant_calibration_batch_size)
      full_bytes = quant_lib.param_bytes(serving.params)
    except Exception as e:  # pylint: disable=broad-except
      self._m_quant_errors.inc()
      self._m_quant_active.set(0.0)
      logging.warning(
          'Quantized (%s) serving prep failed (%r); serving full '
          'precision.', mode, e)
      return serving
    self._m_quant_abs_err.set(report.max_abs_err)
    self._m_quant_rel_err.set(report.max_rel_err)
    self._m_quant_bytes_full.set(float(full_bytes))
    if not report.ok:
      self._m_quant_rejects.inc()
      self._m_quant_active.set(0.0)
      logging.warning(
          'Quantized (%s) generation REJECTED by the parity gate: %s; '
          'serving full precision.', mode, report.describe())
      return serving
    quant_bytes = quant_lib.param_bytes(quantized.params)
    self._m_quant_bytes_ratio.set(quant_bytes / max(full_bytes, 1))
    self._m_quant_active.set(1.0)
    logging.info(
        'Quantized (%s) serving adopted: %s; param bytes %d -> %d '
        '(%.3fx).', mode, report.describe(), full_bytes, quant_bytes,
        quant_bytes / max(full_bytes, 1))
    return quantized

  def maybe_reload(self) -> bool:
    """One reload poll: restore the predictor, and if a NEW generation
    loaded, prepare it fully off-thread (params placed, new buckets
    warmed) and hand it to the dispatcher for adoption between
    dispatches. Returns True when a swap was staged. Never raises —
    the last-good generation keeps serving (``serving/reload_errors``,
    mirroring the predictor's own ``predictor/load_fallbacks``).

    Both last-good shapes dump an incident bundle when
    ``postmortem_dir`` is set: a reload that RAISES here, and a broken
    committed export the predictor absorbed internally (visible only as
    a ``predictor/load_fallbacks`` increment across ``restore()``)."""
    fallbacks0 = self._m_predictor_fallbacks.value
    try:
      if not self._predictor.restore():
        self._note_predictor_fallback(fallbacks0)
        return False
      with self._cond:
        current = self._pending_model or self._model
      if (int(self._predictor.model_version) == current.version and
          self._same_generation(current)):
        self._note_predictor_fallback(fallbacks0)
        return False
      new_model = self._build_executor(reuse_from=current)
      new_model.warm()  # compile before adoption: swap cost ~pointer swap
      with self._cond:
        self._pending_model = new_model
        # Wake an idle dispatcher: a deploy must be adopted (and show in
        # model_version / healthz) even when no traffic is flowing.
        self._cond.notify_all()
      return True
    except Exception as e:  # pylint: disable=broad-except
      self._m_reload_errors.inc()
      flight.event('error', f'{self._metrics_prefix}/reload_failed', repr(e))
      logging.warning(
          'Serving reload failed (%r); continuing on model version %d.',
          e, self.model_version)
      # Last-good fallback is an INCIDENT even though serving survives:
      # record what the plane was doing around the broken generation.
      # Rate-limited inside dump() — the poller retrying the same broken
      # export coalesces to one bundle per interval.
      from tensor2robot_tpu.observability import postmortem

      postmortem.dump(self._postmortem_dir, 'serving_reload_failure',
                      error=e,
                      extra={'model_version': self.model_version})
      return False

  def _note_predictor_fallback(self, fallbacks_before: int) -> None:
    """Bundles a reload the PREDICTOR degraded to last-good internally."""
    if self._m_predictor_fallbacks.value <= fallbacks_before:
      return
    flight.event('error', f'{self._metrics_prefix}/reload_fallback',
                 f'predictor kept last-good version={self.model_version}')
    from tensor2robot_tpu.observability import postmortem

    postmortem.dump(self._postmortem_dir, 'serving_reload_failure',
                    extra={'model_version': self.model_version,
                           'predictor_fallback': True})

  def _same_generation(self, current) -> bool:
    if not isinstance(current, JitBucketExecutor):
      return True  # callable executors track the predictor in place
    try:
      serving = self._predictor.stateless_serving_fn()
    except NotImplementedError:
      return False
    # Compare against the SOURCE generation: under quantization the
    # executor serves a derived tree whose identity the predictor never
    # hands out again — matching on it would re-quantize every poll.
    return (serving.params is current.source_params_ref and
            serving.program_key == current.source_program_key)

  def _reload_loop(self) -> None:
    while not self._reload_stop.wait(self._reload_interval):
      self.maybe_reload()

  # ------------------------------------------------------------- reporting

  def report(self) -> Dict[str, Any]:
    """The plane's section of ``metrics.report()`` / ``/metricsz``
    (keyed by ``metrics_prefix``; ``'serving'`` for a standalone plane)."""
    p = self._metrics_prefix
    snap = metrics_lib.snapshot(p + '/')
    latency = snap.get(f'{p}/request_latency_ms', {}) or {}
    return {
        'request_trace_sample': self._trace_sample,
        'request_latency_exemplars': latency.get('exemplars', {}),
        'slow_requests': self.slow_requests(),
        'max_batch': self._max_batch,
        'batch_deadline_ms': self._deadline_s * 1e3,
        'buckets': list(self._buckets),
        # Which executor serves: 'JitBucketExecutor' (AOT buckets over
        # the export's jax core) or the degraded whole-batch
        # 'PredictCallableExecutor' — a check reads it, not the log.
        'executor': type(self.current_executor()).__name__,
        'model_version': self.model_version,
        'queue_depth': snap.get(f'{p}/queue_depth', 0.0),
        'requests': snap.get(f'{p}/requests', 0),
        'request_errors': snap.get(f'{p}/request_errors', 0),
        'actions': snap.get(f'{p}/actions', 0),
        'actions_per_sec': snap.get(f'{p}/actions_per_sec', 0.0),
        'request_latency_ms_p50': latency.get('p50', 0.0),
        'request_latency_ms_p99': latency.get('p99', 0.0),
        'batch_size': snap.get(f'{p}/batch_size', {}),
        'dispatches': snap.get(f'{p}/dispatches', 0),
        'padded_examples': snap.get(f'{p}/padded_examples', 0),
        'model_swaps': snap.get(f'{p}/model_swaps', 0),
        'reload_errors': snap.get(f'{p}/reload_errors', 0),
        'bucket_compiles': snap.get('serving/bucket_compiles', 0),
        'quantize': self._quantize,
        'quantized_active': bool(snap.get(f'{p}/quant/active', 0.0)),
        'param_bytes': int(snap.get(f'{p}/param_bytes', 0.0)),
        'quant_parity_rejects': snap.get(f'{p}/quant_parity_rejects', 0),
        'quant_errors': snap.get(f'{p}/quant_errors', 0),
        'quant_param_bytes_full': int(
            snap.get(f'{p}/quant/param_bytes_full', 0.0)),
        'quant_param_bytes_ratio': snap.get(
            f'{p}/quant/param_bytes_ratio', 0.0),
        'quant_parity_max_abs_err': snap.get(
            f'{p}/quant/parity_max_abs_err', 0.0),
        'quant_parity_max_rel_err': snap.get(
            f'{p}/quant/parity_max_rel_err', 0.0),
    }
