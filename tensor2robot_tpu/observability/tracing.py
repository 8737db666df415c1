"""Host-side span tracing: one always-on ring of finished spans.

``with span('data/decode', key=n):`` does three things at once:

1. accumulates the span's wall time into the metrics registry
   (histogram ``'<name>_ms'``), so per-scope totals are queryable
   without any trace viewer;
2. appends ONE tuple ``(name, start_ns, end_ns, thread, key)`` to the
   process-global span ring: bounded (the last :data:`RING_CAPACITY`
   spans stay), always on, and lock-free on this path (a ``deque``
   append and an ``itertools.count`` draw are each atomic). Times are
   ``time.perf_counter_ns()``; ``thread`` is the recording thread's
   name; ``key`` is whatever identifier the call site shares with the
   work the span belongs to (the trainer keys every stage by the batch
   ordinal, which is the dispatch ordinal). The parent of a span is the
   span that encloses it on its thread: derived when read, not stored;
3. enters a ``jax.profiler.TraceAnnotation`` so that a ``jax.profiler``
   trace taken WITH the host tracer shows the span on the host threads.
   That tracer slows a TPU host several times over (PERF.md), so device
   traces are as a rule taken without it, and a reader places the ring's
   spans on the trace's clock itself: :func:`clock_anchor` ties
   ``perf_counter_ns`` to the wall clock, the trace's
   ``profile_start_time`` ties the wall clock to the trace
   (``benchmark/metrics/_program_spans.py`` does exactly this).

:func:`record` takes a finished span from a call site that already holds
both timestamps (the trainer's loop reads the clock once a boundary and
hands the same reads to its breakdown and to the ring). :func:`recent`
returns the ring's spans, :func:`taken` how many it ever took: a reader
that finds ``taken() > RING_CAPACITY`` and the oldest span younger than
the stretch it wants knows that stretch has been overwritten.

The operator's capture surface (:func:`start_capture` / :func:`capture`
/ :func:`chrome_trace` / :func:`dump_chrome_trace`) is a VIEW of the
ring: a mark at start, the spans since the mark turned into Chrome-trace
``X`` events when read (viewable in ``chrome://tracing`` / Perfetto, or
summarized by ``tools/trace_summary.py``). ``max_events`` bounds the
view; what a view could not hold, beyond ``max_events`` or overwritten
in the ring before it was read, counts as dropped
(``tracing/dropped_events``).

jax itself is imported lazily so the metrics/tracing pair stays
importable on hosts without jax (the serving-host contract); everything
degrades gracefully to host-only timing. Spans nest lexically (the
Chrome trace nests ``X`` events per thread by ts/dur containment).
:func:`step_annotation` wraps ``jax.profiler.StepTraceAnnotation`` so
trainer dispatches carry step markers in captured traces (TensorBoard's
step-time view keys off them).

**Cross-process request tracing** (the fleet half of this module): a
request entering the fleet carries a W3C-``traceparent``-style context —
a 32-hex trace id shared by every hop plus the 16-hex span id of the
hop that forwarded it (:class:`TraceContext`,
:func:`parse_traceparent`/:func:`format_traceparent`). Each process
records its finished spans (balancer proxy + per-backend attempts,
serving ingress, batcher request/queued/dispatch) into a bounded
process-global :class:`SpanIndex` served at ``GET /tracez`` by every
fleet HTTP surface (serving server, balancer, ``/metricsz``).
``tools/assemble_trace.py`` then scrapes every process, estimates each
backend's clock offset from probe round-trips, and merges one causally
ordered cross-process timeline for a trace id — including a retried
request whose one trace spans a failed AND a succeeded replica. Span
recording follows the flight-ring cost discipline: bounded preallocated
ring, batched ``record_spans`` (one lock per dispatch, not per
request), and nothing at all on untraced requests.
"""

from __future__ import annotations

import binascii
import collections
import contextlib
import gzip
import itertools
import json
import os
import threading
import time
import zlib
from typing import (Any, Dict, Hashable, Iterator, List, NamedTuple, Optional,
                    Sequence, Tuple)

from tensor2robot_tpu.observability import flight, metrics

__all__ = [
    'span', 'record', 'recent', 'taken', 'clock_anchor', 'RING_CAPACITY',
    'step_annotation', 'start_capture', 'stop_capture', 'capture',
    'capturing', 'chrome_trace', 'dump_chrome_trace',
    'TraceContext', 'parse_traceparent', 'format_traceparent',
    'mint_trace_id', 'mint_span_id', 'SpanIndex', 'span_index',
    'record_span', 'record_spans', 'spans', 'set_service', 'service',
    'tracez_document', 'TRACEPARENT_HEADER',
]

# (name, start_ns, end_ns, thread, key), times in perf_counter_ns.
Span = Tuple[str, int, int, str, Optional[Hashable]]

# The last this-many finished spans stay. The record-fed trainer writes
# some tens a second, so the ring reaches back a quarter of an hour or
# more; full, it holds ~10 MB of tuples.
RING_CAPACITY = 65536

# One store. An entry is a span behind its sequence number: the draw
# from ``_SEQ`` is atomic, so the numbers count every span ever taken
# with no lock, and a view can tell "since my mark" exactly.
_RING: 'collections.deque' = collections.deque(maxlen=RING_CAPACITY)
_SEQ = itertools.count()


_ANNOTATION_CLS = None  # lazily resolved; False = unavailable


def _annotation_class():
  """``jax.profiler.TraceAnnotation`` once jax is ALREADY loaded, else
  None — tracing must never be the thing that imports jax on a
  jax-less serving host."""
  global _ANNOTATION_CLS
  if _ANNOTATION_CLS is None:
    import sys

    if 'jax' not in sys.modules:
      return None  # don't cache: jax may load later in the process
    try:
      import jax

      _ANNOTATION_CLS = jax.profiler.TraceAnnotation
    except Exception:  # pylint: disable=broad-except
      _ANNOTATION_CLS = False
  return _ANNOTATION_CLS or None


def record(name: str, t0_ns: int, t1_ns: int,
           key: Optional[Hashable] = None) -> None:
  """Takes one finished span, ``perf_counter_ns`` endpoints, recorded
  under the calling thread's name: histogram, flight feed, ring."""
  metrics.histogram(name + '_ms').observe((t1_ns - t0_ns) / 1e6)
  # Flight-recorder feed: coarse (>= flight.span_feed_min_ms) spans
  # land in the crash-forensics ring; the duration filter runs before
  # any locking, so hot-loop micro-spans pay two float compares.
  flight.note_span(name, t0_ns / 1e9, t1_ns / 1e9)
  _RING.append((next(_SEQ), name, t0_ns, t1_ns,
                threading.current_thread().name, key))


class span:  # noqa: N801 - context manager used as a function
  """Times a host-side region under ``name`` (slash-scoped).

  A slotted class rather than a ``@contextmanager`` generator: this
  sits in per-batch hot paths, and the generator protocol alone costs
  ~3 µs per use (measured) — the class form stays near 1 µs.

  ``key`` ties the span to the work it belongs to (see the module
  docstring). ``annotate=False`` skips the jax TraceAnnotation — for
  regions inside tight per-record loops where even a no-op TraceMe is
  measurable; the registry histogram and the ring still record.
  """

  __slots__ = ('_name', '_key', '_annotate', '_ann', '_t0')

  def __init__(self, name: str, key: Optional[Hashable] = None,
               annotate: bool = True):
    self._name = name
    self._key = key
    self._annotate = annotate
    self._ann = None
    self._t0 = 0

  def __enter__(self) -> 'span':
    if self._annotate:
      # The annotation is a TraceMe no-op (~100 ns) outside an active
      # jax profiler session; we cannot cheaply query session state, so
      # err on 'annotate' whenever jax is loaded.
      cls = _annotation_class()
      if cls is not None:
        self._ann = cls(self._name)
        self._ann.__enter__()
    self._t0 = time.perf_counter_ns()
    return self

  def __exit__(self, *exc) -> bool:
    t1 = time.perf_counter_ns()
    if self._ann is not None:
      self._ann.__exit__(None, None, None)
      self._ann = None
    record(self._name, self._t0, t1, self._key)
    return False


def clock_anchor() -> Tuple[int, int]:
  """A fresh ``(time.time_ns(), time.perf_counter_ns())`` pair: what a
  reader needs to move the ring's times onto the wall clock, and from
  there (``profile_start_time``) onto a profiler trace's. Read when
  asked, never kept: the wall clock is slewed against the monotonic
  one, so an anchor ages."""
  return time.time_ns(), time.perf_counter_ns()


def _snapshot() -> Tuple[List[tuple], int]:
  """The ring's entries, oldest first, and how many spans it has ever
  taken: the highest number among them, plus one. A scan, not the last
  entry's: two threads may append in the other order than they drew."""
  entries = list(_RING)  # one C call: consistent under appends
  return entries, max((e[0] for e in entries), default=-1) + 1


def taken() -> int:
  """How many spans the ring has ever taken (it holds the last
  :data:`RING_CAPACITY`). For readers, not for hot paths."""
  return _snapshot()[1]


def recent(since_ns: Optional[int] = None) -> List[Span]:
  """The ring's spans, oldest first; with ``since_ns``
  (``perf_counter_ns``) only those that ended at or after it."""
  entries, _ = _snapshot()
  if since_ns is None:
    return [e[1:] for e in entries]
  return [e[1:] for e in entries if e[3] >= since_ns]


# ------------------------------------------------- the capture view

_capture_lock = threading.Lock()
# (first sequence number of the view, max_events, drops already mirrored
# into the registry); None = no capture.  # GUARDED_BY(_capture_lock)
_capture_state: Optional[List[int]] = None
_last_dropped = 0  # of the newest capture  # GUARDED_BY(_capture_lock)


def _chrome_event(name: str, t0_ns: int, t1_ns: int, thread: str,
                  key: Optional[Hashable]) -> dict:
  event = {
      'name': name,
      'ph': 'X',
      'ts': t0_ns / 1e3,
      'dur': (t1_ns - t0_ns) / 1e3,
      'pid': os.getpid(),
      # Chrome-trace thread ids are numbers; the name rides in args.
      'tid': zlib.crc32(thread.encode()) & 0x7fffffff,
      'args': {'thread': thread},
  }
  if key is not None:
    event['args']['key'] = key
  return event


def _view() -> Tuple[List[dict], int]:  # HOLDS(_capture_lock)
  """The open capture's events and its dropped count so far."""
  mark, cap, mirrored = _capture_state
  entries, taken_now = _snapshot()
  kept = [e for e in entries if e[0] >= mark][:cap]
  dropped = taken_now - mark - len(kept)
  if dropped > mirrored:
    # Registry mirror: a truncated capture is DETECTABLE from report()/
    # /metricsz ('tracing/dropped_events'), not only from the trace
    # file's own metadata.
    metrics.counter('tracing/dropped_events').inc(dropped - mirrored)
    _capture_state[2] = dropped
  return [_chrome_event(*e[1:]) for e in kept], dropped


def start_capture(max_events: int = 200_000) -> None:
  """Marks the ring: the capture is the spans from here on, the first
  ``max_events`` of them (and no more than the ring still holds when
  they are read; the rest count as dropped)."""
  global _capture_state, _last_dropped
  with _capture_lock:
    _capture_state = [taken(), int(max_events), 0]
    _last_dropped = 0


def stop_capture() -> List[dict]:
  """Ends the capture and returns its events."""
  global _capture_state, _last_dropped
  with _capture_lock:
    if _capture_state is None:
      return []
    events, _last_dropped = _view()
    _capture_state = None
  return events


def capturing() -> bool:
  # ANALYSIS_OK(lock-discipline): advisory single-read probe; callers
  # must not (and do not) make correctness decisions on it.
  return _capture_state is not None


@contextlib.contextmanager
def capture(max_events: int = 200_000) -> Iterator[List[dict]]:
  """``with capture() as events:`` — events is filled on exit."""
  start_capture(max_events)
  events: List[dict] = []
  try:
    yield events
  finally:
    events.extend(stop_capture())


def chrome_trace(events: Optional[List[dict]] = None) -> Dict[str, object]:
  """Wraps events (default: the open capture's so far) as a Chrome-trace
  JSON object (Perfetto-loadable)."""
  with _capture_lock:
    if _capture_state is not None:
      seen, dropped = _view()
    else:
      seen, dropped = [], _last_dropped
  return {
      'traceEvents': seen if events is None else events,
      'displayTimeUnit': 'ms',
      'metadata': {
          'producer': 'tensor2robot_tpu.observability.tracing',
          'dropped_events': dropped,
      },
  }


def dump_chrome_trace(path: str,
                      events: Optional[List[dict]] = None) -> str:
  """Writes a Chrome-trace JSON (``.gz`` suffix → gzipped) to ``path``."""
  trace = chrome_trace(events)
  dirname = os.path.dirname(path)
  if dirname:
    os.makedirs(dirname, exist_ok=True)
  if path.endswith('.gz'):
    with gzip.open(path, 'wt') as f:
      json.dump(trace, f)
  else:
    with open(path, 'w') as f:
      json.dump(trace, f)
  return path


# --------------------------------------------------- cross-process tracing


TRACEPARENT_HEADER = 'traceparent'

# W3C trace-context version we emit; parsing accepts any version whose
# field layout matches (version-format forward compatibility).
_TRACEPARENT_VERSION = '00'


class TraceContext(NamedTuple):
  """One hop's trace coordinates: the fleet-wide trace id plus the span
  id of the hop that forwarded the request (the next span's parent)."""

  trace_id: str
  span_id: str

  def child(self) -> 'TraceContext':
    """A fresh context under the same trace (for the next hop)."""
    return TraceContext(self.trace_id, mint_span_id())


def mint_trace_id() -> str:
  return binascii.hexlify(os.urandom(16)).decode()


def mint_span_id() -> str:
  return binascii.hexlify(os.urandom(8)).decode()


def format_traceparent(ctx: TraceContext) -> str:
  """``00-<trace_id>-<span_id>-01`` (sampled flag always set: a context
  only exists for requests someone chose to trace)."""
  return f'{_TRACEPARENT_VERSION}-{ctx.trace_id}-{ctx.span_id}-01'


def parse_traceparent(header: Optional[str]) -> Optional[TraceContext]:
  """A :class:`TraceContext` from a ``traceparent`` header, or None.

  Malformed headers are None, never an error — tracing must not turn a
  bad client header into a failed request.
  """
  if not header:
    return None
  parts = header.strip().split('-')
  if len(parts) < 3:
    return None
  trace_id, span_id = parts[1], parts[2]
  if len(trace_id) != 32 or len(span_id) != 16:
    return None
  try:
    int(trace_id, 16), int(span_id, 16)
  except ValueError:
    return None
  if trace_id == '0' * 32 or span_id == '0' * 16:
    return None
  return TraceContext(trace_id, span_id)


class SpanIndex:
  """Bounded ring of finished spans, queryable by trace/request id.

  Same retention policy as the flight ring (keep the LAST N, overwrite
  in place): ``/tracez`` is an incident surface — the recent story
  matters, old spans age out. Span shape (a plain dict, JSON-ready):
  ``trace_id / span_id / parent_id / name / kind / start / end /
  request_id / detail / service`` with wall-clock start/end so spans
  from different processes land on comparable axes (modulo the clock
  offset ``tools/assemble_trace.py`` estimates and removes).
  """

  def __init__(self, capacity: int = 4096):
    if capacity < 1:
      raise ValueError(f'capacity must be >= 1, got {capacity}')
    self._capacity = int(capacity)
    self._lock = threading.Lock()
    self._slots: List[Optional[dict]] = [None] * self._capacity  # GUARDED_BY(self._lock)
    self._next = 0  # GUARDED_BY(self._lock)
    self._recorded = 0  # GUARDED_BY(self._lock)

  @property
  def capacity(self) -> int:
    return self._capacity

  @property
  def recorded(self) -> int:
    with self._lock:
      return self._recorded

  def record(self, span_dict: dict) -> None:
    with self._lock:
      self._slots[self._next] = span_dict
      self._next = (self._next + 1) % self._capacity
      self._recorded += 1

  def record_many(self, span_dicts: Sequence[dict]) -> None:
    """Batched record: one lock for a whole dispatch's spans."""
    if not span_dicts:
      return
    with self._lock:
      for span_dict in span_dicts:
        self._slots[self._next] = span_dict
        self._next = (self._next + 1) % self._capacity
      self._recorded += len(span_dicts)

  def spans(self, trace_id: Optional[str] = None,
            request_id: Optional[str] = None,
            last_secs: Optional[float] = None) -> List[dict]:
    """Matching spans oldest → newest (copies; safe to mutate)."""
    with self._lock:
      if self._recorded >= self._capacity:
        raw = self._slots[self._next:] + self._slots[:self._next]
      else:
        raw = self._slots[:self._next]
    cutoff = None if last_secs is None else time.time() - last_secs
    out = []
    for entry in raw:
      if entry is None:
        continue
      if trace_id is not None and entry.get('trace_id') != trace_id:
        continue
      if request_id is not None and entry.get('request_id') != request_id:
        continue
      if cutoff is not None and entry.get('end', 0.0) < cutoff:
        continue
      out.append(dict(entry))
    return out

  def clear(self) -> None:
    with self._lock:
      self._slots = [None] * self._capacity
      self._next = 0
      self._recorded = 0


# Process-global index (flight-recorder style): every subsystem's spans
# land in one ring so /tracez serves the whole process's story.
_SPAN_INDEX = SpanIndex()

# Human label for this process in assembled fleet timelines ('balancer',
# 'replica-8001', ...). Plain str write: racing readers see old or new,
# both valid.
_service = f'pid-{os.getpid()}'

_SPANS_COUNTER = metrics.counter('tracing/spans')


def span_index() -> SpanIndex:
  return _SPAN_INDEX


def set_service(name: str) -> None:
  """Labels this process's spans in assembled fleet timelines."""
  global _service
  _service = str(name)


def service() -> str:
  return _service


def record_span(name: str,
                kind: str,
                trace_id: str,
                span_id: str,
                parent_id: str,
                start: float,
                end: float,
                request_id: str = '',
                detail: str = '',
                service_label: Optional[str] = None) -> None:
  """Records one finished span into the process-global index."""
  _SPAN_INDEX.record({
      'trace_id': trace_id, 'span_id': span_id, 'parent_id': parent_id,
      'name': name, 'kind': kind, 'start': start, 'end': end,
      'request_id': request_id, 'detail': detail,
      'service': service_label if service_label is not None else _service,
  })
  _SPANS_COUNTER.inc()


def record_spans(span_dicts: Sequence[dict],
                 service_label: Optional[str] = None) -> None:
  """Batched :func:`record_span` (one ring lock per call). Each dict
  must already carry the span fields; ``service`` is filled if absent."""
  if not span_dicts:
    return
  label = service_label if service_label is not None else _service
  for span_dict in span_dicts:
    span_dict.setdefault('service', label)
  _SPAN_INDEX.record_many(span_dicts)
  _SPANS_COUNTER.inc(len(span_dicts))


def spans(trace_id: Optional[str] = None,
          request_id: Optional[str] = None,
          last_secs: Optional[float] = None) -> List[dict]:
  return _SPAN_INDEX.spans(trace_id=trace_id, request_id=request_id,
                           last_secs=last_secs)


def tracez_document(trace_id: Optional[str] = None,
                    request_id: Optional[str] = None,
                    probe_only: bool = False) -> Dict[str, Any]:
  """The ``GET /tracez`` reply document.

  Always carries the server's wall clock (``now``) — the assembler's
  clock-offset probe reads it against its own send/receive timestamps
  (offset ≈ server_now − (t_send+t_recv)/2, error ≤ RTT/2).
  ``probe_only`` skips the span payload so offset probes stay cheap.
  """
  doc: Dict[str, Any] = {
      'kind': 'tracez',
      'service': _service,
      'pid': os.getpid(),
      'now': time.time(),
  }
  if not probe_only:
    doc['spans'] = _SPAN_INDEX.spans(trace_id=trace_id,
                                     request_id=request_id)
  return doc


def step_annotation(step: int, name: str = 'train'):
  """A ``jax.profiler.StepTraceAnnotation`` context for one dispatch.

  Captured traces then carry per-step markers (TensorBoard's step-time
  breakdown keys off them). Falls back to a null context without jax.
  """
  try:
    import jax

    return jax.profiler.StepTraceAnnotation(name, step_num=int(step))
  except Exception:  # pylint: disable=broad-except
    return contextlib.nullcontext()
