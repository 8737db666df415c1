"""Crash-forensics flight recorder: a bounded ring of structured events.

The incident half of the observability subsystem (``metrics.py`` counts,
``tracing.py`` times, this module REMEMBERS): a fixed-size, thread-safe
ring buffer holding the last N structured events — span completions,
dispatch boundaries, checkpoint save/commit/torn-skips, hot-swap
adoptions, non-finite skips, error-budget charges, shutdown proposals,
per-request serving lifecycles — so that when a process dies abnormally
(preemption exit 42, liveness exit 43, a non-finite raise, an uncaught
trainer exception, a serving reload falling back to last-good) the
postmortem bundle (``observability/postmortem.py``) can answer *what was
the process doing in the seconds before*, not just where its counters
ended up.

Design constraints, in the observability tradition:

* **Pure stdlib** (the serving-host contract — no jax/TF import ever).
* **Bounded memory by construction.** The ring is a preallocated slot
  list overwritten in place; detail strings are truncated at record
  time (:data:`MAX_DETAIL_CHARS`), so the ring's byte footprint is
  stable no matter how many events flow through it (pinned by the
  100k-event soak in ``tests/test_postmortem.py``). Overwritten events
  are simply gone — a flight recorder keeps the LAST N, as the span ring
  of ``tracing`` does (a ``tracing.start_capture`` view of that ring
  keeps the first N since its mark and counts drops): incidents need
  the end of the story.
* **Cheap enough for dispatch boundaries.** ``event()`` is one enabled
  check, one tuple build, one lock'd slot store (~1 µs); disabled it is
  a single module-global read. Span feeding filters on duration BEFORE
  taking any lock, so per-record hot-loop spans (< ``span_feed_min_ms``)
  never touch the ring.

Event shape: ``(time.time(), kind, name, detail)`` where ``kind`` is a
coarse subsystem tag (``'span' | 'dispatch' | 'checkpoint' | 'swap' |
'nonfinite' | 'budget' | 'shutdown' | 'liveness' | 'request' |
'router' | 'balancer' | 'slo' | 'anomaly' | 'collect' | 'actuator' |
'chaos' | 'program' | 'error'``), ``name`` a
slash-scoped identifier like metric names, and ``detail`` a short
``k=v``-style string (machine-greppable: the postmortem renderer parses
``dur_ms=`` / ``id=`` tokens out of it). ``'router'`` carries the
serving router's page-in/page-out/shed decisions, ``'balancer'`` the
front door's eject/readmit transitions — so a latency incident bundle
names the paging and fleet-membership churn around it. ``'slo'``
carries burn-rate alert/clear transitions (``observability/slo.py``),
``'anomaly'`` the anomaly watch's detections (``observability/
anomaly.py``) — both also escalate to rate-limited LIVE postmortem
bundles. Traced requests' ``'request'`` events carry a ``trace=`` token
joining the ring to the cross-process ``/tracez`` span index.
``'collect'`` carries the actor–learner loop's lifecycle: actor
spawn/crash/restart/DEAD verdicts (``collect/actor.py`` supervision),
shard commits and suppressed markers, and follow-mode shard
ingest/skip decisions (``data/follow.py``). ``'actuator'`` carries
every closed-loop fleet action — applied, dry-run, budget-denied, or
refused — with the signals that justified it
(``observability/actuator.py``), and ``'chaos'`` the chaos harness's
fault injections/clears (``utils/chaos.py``): a soak's verdict is read
by joining the two on the same timeline. ``'program'`` carries the
compiled-program ledger's steady-state recompile flags
(``observability/programs.py``) — the runtime twin of the static
``recompile-hazard`` rule, landed within the dispatch that paid the
recompile.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence

from tensor2robot_tpu.observability import metrics as metrics_lib

__all__ = [
    'FlightRecorder', 'recorder', 'event', 'events', 'events_many',
    'set_enabled', 'enabled', 'set_span_feed_min_ms', 'span_feed_min_ms',
    'note_span', 'MAX_DETAIL_CHARS', 'DEFAULT_CAPACITY',
]

DEFAULT_CAPACITY = 4096
MAX_DETAIL_CHARS = 256

# Coarse-span feed threshold (ms): tracing.span exits at or above this
# duration are mirrored into the ring. 5 ms keeps dispatch-scale events
# (wait_batch, checkpoint/save, device_wait) and excludes per-record
# micro-spans; None disables the feed entirely.
DEFAULT_SPAN_FEED_MIN_MS = 5.0


class FlightRecorder:
  """Fixed-size, thread-safe ring of ``(time, kind, name, detail)``.

  The slot list is allocated once at construction and overwritten in
  place modulo ``capacity`` — steady-state recording allocates only the
  event tuple itself, and the ring never grows.
  """

  def __init__(self, capacity: int = DEFAULT_CAPACITY):
    if capacity < 1:
      raise ValueError(f'capacity must be >= 1, got {capacity}')
    self._capacity = int(capacity)
    self._lock = threading.Lock()
    self._slots: List[Optional[tuple]] = [None] * self._capacity  # GUARDED_BY(self._lock)
    self._next = 0  # GUARDED_BY(self._lock)
    self._recorded = 0  # GUARDED_BY(self._lock)

  @property
  def capacity(self) -> int:
    return self._capacity

  @property
  def recorded(self) -> int:
    """Total events ever recorded (>= capacity means overwrites began)."""
    with self._lock:
      return self._recorded

  def record(self, kind: str, name: str, detail: str = '',
             t: Optional[float] = None) -> None:
    """Stores one event, overwriting the oldest once the ring is full."""
    if len(detail) > MAX_DETAIL_CHARS:
      detail = detail[:MAX_DETAIL_CHARS - 1] + '…'
    entry = (time.time() if t is None else t, kind, name, detail)
    with self._lock:
      self._slots[self._next] = entry
      self._next = (self._next + 1) % self._capacity
      self._recorded += 1

  def record_many(self, entries: Sequence[tuple]) -> None:
    """Stores ``(kind, name, detail[, t])`` tuples under ONE lock.

    The serving dispatcher emits one lifecycle event per request per
    phase; at batch 64 that is 64 lock acquisitions per phase the
    per-event path would pay — batched, the phase costs one. Entries
    without an explicit timestamp share *now* (they describe the same
    instant); a 4-tuple carries its own (e.g. a request's queue time,
    captured lock-free on the client thread and recorded later by the
    dispatcher).
    """
    if not entries:
      return
    now = time.time()
    prepared = []
    for entry in entries:
      kind, name, detail = entry[0], entry[1], entry[2]
      if len(detail) > MAX_DETAIL_CHARS:
        detail = detail[:MAX_DETAIL_CHARS - 1] + '…'
      prepared.append((entry[3] if len(entry) > 3 else now,
                       kind, name, detail))
    with self._lock:
      for entry in prepared:
        self._slots[self._next] = entry
        self._next = (self._next + 1) % self._capacity
      self._recorded += len(prepared)

  def events(self, last_secs: Optional[float] = None,
             kinds: Optional[Sequence[str]] = None) -> List[Dict[str, object]]:
    """Events oldest → newest, optionally windowed/filtered.

    Returns dicts (JSON-ready) rather than raw tuples; the copy is taken
    under the lock, the dict expansion outside it.
    """
    with self._lock:
      if self._recorded >= self._capacity:
        raw = self._slots[self._next:] + self._slots[:self._next]
      else:
        raw = self._slots[:self._next]
    if last_secs is not None:
      cutoff = time.time() - last_secs
      raw = [e for e in raw if e is not None and e[0] >= cutoff]
    out = []
    for entry in raw:
      if entry is None:
        continue
      t, kind, name, detail = entry
      if kinds is not None and kind not in kinds:
        continue
      out.append({'time': t, 'kind': kind, 'name': name, 'detail': detail})
    return out

  def clear(self) -> None:
    with self._lock:
      self._slots = [None] * self._capacity
      self._next = 0
      self._recorded = 0

  def ring_bytes(self) -> int:
    """Approximate resident bytes of the ring (soak-test probe).

    Slot-list overhead plus per-event tuple/str payloads. Detail
    truncation and the fixed slot count bound this regardless of event
    volume.
    """
    import sys

    with self._lock:
      slots = list(self._slots)
    total = sys.getsizeof(slots)
    for entry in slots:
      if entry is None:
        continue
      total += sys.getsizeof(entry)
      total += sum(sys.getsizeof(x) for x in entry)
    return total


# Process-global recorder (registry-style): every subsystem records into
# the same ring, so the postmortem bundle interleaves trainer, data,
# checkpoint and serving events on one timeline.
_RECORDER = FlightRecorder()

# Module-global fast-path switches. Plain reads/writes of immutable
# values: a racing reader sees either the old or the new setting, both
# of which are valid — no lock needed on the hot path.
_enabled = True
_span_feed_min_ms: Optional[float] = DEFAULT_SPAN_FEED_MIN_MS

# Bound once: a registry lookup per event would double the cost of the
# hot path (registry lock + dict probe) — the serving plane records four
# lifecycle events per traced request.
_EVENTS_COUNTER = metrics_lib.counter('flight/events')


def recorder() -> FlightRecorder:
  return _RECORDER


def set_enabled(on: bool) -> None:
  """Master switch; disabled, ``event()`` costs one global read."""
  global _enabled
  _enabled = bool(on)


def enabled() -> bool:
  return _enabled


def event(kind: str, name: str, detail: str = '') -> None:
  """Records one structured event into the process-global ring."""
  if not _enabled:
    return
  _RECORDER.record(kind, name, detail)
  _EVENTS_COUNTER.inc()


def events_many(entries: Sequence[tuple]) -> None:
  """Batched :func:`event`: ``(kind, name, detail)`` tuples, one lock."""
  if not _enabled or not entries:
    return
  _RECORDER.record_many(entries)
  _EVENTS_COUNTER.inc(len(entries))


def set_span_feed_min_ms(min_ms: Optional[float]) -> None:
  """Spans at/above ``min_ms`` mirror into the ring; None disables."""
  global _span_feed_min_ms
  _span_feed_min_ms = None if min_ms is None else float(min_ms)


def span_feed_min_ms() -> Optional[float]:
  return _span_feed_min_ms


def note_span(name: str, t0: float, t1: float) -> None:
  """The ``tracing.span`` exit hook (perf_counter endpoints).

  Duration-filtered BEFORE any locking so sub-threshold hot-loop spans
  cost two float compares; the stored timestamp is wall-clock *now* (the
  span just ended), keeping ring timestamps on one comparable axis.
  """
  if not _enabled or _span_feed_min_ms is None:
    return
  dur_ms = (t1 - t0) * 1e3
  if dur_ms < _span_feed_min_ms:
    return
  _RECORDER.record('span', name, f'dur_ms={dur_ms:.3f}')
  _EVENTS_COUNTER.inc()


def events(last_secs: Optional[float] = None,
           kinds: Optional[Sequence[str]] = None) -> List[Dict[str, object]]:
  """Events from the process-global ring (oldest → newest)."""
  return _RECORDER.events(last_secs=last_secs, kinds=kinds)
