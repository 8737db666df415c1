"""The measured window, driven through the program's public entry only.

Two pieces are handed to ``train_eval_model``:

* :class:`TimedGenerator` wraps the program's record generator. Its
  iterator times every ``next()`` in the thread that calls it (the
  trainer's prefetch thread), keeps the first batches for the check,
  and ends the stream at the deadline — on a multiple of K batches, so
  the trainer never meets a short group and compiles nothing new.
* :class:`WindowCallback` is a ``TrainerCallback``. A dispatch boundary
  is the moment its outputs are ready (``jax.block_until_ready``); it is
  taken one dispatch behind, as the trainer's own loop waits, so that
  timing never drains the device's queue. The callback walks three
  phases: *check* (the first optimizer steps, whose state the check
  reads), *warm-up*, *window*.

One object, the trainer that ``train_eval_model`` builds, runs all three
phases in one call to ``Trainer.train``.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

FEED_SPAN = 'bench/feed_next'
CALLBACK_SPAN = 'bench/callback'


class Shared:
  """What the callback tells the iterator: when the stream may end."""

  def __init__(self, keep_batches: int, group: int):
    self.keep_batches = keep_batches
    self.group = group
    self.deadline: Optional[float] = None  # perf_counter seconds
    self.window_open = threading.Event()
    self.kept: List[Dict[str, np.ndarray]] = []
    self.feed_ms: List[float] = []       # next() times inside the window
    # (name, start, end) in epoch nanoseconds, inside the window: what the
    # host was doing, for the idle gaps of a trace taken without the host
    # tracer.
    self.spans: List[tuple] = []
    self.lock = threading.Lock()


def _flatten(features, labels) -> Dict[str, np.ndarray]:
  out = {}
  for prefix, struct in (('features', features), ('labels', labels)):
    if struct is None:
      continue
    for key, value in struct.items():
      out[f'{prefix}/{key}'] = value
  return out


class _TimedIterator:

  def __init__(self, it, shared: Shared):
    self._it = it
    self._shared = shared
    self._handed = 0

  def __iter__(self):
    return self

  def __next__(self):
    s = self._shared
    if (s.deadline is not None and time.perf_counter() >= s.deadline and
        self._handed % s.group == 0):
      raise StopIteration
    t0, t0_ns = time.perf_counter(), time.time_ns()
    with jax.profiler.TraceAnnotation(FEED_SPAN):
      features, labels = next(self._it)
    dt = (time.perf_counter() - t0) * 1e3
    if self._handed < s.keep_batches:
      s.kept.append(_flatten(features, labels))
    elif s.window_open.is_set():
      with s.lock:
        s.feed_ms.append(dt)
        s.spans.append((FEED_SPAN, t0_ns, time.time_ns()))
    self._handed += 1
    return features, labels

  def __getattr__(self, name):
    return getattr(self._it, name)


class TimedGenerator:
  """The program's generator with its iterator wrapped; everything else
  (spec hand-shake, batch size) is the generator's own."""

  def __init__(self, generator, shared: Shared):
    self._generator = generator
    self._shared = shared

  def create_iterator(self, mode):
    return _TimedIterator(self._generator.create_iterator(mode), self._shared)

  def __getattr__(self, name):
    return getattr(self._generator, name)


class WindowCallback:
  """See the module docstring. ``on_check(index, trainer, scalars)`` is
  called after each check dispatch, with the device drained."""

  def __init__(self, shared: Shared, *, check_dispatches: int,
               warmup_dispatches: int, seconds: float,
               examples_per_dispatch: int,
               on_check: Callable[[int, Any, Dict], None],
               on_window_open: Callable[[], None] = lambda: None,
               skip_window: bool = False):
    self._shared = shared
    self._check = check_dispatches
    self._warmup = warmup_dispatches
    self._seconds = seconds
    self._examples = examples_per_dispatch
    self._on_check = on_check
    self._on_window_open = on_window_open
    self._skip_window = skip_window
    self._calls = 0
    self._prev = None
    self.first_dispatch_done: Optional[float] = None
    self.t_open: Optional[float] = None
    self.boundaries: List[float] = []   # each closes one dispatch

  # TrainerCallback's surface (duck-typed; the trainer calls all five).
  def begin(self, trainer):
    del trainer

  def after_checkpoint(self, trainer, step):
    del trainer, step

  def after_eval(self, trainer, step, metrics):
    del trainer, step, metrics

  def end(self, trainer):
    del trainer

  def after_step(self, trainer, step, scalars):
    del step
    t0_ns = time.time_ns()
    with jax.profiler.TraceAnnotation(CALLBACK_SPAN):
      self._calls += 1
      if self._calls <= self._check:
        jax.block_until_ready(scalars)
        if self.first_dispatch_done is None:
          self.first_dispatch_done = time.perf_counter()
        self._on_check(self._calls, trainer, scalars)
        if self._skip_window and self._calls == self._check:
          self._shared.deadline = time.perf_counter()
      elif self._prev is not None:
        jax.block_until_ready(self._prev)
        now = time.perf_counter()
        if self.t_open is None:
          if self._calls > self._check + self._warmup:
            self._on_window_open()  # may start the profiler: seconds
            now = time.perf_counter()
            self.t_open = now
            self._shared.deadline = now + self._seconds
            self._shared.window_open.set()
        else:
          self.boundaries.append(now)
      self._prev = scalars
    if self.t_open is not None:
      with self._shared.lock:
        self._shared.spans.append((CALLBACK_SPAN, t0_ns, time.time_ns()))

  def finish(self) -> None:
    """The stream has ended: close the last dispatch."""
    if self._prev is not None and self.t_open is not None:
      jax.block_until_ready(self._prev)
      self.boundaries.append(time.perf_counter())
    self._prev = None

  # ------------------------------------------------------------- readings

  def inside(self) -> List[float]:
    """Boundaries up to the deadline, the opening one first."""
    if self.t_open is None:
      return []
    end = self.t_open + self._seconds
    return [self.t_open] + [t for t in self.boundaries if t <= end]

  def examples_per_s(self) -> Optional[float]:
    ts = self.inside()
    if len(ts) < 2:
      return None
    return (len(ts) - 1) * self._examples / (ts[-1] - ts[0])

  def dispatch_gaps_ms(self) -> List[float]:
    ts = self.inside()
    return [(b - a) * 1e3 for a, b in zip(ts, ts[1:])]
