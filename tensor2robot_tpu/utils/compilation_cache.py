"""Persistent XLA compilation cache: one rule for where it lives.

Preemption resilience (PR 1/5) makes restarts *correct*; this makes them
*cheap*: every start of the trainer or the serving plane otherwise pays
full XLA compilation of the train program / all serving buckets before
the first useful step. With the persistent cache a later process
deserializes those executables instead of compiling them.

The rule (:func:`enable_compilation_cache`, called by the trainer and
the serving plane before their first lowering):

* ``JAX_COMPILATION_CACHE_DIR`` set — jax itself already placed the
  cache there when it was imported; this module sets no directory.
* unset — the cache goes to :data:`DEFAULT_DIR`, one fixed path inside
  the checkout (git-ignored). Fixed because the path is part of the
  cache key's surroundings: a directory that moves never hits.

So the cache is on by default and only jax's own variable places it;
there is no config field, flag or repo-specific variable to disagree
with it. The restart payoff is measured by the
``trainer/restart_to_first_step_seconds`` gauge and explained by the
``compile/*`` counters below.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Dict, Optional

from tensor2robot_tpu.observability import metrics as metrics_lib
from tensor2robot_tpu.observability import tracing

ENV_VAR = 'JAX_COMPILATION_CACHE_DIR'
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), '.jax_cache')

_lock = threading.Lock()
_enabled_dir: Optional[str] = None  # GUARDED_BY(_lock)
_counters_installed = False  # GUARDED_BY(_lock)


_COUNTERS = ('compile/cache_hits', 'compile/cache_misses',
             'compile/backend_compiles', 'compile/compile_seconds')


def install_compile_counters() -> None:
  """Wires jax's monitoring events into compile/cache counters.

  Registers process-wide listeners translating jax's internal
  monitoring stream into the metrics registry:

  * ``compile/cache_hits`` / ``compile/cache_misses`` — persistent
    compilation-cache outcomes (``/jax/compilation_cache/*`` events),
    the cause line next to ``trainer/restart_to_first_step_seconds``:
    a slow restart with misses recompiled, one with hits paid disk.
  * ``compile/backend_compiles`` / ``compile/compile_seconds`` — every
    XLA backend compile and its total wall time (the denominator
    restart goodput is trying to erase);
  * the span ``compile/backend`` in the tracing ring
    (``observability/tracing.py``) for each of them, on the thread that
    compiled, ending when the event arrives: a compile that a training
    loop pays lies between that loop's spans, so a reader sees which
    dispatch it delayed.

  Idempotent.
  """
  global _counters_installed
  with _lock:
    if _counters_installed:
      return
    from jax import monitoring

    counter = metrics_lib.counter
    for name in _COUNTERS:
      counter(name)

    # The callbacks run inside jax's compile path and must stay
    # allocation-light and exception-free. They look their counters up
    # at each event: a handle held from install time would be orphaned by
    # a registry reset, and the counts read after it would stand still.
    def on_event(name: str, **kwargs) -> None:
      del kwargs
      if name == '/jax/compilation_cache/cache_hits':
        counter('compile/cache_hits').inc()
      elif name == '/jax/compilation_cache/cache_misses':
        counter('compile/cache_misses').inc()

    def on_duration(name: str, duration_secs: float, **kwargs) -> None:
      del kwargs
      if name == '/jax/core/compile/backend_compile_duration':
        end = time.perf_counter_ns()
        counter('compile/backend_compiles').inc()
        counter('compile/compile_seconds').inc(duration_secs)
        tracing.record('compile/backend', end - int(duration_secs * 1e9), end)

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    _counters_installed = True


def enabled_dir() -> Optional[str]:
  """The cache dir in force since :func:`enable_compilation_cache`."""
  with _lock:
    return _enabled_dir


def report() -> Dict[str, object]:
  """Where the cache is and what compiling cost so far: the ``compile``
  section of ``metrics.report()`` and of the serving plane's ``/statz``."""
  return dict(metrics_lib.snapshot('compile/'), dir=enabled_dir())


def enable_compilation_cache() -> Optional[str]:
  """Turns the persistent compilation cache on; returns its directory.

  Idempotent. With ``JAX_COMPILATION_CACHE_DIR`` set the directory is
  jax's own reading of it and nothing is set here; otherwise
  :data:`DEFAULT_DIR`. Returns None only when the default directory
  cannot be created (a read-only checkout): the run goes on uncached
  and says so.
  """
  global _enabled_dir
  with _lock:
    if _enabled_dir is None:
      import jax

      if os.environ.get(ENV_VAR, '').strip():
        resolved = jax.config.jax_compilation_cache_dir
      else:
        resolved = DEFAULT_DIR
        try:
          os.makedirs(resolved, exist_ok=True)
        except OSError as e:
          logging.warning(
              'Cannot create the compilation cache at %r (%r); every '
              'start of this program will recompile. Set %s to a '
              'writable directory.', resolved, e, ENV_VAR)
          return None
        jax.config.update('jax_compilation_cache_dir', resolved)
      # Cache EVERYTHING: the defaults skip fast-compiling programs, but
      # restart goodput is the sum over all of them (K×M train program +
      # every serving bucket), and disk is cheap next to a restart.
      jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
      jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
      _enabled_dir = resolved
      metrics_lib.gauge('compile_cache/enabled').set(1.0)
      metrics_lib.register_report_provider('compile', report)
      logging.info('Persistent compilation cache at %r', resolved)
  # Installed outside the state lock (the installer takes it itself).
  install_compile_counters()
  with _lock:
    return _enabled_dir
