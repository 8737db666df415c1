"""The device this process runs on: said once, kept where checks read it.

A number from XLA-CPU must never pass for a chip number, and a run that
was meant for the chip must not carry on somewhere else unnoticed. The
trainer and the serving plane call :func:`announce` at start-up: one log
line with platform / device kind / device count, and a ``device``
section in every ``metrics.report()`` (the trainer binary's
``run_report.json``, ``/metricsz``) and in the serving plane's
``/statz``. chip_smoke.py takes its last line from that section — what
the process that ran the steps saw, not a fresh probe.

Nothing here selects or falls back: ``JAX_PLATFORMS`` says where a run
is meant to go, and jax fails at start-up when it cannot go there.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Dict

import jax

from tensor2robot_tpu.observability import metrics as metrics_lib

_lock = threading.Lock()
_announced = False  # GUARDED_BY(_lock)


def describe() -> Dict[str, Any]:
  """``{'platform', 'kind', 'count'}`` as jax reports them, plus the
  installation that produced them."""
  devices = jax.devices()
  return {
      'platform': devices[0].platform,
      'kind': devices[0].device_kind,
      'count': len(devices),
      'process_count': jax.process_count(),
      'jax': jax.__version__,
  }


def announce(who: str) -> Dict[str, Any]:
  """Logs the device line (once per process) and registers the
  ``device`` report section; returns :func:`describe`."""
  global _announced
  info = describe()
  with _lock:
    first, _announced = not _announced, True
  if first:
    metrics_lib.register_report_provider('device', describe)
    logging.info(
        '%s runs on platform=%s device_kind=%r devices=%d processes=%d '
        '(jax %s)', who, info['platform'], info['kind'], info['count'],
        info['process_count'], info['jax'])
  return info
