"""Device: 1 - union of device-op intervals over the traced window.
Source: device_trace."""

from benchmark.metrics import _traced


def read(ctx):
  t = _traced.traced(ctx)
  return 100.0 * (1.0 - t['busy_s'] / t['window_s']) if t['window_s'] else None
