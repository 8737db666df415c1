"""Device step: device-op time (union) inside the train-step program's
events, per optimizer step. Source: device_trace."""

from benchmark.metrics import _traced


def read(ctx):
  t = _traced.traced(ctx)
  steps = t['dispatches'] * ctx['steps_per_dispatch']
  return t['step_busy_s'] * 1e3 / steps if steps else None
