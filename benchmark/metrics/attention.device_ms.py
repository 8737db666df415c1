"""Device step: device time a step of the attention kernels (forward,
recomputed forward, dq and dk/dv, window and full layers together), in
ms. Source: device_trace."""

from benchmark.metrics import _lm_ops


def read(ctx):
  steps = _lm_ops.steps_traced(ctx)
  seconds = _lm_ops.seconds_of(
      ctx, lambda name: name.startswith(_lm_ops.ATTENTION))
  if not steps or not seconds:
    return None
  return 1e3 * seconds / steps
