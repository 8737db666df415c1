"""Device step: device time a step of the router (its down-projection,
the state carried from the layer before, the MLP, softmax: float32 at
``highest``) and of what the top-1 choice costs beside the experts'
matrix products: the sort, the gathers into and out of the routed-row
buffer, the weighted sum and silu x up, attributed by result shape as
``_zaya_ops.py`` says, in ms. Source: device_trace."""

from benchmark.metrics import _lm_ops, _zaya_ops


def read(ctx):
  if 'trunk_shapes' not in ctx:
    return None
  steps = _lm_ops.steps_traced(ctx)
  seconds = _lm_ops.seconds_of(ctx, _zaya_ops.is_router(ctx))
  if not steps or not seconds:
    return None
  return 1e3 * seconds / steps
