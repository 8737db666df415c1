"""Device step: device time a step of what the expert layers cost beside
their matrix products: scores, top-k, the sorts, the gathers both ways,
the combine and the experts' elementwise part, attributed by result
shape as ``_lm_ops.py`` says, in ms. Source: device_trace."""

from benchmark.metrics import _lm_ops


def read(ctx):
  if 'route_shapes' not in ctx:
    return None
  steps = _lm_ops.steps_traced(ctx)
  seconds = _lm_ops.seconds_of(ctx, _lm_ops.is_routing(ctx))
  if not steps or not seconds:
    return None
  return 1e3 * seconds / steps
