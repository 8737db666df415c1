"""Device step: device time a step of what MLA does between its five
products and the attention kernel: the two latent norms, rotary on the
queries' rotary part and on the shared key, the join of content and
rotary parts, the copy of the shared key to every head and the layouts
the kernel takes and gives, forward and backward, attributed by result
shape as ``_glm_ops.py`` says, in ms. Source: device_trace."""

from benchmark.metrics import _glm_ops, _lm_ops


def read(ctx):
  if 'trunk_shapes' not in ctx:
    return None
  steps = _lm_ops.steps_traced(ctx)
  seconds = _lm_ops.seconds_of(ctx, _glm_ops.is_mix(ctx))
  if not steps or not seconds:
    return None
  return 1e3 * seconds / steps
