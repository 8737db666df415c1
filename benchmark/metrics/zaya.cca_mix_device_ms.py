"""Device step: device time a step of what CCA does between its
projections and the attention kernel: the two causal convolutions over
the joint q-k latent, the q-k mean, the L2 norms with the temperature,
rotary, the value shift and the layouts the kernel takes and gives,
forward and backward, attributed by result shape as ``_zaya_ops.py``
says, in ms. Source: device_trace."""

from benchmark.metrics import _lm_ops, _zaya_ops


def read(ctx):
  if 'trunk_shapes' not in ctx:
    return None
  steps = _lm_ops.steps_traced(ctx)
  seconds = _lm_ops.seconds_of(ctx, _zaya_ops.is_mix(ctx))
  if not steps or not seconds:
    return None
  return 1e3 * seconds / steps
