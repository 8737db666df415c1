"""Device step: device time a step of the dense products (attention's
projections, dense MLPs, shared experts, the MTP projection) with what
their scopes hold besides, by the program's own scopes (``_scopes.py``),
in ms. Source: device_trace."""

from benchmark.metrics import _scopes


def read(ctx):
  return _scopes.family_ms(ctx, 'dense')
