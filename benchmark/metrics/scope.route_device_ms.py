"""Device step: device time a step of routing (the expert layer's
``*/moe/route`` and ZAYA's router: scores, choice, sorts, the gathers
both ways, the combine), forward, recomputed and backward together, by
the program's own scopes (``_scopes.py``), in ms. Source: device_trace."""

from benchmark.metrics import _scopes


def read(ctx):
  return _scopes.family_ms(ctx, 'route')
