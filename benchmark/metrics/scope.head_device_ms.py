"""Device step: device time a step of the vocabulary loss (the head's
chunked loss and its gradient; both passes in GLM), by the program's own
scopes (``_scopes.py``), in ms. Source: device_trace."""

from benchmark.metrics import _scopes


def read(ctx):
  return _scopes.family_ms(ctx, 'head')
