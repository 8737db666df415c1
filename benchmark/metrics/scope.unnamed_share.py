"""Device step: the share of the step's device time in ops that no scope
of the program names (``_scopes.py``): the join's coverage, in %.
Source: device_trace."""

from benchmark.metrics import _scopes


def read(ctx):
  out = _scopes.join(ctx)
  return None if out is None else out['unnamed_share']
