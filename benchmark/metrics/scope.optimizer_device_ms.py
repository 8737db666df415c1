"""Device step: device time a step of the optimizer (Adam's update and
the EMA, under the trainer's ``train/optimizer`` scope), by the program's
own scopes (``_scopes.py``), in ms. Source: device_trace."""

from benchmark.metrics import _scopes


def read(ctx):
  return _scopes.family_ms(ctx, 'optimizer')
