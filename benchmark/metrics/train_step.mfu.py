"""Device step, whole: operations the forward and backward passes
require per example (``benchmark/lib/flops.py`` over the reference's
shapes) x examples a second of the traced window, over chips x the
bf16 peak. Source: device_trace (the window and the count of dispatches
in it are the trace's)."""

from benchmark.metrics import _traced


def read(ctx):
  t = _traced.traced(ctx)
  if not t['dispatches'] or not t['window_s']:
    return None
  rate = t['dispatches'] * ctx['examples_per_dispatch'] / t['window_s']
  peak = ctx['chips'] * ctx['peaks']['bf16_flops_per_s']
  return 100.0 * ctx['flops_per_example'] * rate / peak
