"""Kernels: the least time the chip could take for the step's required
operations (the bound is compute: operations over the bf16 peak; the
step's required bytes over 819 GB/s come to less at these shapes) over
the device-busy time inside the step's events. The same work is counted
whatever implements it. Source: device_trace."""

from benchmark.metrics import _traced


def read(ctx):
  t = _traced.traced(ctx)
  if not t['step_busy_s']:
    return None
  work = (t['dispatches'] * ctx['examples_per_dispatch'] *
          ctx['flops_per_example'])
  least = work / (ctx['chips'] * ctx['peaks']['bf16_flops_per_s'])
  return 100.0 * least / t['step_busy_s']
