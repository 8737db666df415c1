"""Kernels: the least time the chip could take for the masked attention
the layers require (``lib/lm_flops.py``: q.k and p.v over the keys the
mask admits, forward and backward, counted once without recomputation;
the bound is compute, over the bf16 peak) over the device time of the
attention kernels. The same work whatever implements it.
Source: device_trace."""

from benchmark.metrics import _lm_ops


def read(ctx):
  if 'attention_flops_per_example' not in ctx:
    return None
  seconds = _lm_ops.seconds_of(
      ctx, lambda name: name.startswith(_lm_ops.ATTENTION))
  if not seconds:
    return None
  work = (ctx['attention_flops_per_example'] * ctx['examples_per_dispatch'] *
          _lm_ops.dispatches_traced(ctx))
  least = work / (ctx['chips'] * ctx['peaks']['bf16_flops_per_s'])
  return 100.0 * least / seconds
