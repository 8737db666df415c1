"""Kernels: the least time the chip could take for the held experts'
three products over the rows the program's counter says were routed,
forward and backward (compute-bound: operations over the bf16 peak),
over the device time of the grouped product's ops. Rows that carry no
token and recomputed products are not required work.
Source: device_trace (the rows: program_counter)."""

from benchmark.metrics import _lm_ops


def read(ctx):
  rows = _lm_ops.rows_routed_per_step(ctx)
  seconds = _lm_ops.seconds_of(
      ctx, lambda name: name.startswith(_lm_ops.GROUPED))
  if rows is None or not seconds:
    return None
  work = rows * ctx['routed_row_flops'] * _lm_ops.steps_traced(ctx)
  least = work / (ctx['chips'] * ctx['peaks']['bf16_flops_per_s'])
  return 100.0 * least / seconds
