"""Kernels: the least time the chip could take for the causal attention
the layers require at the LATENT width (``lib/zaya_flops.py``: q.k and
p.v over ``(S + 1) / 2`` keys a query at 8 heads of 128, forward and
backward, counted once; the bound is compute, over the bf16 peak) over
the device time of the attention kernels. Source: device_trace. The
reading is ``attention.roofline``'s with this cell's count of required
operations in the context (PERF.md, section 7 a)."""

from benchmark.metrics import _zaya_ops

read = _zaya_ops.accepted_reader('attention.roofline')
