"""Kernels: the least time the chip could take for the causal attention
the six decoder layers require (``lib/glm_flops.py``: q.k at 20 heads of
192 + 64 and p.v at 20 heads of 256 over ``(S + 1) / 2`` keys a query,
forward and backward, counted once; the bound is compute, over the bf16
peak) over the device time of the attention kernels: the D=256 kernel's
share of its roofline. Source: device_trace. The reading is
``attention.roofline``'s with this cell's count of required operations
in the context (PERF.md, section 7 a)."""

from benchmark.metrics import _zaya_ops

read = _zaya_ops.accepted_reader('attention.roofline')
