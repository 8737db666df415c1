"""Kernels: the least time the chip could take for the held experts'
three products over the rows the program's counter says were routed
(five expert layers, the MTP module's among them), forward and backward,
over the device time of the grouped product's ops (``%ragged-dot*``).
Source: device_trace (the rows: program_counter). The reading is
``moe.experts_roofline``'s (PERF.md, section 7 a)."""

from benchmark.metrics import _zaya_ops

read = _zaya_ops.accepted_reader('moe.experts_roofline')
