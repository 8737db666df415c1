"""Device step: device time a step of the attention kernels at 20 heads
of 256 (``%flash_attention_fwd`` and ``_bwd``: the kernels' own names;
one of each a decoder layer, the MTP module's among them), in ms.
Source: device_trace. The reading is ``attention.device_ms``'s, under
this cell's name: that entry's list is the benchmark's and this cell
cannot join it (PERF.md, section 7 a)."""

from benchmark.metrics import _zaya_ops

read = _zaya_ops.accepted_reader('attention.device_ms')
