"""Device step: device time a step of the attention kernels inside the
CCA latent (``%flash_attention_fwd``, ``_dq``, ``_dkv``: the kernels'
own names; one forward a layer, the layer's remat keeps its results),
in ms. Source: device_trace. The reading is ``attention.device_ms``'s,
under this cell's name: that entry's list is the benchmark's and this
cell cannot join it (PERF.md, section 7 a)."""

from benchmark.metrics import _zaya_ops

read = _zaya_ops.accepted_reader('attention.device_ms')
