"""Kernels: of the rows the grouped product computed (tiles visited x
rows a tile, as the program's ``moe/rows_computed`` counts them), the
share that carry no token, over the window: with 1,024 rows an expert at
balance the 512-row tile is fed whole tiles. Source: program_counter.
The reading is ``moe.pad_share``'s (PERF.md, section 7 a)."""

from benchmark.metrics import _zaya_ops

read = _zaya_ops.accepted_reader('moe.pad_share')
