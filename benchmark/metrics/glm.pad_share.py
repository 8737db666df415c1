"""Kernels: of the rows the grouped product computed (tiles visited x
rows a tile, as the program's ``moe/rows_computed`` counts them), the
share that carry no token, over the window: 512 rows a held expert at
balance is one 512-row tile when the rows fall on a tile's edge and two
when they do not. Source: program_counter. The reading is
``moe.pad_share``'s (PERF.md, section 7 a)."""

from benchmark.metrics import _zaya_ops

read = _zaya_ops.accepted_reader('moe.pad_share')
