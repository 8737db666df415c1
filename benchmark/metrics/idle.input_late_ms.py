"""Dispatch: the idle part of the gap before step event n, after dispatch
n was enqueued, during which trainer/place/transfer n had not ended,
mean per optimizer step: the batch was late (H2).
Source: program_span (the program's span ring joined to the device trace,
``_program_spans.py``)."""

from benchmark.metrics import _program_spans

read = _program_spans.reader('idle.input_late_ms')
