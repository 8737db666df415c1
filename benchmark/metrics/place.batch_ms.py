"""Dispatch: median duration of trainer/place_stage, the whole placement
of one batch (the log adds put, transfer and GB/s).
Source: program_span (the program's span ring joined to the device trace,
``_program_spans.py``)."""

from benchmark.metrics import _program_spans

read = _program_spans.reader('place.batch_ms')
