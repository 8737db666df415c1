"""Dispatch: median duration of trainer/dispatch, the jitted call that
enqueues a step, over the window's dispatches.
Source: program_span (the program's span ring joined to the device trace,
``_program_spans.py``)."""

from benchmark.metrics import _program_spans

read = _program_spans.reader('dispatch.enqueue_ms')
