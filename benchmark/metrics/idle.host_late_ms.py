"""Dispatch: the idle part of the gap before step event n during which
dispatch n was not yet enqueued (up to the end of trainer/dispatch n),
mean per optimizer step: the host was late (H1). The log splits it by
the loop-thread span over it.
Source: program_span (the program's span ring joined to the device trace,
``_program_spans.py``)."""

from benchmark.metrics import _program_spans

read = _program_spans.reader('idle.host_late_ms')
