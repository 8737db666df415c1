"""Device step: device idle inside the train step's program events (event
time minus the union of op time in it), mean per optimizer step:
bubbles inside the step (H3).
Source: device_trace (the program's span ring joined to the device trace,
``_program_spans.py``)."""

from benchmark.metrics import _program_spans

read = _program_spans.reader('idle.in_step_ms')
