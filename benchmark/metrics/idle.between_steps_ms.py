"""Device: device idle between the end of step event n-1 and the start of
event n, mean per optimizer step. With idle.in_step_ms it sums to
device.idle_share x the wall time a step.
Source: device_trace (the program's span ring joined to the device trace,
``_program_spans.py``)."""

from benchmark.metrics import _program_spans

read = _program_spans.reader('idle.between_steps_ms')
