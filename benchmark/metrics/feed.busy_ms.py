"""Host feed: worker time in data/engine/parse_decode inside the window
over the batches finished in it: what a batch costs the feed
(feed.wait_ms is a wait, near zero until the feed starves). Over the
worker count it is the feed's ceiling.
Source: program_span (the program's span ring joined to the device trace,
``_program_spans.py``)."""

from benchmark.metrics import _program_spans

read = _program_spans.reader('feed.busy_ms')
