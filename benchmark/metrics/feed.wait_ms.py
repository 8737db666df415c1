"""Host feed: how long the trainer's prefetch thread WAITS in one
``next()`` on the record iterator it is handed, mean over the window.
Not the feed's cost: read, parse, JPEG decode and batch assembly run in
the engine's workers, and ``next()`` pops a queue they fill. Near zero
while the workers keep ahead of the device; it rises only once the feed
starves the step. Source: host_clock (the benchmark's timing wrapper)."""


def read(ctx):
  times = ctx['feed_ms']
  return sum(times) / len(times) if times else None
