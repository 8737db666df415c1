"""Dispatch: median wall time between dispatch completions in the
window, from the benchmark's TrainerCallback. A statistic of pieces, so
per-layer only. Source: host_clock."""

import statistics


def read(ctx):
  gaps = ctx['dispatch_gaps_ms']
  return statistics.median(gaps) if gaps else None
