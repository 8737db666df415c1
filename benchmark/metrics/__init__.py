"""One reader a per-layer metric: ``read(ctx)`` returns the number, or
None where there is nothing to read (never 0 for a share of a peak)."""
