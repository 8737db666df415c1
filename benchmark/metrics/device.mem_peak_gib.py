"""Device: ``memory_stats()['peak_bytes_in_use']`` of the fullest chip,
read after the window and before the reference runs.
Source: program_counter."""


def read(ctx):
  peak = ctx['memory_peak_bytes']
  return peak / 2 ** 30 if peak else None
