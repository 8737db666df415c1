"""Kernels: of the rows the grouped product computed (tiles visited x
rows a tile, as the program's ``moe/rows_computed`` counts them), the
share that carry no token, over the window. Source: program_counter."""


def read(ctx):
  moved = ctx.get('moe_counters') or {}
  computed = moved.get('moe/rows_computed')
  if not computed:
    return None
  return 100.0 * (computed - moved['moe/rows_routed']) / computed
