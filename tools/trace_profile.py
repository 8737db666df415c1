"""Device-time profiling via JAX profiler traces (xplane parsing).

Two choices this tool encodes:

1. Device time comes from the profiler trace, not from a host clock
   around repeated dispatches: a scalar data dependency is chained
   through every iteration so the calls cannot overlap or be elided,
   and device op durations are read out of the trace.
2. ``tensorboard-plugin-profile``'s converter is version-broken against
   the installed TF, so the xplane proto is parsed directly.

Usage::

    from tools.trace_profile import device_ms_per_iter, op_table
    ms, ops = device_ms_per_iter(fn, args)        # fn(*args) -> pytree
    print(op_table(ops))
"""

from __future__ import annotations

import collections
import glob
import os
import re
import shutil
import tempfile

_XPLANE_ENV = {'PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION': 'python'}


def _parse_xplane(tracedir):
  for k, v in _XPLANE_ENV.items():
    os.environ.setdefault(k, v)
  import warnings
  with warnings.catch_warnings():
    warnings.simplefilter('ignore')
    from tensorflow.tsl.profiler.protobuf import xplane_pb2  # pylint: disable=g-import-not-at-top

  paths = glob.glob(
      os.path.join(tracedir, '**', '*.xplane.pb'), recursive=True)
  if not paths:
    raise RuntimeError(f'no xplane trace found under {tracedir}')
  xs = xplane_pb2.XSpace()
  with open(max(paths, key=os.path.getmtime), 'rb') as f:
    xs.ParseFromString(f.read())
  return xs


def strip_op_suffix(op_name: str) -> str:
  """``fusion.123`` → ``fusion``: the HLO instance suffix."""
  return re.sub(r'[.\d]+$', '', op_name)


def is_region_event(op_name: str) -> bool:
  """XLA control-flow REGION events (while/conditional) span their body
  ops, which appear as separate events on the same trace line — counting
  both doubles every scan/while program's device time. Shared by every
  xplane walker in this repo (also tools/fusion_roofline.py) so the rule
  can't drift. Accepts a raw or already-stripped op name."""
  return strip_op_suffix(op_name) in ('while', 'conditional')


def device_op_times(tracedir, device_prefix='/device:TPU'):
  """Aggregates per-op device time (ms) from a trace directory.

  With several device planes in the trace (multi-chip runs), reports the
  busiest chip's plane — chips run concurrently, so summing across them
  would overstate per-step device time by the chip count.
  """
  xs = _parse_xplane(tracedir)
  per_plane = []
  for p in xs.planes:
    if not p.name.startswith(device_prefix):
      continue
    ev_meta = {m.id: m.name for m in p.event_metadata.values()}
    ops = collections.Counter()
    total = 0
    for line in p.lines:
      if line.name != 'XLA Ops':
        continue
      for ev in line.events:
        name = ev_meta.get(ev.metadata_id, '?').split(' = ')[0].lstrip('%')
        key = strip_op_suffix(name)
        if is_region_event(key):
          continue
        total += ev.duration_ps
        ops[key] += ev.duration_ps
    per_plane.append((total, ops))
  if not per_plane:
    return 0.0, {}
  total, ops = max(per_plane, key=lambda t: t[0])
  return total / 1e9, {k: v / 1e9 for k, v in ops.most_common()}


def device_ms_per_iter(fn, args, n=20, tracedir=None):
  """Per-call device time (ms) of ``fn(*args)`` measured from a trace.

  Chains a scalar dependency through the iterations so the backend cannot
  elide, cache, or overlap the repeated work.
  """
  import jax
  import jax.numpy as jnp

  # Only a tempdir this call owns is ever wiped; a caller-provided dir is
  # left intact (the newest-mtime pick below still finds this run's
  # trace among any pre-existing ones).
  owns = tracedir is None
  tracedir = tracedir or tempfile.mkdtemp(prefix='t2r_trace_')

  def chained(acc, *args):
    out = fn(*args)
    s = sum(jnp.sum(l.astype(jnp.float32))
            for l in jax.tree_util.tree_leaves(out))
    return acc + s

  chained_j = jax.jit(chained)
  acc = chained_j(jnp.float32(0), *args)
  jax.block_until_ready(acc)
  with jax.profiler.trace(tracedir):
    for _ in range(n):
      acc = chained_j(acc, *args)
    # Forces every chained dispatch to have executed before the trace
    # window closes — an early exit would drop device ops and undercount.
    jax.block_until_ready(acc)
  total_ms, ops = device_op_times(tracedir)
  if owns:
    shutil.rmtree(tracedir, ignore_errors=True)
  return total_ms / n, {k: v / n for k, v in ops.items()}


def op_table(ops, top=15):
  total = sum(ops.values()) or 1.0
  lines = [f'{"ms":>8}  {"%":>5}  op']
  for k, v in list(ops.items())[:top]:
    lines.append(f'{v:8.3f}  {v / total * 100:5.1f}  {k}')
  return '\n'.join(lines)


def device_ms_per_step_loop(step_fn, state, batches, n=10, tracedir=None):
  """Per-step device ms of a STATEFUL step callable (jitted or
  AOT-compiled — ``Compiled`` objects cannot be wrapped by
  :func:`device_ms_per_iter`'s chained jit). The state threading through
  the loop is the data dependency that stops the backend eliding
  repeated dispatches. Returns ``(ms_per_step, final_state)``.
  """
  import jax

  owns = tracedir is None
  tracedir = tracedir or tempfile.mkdtemp(prefix='t2r_trace_')
  # Warm outside the trace (first dispatch after idle can stall).
  state, _ = step_fn(state, *batches[0])
  jax.block_until_ready(state)
  with jax.profiler.trace(tracedir):
    for i in range(n):
      state, _ = step_fn(state, *batches[i % len(batches)])
    jax.block_until_ready(state)
  total_ms, _ = device_op_times(tracedir)
  if owns:
    shutil.rmtree(tracedir, ignore_errors=True)
  return total_ms / n, state
