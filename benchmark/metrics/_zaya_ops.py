"""What the ``zaya.*`` readers share: which of the device's ops in the
traced window belong to the CCA mixing and which to the router and the
routing, told by the instruction's text as ``_lm_ops.py`` says (the
trace carries no scope names; the named scopes ``zaya/cca/mix``,
``zaya/router``, ``zaya/afmoe/moe/route`` are in the compiled HLO's
``op_name`` for a reader that has it, and this attribution was checked
against them on the step compiled for a described v5e: PERF.md, section
3).

Left out of both: the kernels (``%flash_attention_*``, ``%ragged-dot*``),
asynchronous copies (``*-start``/``*-done``) and the ops that only hold
others (``while``, ``conditional``, ``call``: their bodies' ops are
events of their own). Sizes come from the run's context
(``trunk_shapes``), not from a table. A result is told by its
dimensions with the sequence's (S, or S - 1 of a shifted slice) and the
batch's taken out, in any order (the compiler transposes freely):

* the CCA mixing: the joint latent's width (``(heads + kv_heads) *
  head_dim``) or latent heads ``[heads + kv_heads | heads | kv_heads,
  2 head_dim | head_dim | rotary | rotary / 2]``, per-head sums, and
  the same folded for the kernel (``[batch * heads, S, head_dim]``): both convolutions,
  the q-k mean, the L2 norms, rotary, the value shift and the layouts
  the kernel takes and gives. A dense product (a fusion of
  ``kind=kOutput``) with the joint latent's width is the q-k
  projection, not the mixing, and is left out; the grouped
  convolution's products, per head (its operand holds both taps:
  ``2 * head_dim`` wide), are in.
* the router and the routing: results ``[.., router_hidden]`` or
  ``[.., experts]`` per token (the router's products are its cost and
  are in), and whatever leads with the flat token count
  (``[batch * S, ..]``: the choice, the sort, the gathers into and out
  of the routed-row buffer, the weighted sum, silu x up). The key
  latent is as wide as the router's state at the published sizes (256):
  its slices count as the router's, a few tens of microseconds a layer.
"""

from __future__ import annotations

import importlib.util
import os
import re
from typing import Callable, Dict, Tuple

from benchmark.metrics import _lm_ops

_SHAPE = re.compile(r'[a-z0-9]+\[([0-9,]*)\]')
_OPCODE = re.compile(r'\S* ([a-z][\w\-]*)\(')
_HOLDERS = ('while', 'conditional', 'call')


def accepted_reader(name: str):
  """``read`` of the accepted metric ``name`` (its file beside this
  one, found as ``run.py`` finds a reader): what ``zaya.attention_*``,
  ``zaya.experts_roofline`` and ``zaya.pad_share`` read, so that a
  kernel's reading has one body under both names."""
  spec = importlib.util.spec_from_file_location(
      'bench_metric_' + name.replace('.', '_'),
      os.path.join(os.path.dirname(__file__), f'{name}.py'))
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module.read


def result_and_opcode(name: str):
  """(dimensions of each array of the result, the opcode) of an
  instruction's text. A tuple's type is taken to its matching bracket:
  tiled layouts (``{1,0:T(8,128)}``) have brackets of their own."""
  _, sep, rhs = name.partition(' = ')
  if not sep:
    return [], ''
  end = 0
  if rhs.startswith('('):
    depth = 0
    for end, ch in enumerate(rhs):
      depth += (ch == '(') - (ch == ')')
      if depth == 0:
        break
  found = _OPCODE.match(rhs, end)
  if not found:
    return [], ''
  dims = [tuple(int(d) for d in m.group(1).split(',') if d)
          for m in _SHAPE.finditer(rhs[:found.start(1)])]
  return dims, found.group(1)


def _countable(name: str):
  """The result's arrays of an op that is device work of its own; None
  for kernels, asynchronous copies, ops that hold others and custom
  calls that move nothing (``ConcatBitcast``, ``AllocateBuffer``)."""
  if name.startswith(_lm_ops.GROUPED) or name.startswith(_lm_ops.ATTENTION):
    return None
  dims, code = result_and_opcode(name)
  if (code in _HOLDERS or code == 'custom-call' or
      code.endswith(('-start', '-done'))):
    return None
  return dims


def _without(dims: Tuple[int, ...], *wanted) -> Tuple[int, ...]:
  """``dims`` sorted, less one dimension of each ``wanted`` set; None if
  a set has no member in it."""
  rest = list(dims)
  for options in wanted:
    hit = next((d for d in rest if d in options), None)
    if hit is None:
      return None
    rest.remove(hit)
  return tuple(sorted(rest))


def _sets(ctx: Dict):
  s = ctx['trunk_shapes']
  batch, seq = s['batch'], (s['sequence'], s['sequence'] - 1)
  heads, kv, hd = s['heads'], s['kv_heads'], s['head_dim']
  group = heads // kv
  head_counts = (heads + kv, heads, kv)
  widths = (2 * hd, hd, hd // 2, hd // 4)    # both taps; head; rotary; its half
  latent = ((heads + kv) * hd,)
  mix = {latent, (kv, group, hd)}
  mix |= {tuple(sorted((n, w))) for n in head_counts for w in widths}
  mix |= {(n,) for n in head_counts}
  folded = {tuple(sorted(f)) for n in (heads, kv)
            for f in ((batch * n, hd), (batch * n,), (batch * n, 1))}
  router = {(s['router_hidden'],), (s['experts'],)}
  return batch, seq, mix, folded, router, batch * s['sequence'], latent


def is_mix(ctx: Dict) -> Callable[[str], bool]:
  batch, seq, mix, folded, router, _, latent = _sets(ctx)

  def wanted(name: str) -> bool:
    product = 'kind=kOutput' in name
    for dims in _countable(name) or ():
      rest = _without(dims, (batch,), seq)
      if rest in router:
        continue
      if rest in mix and not (product and rest == latent):
        return True
      if _without(dims, seq) in folded:
        return True
    return False

  return wanted


def is_router(ctx: Dict) -> Callable[[str], bool]:
  batch, seq, _, _, router, tokens, _ = _sets(ctx)

  def wanted(name: str) -> bool:
    for dims in _countable(name) or ():
      if dims[:1] == (tokens,) or _without(dims, (batch,), seq) in router:
        return True
    return False

  return wanted
