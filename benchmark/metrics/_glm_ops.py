"""What ``glm.mla_mix_device_ms`` reads: which of the device's ops in
the traced window are MLA's mixing, told by the instruction's text as
``_lm_ops.py`` and ``_zaya_ops.py`` say (the trace carries no scope
names; the named scope ``glm/mla/mix`` is in the compiled HLO's
``op_name`` for a reader that has it, and this attribution was checked
against it on the step compiled for a described v5e: PERF.md, section
3).

The mixing is what MLA does between its five products and the attention
kernel: the two latent norms, rotary on the queries' rotary part and on
the one shared key, the join of content and rotary parts a head, the
copy of the shared key to every head, the split of key content and
value, and the layouts the kernel takes and gives, forward and backward.
A result is told by its dimensions with the sequence's and the batch's
taken out, in any order:

* a latent's width (``q_rank``, ``kv_rank``, ``kv_rank + rope``, both
  latents side by side) or the shared key ``[.., 1, rope]``;
* a head layout ``[.., heads, nope + rope | nope | nope + v_dim | v_dim
  | rope | rope / 2]`` or the same flat (``heads * width``), and the
  per-head sums ``[.., heads]`` round the kernel.

Left out: the kernels, asynchronous copies, ops that hold others, and
every dense product (a fusion of ``kind=kOutput``): the five products
are ``glm/mla/project``. The shared key squeezed to ``[S, rope]`` cannot
be told from the router's scores at the published sizes (both 64 wide)
and is left out too. Sizes: the run's context (``trunk_shapes``) and,
for the latent widths it lacks, the cell's configuration file.
"""

from __future__ import annotations

import json
import os
from typing import Callable, Dict

from benchmark.metrics import _zaya_ops

CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'configs', 'glm-4.7-flash-ep8.json')


def _sets(ctx: Dict):
  s = ctx['trunk_shapes']
  with open(CONFIG) as f:
    cfg = json.load(f)
  heads, q_rank, kv_rank = s['heads'], cfg['q_lora_rank'], cfg['kv_lora_rank']
  nope, rot, vd = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                   cfg['v_head_dim'])
  if s['head_dim'] != nope + rot:
    return None
  widths = (nope + rot, nope, nope + vd, vd, rot, rot // 2)
  mix = {(q_rank,), (kv_rank,), (kv_rank + rot,),
         (q_rank + kv_rank + rot,), (1, rot), (1, rot // 2), (heads,),
         (1, heads)}
  mix |= {tuple(sorted((heads, w))) for w in widths}
  mix |= {(heads * w,) for w in widths}
  return s['batch'], (s['sequence'], s['sequence'] - 1), mix


def is_mix(ctx: Dict) -> Callable[[str], bool]:
  sets = _sets(ctx)
  if sets is None:
    return lambda name: False
  batch, seq, mix = sets

  def wanted(name: str) -> bool:
    if 'kind=kOutput' in name:
      return False
    for dims in _zaya_ops._countable(name) or ():
      for rest in (_zaya_ops._without(dims, (batch,), seq),
                   _zaya_ops._without(dims, seq)):
        if rest in mix:
          return True
    return False

  return wanted
