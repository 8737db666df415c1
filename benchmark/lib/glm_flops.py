"""Operations a glm4_moe_lite trunk's forward and backward passes
require, from the reference's layer list
(``reference/glm_4_7_flash.layers``), never from the program's HLO; the
same rules and the same three totals as ``lib/lm_flops.py``: one
multiply-add is two operations, the backward pass costs twice the
forward, recomputed operations do not count, and norms, activations,
RoPE, softmax, the top-k, the permutation and the optimizer are left
out.

A decoder layer's parts, forward a token: MLA's five products (down to
the query latent and up a head, down to the key/value latent with the
shared rotary key beside it and up a head, the output); causal
attention over ``(S + 1) / 2`` keys at ``heads * (nope + rope)`` for q.k
and ``heads * v_dim`` for p.v (training materialises k and v a head:
the absorbed form's count is a decode path's); then the dense MLP, or
the router, the shared expert and the routed experts at
``rows_per_token`` routed rows a token (what the program's counters say
was routed in the window; None: the rows the held experts see at
balance). After the layers: the MTP module's projection (``2 hidden ->
hidden``) and the head, once a pass (the main one and each MTP
module's).
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark.lib.lm_flops import (  # the same rules, one body
    attended_keys, expert_row_flops, routed_row_train_flops)


def forward_parts(layer: Dict, seq: int,
                  rows_per_token: Optional[float] = None) -> Dict[str, float]:
  """Forward operations a token of one entry, by part."""
  d = layer['hidden']
  if layer['kind'] == 'head':
    return {'head': 2.0 * d * layer['vocab'] * layer['passes']}
  if layer['kind'] == 'mtp_projection':
    return {'mtp_projection': 2.0 * 2 * d * d}
  heads, nope, rot, vd = (layer['heads'], layer['nope'], layer['rope'],
                          layer['v_dim'])
  parts = {
      'mla_projections': 2.0 * (
          d * layer['q_rank'] + layer['q_rank'] * heads * (nope + rot) +
          d * (layer['kv_rank'] + rot) +
          layer['kv_rank'] * heads * (nope + vd) + heads * vd * d),
      'attention': 2.0 * heads * (nope + rot + vd) * attended_keys(seq, None),
  }
  if 'dense_width' in layer:
    parts['dense_mlp'] = 2.0 * 3 * d * layer['dense_width']
    return parts
  rows = rows_per_token
  if rows is None:
    rows = (layer['experts_per_token'] * layer['experts_held'] /
            layer['router_width'])
  parts['router'] = 2.0 * d * layer['router_width']
  parts['shared_experts'] = layer['shared_experts'] * expert_row_flops(layer)
  parts['routed_experts'] = rows * expert_row_flops(layer)
  return parts


def train_parts_per_token(layers: List[Dict], seq: int,
                          rows_per_token: Optional[float] = None
                          ) -> Dict[str, float]:
  """Forward and backward operations a token, summed by part."""
  out: Dict[str, float] = {}
  for layer in layers:
    for part, value in forward_parts(layer, seq, rows_per_token).items():
      out[part] = out.get(part, 0.0) + 3.0 * value
  return out


def train_flops_per_sequence(layers: List[Dict], seq: int,
                             rows_per_token: Optional[float] = None) -> float:
  return seq * sum(
      train_parts_per_token(layers, seq, rows_per_token).values())


def attention_train_flops_per_sequence(layers: List[Dict], seq: int) -> float:
  return seq * train_parts_per_token(layers, seq)['attention']
