"""Operations a ZAYA trunk's forward and backward passes require, from
the reference's layer list (``reference/zaya1_8b.layers``), never from
the program's HLO; the same rules and the same three totals as
``lib/lm_flops.py``: one multiply-add is two operations, the backward
pass costs twice the forward, recomputed operations do not count, and
norms, activations, RoPE, softmax, the depthwise convolution (a
multiply-add a channel and tap, no matrix product), the top-1 choice,
the permutation and the optimizer are left out.

A layer's parts, forward a token: the projections down into the latent
and up from it (q, k, the two value halves, o); the grouped
convolution (``conv_taps`` matrices of ``head_dim x head_dim`` a head,
query and key heads alike); causal attention over ``(S + 1) / 2`` keys
at the LATENT width (``heads * head_dim``); the router (down-projection
and its three-layer MLP); the routed experts at ``rows_per_token``
routed rows a token (what the program's counters say was routed in the
window; None: the rows the held experts see at balance).
"""

from __future__ import annotations

from typing import Dict, List, Optional


def attended_keys(seq: int) -> float:
  """Mean number of keys a query attends to under the causal mask."""
  return (seq + 1) / 2


def expert_row_flops(layer: Dict) -> float:
  """Forward operations of one routed row through one expert."""
  return 2.0 * 3 * layer['hidden'] * layer['expert_width']


def forward_parts(layer: Dict, seq: int,
                  rows_per_token: Optional[float] = None) -> Dict[str, float]:
  """Forward operations a token of one layer, by part."""
  d = layer['hidden']
  if layer['kind'] == 'head':
    return {'head': 2.0 * d * layer['vocab']}
  hd = layer['head_dim']
  q, kv = layer['heads'] * hd, layer['kv_heads'] * hd
  rh = layer['router_hidden']
  rows = rows_per_token
  if rows is None:
    rows = (layer['experts_per_token'] * layer['experts_held'] /
            layer['router_width'])
  return {
      'projections': 2.0 * d * (2 * q + 2 * kv),         # q, o; k, v
      'convolution': 2.0 * layer['conv_taps'] * (
          layer['heads'] + layer['kv_heads']) * hd * hd,
      'attention': 2.0 * 2 * q * attended_keys(seq),
      'router': 2.0 * (d * rh + 2 * rh * rh + rh * layer['router_width']),
      'routed_experts': rows * expert_row_flops(layer),
  }


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


def routed_row_train_flops(layers: List[Dict]) -> float:
  """Forward and backward operations of one routed row."""
  layer = next(l for l in layers if 'expert_width' in l)
  return 3.0 * expert_row_flops(layer)
