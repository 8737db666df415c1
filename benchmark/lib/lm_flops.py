"""Operations a decoder's forward and backward passes require, from the
reference's layer list (``reference.layers``), never from the program's
HLO. One multiply-add is two operations; the backward pass costs twice
the forward (a product for the weight's gradient, one for the input's);
recomputed operations do not count. Norms, activations, RoPE, softmax,
the router's top-k, the permutation and the optimizer are left out, as
is customary: the chip's peak is a matrix-unit peak.

Attention is counted once over the keys the mask admits: a causal layer
sees ``(S + 1) / 2`` keys a query on average, a window layer ``W`` keys
from position ``W`` on and ``i + 1`` before. Routed experts are counted
at ``rows_per_token`` routed rows a token of an expert layer: what the
program's counters say was routed in the window, as a kernel's roofline
counts them; where nothing was counted (None), the rows the held
experts are expected to see at balance (``experts_per_token * held /
router_width``).
"""

from __future__ import annotations

from typing import Dict, List, Optional


def attended_keys(seq: int, window) -> float:
  """Mean number of keys a query attends to."""
  if window is None or window >= seq:
    return (seq + 1) / 2
  return (window * (window + 1) / 2 + (seq - window) * window) / seq


def expert_row_flops(layer: Dict) -> float:
  """Forward operations of one routed row through one expert."""
  return 2.0 * 3 * layer['hidden'] * layer['expert_width']


def forward_parts(layer: Dict, seq: int,
                  rows_per_token: Optional[float] = None) -> Dict[str, float]:
  """Forward operations a token of one layer, by part."""
  d = layer['hidden']
  if layer['kind'] == 'head':
    return {'head': 2.0 * d * layer['vocab']}
  q = layer['heads'] * layer['head_dim']
  kv = layer['kv_heads'] * layer['head_dim']
  parts = {
      'projections': 2.0 * d * (3 * q + 2 * kv),       # q, gate, o; k, v
      'attention': 2.0 * 2 * q * attended_keys(seq, layer['window']),
  }
  if 'dense_width' in layer:
    parts['dense_mlp'] = 2.0 * 3 * d * layer['dense_width']
  else:
    rows = rows_per_token
    if rows is None:
      rows = (layer['experts_per_token'] * layer['experts_held'] /
              layer['router_width'])
    parts['router'] = 2.0 * d * layer['router_width']
    parts['shared_experts'] = (layer['shared_experts'] *
                               expert_row_flops(layer))
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


def routed_row_train_flops(layers: List[Dict]) -> float:
  """Forward and backward operations of one routed row."""
  layer = next(l for l in layers if 'expert_width' in l)
  return 3.0 * expert_row_flops(layer)
