"""Operations the forward and backward passes require, from shapes.

Counted over the plain reference's layer list (``reference.layers``),
never from the program's HLO: one multiply-add is two operations; a
layer's backward pass costs a product for the weight gradient and, where
something upstream needs it, one for the input gradient. Normalisation,
pooling, activations and the optimizer are left out, as is customary for
model FLOP utilisation: the chip's peak is a matrix-unit peak.
"""

from __future__ import annotations

from typing import Dict, List


def layer_forward_flops(layer: Dict) -> float:
  h, w = layer['out_hw']
  macs = h * w * layer['k'] * layer['k'] * layer['cin'] * layer['cout']
  return 2.0 * macs * layer.get('per_example', 1)


def layer_train_flops(layer: Dict) -> float:
  backward = 2 if layer.get('input_grad', True) else 1
  return layer_forward_flops(layer) * (1 + backward)


def train_flops_per_example(layers: List[Dict]) -> float:
  return sum(layer_train_flops(l) for l in layers)
