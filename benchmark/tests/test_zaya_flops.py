"""``lib/zaya_flops.py`` against a hand count of the ZAYA1-8B layer as
the configuration runs it (8,192 tokens)."""

import json
import os

import pytest

from benchmark.lib import zaya_flops
from benchmark.reference import zaya1_8b

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 8192


@pytest.fixture(scope='module')
def layers():
  with open(os.path.join(HERE, 'configs', 'zaya1-8b-ep2.json')) as f:
    return zaya1_8b.layers(json.load(f))


def test_hybrid_layer_by_hand(layers):
  assert len(layers) == 7 and layers[-1]['kind'] == 'head'
  parts = zaya_flops.forward_parts(layers[0], SEQ)
  # q and o are 2048 x 1024, k 2048 x 256, the two value halves 2048 x 128.
  assert parts['projections'] == 2 * 2048 * (1024 + 1024 + 256 + 2 * 128)
  # Two taps of a 128 x 128 matrix for each of the 10 heads of the joint
  # latent; the depthwise convolution is no matrix product.
  assert parts['convolution'] == 2 * 2 * 10 * 128 * 128
  # Causal, no window: (S + 1) / 2 keys a query, q.k and p.v at the LATENT
  # width, 8 heads of 128.
  assert parts['attention'] == 2 * 2 * 8 * 128 * (SEQ + 1) / 2
  assert parts['router'] == 2 * (2048 * 256 + 2 * 256 * 256 + 256 * 16)
  one_expert = 2 * 3 * 2048 * 2048
  # One of 16 chosen a token, 8 held: half a routed row a token expected.
  assert parts['routed_experts'] == one_expert / 2
  assert zaya_flops.routed_row_train_flops([layers[0]]) == 3 * one_expert
  assert set(parts) == {'projections', 'convolution', 'attention', 'router',
                        'routed_experts'}
  # ISSUE 32's reckoning: 41.8 MFLOP a token and layer.
  assert round(sum(parts.values()) / 1e6, 1) == 41.8


def test_head_and_totals(layers):
  assert zaya_flops.forward_parts(layers[-1], SEQ) == {
      'head': 2 * 2048 * 32784}
  parts = zaya_flops.train_parts_per_token(layers, SEQ)
  total = sum(parts.values())
  # ISSUE 32's: 385 MFLOP forward a token, 1.16 GFLOP trained, 18.9 TFLOP
  # a step of two sequences; attention 26%, the head 35%, experts 20%.
  assert round(total / 3e6) == 385
  assert abs(2 * zaya_flops.train_flops_per_sequence(layers, SEQ) / 1e12
             - 18.9) < 0.05
  assert round(100 * parts['attention'] / total) == 26
  assert round(100 * parts['head'] / total) == 35
  assert round(100 * parts['routed_experts'] / total) == 20
  assert zaya_flops.attention_train_flops_per_sequence(layers, SEQ) == (
      SEQ * parts['attention'])


@pytest.mark.parametrize('rows_per_token', [0.0, 0.25, 0.5, 1.0])
def test_routed_rows_as_counted(layers, rows_per_token):
  # The whole step's operations follow the rows the counters say were
  # routed: at half a row a token they are the balanced expectation's.
  one_expert = 2 * 3 * 2048 * 2048
  balanced = zaya_flops.train_flops_per_sequence(layers, SEQ)
  counted = zaya_flops.train_flops_per_sequence(layers, SEQ, rows_per_token)
  assert counted == pytest.approx(
      balanced - SEQ * 6 * 3 * one_expert * (0.5 - rows_per_token),
      rel=1e-12)
