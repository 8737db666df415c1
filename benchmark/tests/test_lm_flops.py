"""``lib/lm_flops.py`` against a hand count of one layer of each kind of
the Trinity-Mini configuration (8,192 tokens)."""

import json
import os

import pytest

from benchmark.lib import lm_flops
from benchmark.reference import trinity_mini

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 8192


@pytest.fixture(scope='module')
def layers():
  with open(os.path.join(HERE, 'configs', 'trinity-mini-ep8.json')) as f:
    return trinity_mini.layers(json.load(f))


def test_dense_window_layer_by_hand(layers):
  # Published layer 1: window attention, the dense MLP.
  parts = lm_flops.forward_parts(layers[0], SEQ)
  # q, gate and o are 2048 x 4096, k and v 2048 x 512.
  assert parts['projections'] == 2 * 2048 * (3 * 4096 + 2 * 512)
  # A query at position i sees min(i + 1, 2048) keys: 1 + ... + 2048 over
  # the first window, 2048 from then on; q.k and p.v, 32 heads of 128.
  keys = (2048 * 2049 / 2 + (SEQ - 2048) * 2048) / SEQ
  assert keys == 1792.125
  assert parts['attention'] == 2 * 2 * 32 * 128 * keys
  assert parts['dense_mlp'] == 2 * 3 * 2048 * 6144
  assert set(parts) == {'projections', 'attention', 'dense_mlp'}


def test_full_layer_with_experts_by_hand(layers):
  # Published layer 3: full attention, experts.
  layer = layers[2]
  assert layer['window'] is None
  parts = lm_flops.forward_parts(layer, SEQ)
  assert parts['attention'] == 2 * 2 * 32 * 128 * (SEQ + 1) / 2
  assert parts['router'] == 2 * 2048 * 128
  one_expert = 2 * 3 * 2048 * 1024
  assert parts['shared_experts'] == one_expert
  # 8 of 128 chosen a token, 16 held: one routed row a token expected.
  assert parts['routed_experts'] == one_expert * 8 * 16 / 128
  assert lm_flops.routed_row_train_flops([layer]) == 3 * one_expert


def test_head_and_totals(layers):
  assert lm_flops.forward_parts(layers[-1], SEQ) == {
      'head': 2 * 2048 * 25024}
  parts = lm_flops.train_parts_per_token(layers, SEQ)
  total = sum(parts.values())
  # ISSUE 27's reckoning: 2,210 MFLOP a token, attention 554 of them,
  # 18.1 TFLOP a sequence; forward and backward are 3 x the forward.
  assert round(total / 1e6) == 2214
  assert round(parts['attention'] / 1e6) == 554
  assert abs(lm_flops.train_flops_per_sequence(layers, SEQ) / 1e12
             - 18.1) < 0.05
  assert lm_flops.attention_train_flops_per_sequence(layers, SEQ) == (
      SEQ * parts['attention'])
  # A full layer costs 2.3 times a window layer at four windows.
  assert round(lm_flops.attended_keys(SEQ, None) /
               lm_flops.attended_keys(SEQ, 2048), 1) == 2.3


@pytest.mark.parametrize('rows_per_token', [0.0, 0.25, 1.0])
def test_routed_rows_as_counted(layers, rows_per_token):
  # The whole step's operations follow the rows the counters say were
  # routed: at one row a token they are the balanced expectation's, and
  # every row less takes one expert's three products off, four layers.
  one_expert = 2 * 3 * 2048 * 1024
  balanced = lm_flops.train_flops_per_sequence(layers, SEQ)
  counted = lm_flops.train_flops_per_sequence(layers, SEQ, rows_per_token)
  assert counted == pytest.approx(
      balanced - SEQ * 4 * 3 * one_expert * (1.0 - rows_per_token),
      rel=1e-12)
  assert lm_flops.attention_train_flops_per_sequence(layers, SEQ) == (
      SEQ * lm_flops.train_parts_per_token(
          layers, SEQ, rows_per_token)['attention'])
