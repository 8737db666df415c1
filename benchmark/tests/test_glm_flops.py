"""``lib/glm_flops.py`` against a hand count of the GLM-4.7-Flash layer
as the configuration runs it (8,192 tokens, the published widths)."""

import json
import os

import pytest

from benchmark.lib import glm_flops
from benchmark.reference import glm_4_7_flash

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 8192


@pytest.fixture(scope='module')
def layers():
  with open(os.path.join(HERE, 'configs', 'glm-4.7-flash-ep8.json')) as f:
    return glm_4_7_flash.layers(json.load(f))


def test_layers_by_hand(layers):
  # The dense layer, four expert layers, the MTP module's expert layer,
  # its projection, the head.
  assert [l['kind'] for l in layers] == ['mla'] * 6 + ['mtp_projection',
                                                       'head']
  assert ['dense_width' in l for l in layers[:6]] == [True] + [False] * 5
  dense = glm_flops.forward_parts(layers[0], SEQ)
  # q: 2048 -> 768 -> 20 x 256; k, v: 2048 -> 512 + 64, 512 -> 20 x 448;
  # o: 5120 -> 2048.
  by_hand = 2 * (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 +
                 5120 * 2048)
  assert dense['mla_projections'] == by_hand == 2 * 21757952
  # Causal, (S + 1) / 2 keys a query: q.k at 20 x 256, p.v at 20 x 256.
  assert dense['attention'] == 2 * 20 * (256 + 256) * (SEQ + 1) / 2
  assert dense['dense_mlp'] == 2 * 3 * 2048 * 10240
  assert set(dense) == {'mla_projections', 'attention', 'dense_mlp'}
  sparse = glm_flops.forward_parts(layers[1], SEQ)
  one_expert = 2 * 3 * 2048 * 1536
  assert sparse['router'] == 2 * 2048 * 64
  assert sparse['shared_experts'] == one_expert
  # 4 of 64 chosen a token, 8 held: half a routed row a token expected.
  assert sparse['routed_experts'] == one_expert / 2
  assert glm_flops.routed_row_train_flops(layers) == 3 * one_expert
  # ISSUE 35's reckoning: 43.5 MFLOP of projections and 83.9 of scores a
  # token and layer.
  assert round(dense['mla_projections'] / 1e6, 1) == 43.5
  assert round(dense['attention'] / 1e6, 1) == 83.9


def test_mtp_head_and_totals(layers):
  assert glm_flops.forward_parts(layers[-2], SEQ) == {
      'mtp_projection': 2 * 4096 * 2048}
  # Two passes through the one head: the main one and the MTP module's.
  assert glm_flops.forward_parts(layers[-1], SEQ) == {
      'head': 2 * 2 * 2048 * 19360}
  parts = glm_flops.train_parts_per_token(layers, SEQ)
  total = sum(parts.values())
  # ISSUE 35's: 1,209 MFLOP forward a token; attention's scores 42% and
  # MLA as a whole 63% of the step, the two vocabulary passes 13%, the
  # routed experts 4%; 29.7 TFLOP a step of one sequence.
  assert round(total / 3e6) == 1209
  assert round(100 * parts['attention'] / total) == 42
  assert round(100 * (parts['attention'] + parts['mla_projections']) /
               total) == 63
  assert round(100 * parts['head'] / total) == 13
  assert round(100 * parts['routed_experts'] / total) == 4
  assert abs(glm_flops.train_flops_per_sequence(layers, SEQ) / 1e12 -
             29.7) < 0.05
  assert glm_flops.attention_train_flops_per_sequence(layers, SEQ) == (
      SEQ * parts['attention'])


@pytest.mark.parametrize('rows_per_token', [0.0, 0.25, 0.5, 2.0])
def test_routed_rows_as_counted(layers, rows_per_token):
  # The whole step's operations follow the rows the counters say were
  # routed, over the five expert layers: at half a row a token they are
  # the balanced expectation's.
  one_expert = 2 * 3 * 2048 * 1536
  balanced = glm_flops.train_flops_per_sequence(layers, SEQ)
  counted = glm_flops.train_flops_per_sequence(layers, SEQ, rows_per_token)
  assert counted == pytest.approx(
      balanced - SEQ * 5 * 3 * one_expert * (0.5 - rows_per_token),
      rel=1e-12)
