"""The FLOP count against a hand count of one conv layer of each model."""

import json
import os

from benchmark.lib import flops
from benchmark.reference import grasp2vec_resnet50, qtopt_grasping44

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
  with open(os.path.join(HERE, 'configs', name + '.json')) as f:
    return json.load(f)


def test_grasping44_conv2_by_hand():
  # conv2: 5x5, 64 -> 64 channels, SAME, on the 79x79 map that the 6x6/2
  # conv (472 -> 236) and the 3x3/3 pool (236 -> 79) leave.
  layers = {l['name']: l for l in
            qtopt_grasping44.layers(_config('qtopt-grasping44'))}
  forward = 2 * 79 * 79 * 5 * 5 * 64 * 64
  assert flops.layer_forward_flops(layers['conv2']) == forward
  assert flops.layer_train_flops(layers['conv2']) == 3 * forward
  # The first conv needs no gradient for the image: forward + weights.
  first = 2 * 236 * 236 * 6 * 6 * 3 * 64
  assert flops.layer_train_flops(layers['conv1_1']) == 2 * first


def test_resnet50_stage2_conv2_by_hand():
  # Scene tower, first block of stage 2: 3x3/2, 128 -> 128 channels, from
  # the 118x118 map (472 -> 236 -> 118) to 59x59; two frames an example.
  layers = {l['name']: l for l in
            grasp2vec_resnet50.layers(_config('grasp2vec-resnet50'))}
  forward = 2 * 59 * 59 * 3 * 3 * 128 * 128 * 2
  layer = layers['scene/block_layer2_block0/conv2']
  assert flops.layer_forward_flops(layer) == forward
  goal = layers['goal/block_layer2_block0/conv2']
  assert flops.layer_forward_flops(goal) == forward / 2


def test_totals_are_the_published_orders():
  # ResNet-50 at 224x224 is about 4.1 GFLOP (multiply-adds) forward; at
  # 472x472 the towers scale by (472/224)^2, three frames an example.
  total = flops.train_flops_per_example(
      grasp2vec_resnet50.layers(_config('grasp2vec-resnet50')))
  per_frame_forward_macs = total / 3 / 3 / 2
  assert 0.9 < per_frame_forward_macs / (4.1e9 * (472 / 224) ** 2) < 1.1
