"""Mock model + input generator: the test pyramid's foundation.

Re-design of ``/root/reference/utils/mocks.py:38-241``: ``MockT2RModel`` is
a 3-layer MLP with batch norm classifying linearly-separable 2-D points
produced by ``MockInputGenerator``. Training it end-to-end exercises specs,
preprocessing, the jitted step, checkpointing, eval, and export without any
robot dependency.
"""

from __future__ import annotations


import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.data.input_generators import AbstractInputGenerator
from tensor2robot_tpu.models.base import DEVICE_TYPE_TPU
from tensor2robot_tpu.models.classification_model import ClassificationModel
from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.specs import SpecStruct, TensorSpec


class _MockMLP(nn.Module):
  """3-layer MLP + batch norm (mocks.py:38-77)."""

  hidden_size: int = 16

  @nn.compact
  def __call__(self, features, train: bool = False):
    x = features['measured_position'].astype(jnp.float32)
    x = nn.Dense(self.hidden_size)(x)
    x = nn.BatchNorm(use_running_average=not train, momentum=0.9)(x)
    x = nn.relu(x)
    x = nn.Dense(self.hidden_size)(x)
    x = nn.relu(x)
    logits = nn.Dense(1)(x)
    return {'a_predicted': jnp.squeeze(logits, axis=-1)}


class MockT2RModel(ClassificationModel):
  """Binary classifier over 2-D points; the universal smoke-test model.

  ``hidden_size`` scales the MLP: the default 16 keeps train-path tests
  fast; the serving bench uses ~2048 — at that width a batch-1 predict
  is dominated by weight-streaming/dispatch, so a batch-64 dispatch
  costs about the same as batch-1 (the economics cross-client batching
  exploits).
  """

  def __init__(self,
               device_type: str = DEVICE_TYPE_TPU,
               multi_dataset: bool = False,
               hidden_size: int = 16,
               **kwargs):
    super().__init__(device_type=device_type, **kwargs)
    self._multi_dataset = multi_dataset
    self._hidden_size = hidden_size

  def create_module(self):
    return _MockMLP(hidden_size=self._hidden_size)

  def get_feature_specification(self, mode: str) -> SpecStruct:
    del mode
    spec = SpecStruct()
    if self._multi_dataset:
      # Same tensor name routed from two datasets (mocks.py:120-151).
      spec['x1/measured_position'] = TensorSpec(
          shape=(2,), dtype=np.float32, name='measured_position',
          dataset_key='dataset1')
      spec['x2/measured_position'] = TensorSpec(
          shape=(2,), dtype=np.float32, name='measured_position',
          dataset_key='dataset2')
    else:
      spec['measured_position'] = TensorSpec(
          shape=(2,), dtype=np.float32, name='measured_position')
    return spec

  def get_label_specification(self, mode: str) -> SpecStruct:
    del mode
    spec = SpecStruct()
    spec['valid_position'] = TensorSpec(
        shape=(), dtype=np.float32, name='valid_position')
    return spec


class MockInputGenerator(AbstractInputGenerator):
  """Linearly-separable 2-D data: label = x0 + x1 > 0 (mocks.py:154-186)."""

  def _create_iterator(self, mode, batch_size):
    rng = np.random.RandomState(0 if mode == ModeKeys.TRAIN else 1)

    def gen():
      while True:
        points = rng.uniform(-1.0, 1.0, size=(batch_size, 2)).astype(
            np.float32)
        labels = (points.sum(axis=1) > 0).astype(np.float32)
        features = SpecStruct()
        features['measured_position'] = points
        packed_labels = SpecStruct()
        packed_labels['valid_position'] = labels
        yield features, packed_labels

    return gen()


class MockRealisticInputGenerator(MockInputGenerator):
  """Alias kept for reference-name parity."""
