"""What the token policies share: one feature, ``tokens``, a packed
sequence of int64 ids as the record holds it; labels are the same tokens
shifted by one, so none are declared, and the loss is behaviour cloning
as next-token prediction, computed by the trunk itself. Everything
between the record shards and the step is the framework's:
``NativeRecordInputGenerator`` parses the fixed-length int64 feature,
the trainer places it (the device holds it as int32: jax narrows 64-bit
integers on placement) and threads the expert layers' non-gradient state
(collection ``moe_state``: a bias and the last step's count an expert)
through the step as ``model_state``.

A policy is a subclass that builds its trunk (``create_module``; the
trunk returns ``loss``, ``next_token_logits`` and the ``moe/*`` counts)
and names the counts it reports (``COUNTERS``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.modes import ModeKeys
from tensor2robot_tpu.models import base, optimizers
from tensor2robot_tpu.preprocessors.base import SpecTransformationPreprocessor
from tensor2robot_tpu.specs import SpecStruct, TensorSpec

# The expert layers' counts that ``model_train_fn`` reports as scalars;
# the trainer adds them to counters of the same names at the dispatch
# boundary, one dispatch behind. ``moe/rows_max_expert`` is the step's
# fullest held expert (the largest over the layers), summed over steps
# like the rest: over ``trainer/dispatches`` it is the mean.
# ``moe/rows_room`` is the rows of the routed-row buffer's rung that each
# expert layer took: over ``moe/tokens`` x min(k, held) it is the share
# of the worst case that was moved (1.0: the ladder never engaged).
COUNTERS = ('moe/tokens', 'moe/rows_routed', 'moe/rows_computed',
            'moe/rows_max_expert', 'moe/rows_dropped', 'moe/rows_room')


class _TokensFromRecords(SpecTransformationPreprocessor):
  """The record's int64 ids arrive; the model consumes int32."""

  def _transform_in_feature_specification(self, spec, mode):
    del mode
    self.update_spec(spec, 'tokens', dtype=np.int64)
    return spec

  def _preprocess_fn(self, features, labels, mode, rng):
    del mode, rng
    features['tokens'] = features['tokens'].astype(jnp.int32)
    return features, labels


class TokenPolicyModel(base.FlaxModel):
  """See the module docstring."""

  COUNTERS = COUNTERS

  def __init__(self, sequence_length: int, learning_rate: float, **kwargs):
    kwargs.setdefault('create_optimizer_fn',
                      lambda: optimizers.create_adam_optimizer(learning_rate))
    super().__init__(**kwargs)
    self._sequence_length = int(sequence_length)
    self._jitted_init = jax.jit(self._init)

  @property
  def default_preprocessor_cls(self):
    return _TokensFromRecords

  def init_variables(self, rng, features, mode=ModeKeys.TRAIN):
    # Under jit the forward pass that flax's init runs is dead code and
    # only the parameters are made: eager, it would run a whole sequence
    # through the trunk op by op before the first step.
    features, _ = self.validated_features(features, mode)
    return self._jitted_init(self._make_rngs(rng, include_params=True),
                             features)

  def _init(self, rngs, features):
    return self.module.init(rngs, features, train=False)

  def get_feature_specification(self, mode: str) -> SpecStruct:
    del mode
    spec = SpecStruct()
    spec['tokens'] = TensorSpec(shape=(self._sequence_length,),
                                dtype=np.int32, name='tokens')
    return spec

  def get_label_specification(self, mode: str):
    del mode
    return None

  @property
  def counter_scalars(self):
    return self.COUNTERS

  def model_train_fn(self, features, labels, inference_outputs, mode):
    del features, labels, mode
    scalars = {name: inference_outputs[name] for name in self.COUNTERS
               if name in inference_outputs}
    return inference_outputs['loss'], scalars

  def create_export_outputs_fn(self, features, inference_outputs):
    del features
    out = SpecStruct()
    out['next_token_logits'] = inference_outputs['next_token_logits']
    return out
