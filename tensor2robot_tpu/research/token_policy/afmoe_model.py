"""A token policy whose trunk is the afmoe decoder (arcee-ai Trinity).

The model declares one feature, ``tokens``: a packed sequence of int64
ids as the record holds it. Its labels are the same tokens shifted by
one, so it declares none; the loss is behaviour cloning as next-token
prediction. Everything between the record shards and the step is the
framework's: ``NativeRecordInputGenerator`` parses the fixed-length
int64 feature, the trainer places it (the device holds it as int32: jax
narrows 64-bit integers on placement) and threads the expert layers'
non-gradient state (collection ``moe_state``: a bias and the last
step's count an expert) through the step as ``model_state``.

Every constructor argument is a key of the published ``config.json``
(https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json),
or says how that config was cut to this chip: ``layer_types`` lists the
layers run, ``experts_held`` the ids of the experts this chip holds of
the ``num_experts`` the router scores, ``vocab_size`` the slice of the
vocabulary held.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.layers import afmoe
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


class AfmoeTokenPolicyModel(base.FlaxModel):
  """See the module docstring."""

  def __init__(self,
               sequence_length: int = 8192,
               vocab_size: int = 200192,
               hidden_size: int = 2048,
               layer_types: Sequence[str] = (afmoe.SLIDING,) * 3 + (
                   afmoe.FULL,),
               num_dense_layers: int = 2,
               num_attention_heads: int = 32,
               num_key_value_heads: int = 4,
               head_dim: int = 128,
               intermediate_size: int = 6144,
               moe_intermediate_size: int = 1024,
               num_experts: int = 128,
               experts_held: Optional[Sequence[int]] = None,
               num_experts_per_tok: int = 8,
               sliding_window: int = 2048,
               rope_theta: float = 10000.0,
               rms_norm_eps: float = 1e-5,
               route_norm: bool = True,
               route_scale: float = 2.826,
               load_balance_coeff: float = 1e-3,
               mup_enabled: bool = True,
               learning_rate: float = 1e-4,
               loss_chunk: int = 2048,
               init_std: float = 0.02,
               **kwargs):
    kwargs.setdefault('create_optimizer_fn',
                      lambda: optimizers.create_adam_optimizer(learning_rate))
    super().__init__(**kwargs)
    self._sequence_length = int(sequence_length)
    self._trunk_kwargs = dict(
        vocab_size=int(vocab_size), hidden_size=int(hidden_size),
        layer_types=tuple(layer_types),
        num_dense_layers=int(num_dense_layers),
        num_heads=int(num_attention_heads),
        num_kv_heads=int(num_key_value_heads), head_dim=int(head_dim),
        sliding_window=int(sliding_window), rope_theta=float(rope_theta),
        eps=float(rms_norm_eps), dense_width=int(intermediate_size),
        expert_kwargs=dict(
            num_experts=int(num_experts),
            experts_held=(None if experts_held is None
                          else tuple(int(e) for e in experts_held)),
            experts_per_token=int(num_experts_per_tok),
            expert_width=int(moe_intermediate_size),
            route_norm=bool(route_norm), route_scale=float(route_scale),
            load_balance_coeff=float(load_balance_coeff)),
        mup_enabled=bool(mup_enabled), loss_chunk=int(loss_chunk),
        init_std=float(init_std))
    self._jitted_init = jax.jit(self._init)

  @property
  def default_preprocessor_cls(self):
    return _TokensFromRecords

  def create_module(self):
    return afmoe.Trunk(dtype=self.compute_dtype, **self._trunk_kwargs)

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
    return COUNTERS

  def model_train_fn(self, features, labels, inference_outputs, mode):
    del features, labels, mode
    scalars = {name: inference_outputs[name] for name in COUNTERS
               if name in inference_outputs}
    return inference_outputs['loss'], scalars

  def create_export_outputs_fn(self, features, inference_outputs):
    del features
    out = SpecStruct()
    out['next_token_logits'] = inference_outputs['next_token_logits']
    return out
