"""A token policy whose trunk is the afmoe decoder (arcee-ai Trinity).

What a token policy is (the one feature, the record path, the counters)
is ``token_model.TokenPolicyModel``'s; this file is the trunk's
constructor.

Every constructor argument is a key of the published ``config.json``
(https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json),
or says how that config was cut to this chip: ``layer_types`` lists the
layers run, ``experts_held`` the ids of the experts this chip holds of
the ``num_experts`` the router scores, ``vocab_size`` the slice of the
vocabulary held.
"""

from __future__ import annotations

from typing import Optional, Sequence

from tensor2robot_tpu.layers import afmoe
from tensor2robot_tpu.research.token_policy.token_model import (
    TokenPolicyModel)


class AfmoeTokenPolicyModel(TokenPolicyModel):
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
    super().__init__(sequence_length, learning_rate, **kwargs)
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

  def create_module(self):
    return afmoe.Trunk(dtype=self.compute_dtype, **self._trunk_kwargs)
