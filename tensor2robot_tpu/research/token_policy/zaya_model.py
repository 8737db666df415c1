"""A token policy whose trunk is the ZAYA decoder (Zyphra ZAYA1).

What a token policy is (the one feature, the record path, the counters)
is ``token_model.TokenPolicyModel``'s; this file is the trunk's
constructor.

Every constructor argument is a key of the published ``config.json``
(https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json), or says
how that config was cut to this chip (``num_hidden_layers`` the layers
run, every one ``hybrid``; ``experts_held`` the ids of the experts this
chip holds of the ``num_experts`` the router scores; ``vocab_size`` the
slice of the vocabulary held), or how the weights start
(``branch_scale_init``: the residual scaling's ``s_out``;
``router_init_gain`` over sqrt(fan_in): the router MLP's matrices;
``router_down_std``: the router's down-projection, ``init_std`` where
None).
"""

from __future__ import annotations

from typing import Optional, Sequence

from tensor2robot_tpu.layers import zaya
from tensor2robot_tpu.research.token_policy import token_model


class ZayaTokenPolicyModel(token_model.TokenPolicyModel):
  """See the module docstring."""

  # ``moe/top1_weight_e6``: the mean probability of the chosen expert in
  # millionths, summed over layers and steps like the rest: how decided
  # the router is.
  COUNTERS = token_model.COUNTERS + ('moe/top1_weight_e6',)

  def __init__(self,
               sequence_length: int = 8192,
               vocab_size: int = 262272,
               hidden_size: int = 2048,
               num_hidden_layers: int = 40,
               num_attention_heads: int = 8,
               num_key_value_heads: int = 2,
               head_dim: int = 128,
               cca_time0: int = 2,
               cca_time1: int = 2,
               partial_rotary_factor: float = 0.5,
               rope_theta: float = 5000000.0,
               moe_intermediate_size: int = 2048,
               router_hidden_size: int = 256,
               num_experts: int = 16,
               experts_held: Optional[Sequence[int]] = None,
               num_experts_per_tok: int = 1,
               rms_norm_eps: float = 1e-5,
               load_balance_coeff: float = 1e-3,
               learning_rate: float = 1e-4,
               loss_chunk: int = 2048,
               init_std: float = 0.02,
               branch_scale_init: float = 1.0,
               router_init_gain: float = 1.0,
               router_down_std: Optional[float] = None,
               **kwargs):
    super().__init__(sequence_length, learning_rate, **kwargs)
    if cca_time0 != cca_time1:
      raise ValueError('the two convolutions share one kernel size')
    self._trunk_kwargs = dict(
        vocab_size=int(vocab_size), hidden_size=int(hidden_size),
        num_layers=int(num_hidden_layers),
        num_heads=int(num_attention_heads),
        num_kv_heads=int(num_key_value_heads), head_dim=int(head_dim),
        conv_taps=int(cca_time0),
        rotary_dim=int(head_dim * partial_rotary_factor),
        rope_theta=float(rope_theta), eps=float(rms_norm_eps),
        router_hidden=int(router_hidden_size),
        expert_kwargs=dict(
            num_experts=int(num_experts),
            experts_held=(None if experts_held is None
                          else tuple(int(e) for e in experts_held)),
            experts_per_token=int(num_experts_per_tok),
            expert_width=int(moe_intermediate_size),
            load_balance_coeff=float(load_balance_coeff)),
        branch_scale=float(branch_scale_init),
        router_init_gain=float(router_init_gain),
        router_down_std=(None if router_down_std is None
                         else float(router_down_std)),
        loss_chunk=int(loss_chunk), init_std=float(init_std))

  def create_module(self):
    return zaya.Trunk(dtype=self.compute_dtype, **self._trunk_kwargs)
