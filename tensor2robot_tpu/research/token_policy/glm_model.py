"""A token policy whose trunk is the glm4_moe_lite decoder (zai-org
GLM-4.7-Flash): latent attention, a dense first layer, sigmoid-routed
experts with a shared one, and a multi-token-prediction module whose
loss is added to the next-token loss.

What a token policy is (the one feature, the record path, the counters)
is ``token_model.TokenPolicyModel``'s; this file is the trunk's
constructor.

Every constructor argument is a key of the published ``config.json``
(https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json),
or says how that config was cut to this chip (``num_hidden_layers`` the
layers run, the first ``first_k_dense_replace`` of them dense;
``experts_held`` the ids of the experts this chip holds of the
``n_routed_experts`` the router scores; ``vocab_size`` the slice of the
vocabulary held), or is a size the config does not give
(``mtp_loss_weight``, ``load_balance_coeff``, the optimizer's rate, and
how the weights start: ``init_std``; ``embed_std``, the embedding's,
``init_std`` where None; ``latent_gain`` over sqrt(fan_in), the two
matrices that lead out of a latent, and ``router_std``, the routers',
``init_std`` where None).
"""

from __future__ import annotations

from typing import Optional, Sequence

from tensor2robot_tpu.layers import glm_moe_lite
from tensor2robot_tpu.research.token_policy import token_model


class GlmTokenPolicyModel(token_model.TokenPolicyModel):
  """See the module docstring."""

  # The two losses in millionths, summed over steps like the counts: over
  # ``trainer/dispatches`` (x steps a dispatch) they are the means.
  COUNTERS = token_model.COUNTERS + ('glm/loss_main_e6', 'glm/loss_mtp_e6')

  def __init__(self,
               sequence_length: int = 8192,
               vocab_size: int = 154880,
               hidden_size: int = 2048,
               num_hidden_layers: int = 47,
               first_k_dense_replace: int = 1,
               num_attention_heads: int = 20,
               q_lora_rank: int = 768,
               kv_lora_rank: int = 512,
               qk_nope_head_dim: int = 192,
               qk_rope_head_dim: int = 64,
               v_head_dim: int = 256,
               rope_theta: float = 1000000.0,
               intermediate_size: int = 10240,
               moe_intermediate_size: int = 1536,
               n_routed_experts: int = 64,
               experts_held: Optional[Sequence[int]] = None,
               num_experts_per_tok: int = 4,
               n_shared_experts: int = 1,
               norm_topk_prob: bool = True,
               routed_scaling_factor: float = 1.8,
               num_nextn_predict_layers: int = 1,
               rms_norm_eps: float = 1e-5,
               mtp_loss_weight: float = 0.3,
               load_balance_coeff: float = 1e-3,
               learning_rate: float = 1e-4,
               loss_chunk: int = 2048,
               init_std: float = 0.02,
               embed_std: Optional[float] = None,
               latent_gain: Optional[float] = None,
               router_std: Optional[float] = None,
               **kwargs):
    super().__init__(sequence_length, learning_rate, **kwargs)
    if n_shared_experts not in (0, 1):
      raise ValueError('the expert layer has one shared expert or none')
    self._trunk_kwargs = dict(
        vocab_size=int(vocab_size), hidden_size=int(hidden_size),
        num_layers=int(num_hidden_layers),
        num_dense_layers=int(first_k_dense_replace),
        attn_kwargs=dict(
            num_heads=int(num_attention_heads), q_rank=int(q_lora_rank),
            kv_rank=int(kv_lora_rank), nope_dim=int(qk_nope_head_dim),
            rope_dim=int(qk_rope_head_dim), v_dim=int(v_head_dim),
            rope_theta=float(rope_theta),
            latent_gain=None if latent_gain is None else float(latent_gain)),
        eps=float(rms_norm_eps), dense_width=int(intermediate_size),
        expert_kwargs=dict(
            num_experts=int(n_routed_experts),
            experts_held=(None if experts_held is None
                          else tuple(int(e) for e in experts_held)),
            experts_per_token=int(num_experts_per_tok),
            expert_width=int(moe_intermediate_size),
            route_norm=bool(norm_topk_prob),
            route_scale=float(routed_scaling_factor),
            load_balance_coeff=float(load_balance_coeff),
            shared_expert=bool(n_shared_experts),
            router_std=None if router_std is None else float(router_std)),
        num_mtp_modules=int(num_nextn_predict_layers),
        mtp_loss_weight=float(mtp_loss_weight), loss_chunk=int(loss_chunk),
        init_std=float(init_std),
        embed_std=None if embed_std is None else float(embed_std))

  def create_module(self):
    return glm_moe_lite.Trunk(dtype=self.compute_dtype, **self._trunk_kwargs)
