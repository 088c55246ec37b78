"""The decoder block that the latent-attention models share
(``models/dots3.py``, ``models/glm5.py``): pre-RMSNorm, latent attention
at one :class:`~deepspeed_tpu.models.latent_attention.LatentSpec`, then a
dense SwiGLU MLP or a routed expert layer — sigmoid scores, the top
``moe_top_k`` of score + a stored bias, gates the chosen scores over their
sum times ``routed_scaling_factor``, plus the shared experts
(``moe/layer.py``, whose ``held_experts`` is one chip's share).

What a block reads of its model's config: ``hidden_size``,
``rms_norm_eps``, ``jnp_dtype``, ``intermediate_size`` (dense) or
``n_routed_experts``, ``moe_top_k``, ``norm_topk_prob``,
``moe_intermediate_size``, ``routed_scaling_factor``, ``n_shared_experts``,
``held_experts`` (routed).
"""

from typing import Any

import flax.linen as nn

from deepspeed_tpu.models.latent_attention import (LatentAttention,
                                                   LatentSpec)
from deepspeed_tpu.models.parts import _Mlp, _Norm
from deepspeed_tpu.moe.layer import MoE


class LatentBlock(nn.Module):
    config: Any
    spec: LatentSpec
    dense: bool                  # a dense MLP, not the routed expert layer

    def setup(self):
        cfg = self.config
        self.attn = LatentAttention(self.spec, cfg.jnp_dtype)
        self.input_norm = _Norm(cfg.rms_norm_eps)
        self.post_attn_norm = _Norm(cfg.rms_norm_eps)
        if self.dense:
            self.mlp = _Mlp(cfg.intermediate_size, cfg.jnp_dtype)
        else:
            self.moe_mlp = MoE(
                hidden_size=cfg.hidden_size,
                num_experts=cfg.n_routed_experts, k=cfg.moe_top_k,
                capacity_factor=None, norm_topk_prob=cfg.norm_topk_prob,
                ffn_hidden_size=cfg.moe_intermediate_size,
                dtype=cfg.jnp_dtype, gated=True, activation=nn.silu,
                scoring="sigmoid", routed_scaling=cfg.routed_scaling_factor,
                shared_ffn_hidden_size=cfg.n_shared_experts
                * cfg.moe_intermediate_size,
                held_experts=cfg.held_experts)

    def __call__(self, x, attend, live=None):
        """``attend(attn, normed x) -> (out, pools)``: the call form the
        model chose (chunk or step) with this layer's cache."""
        a, pools = attend(self.attn, self.input_norm(x))
        x = x + a
        h = self.post_attn_norm(x)
        if self.dense:
            return x + self.mlp(h), pools
        y, _, _ = self.moe_mlp(h, train=False, live=live)
        return x + y, pools
