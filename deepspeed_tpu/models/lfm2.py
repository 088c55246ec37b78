"""LFM2 mixture-of-experts (LiquidAI, ``model_type: lfm2_moe``) — HF
``config.json`` keys to :class:`Lfm2Model`.

The block: pre-RMSNorm (``operator_norm``); by ``layer_types[i]`` either a
gated SHORT CONVOLUTION — ``[B | C | X] = u W_in``, ``z = B * X``, a causal
depthwise convolution of ``conv_L_cache`` taps over ``z``, ``(C * conv) W_out``
— or grouped-query attention with an RMSNorm over each HEAD of q and k
(one gain of ``head_dim`` each) before rope; then ``ffn_norm`` and a dense
SwiGLU in the first ``num_dense_layers`` layers, in the rest a routed
expert layer: float32 sigmoid scores, the top ``num_experts_per_tok`` of
score + ``expert_bias``, gates the chosen scores over their sum ``+ 1e-6``,
no shared expert.  The final RMSNorm (HF's ``embedding_norm``, applied to
the output) and a head tied to the embedding.

A conv layer keeps no row a position.  What it hands from a token to the
next is a FIXED-SIZE state a slot: the last ``conv_L_cache - 1`` rows of
``z`` — as whole tiles under the row's index
(``ops/transformer/short_conv.py::rows_shape``) —, the one state kind
(``conv``) this family declares to the skeleton it is built on
(``models/hybrid.py``: the layer, the serving methods and the slot contract).
K/V pool layers exist for the ATTENTION layers only.
"""

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.hybrid import (Attention, Hybrid, HybridModel,
                                         StateKind)

GATE_SUM_EPS = 1e-6          # HF Lfm2MoeSparseMoeBlock's guard


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int
    hidden_size: int
    layer_types: Tuple[str, ...]
    num_heads: int
    num_kv_heads: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    moe_top_k: int
    num_dense_layers: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    conv_L_cache: int
    rope_theta: float
    max_seq_len: int
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    def layers_of(self, kind):
        return [i for i, t in enumerate(self.layer_types) if t == kind]


def lfm2_config(hf, **overrides):
    """``hf``: a dict of HF ``config.json`` keys."""
    rope = hf.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default" \
            or hf.get("rope_scaling") is not None:
        raise ValueError("rope scaling is not implemented")
    if hf.get("conv_bias") or hf.get("attention_bias"):
        raise ValueError("lfm2_moe as released has no biases")
    kinds = tuple(hf["layer_types"])[:hf["num_hidden_layers"]]
    if len(kinds) != hf["num_hidden_layers"] \
            or set(kinds) - {"conv", "full_attention"}:
        raise ValueError(f"layer_types {kinds!r}")
    if hf["hidden_size"] % hf["num_attention_heads"] \
            or hf["num_attention_heads"] % hf["num_key_value_heads"]:
        raise ValueError("heads must divide the hidden size, KV heads the "
                         "heads")
    base = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        layer_types=kinds, num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_experts=hf["num_experts"], moe_top_k=hf["num_experts_per_tok"],
        num_dense_layers=hf["num_dense_layers"],
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        conv_L_cache=hf["conv_L_cache"],
        rope_theta=float(rope.get("rope_theta", hf.get("rope_theta", 1e6))),
        max_seq_len=hf["max_position_embeddings"],
        norm_eps=hf["norm_eps"])
    base.update(overrides)
    return Lfm2Config(**base)


def lfm2_model(hf, **overrides):
    overrides.pop("scan_layers", None)       # the layers differ: unrolled
    return Lfm2Model(lfm2_config(hf, **overrides))


class ShortConv(nn.Module):
    """The gated short convolution, a state mixer of ``models/hybrid.py``
    over one pool, ``conv [conv layers, rows, ...short_conv.rows_shape]``."""
    config: Lfm2Config

    @nn.compact
    def __call__(self, u, state=None, start=None, last=None, live=None):
        """``u [T, hidden]``; a dead lane's step writes the trash row, so
        ``live`` is not read.  Returns ``(out, (pool,))``."""
        from deepspeed_tpu.ops.transformer.registry import conv_state_update
        cfg = self.config
        h, K = cfg.hidden_size, cfg.conv_L_cache
        dense = lambda n, name: nn.Dense(n, use_bias=False,
                                         dtype=cfg.jnp_dtype, name=name)
        w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                       (K, h), jnp.float32)       # tap j weighs z_{t-K+1+j}
        b, c, x = jnp.split(dense(3 * h, "in_proj")(u), 3, axis=-1)
        with jax.named_scope("conv.short"):
            conv, pool = conv_state_update(b * x, w, state, start=start,
                                           last=last)
            y = c * conv.astype(x.dtype)
        return dense(h, "out_proj")(y), (pool,)


class Lfm2Model(HybridModel):
    """Its head: HF's ``embedding_norm`` (applied to the OUTPUT), then the
    embedding."""

    @staticmethod
    def declare(cfg):
        from deepspeed_tpu.ops.transformer.short_conv import rows_shape
        return Hybrid(
            norms=("operator_norm", "ffn_norm"), final_norm="embedding_norm",
            norm_eps=cfg.norm_eps, tied=True,
            attention_layers=tuple(cfg.layers_of("full_attention")),
            attention=Attention(
                cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                cfg.head_dim, cfg.jnp_dtype, qk_norm_eps=cfg.norm_eps,
                rope_theta=cfg.rope_theta, out_proj="out_proj",
                out_by_head=True, scopes=False),
            mixer=("conv", ShortConv),
            state=(StateKind("conv", lambda dtype: rows_shape(
                cfg.conv_L_cache, cfg.hidden_size, dtype)),),
            dense=("feed_forward", cfg.intermediate_size,
                   cfg.num_dense_layers),
            moe=dict(
                num_experts=cfg.num_experts, k=cfg.moe_top_k,
                norm_topk_prob=cfg.norm_topk_prob,
                ffn_hidden_size=cfg.moe_intermediate_size, scoring="sigmoid",
                routed_scaling=cfg.routed_scaling_factor,
                gate_sum_eps=GATE_SUM_EPS))
