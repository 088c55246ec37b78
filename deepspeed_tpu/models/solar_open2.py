"""Solar Open 2 (Upstage, ``model_type: solar_open2``) — HF ``config.json``
keys to :class:`SolarOpen2Model`.

The block: pre-RMSNorm, a MIXER, a residual add; pre-RMSNorm, a routed expert
layer, a residual add.  By the layer's index the mixer is either

* gated delta-rule LINEAR attention (Kimi Delta Attention, arXiv:2510.26692;
  every layer not in ``gqa_layers``): ``q``, ``k``, ``v`` each a projection,
  a causal depthwise convolution of ``short_conv_kernel_size`` taps and a
  SiLU; ``q`` and ``k`` L2-normalised a head (``q`` times ``d^-1/2``); a
  log-decay a head AND key channel ``g = -exp(A_log) softplus((h Wf_a) Wf_b
  + dt_bias)``; a step size ``beta = sigmoid(h Wb)``, doubled under
  ``kda_allow_neg_eigval``; the recurrence of
  ``ops/transformer/delta_attention.py`` on a float32 state ``[heads, d, d]``;
  an RMSNorm over each head of the output times ``sigmoid((h Wg_a) Wg_b)``,
  then ``o_proj`` — or
* grouped-query softmax attention with NO positional encoding
  (``use_rope: false``) and, under ``use_gqa_gate``, the heads' outputs gated
  elementwise by ``sigmoid(h W_gate)`` before ``o_proj``.

Every layer's FFN is the routed expert layer: float32 sigmoid scores, the
top ``num_experts_per_tok`` of score + a stored bias, gates the chosen
scores over their sum (``norm_topk_prob``) times ``routed_scaling_factor``,
plus ``n_shared_experts`` shared experts.  ``held_experts=(first, count)``
gives the model one chip's share of each expert layer (``moe/layer.py``);
the router keeps its published width.  Final RMSNorm, untied head.

THREE kinds of cache in the slot engine's one manager
(``paging.SlotPages``): the softmax layers' K/V rows in LANE pages, and TWO
state kinds this family declares to the skeleton it is built on
(``models/hybrid.py``: the layer, the serving methods and the slot contract)
— ``conv``, the last ``taps - 1`` rows of the ``[q | k | v]`` projections in
the cache's dtype, as whole tiles under the row's index
(``ops/transformer/short_conv.py::rows_shape``), and ``kda``, the delta-rule
state, FLOAT32 whatever dtype the server passes.
"""

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.hybrid import (Attention, Hybrid, HybridModel,
                                         StateKind)
from deepspeed_tpu.models.parts import _rms

L2_EPS = 1e-6                # under the square root of a head's q / k norm


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int
    hidden_size: int
    num_layers: int
    gqa_layers: Tuple[int, ...]
    num_heads: int
    num_kv_heads: int
    head_dim: int
    kda_heads: int
    kda_head_dim: int
    conv_size: int
    kda_rank: int                # the decay's and the gate's low rank
    allow_neg_eigval: bool
    gqa_gate: bool
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    moe_top_k: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    max_seq_len: int
    rms_norm_eps: float = 1e-5
    held_experts: Optional[Tuple[int, int]] = None
    dtype: str = "bfloat16"

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def kda_layers(self):
        return tuple(i for i in range(self.num_layers)
                     if i not in self.gqa_layers)

    @property
    def kda_width(self):
        return self.kda_heads * self.kda_head_dim


def solar_open2_config(hf, held_experts=None, **overrides):
    """``hf``: a dict of HF ``config.json`` keys."""
    linear = hf["linear_attn_config"]
    if hf.get("rope_scaling") is not None:
        raise ValueError("rope scaling is not implemented")
    if hf.get("use_rope"):
        raise ValueError("use_rope: the softmax layers are built without "
                         "positional encoding (NoPE) only")
    if hf.get("kda_use_full_proj"):
        raise ValueError("kda_use_full_proj: the decay and the output gate "
                         "are built in their low-rank form only")
    if linear.get("num_kv_heads") is not None:
        raise ValueError("linear_attn_config.num_kv_heads: the linear "
                         "layers' k and v have one head a q head only")
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("a grouped router is not implemented")
    if hf.get("first_k_dense_replace", 0) or hf.get("tie_word_embeddings"):
        raise ValueError("solar_open2 as released routes experts in every "
                         "layer and has an untied head")
    layers = hf["num_hidden_layers"]
    gqa = tuple(i for i in hf["gqa_layers"] if i < layers)
    if hf["num_attention_heads"] % hf["num_key_value_heads"]:
        raise ValueError("KV heads must divide the heads")
    base = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=layers, gqa_layers=gqa,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        conv_size=linear["short_conv_kernel_size"],
        kda_rank=linear["head_dim"],
        allow_neg_eigval=bool(hf.get("kda_allow_neg_eigval", False)),
        gqa_gate=bool(hf.get("use_gqa_gate", False)),
        moe_intermediate_size=hf["moe_intermediate_size"],
        n_routed_experts=hf.get("n_routed_experts_published",
                                hf["n_routed_experts"]),
        n_shared_experts=hf["n_shared_experts"],
        moe_top_k=hf["num_experts_per_tok"],
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        max_seq_len=hf["max_position_embeddings"],
        rms_norm_eps=hf["rms_norm_eps"],
        held_experts=tuple(held_experts) if held_experts else None)
    base.update(overrides)
    return SolarOpen2Config(**base)


def solar_open2_model(hf, held_experts=None, **overrides):
    overrides.pop("scan_layers", None)       # the layers differ: unrolled
    return SolarOpen2Model(solar_open2_config(hf, held_experts, **overrides))


class KimiDeltaAttention(nn.Module):
    """The gated delta-rule mixer, a state mixer of ``models/hybrid.py``
    over two pools: ``conv [KDA layers, rows, ...short_conv.rows_shape]`` and
    ``kda [KDA layers, rows, heads, d, d]``."""
    config: SolarOpen2Config

    @nn.compact
    def __call__(self, u, state=None, start=None, last=None, live=None):
        """``u [T, hidden]``.  Returns ``(out, (conv pool, kda pool))``."""
        from deepspeed_tpu.ops.transformer.registry import (
            conv_state_update, delta_state_update)
        cfg = self.config
        H, D, K, W = cfg.kda_heads, cfg.kda_head_dim, cfg.conv_size, \
            cfg.kda_width
        f32 = jnp.float32
        dense = lambda n, name: nn.Dense(n, use_bias=False,
                                         dtype=cfg.jnp_dtype, name=name)
        taps = lambda name: self.param(name, nn.initializers.lecun_normal(),
                                       (K, W), f32)
        with jax.named_scope("attn.kda"):
            z = jnp.concatenate([dense(W, n + "_proj")(u) for n in "qkv"], -1)
            w = jnp.concatenate([taps(n + "_conv1d") for n in "qkv"], -1)
            conv_pool = kda_pool = at = rows = None
            if state is not None:
                conv_pool, kda_pool, at, rows = state
            with jax.named_scope("conv.short"):
                conv, conv_pool = conv_state_update(
                    z, w,
                    None if state is None else (conv_pool, at, rows),
                    start=start, last=last)
                q, k, v = jnp.split(nn.silu(conv), 3, axis=-1)
            decay = dense(W, "f_b_proj")(dense(cfg.kda_rank, "f_a_proj")(u))
            step = dense(H, "b_proj")(u)
            a_log = self.param("A_log", nn.initializers.zeros, (H,), f32)
            dt_bias = self.param("dt_bias", nn.initializers.zeros, (W,), f32)
            with jax.named_scope("kda.scan"):
                heads = lambda x: x.reshape(-1, H, D)
                unit = lambda x: x * jax.lax.rsqrt(
                    jnp.sum(x * x, -1, keepdims=True) + L2_EPS)
                q = (unit(heads(q)) * D ** -0.5).astype(cfg.jnp_dtype)
                k = unit(heads(k)).astype(cfg.jnp_dtype)
                v = heads(v).astype(cfg.jnp_dtype)
                g = -jnp.exp(a_log.astype(f32))[:, None] * heads(
                    jax.nn.softplus(decay.astype(f32) + dt_bias.astype(f32)))
                beta = jax.nn.sigmoid(step.astype(f32)) \
                    * (2.0 if cfg.allow_neg_eigval else 1.0)
                if state is None:
                    kda_pool, at, rows = jnp.zeros((1, 1, H, D, D), f32), 0, 0
                    start = 0
                out, kda_pool = delta_state_update(
                    q, k, v, g, beta, (kda_pool, at, rows), start=start,
                    real=None if last is None else last + 1, live=live)
            gate = dense(W, "g_b_proj")(dense(cfg.kda_rank, "g_a_proj")(u))
            gain = self.param("o_norm", nn.initializers.ones, (D,), f32)
            with jax.named_scope("kda.out_gate"):
                out = _rms(out, gain, cfg.rms_norm_eps).reshape(-1, W) \
                    * jax.nn.sigmoid(gate.astype(f32)).astype(out.dtype)
            # no state given: nothing is kept (the one-row pool was scratch)
            return dense(cfg.hidden_size, "o_proj")(out), (
                conv_pool, kda_pool if state is not None else None)


class SolarOpen2Model(HybridModel):

    @staticmethod
    def declare(cfg):
        from deepspeed_tpu.ops.transformer.short_conv import rows_shape
        return Hybrid(
            norm_eps=cfg.rms_norm_eps, attention_layers=cfg.gqa_layers,
            attention=Attention(
                cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                cfg.head_dim, cfg.jnp_dtype, out_gate=cfg.gqa_gate),
            mixer=("linear_attn", KimiDeltaAttention),
            state=(StateKind("conv", lambda dtype: rows_shape(
                       cfg.conv_size, 3 * cfg.kda_width, dtype)),
                   StateKind("kda", (cfg.kda_heads, cfg.kda_head_dim,
                                     cfg.kda_head_dim), jnp.float32)),
            work="kda", moe=dict(
                num_experts=cfg.n_routed_experts, k=cfg.moe_top_k,
                norm_topk_prob=cfg.norm_topk_prob,
                ffn_hidden_size=cfg.moe_intermediate_size, scoring="sigmoid",
                routed_scaling=cfg.routed_scaling_factor,
                shared_ffn_hidden_size=cfg.n_shared_experts
                * cfg.moe_intermediate_size,
                held_experts=cfg.held_experts))
