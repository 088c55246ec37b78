"""Granite 4.0-H (IBM, ``model_type: granitemoehybrid``) — HF ``config.json``
keys to :class:`GraniteHybridModel`.

The block: pre-RMSNorm, a MIXER, a residual add of ``residual_multiplier``
times its output; pre-RMSNorm, a routed expert layer beside a shared MLP,
the same add.  By ``layer_types[i]`` the mixer is either

* a Mamba-2 STATE-SPACE layer (arXiv:2405.21060; ``"mamba"``): ``[z | xBC |
  dt] = h W_in``; a causal depthwise convolution of ``mamba_d_conv`` taps
  with a bias over ALL of ``xBC`` (``x``, ``B`` and ``C`` one stream) and a
  SiLU; ``x`` in ``mamba_n_heads`` heads of ``mamba_d_head``, ``B`` and ``C``
  of ``mamba_d_state`` shared by every head (``mamba_n_groups`` 1); a step
  size a head ``dt = softplus(dt + dt_bias)``, a decay ``exp(dt A)`` with
  ``A = -exp(A_log)`` — one SCALAR a head and position —; the recurrence of
  ``ops/transformer/ssd.py`` on a float32 state ``[heads, d_head,
  d_state]`` plus the skip ``D x``; ``RMSNorm(y * SiLU(z))`` — the gate
  FIRST, then ONE norm over the whole inner width — and ``out_proj``; or
* grouped-query softmax attention (``"attention"``) with NO positional
  encoding (``position_embedding_type: "nope"``), scores times
  ``attention_multiplier`` (not ``head_dim ** -0.5``), no bias, no gate.

Every layer's FFN: float32 router logits over ``num_local_experts``, the
``num_experts_per_tok`` largest, gates a softmax over the CHOSEN logits —
which is a softmax over all of them, the top-k, renormalised: the scored
form of ``moe/layer.py`` (``scoring="softmax"``, ``noaux_tc``) at a selection
bias of zeros —, plus a shared SwiGLU of ``shared_intermediate_size``.
``held_experts=(first, count)`` gives the model one chip's share of each
expert layer; the router keeps its published width.  The embedding times
``embedding_multiplier``; a final RMSNorm and the TIED head, its logits over
``logits_scaling``.

THREE kinds of cache in the slot engine's one manager
(``paging.SlotPages``): the attention layers' K/V rows in LANE pages, and
TWO state kinds this family declares to the skeleton it is built on
(``models/hybrid.py``: the layer, the serving methods and the slot contract)
— ``conv``, the last ``taps - 1`` rows of ``xBC`` in the cache's dtype, as
whole tiles under the row's index (``ops/transformer/short_conv.py::
rows_shape``: 198 x 128 values on 208 sublanes at the published widths), and
``ssm``, the heads' ``[d_head, d_state]`` states as
``ops/transformer/ssd.py::state_shape`` lays them, FLOAT32 whatever dtype the
server passes (4 MiB a layer at 128 heads of 64 x 128: nine to one over the
K/V, and at many slots larger than the weights).
"""

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.hybrid import (Attention, Hybrid, HybridModel,
                                         StateKind)
from deepspeed_tpu.models.parts import _rms


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int
    hidden_size: int
    layer_types: Tuple[str, ...]
    num_heads: int
    num_kv_heads: int
    attention_scale: float       # the scores' multiplier, not head_dim ** -0.5
    mamba_heads: int
    mamba_head_dim: int
    mamba_state: int
    conv_size: int
    intermediate_size: int       # one expert's width
    shared_intermediate_size: int
    num_experts: int             # the router's width
    moe_top_k: int
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    max_seq_len: int
    rms_norm_eps: float = 1e-5
    held_experts: Optional[Tuple[int, int]] = None
    dtype: str = "bfloat16"
    mamba_groups = 1             # one B and one C for every head

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    def layers_of(self, kind):
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    @property
    def mamba_width(self):
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self):
        """``x``, ``B`` and ``C``: the one stream the convolution runs."""
        return self.mamba_width + 2 * self.mamba_state


def granite_hybrid_config(hf, held_experts=None, **overrides):
    """``hf``: a dict of HF ``config.json`` keys."""
    if hf.get("rope_scaling") is not None:
        raise ValueError("rope scaling is not implemented")
    if hf.get("position_embedding_type", "nope") != "nope":
        raise ValueError("position_embedding_type: the attention layers are "
                         "built without positional encoding (\"nope\") only")
    if hf.get("mamba_n_groups", 1) != 1:
        raise ValueError("mamba_n_groups: one B and one C for every head "
                         "(one group) only")
    if hf.get("mamba_proj_bias") or hf.get("attention_bias"):
        raise ValueError("mamba_proj_bias / attention_bias: the projections "
                         "are built without biases only")
    if not hf.get("mamba_conv_bias", True):
        raise ValueError("mamba_conv_bias: the convolution is built with "
                         "its bias only")
    if hf.get("normalization_function", "rmsnorm") != "rmsnorm" \
            or hf.get("hidden_act", "silu") != "silu":
        raise ValueError("granitemoehybrid as released: RMSNorm and SiLU")
    if not hf.get("tie_word_embeddings", True):
        raise ValueError("granitemoehybrid as released has a tied head")
    kinds = tuple(hf["layer_types"])[:hf["num_hidden_layers"]]
    if len(kinds) != hf["num_hidden_layers"] \
            or set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {kinds!r}")
    if hf["hidden_size"] % hf["num_attention_heads"] \
            or hf["num_attention_heads"] % hf["num_key_value_heads"]:
        raise ValueError("heads must divide the hidden size, KV heads the "
                         "heads")
    if hf["mamba_n_heads"] * hf["mamba_d_head"] \
            != hf["mamba_expand"] * hf["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x "
                         "hidden_size")
    base = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        layer_types=kinds, num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        attention_scale=float(hf["attention_multiplier"]),
        mamba_heads=hf["mamba_n_heads"], mamba_head_dim=hf["mamba_d_head"],
        mamba_state=hf["mamba_d_state"], conv_size=hf["mamba_d_conv"],
        intermediate_size=hf["intermediate_size"],
        shared_intermediate_size=hf["shared_intermediate_size"],
        num_experts=hf.get("num_local_experts_published",
                           hf["num_local_experts"]),
        moe_top_k=hf["num_experts_per_tok"],
        embedding_multiplier=float(hf["embedding_multiplier"]),
        residual_multiplier=float(hf["residual_multiplier"]),
        logits_scaling=float(hf["logits_scaling"]),
        max_seq_len=hf["max_position_embeddings"],
        rms_norm_eps=hf["rms_norm_eps"],
        held_experts=tuple(held_experts) if held_experts else None)
    base.update(overrides)
    return GraniteHybridConfig(**base)


def granite_hybrid_model(hf, held_experts=None, **overrides):
    overrides.pop("scan_layers", None)       # the layers differ: unrolled
    return GraniteHybridModel(
        granite_hybrid_config(hf, held_experts, **overrides))


class Mamba2Mixer(nn.Module):
    """The state-space mixer, a state mixer of ``models/hybrid.py`` over
    two pools: ``conv [SSM layers, rows, ...short_conv.rows_shape]`` and
    ``ssm [SSM layers, rows, ...ssd.state_shape]``.  Also
    ``models/nemotron_h.py``'s, whose config says ``mamba_groups`` 8: ``B``
    and ``C`` a GROUP of heads, and the gate-norm over each group's
    channels separately (one group is the whole width)."""
    config: Any                  # the fields of GraniteHybridConfig read here

    @nn.compact
    def __call__(self, u, state=None, start=None, last=None, live=None):
        """``u [T, hidden]``.  Returns ``(out, (conv pool, ssm pool))``."""
        from deepspeed_tpu.ops.transformer.registry import (
            conv_state_update, ssm_state_update)
        from deepspeed_tpu.ops.transformer.ssd import state_shape
        cfg = self.config
        H, P, N, K = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state, \
            cfg.conv_size
        W, CW, G = cfg.mamba_width, cfg.conv_width, cfg.mamba_groups
        f32 = jnp.float32
        dense = lambda n, name: nn.Dense(n, use_bias=False,
                                         dtype=cfg.jnp_dtype, name=name)
        vector = lambda name, n, init=nn.initializers.zeros: self.param(
            name, init, (n,), f32)
        with jax.named_scope("attn.ssd"):
            z, xbc, dt = jnp.split(dense(W + CW + H, "in_proj")(u),
                                   [W, W + CW], axis=-1)
            w = self.param("conv1d", nn.initializers.lecun_normal(), (K, CW),
                           f32)
            conv_bias = vector("conv1d_bias", CW)
            conv_pool = ssm_pool = at = rows = None
            if state is not None:
                conv_pool, ssm_pool, at, rows = state
            with jax.named_scope("conv.short"):
                conv, conv_pool = conv_state_update(
                    xbc, w,
                    None if state is None else (conv_pool, at, rows),
                    start=start, last=last)
                xbc = nn.silu(conv + conv_bias).astype(cfg.jnp_dtype)
            dt_bias, a_log = vector("dt_bias", H), vector("A_log", H)
            skip = vector("D", H, nn.initializers.ones)
            with jax.named_scope("ssd.scan"):
                x, b, c = jnp.split(xbc, [W, W + G * N], axis=-1)
                x = x.reshape(-1, H, P)
                if G > 1:
                    b, c = b.reshape(-1, G, N), c.reshape(-1, G, N)
                step = jax.nn.softplus(dt.astype(f32) + dt_bias)
                decay = -jnp.exp(a_log) * step            # log, at most 0
                if state is None:
                    ssm_pool, at, rows = jnp.zeros(
                        (1, 1) + state_shape(H, P, N), f32), 0, 0
                    start = 0
                y, ssm_pool = ssm_state_update(
                    x, step, decay, b, c, (ssm_pool, at, rows), start=start,
                    real=None if last is None else last + 1, live=live)
                y = (y + skip[:, None] * x.astype(f32)) \
                    .astype(cfg.jnp_dtype).reshape(-1, W)
            gain = vector("norm", W, nn.initializers.ones)
            with jax.named_scope("ssd.gate_norm"):
                y = (y.astype(f32) * nn.silu(z.astype(f32))) \
                    .astype(cfg.jnp_dtype)
                if G > 1:                # the mean of squares a group
                    y = _rms(y.reshape(-1, G, W // G),
                             gain.reshape(G, W // G),
                             cfg.rms_norm_eps).reshape(-1, W)
                else:
                    y = _rms(y, gain, cfg.rms_norm_eps)
            # no state given: nothing is kept (the one-row pool was scratch)
            return dense(cfg.hidden_size, "out_proj")(y), (
                conv_pool, ssm_pool if state is not None else None)


class GraniteHybridModel(HybridModel):

    @staticmethod
    def declare(cfg):
        from deepspeed_tpu.ops.transformer.short_conv import rows_shape
        from deepspeed_tpu.ops.transformer.ssd import state_shape
        return Hybrid(
            norm_eps=cfg.rms_norm_eps, tied=True,
            attention_layers=cfg.layers_of("attention"),
            attention=Attention(
                cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                cfg.head_dim, cfg.jnp_dtype,
                attention_scale=cfg.attention_scale),
            mixer=("mamba", Mamba2Mixer),
            state=(StateKind("conv", lambda dtype: rows_shape(
                       cfg.conv_size, cfg.conv_width, dtype)),
                   StateKind("ssm", state_shape(
                       cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state),
                       jnp.float32)),
            work="ssd",
            # a softmax over the chosen logits IS the scored form at a zero
            # bias: softmax over all, the top-k, renormalised
            moe=dict(
                num_experts=cfg.num_experts, k=cfg.moe_top_k,
                norm_topk_prob=True, ffn_hidden_size=cfg.intermediate_size,
                scoring="softmax", noaux_tc=True,
                shared_ffn_hidden_size=cfg.shared_intermediate_size,
                held_experts=cfg.held_experts))

    @staticmethod
    def residual(cfg, x, t):
        # one rounding a residual add: the multiplier is no bfloat16 number
        return (x.astype(jnp.float32) + cfg.residual_multiplier
                * t.astype(jnp.float32)).astype(x.dtype)

    def _embed(self, ids):
        x = self.embed_tokens(ids)
        with jax.named_scope("embed.scale"):
            return x * jnp.asarray(self.config.embedding_multiplier, x.dtype)

    def _head(self, h, at=None):
        """Logits of ``h [B, S, hidden]``, or of row ``at[b]`` of each: the
        tied head, over ``logits_scaling``."""
        with jax.named_scope("head.logits"):
            if at is not None:
                h = jnp.take_along_axis(
                    h, at.astype(jnp.int32)[:, None, None], axis=1)
            logits = self.embed_tokens.attend(self.norm(h))
            return logits / jnp.asarray(self.config.logits_scaling,
                                        logits.dtype)
