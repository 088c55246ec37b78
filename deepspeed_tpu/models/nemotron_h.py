"""Nemotron-H (NVIDIA, ``model_type: nemotron_h``; Nemotron 3 Nano 30B-A3B)
— HF ``config.json`` keys to :class:`NemotronHModel`.

The block is ONE sublayer: ``x <- x + Mixer(RMSNorm(x))`` — one norm, no
multiplier, the residual in the model's dtype.  By character ``i`` of
``hybrid_override_pattern`` the mixer is

* ``M`` — a Mamba-2 STATE-SPACE layer (arXiv:2405.21060) in ``n_groups``
  GROUPS: ``[z | xBC | dt] = h W_in``; a causal depthwise convolution of
  ``conv_kernel`` taps with a bias over ALL of ``xBC`` and a SiLU; ``x`` in
  ``mamba_num_heads`` heads of ``mamba_head_dim`` (the inner width is their
  product, NOT ``expand`` x hidden), ``B`` and ``C`` of ``ssm_state_size`` a
  group, head ``h`` reading group ``h // (heads / groups)``'s; ``dt =
  softplus(dt + dt_bias)``, a decay ``exp(dt A)``, ``A = -exp(A_log)``; the
  recurrence of ``ops/transformer/ssd.py`` on a float32 state plus the skip
  ``D x``; the gate FIRST, then an RMS norm over each group's channels
  separately, and ``out_proj`` — ``models/granite_hybrid.py::Mamba2Mixer``,
  which this family shares (there one group: the whole width);
* ``E`` — the expert layer ALONE: float32 sigmoid scores over
  ``n_routed_experts``, the ``num_experts_per_tok`` largest of score +
  ``e_score_correction_bias``, gates the chosen SCORES over their sum (plus
  1e-20) times ``routed_scaling_factor``; an expert is UN-GATED, ``relu(h
  U)^2 D`` (``mlp_hidden_act: relu2`` — two matrices, not three); one shared
  expert of the same form every token takes.  ``held_experts=(first,
  count)`` gives the model one chip's share of each expert layer; the router
  keeps its published width;
* ``*`` — grouped-query softmax attention with NO positional encoding (the
  Mamba layers carry position; ``rope_theta`` is read by nothing), scores at
  ``head_dim ** -0.5``, no bias, no QK-norm, no gate.

A final RMSNorm and an UNTIED head.  ``-`` (a dense MLP block), group-limited
routing (``n_group`` / ``topk_group`` other than 1), projection biases and a
tied head are refused by name.

**Names.**  The checkpoint calls every block's sublayer ``mixer``
(``backbone.layers.N.mixer``, under ``backbone.layers.N.norm``); here a
block's ONE sublayer is the attribute ``mamba``, ``moe_mlp`` or
``self_attn`` by its kind, so that the parameter tree and the ``op_name``
frames tell the three apart (``profiling/flops_profiler``'s by-part table
reads ``moe_mlp`` as the experts and ``self_attn`` as attention): a loader
maps ``backbone.layers.N.mixer.*`` to ``layers_N/<kind>/*`` by
``hybrid_override_pattern[N]``, ``backbone.norm_f`` to ``norm_f`` and
``backbone.embeddings`` to ``embed_tokens``.

The slot engine's caches (``models/hybrid.py``, which owns the layer, the
serving methods and the contract): K/V lane pages for the ``*`` blocks, the
state kinds ``conv`` (the last ``taps - 1`` rows of ``xBC``, 3 x 6,144 values
a slot and ``M`` block at the published widths: 144 whole tiles) and ``ssm``
(float32, 64 heads x 64 x 128 = 2 MiB a slot and ``M`` block) for the ``M``
blocks, nothing for the ``E`` blocks.
"""

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.granite_hybrid import Mamba2Mixer
from deepspeed_tpu.models.hybrid import (Attention, Hybrid, HybridModel,
                                         StateKind)

KINDS = "ME*"                # Mamba-2, the experts alone, attention


def relu2(x):
    """``mlp_hidden_act: relu2`` — the square of the rectified input."""
    return jnp.square(jax.nn.relu(x))


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int
    hidden_size: int
    pattern: str                 # a block's kind a character: ``M E *``
    num_heads: int
    num_kv_heads: int
    head_dim: int                # stated: heads x head_dim is not the width
    mamba_heads: int
    mamba_head_dim: int
    mamba_state: int
    mamba_groups: int
    conv_size: int
    moe_intermediate_size: int   # one routed expert's width
    shared_intermediate_size: int
    num_experts: int             # the router's width
    moe_top_k: int
    routed_scaling: float
    max_seq_len: int
    rms_norm_eps: float = 1e-5
    held_experts: Optional[Tuple[int, int]] = None
    dtype: str = "bfloat16"

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def num_layers(self):
        return len(self.pattern)

    def blocks_of(self, kind):
        return tuple(i for i, c in enumerate(self.pattern) if c == kind)

    @property
    def stored_expert_width(self):
        """A routed expert's width as its matrices are stored: whole lane
        tiles (1856 = 14.5 x 128 as 1920), the added columns of ``U`` and
        rows of ``D`` zeros — exact, ``relu(0)^2 = 0``.  As published, XLA
        keeps each ``[64, 2688, 1856]`` tensor in a layout without the
        padding its tiles want and copies it (630 MB an expert block) for
        the kernel inside the decode block: the cell did not fit."""
        return -(-self.moe_intermediate_size // 128) * 128

    @property
    def mamba_width(self):
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self):
        """``x`` and every group's ``B`` and ``C``: the one stream the
        convolution runs."""
        return self.mamba_width + 2 * self.mamba_groups * self.mamba_state


def nemotron_h_config(hf, held_experts=None, **overrides):
    """``hf``: a dict of HF ``config.json`` keys."""
    layers = hf["num_hidden_layers"]
    pattern = hf["hybrid_override_pattern"][:layers]
    if "-" in pattern:
        raise ValueError("hybrid_override_pattern: '-' (a dense MLP block) "
                         "is not built; M, E and * are")
    if len(pattern) != layers or set(pattern) - set(KINDS):
        raise ValueError(f"hybrid_override_pattern {pattern!r} for "
                         f"{layers} blocks of M, E and *")
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("n_group / topk_group: group-limited routing is "
                         "not built (1 and 1 only)")
    for key in ("mamba_proj_bias", "attention_bias", "mlp_bias", "use_bias"):
        if hf.get(key):
            raise ValueError(f"{key}: the projections are built without "
                             f"biases only")
    if not hf.get("use_conv_bias", True):
        raise ValueError("use_conv_bias: the convolution is built with its "
                         "bias only")
    if hf.get("tie_word_embeddings", False):
        raise ValueError("tie_word_embeddings: nemotron_h as released has "
                         "an untied head")
    if hf.get("mlp_hidden_act", "relu2") != "relu2" \
            or hf.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError("nemotron_h as released: relu2 experts, SiLU in "
                         "the Mamba layers")
    if hf.get("n_shared_experts", 1) != 1:
        raise ValueError("n_shared_experts: one shared expert only")
    if hf.get("moe_latent_size"):
        raise ValueError("moe_latent_size: experts in a latent width are "
                         "not built")
    if hf.get("sliding_window") is not None:
        raise ValueError("sliding_window: full attention only")
    if not hf.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob: the gates are the chosen scores "
                         "over their sum only")
    if hf["num_attention_heads"] % hf["num_key_value_heads"]:
        raise ValueError("KV heads must divide the heads")
    inner = hf["mamba_num_heads"] * hf["mamba_head_dim"]
    if inner % hf["n_groups"] or hf["mamba_num_heads"] % hf["n_groups"]:
        raise ValueError(
            f"mamba_num_heads x mamba_head_dim = {inner} and "
            f"mamba_num_heads are not divisible by n_groups "
            f"{hf['n_groups']}")
    base = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        pattern=pattern, num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        mamba_heads=hf["mamba_num_heads"],
        mamba_head_dim=hf["mamba_head_dim"],
        mamba_state=hf["ssm_state_size"], mamba_groups=hf["n_groups"],
        conv_size=hf["conv_kernel"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        shared_intermediate_size=hf["moe_shared_expert_intermediate_size"],
        num_experts=hf.get("n_routed_experts_published",
                           hf["n_routed_experts"]),
        moe_top_k=hf["num_experts_per_tok"],
        routed_scaling=float(hf["routed_scaling_factor"]),
        max_seq_len=hf["max_position_embeddings"],
        rms_norm_eps=hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5)),
        held_experts=tuple(held_experts) if held_experts else None)
    base.update(overrides)
    return NemotronHConfig(**base)


def nemotron_h_model(hf, held_experts=None, **overrides):
    overrides.pop("scan_layers", None)       # the blocks differ: unrolled
    return NemotronHModel(nemotron_h_config(hf, held_experts, **overrides))


class NemotronHModel(HybridModel):

    @staticmethod
    def declare(cfg):
        from deepspeed_tpu.ops.transformer.short_conv import rows_shape
        from deepspeed_tpu.ops.transformer.ssd import state_shape
        return Hybrid(
            norm_eps=cfg.rms_norm_eps, norms=("norm",), final_norm="norm_f",
            attention_layers=cfg.blocks_of("*"),
            expert_blocks=cfg.blocks_of("E"),
            attention=Attention(
                cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                cfg.head_dim, cfg.jnp_dtype),
            mixer=("mamba", Mamba2Mixer),
            state=(StateKind("conv", lambda dtype: rows_shape(
                       cfg.conv_size, cfg.conv_width, dtype)),
                   StateKind("ssm", state_shape(
                       cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state),
                       jnp.float32)),
            work="ssd",
            moe=dict(
                num_experts=cfg.num_experts, k=cfg.moe_top_k,
                gated=False, activation=relu2, norm_topk_prob=True,
                ffn_hidden_size=cfg.moe_intermediate_size,
                ffn_stored_size=cfg.stored_expert_width,
                scoring="sigmoid", routed_scaling=cfg.routed_scaling,
                gate_sum_eps=1e-20,
                shared_ffn_hidden_size=cfg.shared_intermediate_size,
                held_experts=cfg.held_experts))
