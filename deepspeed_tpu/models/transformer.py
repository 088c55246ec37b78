"""Decoder-only transformer — the framework's flagship model family.

This is the TPU-native counterpart of the model surface the reference serves
through kernel injection (``module_inject/containers/{opt,llama,gptneox,...}``
+ ``model_implementations/transformers/ds_transformer.py:19``): one
configurable decoder covering the OPT/GPT/Llama architecture space, written
flax-first so that:

* attention routes through the Pallas flash-attention kernel on TPU
  (``ops/transformer/flash_attention.py``) with a jnp fallback for CPU tests;
* parameter names match the AutoTP sharding rules
  (``runtime/zero/partition.py DEFAULT_TP_RULES``) so tensor parallelism is
  a config flag, not a model rewrite;
* sequence-parallel sharding constraints are applied at block boundaries
  when an ``sp`` mesh axis is live;
* the whole stack is scan-over-layers for O(1) compile time at depth, with
  ``jax.checkpoint`` policies from the activation-checkpointing config.
"""

import os

from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.contract import SlotContract
from deepspeed_tpu.utils.logging import logger


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50272
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None       # GQA; None → MHA
    ffn_hidden_size: Optional[int] = None    # None → 4*hidden
    max_seq_len: int = 2048
    activation: str = "relu"                 # relu (OPT) | gelu tanh (GPT2) | gelu_exact (neox) | silu (llama gated)
    gated_mlp: bool = False                  # llama-style SwiGLU
    position_embedding: str = "learned"      # learned (OPT/GPT) | rope (llama/neox) | alibi (bloom)
    rope_theta: float = 10000.0
    rope_dim: Optional[int] = None           # partial rotary (neox rotary_pct / gptj rotary_dim)
    rope_interleaved: bool = False           # gptj rotate-every-two layout
    layernorm_epsilon: float = 1e-5
    rms_norm: bool = False                   # llama
    parallel_residual: bool = False          # x + attn(ln(x)) + mlp(ln'(x)) (neox/gptj)
    shared_attn_mlp_norm: bool = False       # gptj: one ln feeds both branches
    embedding_norm: bool = False             # bloom word_embeddings_layernorm
    attention_bias: Optional[bool] = None    # None → not rms_norm
    attention_out_bias: Optional[bool] = None  # gpt-neo: o_proj biased, qkv not
    mlp_bias: Optional[bool] = None          # None → not rms_norm
    # gpt-neo: per-layer "global"/"local" pattern + band width; local layers
    # attend to the trailing `window_size` positions only.  Requires
    # scan_layers=False (layers are no longer homogeneous).
    attention_layers: Optional[tuple] = None
    window_size: int = 256
    # None → 1/sqrt(head_dim); gpt-neo uses 1.0 (unscaled logits)
    attention_softmax_scale: Optional[float] = None
    # olmoe: RMSNorm over the WHOLE projected query and key vectors
    # (width heads x head_dim, float32 gain) before the head split's rope
    qk_norm: bool = False
    # MoE trunk (reference Megatron-DeepSpeed MoE-GPT layout): every
    # `moe_every`-th block swaps its MLP for a `moe/layer.py` MoE with
    # `moe_num_experts` experts sharded over the `ep` mesh axis.  0 = dense.
    moe_num_experts: int = 0
    moe_every: int = 2
    # index of the FIRST MoE layer; -1 → `moe_every - 1` (the Megatron
    # default, where MoE layers sit at every-1, 2*every-1, ...).  Lets
    # checkpoints whose pattern starts elsewhere (e.g. layers 0,2,4 with
    # interval 2) map without remapping layer indices.
    moe_layer_offset: int = -1
    moe_top_k: int = 1
    # None = DROPLESS (moe/dropless.py): no capacity, every chosen (token,
    # expert) pair computed, train or eval — what an OLMoE/Mixtral-style
    # model is served with.  A number selects the GShard capacity gate.
    moe_capacity_factor: Optional[float] = 1.25
    moe_eval_capacity_factor: float = 1.0
    # divide the chosen gates by their sum (HF norm_topk_prob); top-1
    # gates are never renormalised
    moe_norm_topk_prob: bool = True
    moe_ep_size: int = 1
    moe_aux_coef: float = 0.01
    # Megatron-style MoE experts carry per-expert biases (dense_h_to_4h.bias
    # / dense_4h_to_h.bias) — needed for exact checkpoint parity
    moe_expert_bias: bool = False
    lm_head_bias: bool = False               # gptj
    # opt-350m: embeddings live in a smaller space with project_in /
    # project_out linears around the trunk (HF word_embed_proj_dim)
    embed_proj_dim: Optional[int] = None
    # opt-350m is the post-LN OPT: norms AFTER the residual adds, and no
    # final norm (HF do_layer_norm_before=False)
    pre_layer_norm: bool = True
    dropout: float = 0.0
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    use_flash_attention: bool = True
    fused_qkv: bool = False                  # single fused QKV gemm (MHA only)
    # >1: sequence-chunked cross-entropy — the [B,S,V] logits tensor never
    # materializes (per-chunk head matmul + CE under jax.checkpoint); cuts
    # the loss section's HBM traffic at large vocabularies
    loss_seq_chunks: int = 0
    sparse_attention: Optional[object] = None  # SparsityConfig → block-sparse
    # int8 KV cache (beyond the reference's fp16 cache): payload int8 +
    # per-(position, kv-head) scales; decode is HBM-bound on the KV stream
    # at large batch, so halving its bytes buys real decode throughput
    kv_cache_quant: bool = False
    # run the decode kernel's score/PV matmuls int8×int8 on the MXU
    # (requires kv_cache_quant): removes the in-kernel int8→bf16 slab
    # casts at the cost of additionally quantizing q and the probability
    # rows (~0.5% extra attention error).  Measured NEUTRAL-to-slower on
    # v5e at OPT-1.3B shapes (the quantize work offsets the cast
    # savings) — opt-in for shapes where the KV stream dominates harder
    decode_int8_matmuls: bool = False
    # "ulysses" | "ring" routes training attention through explicit
    # sequence-parallel collectives over the live sp mesh axis; None leaves
    # seq sharding to GSPMD constraint propagation
    sequence_parallel_impl: Optional[str] = None
    remat: bool = True
    # what a rematerialized block keeps for its backward.  "fit": the
    # training engine picks a rung of REMAT_LADDER from the device memory
    # its compiled step leaves (runtime/engine.py `_fit_train_exe`); with
    # no engine choosing, "fit" is rung 0 = "nothing_saveable".  "fit:N"
    # pins rung N; any other name is resolve_remat_policy's
    remat_policy: str = "fit"
    scan_layers: bool = True

    def __post_init__(self):
        if self.moe_num_experts > 0 and self.scan_layers:
            raise ValueError("MoE trunk requires scan_layers=False (mixed "
                             "dense/MoE blocks are heterogeneous; expert "
                             "params shard over ep, not a layer axis)")
        if self.moe_num_experts > 0:
            if self.moe_layer_offset < -1:
                raise ValueError(
                    f"moe_layer_offset={self.moe_layer_offset}: only -1 "
                    f"(the moe_every-1 default) or a layer index >= 0 is "
                    f"meaningful")
            off = resolve_moe_offset(self)
            if off >= self.num_layers:
                raise ValueError(
                    f"first MoE layer {off} (moe_layer_offset/moe_every-1) "
                    f"is past num_layers={self.num_layers} — the model "
                    f"would silently build all-dense despite "
                    f"moe_num_experts={self.moe_num_experts}")
        if self.decode_int8_matmuls and not self.kv_cache_quant:
            raise ValueError("decode_int8_matmuls requires "
                             "kv_cache_quant=True (the MXU path consumes "
                             "int8 KV payloads)")
        if self.attention_layers is not None:
            if len(self.attention_layers) != self.num_layers:
                raise ValueError(
                    f"attention_layers has {len(self.attention_layers)} "
                    f"entries for {self.num_layers} layers")
            if self.scan_layers:
                raise ValueError("attention_layers (per-layer local/global "
                                 "patterns) requires scan_layers=False")
        if self.fused_qkv and self.kv_heads != self.num_heads:
            logger.warning(
                "fused_qkv requested but num_kv_heads != num_heads (GQA) — "
                "falling back to separate q/k/v projections; the param tree "
                "will carry q_proj/k_proj/v_proj, not qkv_proj")

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def ffn_size(self):
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def attn_bias_enabled(self):
        return self.attention_bias if self.attention_bias is not None \
            else not self.rms_norm

    @property
    def attn_out_bias_enabled(self):
        return self.attention_out_bias if self.attention_out_bias is not None \
            else self.attn_bias_enabled

    def window_for_layer(self, layer_idx):
        """Band width for this layer, or None for full (global) attention."""
        if self.attention_layers is None or layer_idx is None:
            return None
        return self.window_size \
            if self.attention_layers[layer_idx] == "local" else None

    @property
    def mlp_bias_enabled(self):
        return self.mlp_bias if self.mlp_bias is not None else not self.rms_norm

    @property
    def jnp_dtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                "float16": jnp.float16}[self.dtype]

    def remat_saved_bytes(self, tokens, rung):
        """Bytes rung ``rung`` of :data:`REMAT_LADDER` keeps from the
        forward of ``tokens`` tokens through every layer, by the shapes of
        the named values (the engine's reckoning before it compiles)."""
        item = jnp.dtype(self.jnp_dtype).itemsize
        q, kv = self.num_heads * self.head_dim, self.kv_heads * self.head_dim
        a_token = {"flash_out": q * item, "flash_lse": self.num_heads * 4,
                   "mlp_up": self.ffn_size * item,
                   "mlp_gate": self.ffn_size * item if self.gated_mlp else 0,
                   "attn_q": q * item, "attn_k": kv * item, "attn_v": kv * item,
                   "attn_o": self.hidden_size * item}
        return tokens * self.num_layers * sum(
            a_token[n] for n in remat_rung_names(rung))

    def num_params(self):
        """Analytic parameter count (embeddings + blocks + final norm)."""
        h, v, l = self.hidden_size, self.vocab_size, self.num_layers
        f = self.ffn_size
        kvh = self.kv_heads * self.head_dim
        attn = h * h + h * kvh * 2 + h * h  # q, k, v, o kernels
        if self.qk_norm:
            attn += h + kvh
        mlp = h * f * (3 if self.gated_mlp else 2)
        norm_size = h if self.rms_norm else 2 * h
        norms_per_layer = 1 if (self.parallel_residual
                                and self.shared_attn_mlp_norm) else 2
        per_layer = attn + norms_per_layer * norm_size
        # an expert layer holds a router and moe_num_experts MLPs in the
        # dense MLP's place (kernels only; moe_expert_bias is not counted,
        # like the dense biases)
        n_moe = sum(_is_moe_layer(self, i) for i in range(l))
        mlps = (l - n_moe) * mlp \
            + n_moe * (h * self.moe_num_experts + self.moe_num_experts * mlp)
        emb = v * h + (self.max_seq_len * h
                       if self.position_embedding == "learned" else 0)
        head = 0 if self.tie_word_embeddings else v * h
        return emb + l * per_layer + mlps + norm_size + head


# What a rematerialized block can keep, as ``checkpoint_name`` tags, in
# the order worth keeping: milliseconds of replay bought per byte held.
# Rung N of the ladder saves the names of its first N steps; rung 0 saves
# nothing.  (a) the flash kernel's out + lse: one [tokens, h] buys a
# whole kernel call; (b) the up-projection (and a gated MLP's gate): 4 h
# of bytes a token for 4 h^2 of replay; (c) q/k/v: 3 for 3; (d) o_proj: 1
# for 1.  The down-projection's output is deliberately NOT a name: it
# feeds only the residual sum, nothing replays it.
REMAT_LADDER = (
    ("flash_out", "flash_lse"),
    ("mlp_up", "mlp_gate"),
    ("attn_q", "attn_k", "attn_v"),
    ("attn_o",),
)


def remat_rung_names(rung):
    """The names rung ``rung`` of :data:`REMAT_LADDER` saves."""
    if not 0 <= rung <= len(REMAT_LADDER):
        raise ValueError(f"remat rung {rung}: the ladder has rungs 0.."
                         f"{len(REMAT_LADDER)}")
    return tuple(n for step in REMAT_LADDER[:rung] for n in step)


def resolve_remat_policy(name):
    """Map a policy name to a jax.checkpoint policy.

    ``"fit"`` is rung 0 of :data:`REMAT_LADDER` here (an engine that fits
    the saved set hands the module ``"fit:N"``); ``"fit:N"`` saves rung
    N's names.

    Beyond the stock ``jax.checkpoint_policies`` names, ``dots_and_attn_saveable``
    saves weight-stationary dot outputs AND the flash-attention residuals
    (tagged ``flash_out``/``flash_lse`` in the kernel's vjp) — the backward
    pass then reuses the O(S) attention residuals instead of re-running the
    forward kernel, the right default trade on HBM-rich chips."""
    if name == "fit" or name.startswith("fit:"):
        names = remat_rung_names(int(name[4:] or 0))
        return jax.checkpoint_policies.save_only_these_names(*names) \
            if names else jax.checkpoint_policies.nothing_saveable
    if name in ("dots_and_attn_saveable", "attn_residuals_saveable"):
        cp = jax.checkpoint_policies
        return cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable,
            cp.save_only_these_names("flash_out", "flash_lse"))
    if name == "flash_only_saveable":
        # long-context middle ground: save ONLY the flash-attention
        # residuals (out + lse, O(S) per layer) so the backward never
        # re-runs the attention kernel, while every projection/MLP dot
        # (O(S·M) each — the HBM hogs at long seq) is rematerialized
        return jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse")
    return getattr(jax.checkpoint_policies, name, None)


def _norm(config, name):
    if config.rms_norm:
        return nn.RMSNorm(epsilon=config.layernorm_epsilon, name=name,
                          param_dtype=jnp.float32)
    return nn.LayerNorm(epsilon=config.layernorm_epsilon, name=name,
                        param_dtype=jnp.float32)


@jax.named_scope("attn.rope")
def _rope(q, k, positions, head_dim, theta, rope_dim=None, interleaved=False):
    """Rotary position embeddings.  Default: neox/llama half-split layout;
    ``interleaved`` selects the gptj rotate-every-two layout; ``rope_dim``
    rotates only the first ``rope_dim`` features (neox ``rotary_pct`` /
    gptj ``rotary_dim``)."""
    d = rope_dim or head_dim
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]

    def rot(x):
        rx, pass_through = x[..., :d], x[..., d:]
        if interleaved:
            x1, x2 = rx[..., 0::2], rx[..., 1::2]
            r1 = x1 * cos - x2 * sin
            r2 = x2 * cos + x1 * sin
            out = jnp.stack([r1, r2], axis=-1).reshape(rx.shape)
        else:
            x1, x2 = rx[..., :half], rx[..., half:]
            out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                                  axis=-1)
        if pass_through.shape[-1]:
            out = jnp.concatenate([out, pass_through], axis=-1)
        return out.astype(x.dtype)

    return rot(q), rot(k)


def alibi_slopes(n_heads):
    """ALiBi per-head slopes (bloom layout)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if np.log2(n_heads).is_integer():
        slopes = pow2_slopes(n_heads)
    else:
        p = 2 ** int(np.floor(np.log2(n_heads)))
        slopes = pow2_slopes(p) + pow2_slopes(2 * p)[0::2][: n_heads - p]
    return jnp.asarray(slopes, dtype=jnp.float32)


def alibi_bias(n_heads, kv_len):
    """[H, T] key-positional ALiBi bias.  The relative form
    ``slope·(t - s)`` differs from this per query row only by a constant,
    which softmax cancels — so the key-absolute form is exact for causal
    attention (what bloom itself implements)."""
    return alibi_slopes(n_heads)[:, None] * jnp.arange(kv_len)[None, :]


def reference_attention(q, k, v, causal=True, mask=None, bias=None,
                        window=None, scale=None):
    """jnp attention used as the CPU fallback and the golden reference for
    the Pallas kernel tests.  q,k,v: [B, S, H, D] / [B, S, KVH, D];
    ``bias``: optional [H, T] additive logit bias (ALiBi); ``window``:
    optional band width (gpt-neo local attention — attend to the trailing
    ``window`` positions only); ``scale``: what the scores are multiplied
    by (default ``D ** -0.5``)."""
    B, S, H, D = q.shape
    KVH = k.shape[2]
    if KVH != H:
        rep = H // KVH
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / np.sqrt(D) if scale is None else scale
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias[None, :, None, :].astype(jnp.float32)
    if causal:
        rows = jnp.arange(S)[:, None]
        cols = jnp.arange(k.shape[1])[None, :]
        causal_mask = cols <= rows
        if window is not None:
            causal_mask = causal_mask & (cols > rows - window)
        logits = jnp.where(causal_mask[None, None], logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :].astype(bool), logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def _prefill_attention(q, k, v, config, window=None):
    """Causal self-attention for a from-zero generation prefill: ONLY the
    flash kernel or the dense causal reference — never ``_attention``'s
    sequence-parallel shard_map or block-sparse branches.  Generation
    inputs are unsharded (an sp>1 topology would shard_map over them and
    crash or mis-attend), and decode attends dense over the same cache,
    so a sparse prefill would silently diverge from its own decode."""
    if window is None and config.use_flash_attention and q.shape[1] > 1:
        from deepspeed_tpu.ops.transformer.flash_attention import (
            flash_attention, pallas_supported)
        if pallas_supported():
            return flash_attention(q, k, v, causal=True)
    return reference_attention(q, k, v, causal=True, window=window)


def _attention(q, k, v, config, mask=None, bias=None, window=None):
    if window is not None:
        # banded local attention (gpt-neo): dense path with a band mask —
        # the flash/sparse kernels are bypassed (HF computes it dense too)
        return reference_attention(q, k, v, causal=True, mask=mask, bias=bias,
                                   window=window)
    if config.sparse_attention is not None and q.shape[1] > 1 and bias is None:
        from deepspeed_tpu.ops.sparse_attention.block_sparse import (
            block_sparse_attention, cached_layout)
        sc = config.sparse_attention
        if mask is not None and mask.ndim != 2:
            logger.warning(
                "sparse_attention only folds 2-D key-padding masks; got a "
                f"{mask.ndim}-D mask — falling back to dense attention")
        else:
            layout = cached_layout(sc, q.shape[1], causal=True)
            if k.shape[2] != q.shape[2]:  # GQA: expand kv heads for the kernel
                k = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
                v = jnp.repeat(v, q.shape[2] // v.shape[2], axis=2)
            return block_sparse_attention(q, k, v, layout, sc.block,
                                          causal=True, key_padding_mask=mask)
    if config.sequence_parallel_impl and q.shape[1] > 1 and mask is None \
            and bias is None:
        from deepspeed_tpu.parallel.topology import get_topology
        topo = get_topology()
        if topo is not None and topo.get_sequence_parallel_world_size() > 1:
            from deepspeed_tpu.parallel.sequence import shard_map_attention
            if k.shape[2] != q.shape[2]:  # GQA: expand for the sp kernels
                k = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
                v = jnp.repeat(v, q.shape[2] // v.shape[2], axis=2)
            batch_axes, head_axes = _batch_head_axes(topo)
            fn = shard_map_attention(topo.mesh,
                                     impl=config.sequence_parallel_impl,
                                     axis="sp", causal=True,
                                     batch_axes=batch_axes,
                                     head_axes=head_axes)
            return fn(q, k, v)
    if config.use_flash_attention and q.shape[1] > 1 and mask is None \
            and bias is None:
        from deepspeed_tpu.ops.transformer.flash_attention import \
            pallas_supported
        if pallas_supported():
            return _flash_on_mesh(q, k, v)
    return reference_attention(q, k, v, causal=True, mask=mask, bias=bias)


def _flash_on_mesh(q, k, v):
    """Causal flash attention on [B, S, H, D].  On a multi-device mesh the
    kernel runs per shard under ``shard_map`` — batch over the data-parallel
    axes, heads over ``tp`` — because GSPMD cannot partition a Mosaic kernel
    (the TPU compiler refuses: "wrap the call in a shard_map"; the CPU
    interpreter lowers the kernel to plain HLO and never sees this).
    Shapes the mesh does not divide, and pipeline stage bodies (already
    inside a ``pp`` shard_map), keep the bare call."""
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
    from deepspeed_tpu.parallel.topology import get_topology
    attend = partial(flash_attention, causal=True)
    topo = get_topology()
    if topo is None or topo.mesh.size == 1 or topo.mesh.shape["pp"] > 1:
        return attend(q, k, v)
    mesh = topo.mesh
    batch_axes, head_axes = _batch_head_axes(topo)
    n_batch = int(np.prod([mesh.shape[a] for a in batch_axes or ()]))
    tp = mesh.shape["tp"]
    if q.shape[0] % n_batch or q.shape[2] % tp or k.shape[2] % tp:
        return attend(q, k, v)
    spec = P(batch_axes, None, head_axes, None)
    return jax.shard_map(attend, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _batch_head_axes(topo):
    """The mesh axes a [B, S, H, D] activation's batch and head dims are
    sharded over: the data-parallel axes of size > 1, and ``tp``."""
    mesh = topo.mesh
    batch_axes = tuple(a for a in topo.get_data_parallel_axes()
                       if mesh.shape[a] > 1) or None
    return batch_axes, "tp" if mesh.shape["tp"] > 1 else None


_CACHE_DATA_KEYS = ("k", "v", "k_scale", "v_scale")


def _cache_data(cache):
    """The data arrays of a cache dict (payloads + optional quant scales),
    without the per-layer/per-row bookkeeping markers."""
    return {kk: cache[kk] for kk in _CACHE_DATA_KEYS if kk in cache}


def _paged_write(cache, k_new, v_new, ks_new, vs_new, positions, per_row,
                 page_runs=False):
    """Write this step's K/V rows into a PAGED cache pool.

    Pool layout (``init_paged_cache``): ``[L, num_pages, page_size,
    KVH*D]``; ``cache["pages"]`` is the per-row page table ``[B,
    n_pages]`` mapping virtual page index ``pos // page_size`` to a
    physical page.  Each virtual write position resolves to ``(pages[b,
    pos // page], pos % page)`` — one batched scatter per buffer, no
    per-page Python loop, so the program shape is independent of where
    the host placed the pages.  Unmapped virtual pages alias the
    reserved TRASH page 0: retired/free lanes keep scattering masked
    garbage there instead of into reclaimed pages (the paged analog of
    the dense path's "dead lanes write into their own lane" safety
    argument).

    ``page_runs`` (static; ``ops/transformer/registry.py::
    paged_write_form`` decides it): each row's block is WHOLE PAGES, or
    one run inside one page, and its start — ``positions[0, 0]`` for a
    block of one row, the row's own ``positions[b, 0]`` for several
    (:func:`_write_row_runs`) — is run-aligned: the caller's promise,
    which no shape shows.  Each run then goes into the pool as one
    contiguous ``dynamic_update_slice`` at ``(layer, pages[b, start_b //
    page + j], start_b % page, 0)``, in place on a donated pool: the same
    values on the same pool rows as the scatter, one block write a page
    in place of ``page`` row updates."""
    li = cache["layer"]
    pages = cache["pages"]                      # [B, n_pages] int32
    page = cache["k"].shape[-2]
    B_, S_ = k_new.shape[0], k_new.shape[1]
    if page_runs and B_ > 1:
        # the chunk program's rows (serving/slots.py): a start a row
        news = {"k": k_new, "v": v_new}
        if ks_new is not None:
            news.update(k_scale=ks_new, v_scale=vs_new)
        return _write_row_runs(_cache_data(cache), news, li, pages,
                               positions[:, 0].astype(jnp.int32))
    if page_runs:
        start = positions[0, 0].astype(jnp.int32)
        run = min(S_, page)
        first = start // page
        # whole pages start at row 0 of each; a shorter run at its offset
        off = start % page if run < page else jnp.zeros((), jnp.int32)
        lane0 = jnp.zeros((), jnp.int32)

        def w(buf, new):
            new = new.astype(buf.dtype)
            for b in range(B_):
                for j in range(S_ // run):
                    # indexed one entry at a time, so a run's page index
                    # clamps to the table row like the scatter's would
                    buf = jax.lax.dynamic_update_slice(
                        buf, new[b, j * run:(j + 1) * run][None, None],
                        (li, pages[b, first + j], off, lane0))
            return buf
    elif per_row and S_ == 1:
        pos = positions[:, 0]                   # [B] per-row decode
        pidx = (pos // page).astype(jnp.int32)
        off = (pos % page).astype(jnp.int32)
        phys = pages[jnp.arange(B_), pidx]      # [B]

        def w(buf, new):
            return buf.at[li, phys, off].set(new[:, 0].astype(buf.dtype))
    elif per_row:
        # per-row MULTI-token block (speculative verify): each row writes
        # S_ contiguous positions from ITS OWN start, resolved through
        # its table row in one batched scatter.  Dead lanes' table rows
        # are redirected to the trash page by the caller, so their
        # (possibly lane-overflowing, gather-clamped) virtual positions
        # can only ever land on trash.
        pos = positions                         # [B, S]
        pidx = (pos // page).astype(jnp.int32)
        off = (pos % page).astype(jnp.int32)
        phys = jnp.take_along_axis(pages, pidx, axis=1)      # [B, S]

        def w(buf, new):
            return buf.at[li, phys, off].set(new.astype(buf.dtype))
    else:
        # row-uniform multi-token block (chunked prefill / shared-pos
        # decode): positions start..start+S-1 may span page boundaries
        pos = positions[0, 0] + jnp.arange(S_)  # [S]
        pidx = (pos // page).astype(jnp.int32)
        off = jnp.broadcast_to((pos % page).astype(jnp.int32), (B_, S_))
        phys = pages[:, pidx]                   # [B, S]

        def w(buf, new):
            return buf.at[li, phys, off].set(new.astype(buf.dtype))

    out = {"k": w(cache["k"], k_new), "v": w(cache["v"], v_new)}
    if ks_new is not None:
        out["k_scale"] = w(cache["k_scale"], ks_new)
        out["v_scale"] = w(cache["v_scale"], vs_new)
    return out


@jax.jit
def _write_row_runs(bufs, news, li, pages, starts):
    """:func:`_paged_write`'s page runs for a block of several rows, each
    from ITS OWN run-aligned start (``starts [B]``): ``news[key] [B, S,
    ...]`` into ``bufs[key]``, one ``dynamic_update_slice`` a row, run
    and buffer.  Jitted, with the layer a traced operand (like the chunk
    kernel's call, ``paged_attention._paged_chunk_call``): an unrolled
    model calls it once a layer with the same shapes, so the ``buffers x
    rows x runs`` block writes are traced and lowered ONCE a program —
    unrolled into every layer they were 4 s of a serving program's
    set-up at 4 rows x 24 layers (PERF.md §6, PR 38).  XLA inlines the
    call: the pool stays in place."""
    page = bufs["k"].shape[-2]
    B_, S_ = news["k"].shape[:2]
    run = min(S_, page)
    first = starts // page
    # whole pages start at row 0 of each; a shorter run at its offset
    off = starts % page if run < page else jnp.zeros_like(starts)
    lane0 = jnp.zeros((), jnp.int32)
    out = {}
    for key, buf in bufs.items():
        new = news[key].astype(buf.dtype)
        for b in range(B_):
            for j in range(S_ // run):
                # indexed one entry at a time, so a run's page index
                # clamps to the table row like the scatter's would
                buf = jax.lax.dynamic_update_slice(
                    buf, new[b, j * run:(j + 1) * run][None, None],
                    (li, pages[b, first[b] + j], off[b], lane0))
        out[key] = buf
    return out


def _paged_gather(cache):
    """Materialize THIS layer's virtual [B, n_pages*page_size, ...] view
    of the paged pool via the page table — a transient 1/L the size of
    the monolithic per-layer cache slice the dense paths already
    materialize.  Virtual positions on unmapped (trash) pages carry
    garbage; every attention path masks KV positions beyond each query's
    own position, and the host never maps a live write/read position to
    the trash page, so the garbage is never attended."""
    li, pages = cache["layer"], cache["pages"]
    B, n = pages.shape
    page = cache["k"].shape[-2]

    def g(buf):
        v = buf[li, pages]                      # [B, n, page, F]
        return v.reshape(B, n * page, v.shape[-1])

    out = {"k": g(cache["k"]), "v": g(cache["v"])}
    if "k_scale" in cache:
        out["k_scale"] = g(cache["k_scale"])
        out["v_scale"] = g(cache["v_scale"])
    return out


def cached_attention(q, k_cache, v_cache, q_positions, bias=None,
                     window=None, layer=None, k_scale=None, v_scale=None,
                     int8_matmuls=False):
    """Decode attention against a KV cache.

    q: [B, S, H, D]; caches: [B, S_max, KVH*D] (S-major, heads flattened —
    the decode kernel's full-lane-width DMA layout; the cache write is the
    raw projection output) — or, with ``layer`` given, the FULL
    layer-stacked [L, B, S_max, KVH*D] cache (the Pallas kernel indexes the
    layer itself; no per-layer slice is materialized).  q_positions: [B, S]
    absolute positions.  KV entries at positions > q_pos are masked — this
    covers both causality and the unwritten cache tail.  TPU-native analog
    of the reference ``softmax_context`` KV-cache op
    (``csrc/transformer/inference/csrc/pt_binding.cpp``).

    PER-ROW CONTIGUITY (the ``1 < S <= 512`` Pallas chunk branch): the
    chunk kernel receives only each row's FIRST position
    (``starts = q_positions[:, 0]``) and derives the rest as
    ``starts[b] + iota(S)`` — so when that branch is taken, every row's
    positions must be contiguous and ascending
    (``q_positions[b, i] == q_positions[b, 0] + i``), which is exactly
    what ``prefill_chunked`` / multi-token decode feed it.  Gapped or
    reordered positions would silently diverge from the dense fallback's
    per-position mask (regression-tested against the dense path in
    tests/unit/test_decode_attention.py); such callers must route to the
    dense path (pass a ``bias``/``window``, or S > 512).
    """
    B, S, H, D = q.shape
    S_max, KVH = k_cache.shape[-2], k_cache.shape[-1] // D
    # NOTE: on TPU, f32 matmuls run as multi-pass bf16 on the MXU (jax
    # default precision), so single-token decode and batched prefill round
    # differently — logits agree to ~1e-2, not 1e-6.  Hardware numerics,
    # not a cache bug (the CPU mesh reproduces exact parity).
    # kernel selection goes through the ONE capability-probed dispatch
    # table (ops/transformer/registry.py) — this function only ever sees
    # monolithic caches (the paged pool dispatches in write_and_attend)
    from deepspeed_tpu.ops.transformer.registry import select_kernel
    mode = select_kernel(s=S, paged=False, has_bias=bias is not None,
                         has_window=window is not None)
    if mode == "pallas_decode":
        # single-token decode: the Pallas online-softmax kernel streams the
        # cache blockwise instead of materializing [B,H,1,S_max] fp32
        # logits; sliding windows (mistral-style) mask inside the kernel
        from deepspeed_tpu.ops.transformer.decode_attention import (
            decode_attention)
        lengths = (q_positions[:, 0] + 1).astype(jnp.int32)
        return decode_attention(q[:, 0], k_cache, v_cache,
                                lengths, layer=layer,
                                k_scale=k_scale,
                                v_scale=v_scale,
                                window=window,
                                int8_matmuls=int8_matmuls)[:, None]
    if mode == "pallas_chunked_prefill":
        # multi-token block vs cache (chunked prefill / incremental
        # multi-token feed): the chunk kernel keeps score tiles at
        # [S, block_k] and never dequantizes the whole cache — the dense
        # fallback below materializes [B, H, S, S_max] fp32 scores (and,
        # quantized, a full-precision cache copy) per layer.  S is capped
        # at MAX_CHUNK_S (512): the kernel's q block and f32 accumulator
        # scale with S x H x D and would blow VMEM on longer blocks —
        # those keep the dense HBM fallback.
        from deepspeed_tpu.ops.transformer.decode_attention import (
            chunk_prefill_attention)
        starts = q_positions[:, 0].astype(jnp.int32)
        return chunk_prefill_attention(q, k_cache, v_cache, starts,
                                       layer=layer, k_scale=k_scale,
                                       v_scale=v_scale)
    if layer is not None:
        # dense fallback needs the layer slice after all
        sl = lambda c: jax.lax.dynamic_index_in_dim(c, layer, 0,
                                                    keepdims=False)
        k_cache, v_cache = sl(k_cache), sl(v_cache)
        if k_scale is not None:
            k_scale, v_scale = sl(k_scale), sl(v_scale)
    if k_scale is not None:
        # int8 payloads: dequantize for the dense path.  This re-expands
        # the WHOLE cache to full precision every step — the quantized
        # cache only pays off through the Pallas decode kernel (single
        # token, no alibi bias / sliding window)
        if S == 1:
            # multi-token prefill (S > 1) always takes this path and the
            # one-off dequant there is expected — only a *decode* step
            # landing here (alibi bias or no Pallas support) repeats the
            # full-cache dequant every token and actually hurts
            from deepspeed_tpu.utils.logging import warning_once
            warning_once(
                "kv_cache_quant decode fell back to dense attention "
                "(alibi bias or no Pallas support) — the full cache is "
                "dequantized per step, so the int8 cache SLOWS decode "
                "here instead of speeding it up")
        deq = lambda c, s: (c.reshape(B, S_max, KVH, D).astype(jnp.float32)
                            * s[..., None]).astype(q.dtype)
        k_cache = deq(k_cache, k_scale)
        v_cache = deq(v_cache, v_scale)
        k_cache = k_cache.reshape(B, S_max, KVH * D)
        v_cache = v_cache.reshape(B, S_max, KVH * D)
    # [B, S_max, KVH*D] → head-major [B, KVH, S_max, D] for the einsum
    k_cache = k_cache.reshape(B, S_max, KVH, D).transpose(0, 2, 1, 3)
    v_cache = v_cache.reshape(B, S_max, KVH, D).transpose(0, 2, 1, 3)
    if KVH != H:
        rep = H // KVH
        k_cache = jnp.repeat(k_cache, rep, axis=1)
        v_cache = jnp.repeat(v_cache, rep, axis=1)
    scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bshd,bhtd->bhst", q, k_cache).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias[None, :, None, :].astype(jnp.float32)
    kv_pos = jnp.arange(S_max)
    ok = q_positions[:, None, :, None] >= kv_pos[None, None, None, :]
    if window is not None:
        ok = ok & (kv_pos[None, None, None, :]
                   > q_positions[:, None, :, None] - window)
    logits = jnp.where(ok, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bhtd->bshd", probs, v_cache)


class Attention(nn.Module):
    config: TransformerConfig
    layer_idx: Optional[int] = None

    @nn.compact
    def __call__(self, x, positions, mask=None, cache=None, prefill=False):
        cfg = self.config
        D, H, KVH = cfg.head_dim, cfg.num_heads, cfg.kv_heads
        window = cfg.window_for_layer(self.layer_idx)
        dense = partial(nn.DenseGeneral, use_bias=cfg.attn_bias_enabled,
                        dtype=cfg.jnp_dtype, param_dtype=jnp.float32)
        if cfg.fused_qkv and KVH == H:
            # one [h, 3·H·D] gemm instead of three [h, H·D] gemms — better
            # MXU utilization at small hidden sizes (checkpoint conversion
            # policies emit separate projections, so this is opt-in)
            qkv = dense(features=(3, H, D), name="qkv_proj")(x)
            q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        else:
            # fused_qkv with GQA falls back (warned once at config time)
            q = dense(features=(H, D), name="q_proj")(x)
            k = dense(features=(KVH, D), name="k_proj")(x)
            v = dense(features=(KVH, D), name="v_proj")(x)
        if cache is None:
            # names a remat policy may save (REMAT_LADDER; a cached forward
            # takes no gradient).  Named in their lane-dense [B, S, heads *
            # D] form: kept as [.., heads, D=64] a stacked copy is padded
            # to 128 lanes in HBM, twice its bytes
            q, k, v = (checkpoint_name(t.reshape(*t.shape[:2], -1), n)
                       .reshape(t.shape) for t, n in
                       ((q, "attn_q"), (k, "attn_k"), (v, "attn_v")))
        if cfg.qk_norm:
            # over the whole projected vector, not per head (HF
            # OlmoeAttention q_norm / k_norm)
            def whole(t, name):
                n = nn.RMSNorm(epsilon=cfg.layernorm_epsilon, name=name,
                               param_dtype=jnp.float32)(
                    t.reshape(*t.shape[:2], -1))
                return n.astype(cfg.jnp_dtype).reshape(t.shape)
            q, k = whole(q, "q_norm"), whole(k, "k_norm")
        if cfg.position_embedding == "rope":
            q, k = _rope(q, k, positions, D, cfg.rope_theta,
                         rope_dim=cfg.rope_dim,
                         interleaved=cfg.rope_interleaved)
        if cfg.attention_softmax_scale is not None:
            # every attention path divides by sqrt(D); fold any other scale
            # (gpt-neo: 1.0, i.e. unscaled logits) into q up front so the
            # flash/decode kernels need no changes
            q = q * jnp.asarray(cfg.attention_softmax_scale * np.sqrt(D),
                                q.dtype)
        if cache is None:
            kv_len = x.shape[1]
        elif "pages" in cache:               # paged: the virtual length
            kv_len = cache["pages"].shape[1] * cache["k"].shape[-2]
        else:
            kv_len = cache["k"].shape[-2]
        bias = alibi_bias(H, kv_len) \
            if cfg.position_embedding == "alibi" else None
        if cache is not None:
            if cfg.sparse_attention is not None:
                # KV-cache decode attends densely over the cache; a
                # sparse-trained model sees a (slightly) different pattern
                # at generation time.  Surface it instead of silently
                # diverging.
                logger.warning(
                    "sparse_attention model decoding with dense KV-cache "
                    "attention — train/decode attention patterns differ")
            # write this step's k/v at the current position, attend over
            # cache; cache layout is [.., S_max, KVH*D] (S-major, heads
            # flattened — the decode kernel's full-lane-width DMA layout;
            # the write is the raw projection output, no transpose).
            # ALL cache layouts (monolithic / layer-stacked / paged pool)
            # and program classes (decode, chunked prefill, speculative
            # verify) go through the ONE kernel-registry dispatch point —
            # write form, kernel selection (capability-probed), the fused
            # aliased decode write, and the reference/gather fallback all
            # live there (ops/transformer/registry.py).
            from deepspeed_tpu.ops.transformer.registry import (
                write_and_attend)
            out, new_cache = write_and_attend(
                cfg, q, k, v, positions, cache, bias=bias, window=window,
                prefill=prefill)
        else:
            out = _attention(q, k, v, cfg, mask=mask, bias=bias,
                             window=window)
            new_cache = None
        proj = dense(features=cfg.hidden_size, axis=(-2, -1),
                     use_bias=cfg.attn_out_bias_enabled, name="o_proj")(
            out.reshape(*out.shape[:2], H, D))
        return checkpoint_name(proj, "attn_o"), new_cache


# ``TransformerConfig.activation`` -> the function, for the dense MLP and
# the gated experts alike
ACTIVATIONS = {"relu": nn.relu, "gelu": nn.gelu,
               "gelu_exact": partial(nn.gelu, approximate=False),
               "silu": nn.silu,
               # clip text encoder: x * sigmoid(1.702 x)
               "quick_gelu": lambda x: x * nn.sigmoid(1.702 * x)}


class MLP(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = partial(nn.Dense, use_bias=cfg.mlp_bias_enabled,
                        dtype=cfg.jnp_dtype, param_dtype=jnp.float32)
        act = ACTIVATIONS[cfg.activation]
        if cfg.gated_mlp:
            gate = checkpoint_name(
                dense(cfg.ffn_size, name="gate_proj")(x), "mlp_gate")
            up = checkpoint_name(
                dense(cfg.ffn_size, name="up_proj")(x), "mlp_up")
            h = act(gate) * up
        else:
            h = act(checkpoint_name(
                dense(cfg.ffn_size, name="up_proj")(x), "mlp_up"))
        # the down-projection's output is not a name: nothing replays it
        return dense(cfg.hidden_size, name="down_proj")(h)


def resolve_moe_offset(cfg):
    """The index of the first MoE layer; the -1 sentinel means
    ``moe_every - 1`` (the Megatron default pattern)."""
    off = cfg.moe_layer_offset
    return cfg.moe_every - 1 if off < 0 else off


def _is_moe_layer(cfg, layer_idx):
    if cfg.moe_num_experts <= 0 or layer_idx is None:
        return False
    off = resolve_moe_offset(cfg)
    return layer_idx >= off and (layer_idx - off) % cfg.moe_every == 0


def _block_mlp(cfg, layer_idx, h, train=True, live=None):
    """Dense MLP or MoE for one block; returns (out, aux_loss).  A plain
    function (submodules attach to the calling compact method) so flax's
    module summary never re-invokes it as a standalone module method.
    ``train`` selects the gate's capacity/noise regime (reference
    ``TopKGate`` train vs eval capacity).  ``live`` ([B, S] bool, serving
    only): the tokens an expert layer may route — a dead decode lane or a
    chunk's padded tail takes no expert; a dense MLP ignores it."""
    if not _is_moe_layer(cfg, layer_idx):
        return MLP(cfg, name="mlp")(h), 0.0
    from deepspeed_tpu.moe.layer import MoE
    # gated experts follow gated_mlp and share the dense MLP's activation
    # table; the un-gated Megatron experts keep their gelu
    gated = dict(gated=True, activation=ACTIVATIONS[cfg.activation]) \
        if cfg.gated_mlp else {}
    out, aux, _ = MoE(hidden_size=cfg.hidden_size,
                      num_experts=cfg.moe_num_experts,
                      ep_size=cfg.moe_ep_size, k=cfg.moe_top_k,
                      capacity_factor=cfg.moe_capacity_factor,
                      eval_capacity_factor=cfg.moe_eval_capacity_factor,
                      norm_topk_prob=cfg.moe_norm_topk_prob,
                      ffn_hidden_size=cfg.ffn_size,
                      expert_bias=cfg.moe_expert_bias,
                      dtype=cfg.jnp_dtype, name="moe_mlp", **gated)(
        h, train=train, live=live)
    return out.astype(cfg.jnp_dtype), aux


class Block(nn.Module):
    config: TransformerConfig
    layer_idx: Optional[int] = None


    @nn.compact
    def __call__(self, x, positions, mask=None, cache=None, train=True,
                 prefill=False, live=None):
        # ``prefill``: STATIC bool — this call is a from-zero multi-token
        # prefill, so attention can take the flash path over the fresh
        # q/k/v (see Attention).  Threaded as a positional static arg
        # because jax.checkpoint turns `positions` into a tracer, hiding
        # the fact from any staticness test inside.
        cfg = self.config
        if not cfg.pre_layer_norm:
            # post-LN (opt-350m): norm follows each residual add
            attn, new_cache = Attention(cfg, layer_idx=self.layer_idx,
                                        name="attn")(x, positions, mask,
                                                     cache, prefill=prefill)
            x = _norm(cfg, "input_norm")(x + attn).astype(cfg.jnp_dtype)
            mlp_out, aux = _block_mlp(cfg, self.layer_idx, x, train=train,
                                      live=live)
            x = _norm(cfg, "post_attn_norm")(x + mlp_out).astype(cfg.jnp_dtype)
            return x, new_cache, aux
        normed = _norm(cfg, "input_norm")(x).astype(cfg.jnp_dtype)
        attn, new_cache = Attention(cfg, layer_idx=self.layer_idx,
                                    name="attn")(normed, positions, mask,
                                                 cache, prefill=prefill)
        if cfg.parallel_residual:
            mlp_in = normed if cfg.shared_attn_mlp_norm else \
                _norm(cfg, "post_attn_norm")(x).astype(cfg.jnp_dtype)
            mlp_out, aux = _block_mlp(cfg, self.layer_idx, mlp_in,
                                      train=train, live=live)
            x = x + attn + mlp_out
        else:
            x = x + attn
            mlp_out, aux = _block_mlp(
                cfg, self.layer_idx,
                _norm(cfg, "post_attn_norm")(x).astype(cfg.jnp_dtype),
                train=train, live=live)
            x = x + mlp_out
        return x, new_cache, aux


class ScanBlock(Block):
    """Block with the (carry, output) signature nn.scan requires.  The
    carry is ``(activation, stacked_cache)``: the FULL ``[L, ...]`` KV
    cache rides the carry with a per-iteration layer counter, so decode
    writes ONE token slice per step in place — the previous ys-based
    design re-materialized the entire cache every decode step (a
    ~full-HBM-cache write per generated token)."""

    @nn.compact
    def __call__(self, carry, positions, mask=None, prefill=False):
        x, cache = carry
        x, new_cache, aux = Block.__call__(self, x, positions, mask, cache,
                                           True, prefill)
        if new_cache is not None:
            new_cache = dict(new_cache, layer=new_cache["layer"] + 1)
        return (x, new_cache), aux


class Transformer(nn.Module):
    """Decoder-only LM.  ``__call__(batch)`` returns the causal-LM loss when
    ``batch`` has ``labels`` (or shifts ``input_ids``), else logits."""
    config: TransformerConfig

    def setup(self):
        cfg = self.config
        embed_dim = cfg.embed_proj_dim or cfg.hidden_size
        self.embed_tokens = nn.Embed(cfg.vocab_size, embed_dim,
                                     param_dtype=jnp.float32, name="embed_tokens")
        if cfg.embed_proj_dim is not None:
            self.project_in = nn.Dense(cfg.hidden_size, use_bias=False,
                                       dtype=cfg.jnp_dtype,
                                       param_dtype=jnp.float32,
                                       name="project_in")
            self.project_out = nn.Dense(cfg.embed_proj_dim, use_bias=False,
                                        dtype=cfg.jnp_dtype,
                                        param_dtype=jnp.float32,
                                        name="project_out")
        if cfg.position_embedding == "learned":
            self.embed_positions = nn.Embed(cfg.max_seq_len, cfg.hidden_size,
                                            param_dtype=jnp.float32,
                                            name="embed_positions")
        if cfg.embedding_norm:
            self.embed_norm = _norm(cfg, "embed_norm")
        block = ScanBlock if cfg.scan_layers else Block
        if cfg.remat:
            policy = resolve_remat_policy(cfg.remat_policy)
            # `train` and `prefill` gate Python control flow (MoE gate
            # regime / flash-vs-cached attention) and must stay static
            # bools through jax.checkpoint, so they ride positionally:
            # non-scan Block(self, x, positions, mask, cache, train,
            # prefill) -> (5, 6); ScanBlock(self, carry, positions, mask,
            # prefill) -> (4,).  (kwargs are not covered by
            # static_argnums.)
            static = (4,) if cfg.scan_layers else (5, 6)
            block = nn.remat(block, policy=policy, static_argnums=static)
        if cfg.scan_layers:
            self.blocks = nn.scan(
                block,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, name="layers")
        else:
            self.block_list = [block(cfg, layer_idx=i, name=f"layers_{i}")
                               for i in range(cfg.num_layers)]
        if cfg.pre_layer_norm:
            self.final_norm = _norm(cfg, "final_norm")
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Dense(cfg.vocab_size, use_bias=cfg.lm_head_bias,
                                    dtype=cfg.jnp_dtype, param_dtype=jnp.float32,
                                    name="lm_head")

    def hidden_states(self, input_ids, mask=None, cache=None, start_pos=0,
                      with_aux=False, train=True, live=None):
        cfg = self.config
        if live is not None and cfg.scan_layers:
            raise ValueError("live= reaches expert layers only, and an "
                             "expert trunk is never scanned")
        B, S = input_ids.shape
        # start_pos: scalar, or [B] per-row offsets (padded-prompt decode —
        # each row continues from its own prompt length).  The RANK of
        # start_pos statically selects the cache-write path: per-row
        # offsets need a scatter, the shared-position fast path keeps the
        # proven dynamic_update_slice (see Attention).
        start = jnp.asarray(start_pos)
        per_row_pos = start.ndim >= 1
        if start.ndim == 1:
            start = start[:, None]
        positions = start + jnp.broadcast_to(jnp.arange(S), (B, S))
        x = self.embed_tokens(input_ids).astype(cfg.jnp_dtype)
        if cfg.embed_proj_dim is not None:
            x = self.project_in(x)
        if cfg.position_embedding == "learned":
            x = x + self.embed_positions(positions).astype(cfg.jnp_dtype)
        if cfg.embedding_norm:
            x = self.embed_norm(x).astype(cfg.jnp_dtype)
        marker = {"per_row": jnp.zeros((), jnp.int32)} if per_row_pos else {}
        if cache is not None and "pages" in cache:
            # paged pool: the per-row page table threads every layer's
            # cache dict unchanged (pages are constant across layers),
            # and so does the caller's promise of run-aligned starts
            # (``_paged_write``)
            marker["pages"] = cache["pages"]
            if "page_runs" in cache:
                marker["page_runs"] = cache["page_runs"]
        # from-zero multi-token prefill, decided where the start is
        # still STATICALLY visible (generation passes a literal 0;
        # inside the remat-wrapped block `positions` is a tracer):
        # attention then takes the flash path over the fresh q/k/v
        # instead of the dense cached fallback (see Attention)
        prefill = (cache is not None and S > 1
                   and isinstance(start_pos, (int, np.integer))
                   and int(start_pos) == 0)
        if cfg.scan_layers:
            carry_cache = None if cache is None else \
                {**_cache_data(cache),
                 "layer": jnp.asarray(0, jnp.int32), **marker}
            (x, out_cache), aux_layers = self.blocks((x, carry_cache),
                                                     positions, mask,
                                                     prefill)
            aux = jnp.sum(aux_layers)
            new_cache = None if cache is None else _cache_data(out_cache)
        else:
            aux = 0.0
            # the full stacked cache threads through the loop; each layer
            # writes only its token slice (see Attention stacked-carry path)
            cur = None if cache is None else _cache_data(cache)
            for i, blk in enumerate(self.block_list):
                layer_cache = None if cur is None else \
                    {**cur, "layer": jnp.asarray(i, jnp.int32), **marker}
                # train/prefill positional: static_argnums only covers
                # positionals
                x, nc, a = blk(x, positions, mask, layer_cache, train,
                               prefill, live)
                if cur is not None:
                    cur = _cache_data(nc)
                aux = aux + a
            new_cache = cur
        h = self.final_norm(x).astype(cfg.jnp_dtype) \
            if cfg.pre_layer_norm else x
        if with_aux:
            return h, new_cache, aux
        return (h, new_cache) if cache is not None else h

    def _head(self, x):
        if self.config.embed_proj_dim is not None:
            x = self.project_out(x)
        if self.config.tie_word_embeddings:
            emb = self.embed_tokens.embedding.astype(self.config.jnp_dtype)
            return x @ emb.T
        return self.lm_head(x)

    def _head_pure(self, ref):
        """Pure head closure over concrete weight arrays — safe to call
        inside ``jax.checkpoint``/``lax.map`` (a bound ``nn.Dense`` is not:
        flax modules cannot be invoked under raw jax transforms).  ``ref``
        is any [..., S, h] activation; a zero-width slice through lm_head /
        project_out forces their params to exist at init time with no
        compute."""
        cfg = self.config
        proj = None
        head_ref = ref[..., :0, :]
        if cfg.embed_proj_dim is not None:
            # zero-width pass both forces project_out's params to exist and
            # gives lm_head its (projected-width) init reference
            head_ref = self.project_out(head_ref)
            proj = jnp.asarray(
                self.project_out.variables["params"]["kernel"], cfg.jnp_dtype)
        # keep the projection as a separate matmul: folding proj @ W would
        # materialize a [hidden, vocab] weight and ~2x the head FLOPs
        chain = (lambda x: x) if proj is None else (lambda x: x @ proj)
        if cfg.tie_word_embeddings:
            W = self.embed_tokens.embedding.astype(cfg.jnp_dtype).T
            return lambda x: chain(x) @ W
        self.lm_head(head_ref)
        p = self.lm_head.variables["params"]
        W = jnp.asarray(p["kernel"], cfg.jnp_dtype)
        if "bias" in p:
            b = jnp.asarray(p["bias"], cfg.jnp_dtype)
            return lambda x: chain(x) @ W + b
        return lambda x: chain(x) @ W

    def logits(self, input_ids, mask=None):
        return self._head(self.hidden_states(input_ids, mask, train=False))

    def decode(self, input_ids, cache, start_pos, logits_at=None, live=None):
        """KV-cached decode/prefill step: returns (logits, new_cache).
        ``input_ids``: [B, S_step]; positions are ``start_pos + arange``.

        ``live`` ([B, S_step] bool, optional): the tokens that are real —
        the serving programs pass it for a model with expert layers, so
        that dead lanes and a chunk's padded tail are routed nowhere
        (``moe/dropless.py``).

        ``logits_at`` ([B] int32, optional): project ONLY these per-row
        positions through the vocab head, returning [B, 1, V].  Generation
        prefill needs just each row's last real position — the full
        [B, S, V] prefill logits are a multi-GB temporary at long prompts
        (bs16 x 3968 x 50k vocab = 6.4 GB bf16) that OOMs a 16 GB chip."""
        h, new_cache = self.hidden_states(input_ids, cache=cache,
                                          start_pos=start_pos, train=False,
                                          live=live)
        if logits_at is not None:
            h = jnp.take_along_axis(
                h, logits_at.astype(jnp.int32)[:, None, None], axis=1)
        return self._head(h), new_cache

    def prefill_chunked(self, input_ids, cache, chunk_size, logits_at=None):
        """Memory-bounded prefill: the prompt runs through the trunk in
        ``chunk_size``-token blocks via an ``nn.scan`` over chunks (params
        broadcast, cache carried), each chunk attending to the cache
        through the Pallas chunk kernel — per-layer transients are
        O(B·chunk) instead of O(B·prompt), which is what lets a 4k-prompt
        or bs128 prefill fit next to the KV cache (reference analog: the
        workspace-resident incremental prefill of ``inference_context.h``).

        The prompt is right-padded to a chunk multiple; padded positions
        write garbage K/V beyond the live region, which is safe: every
        attention path masks positions beyond each query's own position,
        and decode overwrites position ``prompt_len + t`` before reading
        it.  Returns ``(logits, cache)`` like :meth:`decode` —
        ``logits_at`` ([B] int32) selects the per-row positions projected
        through the vocab head ([B, 1, V]); default is the last prompt
        position.
        """
        cfg = self.config
        B, P = input_ids.shape
        C = int(chunk_size)
        n = -(-P // C)
        ids = jnp.pad(input_ids, ((0, 0), (0, n * C - P)))
        chunks = ids.reshape(B, n, C).swapaxes(0, 1)          # [n, B, C]
        starts = (jnp.arange(n) * C).astype(jnp.int32)
        if logits_at is None:
            logits_at = jnp.full((B,), P - 1, jnp.int32)
        logits_at = logits_at.astype(jnp.int32)

        # each chunk selects its rows' requested hidden vectors and merges
        # them into a [B, 1, hidden] carry — stacking every chunk's full
        # hidden states as scan outputs would reintroduce the O(B x P x h)
        # transient this method exists to avoid
        def _chunk_body(mdl, carry, xs):
            cache, h_sel = carry
            start, chunk_ids = xs
            h, new_cache = mdl.hidden_states(chunk_ids, cache=cache,
                                             start_pos=start, train=False)
            local = jnp.clip(logits_at - start, 0, C - 1)
            h_c = jnp.take_along_axis(h, local[:, None, None], axis=1)
            in_chunk = ((logits_at >= start)
                        & (logits_at < start + C))[:, None, None]
            return (_cache_data(new_cache),
                    jnp.where(in_chunk, h_c, h_sel)), ()

        scanner = nn.scan(_chunk_body, variable_broadcast="params",
                          split_rngs={"params": False, "dropout": False},
                          in_axes=0, out_axes=0)
        h0 = jnp.zeros((B, 1, cfg.hidden_size), cfg.jnp_dtype)
        (new_cache, h_last), _ = scanner(self, (_cache_data(cache), h0),
                                         (starts, chunks))
        return self._head(h_last), new_cache

    def init_cache(self, batch_size, max_len, dtype=None):
        """Zero KV cache: [L, B, max_len, KVH*D] per k/v (layer-stacked for
        the scanned trunk; S-major with flattened heads so decode cache
        writes are the raw projection output and the decode kernel's KV
        DMAs are contiguous full-lane-width slabs).  With
        ``kv_cache_quant`` the payloads are int8 plus per-(position,
        kv-head) float scales [L, B, max_len, KVH]."""
        cfg = self.config
        dtype = dtype or cfg.jnp_dtype
        shape = (cfg.num_layers, batch_size, max_len,
                 cfg.kv_heads * cfg.head_dim)
        if cfg.kv_cache_quant:
            sshape = shape[:-1] + (cfg.kv_heads,)
            return {"k": jnp.zeros(shape, jnp.int8),
                    "v": jnp.zeros(shape, jnp.int8),
                    "k_scale": jnp.zeros(sshape, jnp.float32),
                    "v_scale": jnp.zeros(sshape, jnp.float32)}
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def slot_contract(self):
        """For the slot engine (``models/contract.py``): every default, and
        the expert load where the expert layers are dropless."""
        cfg = self.config
        return SlotContract(
            vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
            dtype=cfg.dtype, num_layers=cfg.num_layers,
            attention_bias=cfg.position_embedding == "alibi",
            routes_experts=cfg.moe_num_experts > 0
            and cfg.moe_capacity_factor is None,
            expert_layers=sum(_is_moe_layer(cfg, i)
                              for i in range(cfg.num_layers)),
            experts=cfg.moe_num_experts)

    def init_paged_cache(self, num_pages, page_size, dtype=None):
        """Zero PAGED KV pool: ``[L, num_pages, page_size, KVH*D]`` per
        k/v (+ per-(position, kv-head) scales with ``kv_cache_quant``).
        Physical pages are position-order-free: a consumer threads a
        per-row page table (``cache["pages"]``: virtual page ``pos //
        page_size`` → physical page) through ``decode``, and the
        attention paths see the gathered virtual view.  Page 0 is
        conventionally the serving engine's reserved trash page (never
        allocated; unmapped table entries point at it)."""
        cfg = self.config
        dtype = dtype or cfg.jnp_dtype
        shape = (cfg.num_layers, int(num_pages), int(page_size),
                 cfg.kv_heads * cfg.head_dim)
        if cfg.kv_cache_quant:
            sshape = shape[:-1] + (cfg.kv_heads,)
            return {"k": jnp.zeros(shape, jnp.int8),
                    "v": jnp.zeros(shape, jnp.int8),
                    "k_scale": jnp.zeros(sshape, jnp.float32),
                    "v_scale": jnp.zeros(sshape, jnp.float32)}
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def __call__(self, batch):
        if isinstance(batch, dict):
            input_ids = batch["input_ids"]
            labels = batch.get("labels")
            mask = batch.get("attention_mask")
        else:
            input_ids, labels, mask = batch, None, None
        if labels is None:
            labels = derive_causal_labels(input_ids, mask)
        cfg = self.config
        C = cfg.loss_seq_chunks
        if C > 1 and input_ids.shape[1] % C != 0:
            logger.warning(
                f"loss_seq_chunks={C} does not divide seq_len="
                f"{input_ids.shape[1]} — falling back to full-logits loss "
                f"(materializes the [B,S,V] tensor)")
            C = 0
        h, _, aux = self.hidden_states(input_ids, mask, with_aux=True)
        if C > 1:
            loss = chunked_cross_entropy_loss(h, labels, self._head_pure(h), C)
        else:
            loss = cross_entropy_loss(self._head(h), labels)
        if cfg.moe_num_experts > 0:
            loss = loss + cfg.moe_aux_coef * aux
        return loss


def derive_causal_labels(input_ids, attention_mask=None, ignore_index=-100):
    """Next-token labels from inputs; padded positions (mask==0) are
    excluded so pad ids are never trained as targets."""
    labels = jnp.pad(input_ids[..., 1:], [(0, 0)] * (input_ids.ndim - 1) + [(0, 1)],
                     constant_values=ignore_index)
    if attention_mask is not None:
        next_mask = jnp.pad(attention_mask[..., 1:],
                            [(0, 0)] * (attention_mask.ndim - 1) + [(0, 1)],
                            constant_values=0)
        labels = jnp.where(next_mask.astype(bool), labels, ignore_index)
    return labels


@jax.named_scope("loss")
def chunked_cross_entropy_loss(h, labels, head_fn, n_chunks,
                               ignore_index=-100):
    """Sequence-chunked causal-LM loss: the head matmul + CE run per chunk
    under ``jax.checkpoint`` so only one chunk's [B, S/C, V] logits is ever
    live (fwd or bwd) — the backward recomputes each chunk's logits instead
    of storing the full [B, S, V] fp32 tensor.  Matches
    ``cross_entropy_loss`` exactly (sum-of-nll / count composition)."""
    B, S, _ = h.shape
    if S % n_chunks:
        raise ValueError(f"seq_len {S} not divisible by n_chunks {n_chunks}")
    csz = S // n_chunks

    @jax.checkpoint
    def one(args):
        hb, lb = args
        # the head closure carries no flax frame (``_head_pure``)
        with jax.named_scope("head"):
            logits = head_fn(hb).astype(jnp.float32)
        valid = lb != ignore_index
        safe = jnp.where(valid, lb, 0)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - gold) * valid), jnp.sum(valid)

    if os.environ.get("DSTPU_LOSS_CHUNK_UNROLL", "0") == "1":
        # unrolled variant: chunks slice h directly (no chunk-major copy of
        # the full activation, no dynamic-update-slice in the backward) and
        # XLA can interleave chunk i's CE (VPU) with chunk i+1's head
        # matmul (MXU)
        parts = [one((jax.lax.dynamic_slice_in_dim(h, i * csz, csz, axis=1),
                      jax.lax.dynamic_slice_in_dim(labels, i * csz, csz,
                                                   axis=1)))
                 for i in range(n_chunks)]
        sums = jnp.stack([p[0] for p in parts])
        counts = jnp.stack([p[1] for p in parts])
    else:
        # chunk-major copy once, then a compact while loop over chunks
        hc = h.reshape(B, n_chunks, csz, h.shape[-1]).transpose(1, 0, 2, 3)
        lc = labels.reshape(B, n_chunks, csz).transpose(1, 0, 2)
        sums, counts = jax.lax.map(one, (hc, lc))
    return jnp.sum(sums) / jnp.maximum(jnp.sum(counts), 1)


@jax.named_scope("loss")
def cross_entropy_loss(logits, labels, ignore_index=-100, z_loss=0.0):
    """Causal-LM loss with ignore-index masking, computed in fp32."""
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    loss = nll.sum() / jnp.maximum(valid.sum(), 1)
    if z_loss > 0.0:
        loss = loss + z_loss * jnp.mean((logz * valid) ** 2)
    return loss
