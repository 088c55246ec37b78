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
(``paging.SlotPages``): the attention layers' K/V rows in LANE pages under
the slot's page table, growing with the context, and TWO fixed-size states a
slot behind its STATE ROW — ``conv``, the last ``taps - 1`` rows of ``xBC``
in the cache's dtype, and ``ssm``, the scan's state, FLOAT32 whatever dtype
the server passes (4 MiB a layer at 128 heads of 64 x 128: nine to one over
the K/V, and at many slots larger than the weights).  A request's first
chunk starts both from zeros, a chunk leaves both as they stand after its
last REAL row, and a dead lane of a decode block writes the trash row.

This is a serving model: :meth:`GraniteHybridModel.decode` over the slot
engine's pools and a plain uncached forward (``__call__``).  It has no
``generate()`` cache and no training step (the scan and the dropless expert
kernels have no VJP).
"""

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.contract import SlotContract
from deepspeed_tpu.models.latent_attention import _rms, causal_pairs
from deepspeed_tpu.models.latent_block import _Norm
from deepspeed_tpu.models.transformer import reference_attention
from deepspeed_tpu.moe.layer import MoE

CHUNK_CAP = 2048             # whole 512-query blocks of the paged chunk kernel


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int
    hidden_size: int
    layer_types: Tuple[str, ...]
    num_heads: int
    num_kv_heads: int
    attention_scale: float       # what the attention registry's kernels take
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
    # what the attention registry reads off a config
    kv_cache_quant: bool = False
    decode_int8_matmuls: bool = False

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
    """The state-space mixer.  ``state`` is ``None`` (a sequence from its
    start, nothing kept) or ``(conv pool [SSM layers, rows,
    ...short_conv.rows_shape], ssm pool [SSM layers, rows, ...state_shape],
    layer index in the pools, rows)`` — ``rows [N]`` for one token a lane, a
    scalar row for a chunk of one slot."""
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, u, state=None, start=None, last=None, live=None):
        """``u [T, hidden]``.  A chunk (``start`` a scalar, or ``state``
        None): ``T`` consecutive positions of ONE sequence from ``start``,
        ``last`` its last real row (the padded tail reaches neither state).
        A step (``start`` None, ``state`` given): row ``n`` is lane ``n``'s
        one token, ``live [N]`` the lanes that are.  Returns ``(out, conv
        pool, ssm pool)``."""
        from deepspeed_tpu.ops.transformer.registry import (
            conv_state_update, ssm_state_update)
        from deepspeed_tpu.ops.transformer.ssd import state_shape
        cfg = self.config
        H, P, N, K = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state, \
            cfg.conv_size
        W, CW = cfg.mamba_width, cfg.conv_width
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
                x, b, c = jnp.split(xbc, [W, W + N], axis=-1)
                x = x.reshape(-1, H, P)
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
                y = _rms((y.astype(f32) * nn.silu(z.astype(f32)))
                         .astype(cfg.jnp_dtype), gain, cfg.rms_norm_eps)
            # no state given: nothing is kept (the one-row pool was scratch)
            return dense(cfg.hidden_size, "out_proj")(y), conv_pool, \
                ssm_pool if state is not None else None


class NopeAttention(nn.Module):
    """Grouped-query softmax attention with no positional encoding at the
    config's own scale, no biases, no QK-norm, no gate."""
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, u, positions, cache=None):
        """``u [B, S, hidden]``, ``positions [B, S]``; ``cache``: what
        ``ops/transformer/registry.py::write_and_attend`` takes (the K/V
        pools, this layer's index in them, the page table) or None for
        plain causal attention over ``u`` alone."""
        cfg = self.config
        H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dense = lambda n, name: nn.DenseGeneral(
            (n, D), use_bias=False, dtype=cfg.jnp_dtype, name=name)
        q, k = dense(H, "q_proj")(u), dense(KVH, "k_proj")(u)
        v = dense(KVH, "v_proj")(u)
        if cache is None:
            out = reference_attention(q, k, v, causal=True,
                                      scale=cfg.attention_scale)
        else:
            from deepspeed_tpu.ops.transformer.registry import (
                write_and_attend)
            with jax.named_scope("attn.full"):
                out, cache = write_and_attend(cfg, q, k, v, positions, cache)
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.jnp_dtype,
                        name="o_proj")(out.reshape(out.shape[:2] + (H * D,))), \
            cache


class GraniteHybridLayer(nn.Module):
    config: GraniteHybridConfig
    layer_idx: int

    def setup(self):
        cfg = self.config
        self.input_layernorm = _Norm(cfg.rms_norm_eps)
        self.post_attention_layernorm = _Norm(cfg.rms_norm_eps)
        if cfg.layer_types[self.layer_idx] == "attention":
            self.self_attn = NopeAttention(cfg)
        else:
            self.mamba = Mamba2Mixer(cfg)
        # a softmax over the chosen logits IS the scored form at a zero
        # bias: softmax over all, the top-k, renormalised
        self.moe_mlp = MoE(
            hidden_size=cfg.hidden_size, num_experts=cfg.num_experts,
            k=cfg.moe_top_k, capacity_factor=None, norm_topk_prob=True,
            ffn_hidden_size=cfg.intermediate_size, dtype=cfg.jnp_dtype,
            gated=True, activation=nn.silu, scoring="softmax", noaux_tc=True,
            shared_ffn_hidden_size=cfg.shared_intermediate_size,
            held_experts=cfg.held_experts)

    def __call__(self, x, mix, live=None):
        """``mix(mixer, normed x) -> (out, cache)``: the call form the model
        chose (chunk or step) with this layer's cache."""
        cfg = self.config
        mixer = self.self_attn if cfg.layer_types[self.layer_idx] \
            == "attention" else self.mamba
        # one rounding a residual add: the multiplier is no bfloat16 number
        add = lambda x, t: (x.astype(jnp.float32) + cfg.residual_multiplier
                            * t.astype(jnp.float32)).astype(x.dtype)
        a, cache = mix(mixer, self.input_layernorm(x))
        x = add(x, a)
        y, _, _ = self.moe_mlp(self.post_attention_layernorm(x), train=False,
                               live=live)
        return add(x, y), cache


class GraniteHybridModel(nn.Module):
    config: GraniteHybridConfig

    def setup(self):
        cfg = self.config
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                                     dtype=cfg.jnp_dtype)
        self.layers = [GraniteHybridLayer(cfg, i)
                       for i in range(cfg.num_layers)]
        self.norm = _Norm(cfg.rms_norm_eps)

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

    def __call__(self, batch):
        """Logits ``[B, S, V]`` of ``batch["input_ids"] [B, S]``: the plain
        causal forward, a row at a time, no cache."""
        cfg, rows = self.config, []
        for ids in batch["input_ids"]:
            x = self._embed(ids)
            positions = jnp.arange(ids.shape[0])[None]
            for i, layer in enumerate(self.layers):
                if cfg.layer_types[i] == "attention":
                    mix = lambda op, u: (op(u[None], positions)[0][0], None)
                else:
                    mix = lambda op, u: (op(u, start=0)[0], None)
                x, _ = layer(x, mix)
            rows.append(self._head(x[None])[0])
        return jnp.stack(rows)

    # ---- the serving path ---- #
    def slot_contract(self):
        """For the slot engine (``models/contract.py``): K/V pages under the
        slot's table for the attention layers; behind its STATE ROW the
        state-space layers' two states, ``conv`` and the float32 ``ssm``;
        one chunk a dispatch (the state is a slot's); dropless experts in
        every layer, a share of them held."""
        cfg = self.config
        return SlotContract(
            vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
            dtype=cfg.dtype, num_layers=cfg.num_layers,
            lane_layers=len(cfg.layers_of("attention")), kv_pages=True,
            state_kinds=("conv", "ssm"), chunk_cap=CHUNK_CAP,
            chunk_fault=self._chunk_fault, own_chunk_path=True,
            routes_experts=True, holds_share=cfg.held_experts is not None,
            expert_layers=cfg.num_layers,
            experts=(cfg.held_experts or (0, cfg.num_experts))[1],
            chunk_work=self._chunk_work, block_work=self._block_work,
            work_counters=("ssd_scan_rows", "ssd_state_rows", "full_keys"))

    @staticmethod
    def _chunk_fault(chunk):
        from deepspeed_tpu.ops.transformer.registry import MAX_CHUNK_S
        if chunk > MAX_CHUNK_S and chunk % MAX_CHUNK_S:
            return (f"a chunk over {MAX_CHUNK_S} is whole {MAX_CHUNK_S}-query "
                    f"blocks of the paged chunk kernel; {chunk} is not")
        return None

    def _chunk_work(self, start, end, page_size, ring_pages, layers):
        """What a prefill chunk over REAL positions ``start .. end - 1``
        does, as its dispatch span's args: ``ssd_scan_rows`` — positions x
        state-space layers the scan advanced over —, ``ssd_state_rows`` —
        state rows read and written, one a state-space layer — and
        ``full_keys``, (query, key) pairs the attention layers attend."""
        cfg = self.config
        mamba = len(cfg.layers_of("mamba"))
        return {"ssd_scan_rows": mamba * (end - start),
                "ssd_state_rows": mamba,
                "full_keys": len(cfg.layers_of("attention"))
                * causal_pairs(start, end, end)}

    def _block_work(self, live, ring_pages, layers):
        """The same for a decode block, from ``live`` — ``(context, steps)``
        a live slot: a step scans one position and moves one state row a
        live lane and state-space layer."""
        cfg = self.config
        mamba, steps = len(cfg.layers_of("mamba")), sum(n for _, n in live)
        return {"ssd_scan_rows": mamba * steps,
                "ssd_state_rows": mamba * steps,
                "full_keys": len(cfg.layers_of("attention"))
                * sum(first + i for first, n in live for i in range(n))}

    def init_paged_cache(self, num_pages, page_size, dtype=None,
                         state_rows=1):
        """``k`` / ``v [attention layers, num_pages, page, KV heads x
        head_dim]`` behind the slot's page table, and behind its state row
        (``paging.SlotPages`` sizes both: trash + one row a slot) ``conv
        [SSM layers, state_rows, R, 128]`` in ``dtype`` — a row's ``(taps -
        1) x conv width`` values as whole tiles under the row's index
        (``ops/transformer/short_conv.py::rows_shape``: 198 x 128 values on
        208 sublanes here) — and ``ssm [SSM layers, state_rows, ...]`` — a
        row the heads' ``[d_head, d_state]`` states as
        ``ops/transformer/ssd.py::state_shape`` lays them — in FLOAT32
        whatever ``dtype`` is: the state is summed into over the whole
        context.  In both the row's index is a LEADING dimension: XLA tiles
        the last two, and a row that is a sublane of its tiles is written
        back a masked store a tile."""
        from deepspeed_tpu.ops.transformer.short_conv import rows_shape
        from deepspeed_tpu.ops.transformer.ssd import state_shape
        cfg = self.config
        dtype = dtype or cfg.jnp_dtype
        mamba = len(cfg.layers_of("mamba"))
        kv = (len(cfg.layers_of("attention")), int(num_pages),
              int(page_size), cfg.num_kv_heads * cfg.head_dim)
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
                "conv": jnp.zeros((mamba, int(state_rows)) + rows_shape(
                    cfg.conv_size, cfg.conv_width, dtype), dtype),
                "ssm": jnp.zeros((mamba, int(state_rows)) + state_shape(
                    cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state),
                    jnp.float32)}

    def decode(self, input_ids, cache, start_pos, logits_at=None, live=None):
        """The slot programs' call: a prefill chunk of one slot
        (``input_ids [1, C]``, scalar ``start_pos``) or one token a lane
        (``[N, 1]``, ``start_pos [N]``).  ``cache["pages"]`` is the table
        row(s): the slot's pages, then its state row."""
        cfg = self.config
        per_row = jnp.ndim(start_pos) == 1
        kv = {"k": cache["k"], "v": cache["v"]}
        conv_pool, ssm_pool = cache["conv"], cache["ssm"]
        attention, mamba = cfg.layers_of("attention"), cfg.layers_of("mamba")
        flat_live = None if live is None else live.reshape(-1)
        with jax.named_scope("slots.tables"):
            table, rows = cache["pages"][:, :-1], cache["pages"][:, -1]
            ids = input_ids[:, 0] if per_row else input_ids[0]
            if per_row:
                positions = start_pos[:, None]
                marker = {"per_row": jnp.zeros((), jnp.int32)}
            else:
                positions = (start_pos
                             + jnp.arange(input_ids.shape[1]))[None]
                marker = {"page_runs": cache["page_runs"]} \
                    if "page_runs" in cache else {}
                row = rows[0]
            last = None if logits_at is None \
                else logits_at[0].astype(jnp.int32)
        x = self._embed(ids)
        for i, layer in enumerate(self.layers):
            if i in attention:
                layer_cache = {**kv, "pages": table, **marker,
                               "layer": jnp.asarray(attention.index(i),
                                                    jnp.int32)}

                def mix(op, u, layer_cache=layer_cache):
                    u = u[:, None] if per_row else u[None]
                    out, new = op(u, positions, layer_cache)
                    return (out[:, 0] if per_row else out[0]), new

                x, new = layer(x, mix, live=flat_live)
                kv = {"k": new["k"], "v": new["v"]}
            else:
                at = mamba.index(i)

                def mix(op, u, at=at):
                    if per_row:
                        out, *pools = op(u, (conv_pool, ssm_pool, at, rows),
                                         live=flat_live)
                    else:
                        out, *pools = op(u, (conv_pool, ssm_pool, at, row),
                                         start_pos, last)
                    return out, pools

                x, (conv_pool, ssm_pool) = layer(x, mix, live=flat_live)
        with jax.named_scope("slots.tables"):
            h = x[:, None] if per_row else x[None]
        return self._head(h, logits_at), {**kv, "conv": conv_pool,
                                          "ssm": ssm_pool}
