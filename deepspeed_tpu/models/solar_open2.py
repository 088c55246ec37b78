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
(``paging.SlotPages``): the softmax layers' K/V rows in LANE pages under the
slot's page table, growing with the context, and TWO fixed-size states a
slot behind its STATE ROW — ``conv``, the last ``taps - 1`` rows of the
``[q | k | v]`` projections in the cache's dtype, and ``kda``, the
delta-rule state, FLOAT32 whatever dtype the server passes.  A request's
first chunk starts both from zeros, a chunk leaves both as they stand after
its last REAL row, and a dead lane of a decode block writes the trash row.

This is a serving model: :meth:`SolarOpen2Model.decode` over the slot
engine's pools and a plain uncached forward (``__call__``).  It has no
``generate()`` cache and no training step (the state scan and the dropless
expert kernels have no VJP).
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
    # what the attention registry reads off a config
    kv_cache_quant: bool = False
    decode_int8_matmuls: bool = False

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
    """The gated delta-rule mixer.  ``state`` is ``None`` (a sequence from
    its start, nothing kept) or ``(conv pool [KDA layers, rows,
    ...short_conv.rows_shape], kda pool [KDA layers, rows, heads, d, d],
    layer index in the pools, rows)`` — ``rows [N]`` for one token a lane, a
    scalar row for a chunk of one slot."""
    config: SolarOpen2Config

    @nn.compact
    def __call__(self, u, state=None, start=None, last=None, live=None):
        """``u [T, hidden]``.  A chunk (``start`` a scalar, or ``state``
        None): ``T`` consecutive positions of ONE sequence from ``start``,
        ``last`` its last real row (the padded tail reaches neither state).
        A step (``start`` None, ``state`` given): row ``n`` is lane ``n``'s
        one token, ``live [N]`` the lanes that are.  Returns ``(out, conv
        pool, kda pool)``."""
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
            return dense(cfg.hidden_size, "o_proj")(out), conv_pool, \
                kda_pool if state is not None else None


class GatedAttention(nn.Module):
    """Grouped-query softmax attention with no positional encoding and a
    sigmoid gate on the heads' outputs, no biases, no QK-norm."""
    config: SolarOpen2Config

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
            out = reference_attention(q, k, v, causal=True)
        else:
            from deepspeed_tpu.ops.transformer.registry import (
                write_and_attend)
            with jax.named_scope("attn.full"):
                out, cache = write_and_attend(cfg, q, k, v, positions, cache)
        out = out.reshape(out.shape[:2] + (H * D,))
        if cfg.gqa_gate:
            gate = nn.Dense(H * D, use_bias=False, dtype=cfg.jnp_dtype,
                            name="gate_proj")(u)
            with jax.named_scope("attn.out_gate"):
                out = out * jax.nn.sigmoid(
                    gate.astype(jnp.float32)).astype(out.dtype)
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.jnp_dtype,
                        name="o_proj")(out), cache


class SolarOpen2Layer(nn.Module):
    config: SolarOpen2Config
    layer_idx: int

    def setup(self):
        cfg = self.config
        self.input_layernorm = _Norm(cfg.rms_norm_eps)
        self.post_attention_layernorm = _Norm(cfg.rms_norm_eps)
        if self.layer_idx in cfg.gqa_layers:
            self.self_attn = GatedAttention(cfg)
        else:
            self.linear_attn = KimiDeltaAttention(cfg)
        self.moe_mlp = MoE(
            hidden_size=cfg.hidden_size, num_experts=cfg.n_routed_experts,
            k=cfg.moe_top_k, capacity_factor=None,
            norm_topk_prob=cfg.norm_topk_prob,
            ffn_hidden_size=cfg.moe_intermediate_size, dtype=cfg.jnp_dtype,
            gated=True, activation=nn.silu, scoring="sigmoid",
            routed_scaling=cfg.routed_scaling_factor,
            shared_ffn_hidden_size=cfg.n_shared_experts
            * cfg.moe_intermediate_size,
            held_experts=cfg.held_experts)

    def __call__(self, x, mix, live=None):
        """``mix(mixer, normed x) -> (out, cache)``: the call form the model
        chose (chunk or step) with this layer's cache."""
        mixer = self.self_attn if self.layer_idx in self.config.gqa_layers \
            else self.linear_attn
        a, cache = mix(mixer, self.input_layernorm(x))
        x = x + a
        y, _, _ = self.moe_mlp(self.post_attention_layernorm(x), train=False,
                               live=live)
        return x + y, cache


class SolarOpen2Model(nn.Module):
    config: SolarOpen2Config

    def setup(self):
        cfg = self.config
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                                     dtype=cfg.jnp_dtype)
        self.layers = [SolarOpen2Layer(cfg, i) for i in range(cfg.num_layers)]
        self.norm = _Norm(cfg.rms_norm_eps)
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                dtype=cfg.jnp_dtype)

    def _head(self, h, at=None):
        """Logits of ``h [B, S, hidden]``, or of row ``at[b]`` of each."""
        with jax.named_scope("head.logits"):
            if at is not None:
                h = jnp.take_along_axis(
                    h, at.astype(jnp.int32)[:, None, None], axis=1)
            return self.lm_head(self.norm(h))

    def __call__(self, batch):
        """Logits ``[B, S, V]`` of ``batch["input_ids"] [B, S]``: the plain
        causal forward, a row at a time, no cache."""
        cfg, rows = self.config, []
        for ids in batch["input_ids"]:
            x = self.embed_tokens(ids)
            positions = jnp.arange(ids.shape[0])[None]
            for i, layer in enumerate(self.layers):
                if i in cfg.gqa_layers:
                    mix = lambda op, u: (op(u[None], positions)[0][0], None)
                else:
                    mix = lambda op, u: (op(u, start=0)[0], None)
                x, _ = layer(x, mix)
            rows.append(self._head(x[None])[0])
        return jnp.stack(rows)

    # ---- the serving path ---- #
    def slot_contract(self):
        """For the slot engine (``models/contract.py``): K/V pages under the
        slot's table for the softmax layers; behind its STATE ROW the linear
        layers' two states, ``conv`` and the float32 ``kda``; one chunk a
        dispatch (the state is a slot's); dropless experts in every layer, a
        share of them held."""
        cfg = self.config
        return SlotContract(
            vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
            dtype=cfg.dtype, num_layers=cfg.num_layers,
            lane_layers=len(cfg.gqa_layers), kv_pages=True,
            state_kinds=("conv", "kda"), chunk_cap=CHUNK_CAP,
            chunk_fault=self._chunk_fault, own_chunk_path=True,
            routes_experts=True, holds_share=cfg.held_experts is not None,
            expert_layers=cfg.num_layers,
            experts=(cfg.held_experts or (0, cfg.n_routed_experts))[1],
            chunk_work=self._chunk_work, block_work=self._block_work,
            work_counters=("kda_scan_rows", "kda_state_rows", "full_keys"))

    @staticmethod
    def _chunk_fault(chunk):
        from deepspeed_tpu.ops.transformer.registry import MAX_CHUNK_S
        if chunk > MAX_CHUNK_S and chunk % MAX_CHUNK_S:
            return (f"a chunk over {MAX_CHUNK_S} is whole {MAX_CHUNK_S}-query "
                    f"blocks of the paged chunk kernel; {chunk} is not")
        return None

    def _chunk_work(self, start, end, page_size, ring_pages, layers):
        """What a prefill chunk over REAL positions ``start .. end - 1``
        does, as its dispatch span's args: ``kda_scan_rows`` — positions x
        linear layers the state scan advanced over —, ``kda_state_rows`` —
        state rows read and written, one a linear layer — and ``full_keys``,
        (query, key) pairs the softmax layers attend."""
        cfg = self.config
        linear = len(cfg.kda_layers)
        return {"kda_scan_rows": linear * (end - start),
                "kda_state_rows": linear,
                "full_keys": len(cfg.gqa_layers)
                * causal_pairs(start, end, end)}

    def _block_work(self, live, ring_pages, layers):
        """The same for a decode block, from ``live`` — ``(context, steps)``
        a live slot: a step scans one position and moves one state row a
        live lane and linear layer."""
        cfg = self.config
        steps = sum(n for _, n in live)
        return {"kda_scan_rows": len(cfg.kda_layers) * steps,
                "kda_state_rows": len(cfg.kda_layers) * steps,
                "full_keys": len(cfg.gqa_layers)
                * sum(first + i for first, n in live for i in range(n))}

    def init_paged_cache(self, num_pages, page_size, dtype=None,
                         state_rows=1):
        """``k`` / ``v [softmax layers, num_pages, page, KV heads x
        head_dim]`` behind the slot's page table, and behind its state row
        (``paging.SlotPages`` sizes both: trash + one row a slot) ``conv
        [linear layers, state_rows, R, 128]`` in ``dtype`` — a row's ``(taps
        - 1) x 3 x width`` values as whole tiles under the row's index
        (``ops/transformer/short_conv.py::rows_shape``) — and ``kda [linear
        layers, state_rows, heads, d, d]`` in FLOAT32 whatever ``dtype`` is:
        the state is summed into over the whole context.  In both the row's
        index is a LEADING dimension: XLA tiles the last two, and a row that
        is a sublane of its tiles is written back a masked store a tile."""
        from deepspeed_tpu.ops.transformer.short_conv import rows_shape
        cfg = self.config
        dtype = dtype or cfg.jnp_dtype
        linear = len(cfg.kda_layers)
        kv = (len(cfg.gqa_layers), int(num_pages), int(page_size),
              cfg.num_kv_heads * cfg.head_dim)
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
                "conv": jnp.zeros((linear, int(state_rows)) + rows_shape(
                    cfg.conv_size, 3 * cfg.kda_width, dtype), dtype),
                "kda": jnp.zeros((linear, int(state_rows), cfg.kda_heads,
                                  cfg.kda_head_dim, cfg.kda_head_dim),
                                 jnp.float32)}

    def decode(self, input_ids, cache, start_pos, logits_at=None, live=None):
        """The slot programs' call: a prefill chunk of one slot
        (``input_ids [1, C]``, scalar ``start_pos``) or one token a lane
        (``[N, 1]``, ``start_pos [N]``).  ``cache["pages"]`` is the table
        row(s): the slot's pages, then its state row."""
        cfg = self.config
        per_row = jnp.ndim(start_pos) == 1
        kv = {"k": cache["k"], "v": cache["v"]}
        conv_pool, kda_pool = cache["conv"], cache["kda"]
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
        x = self.embed_tokens(ids)
        for i, layer in enumerate(self.layers):
            if i in cfg.gqa_layers:
                layer_cache = {**kv, "pages": table, **marker,
                               "layer": jnp.asarray(cfg.gqa_layers.index(i),
                                                    jnp.int32)}

                def mix(op, u, layer_cache=layer_cache):
                    u = u[:, None] if per_row else u[None]
                    out, new = op(u, positions, layer_cache)
                    return (out[:, 0] if per_row else out[0]), new

                x, new = layer(x, mix, live=flat_live)
                kv = {"k": new["k"], "v": new["v"]}
            else:
                at = cfg.kda_layers.index(i)

                def mix(op, u, at=at):
                    if per_row:
                        out, *pools = op(u, (conv_pool, kda_pool, at, rows),
                                         live=flat_live)
                    else:
                        out, *pools = op(u, (conv_pool, kda_pool, at, row),
                                         start_pos, last)
                    return out, pools

                x, (conv_pool, kda_pool) = layer(x, mix, live=flat_live)
        with jax.named_scope("slots.tables"):
            h = x[:, None] if per_row else x[None]
        return self._head(h, logits_at), {**kv, "conv": conv_pool,
                                          "kda": kda_pool}
