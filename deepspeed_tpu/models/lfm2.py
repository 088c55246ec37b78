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
``z``.  In the slot engine that state is a kind of its own beside the
attention layers' K/V pages (``paging.SlotPages``, ``state_kinds``): the
pool ``conv [conv layers, 1 + slots, (conv_L_cache - 1) x hidden]``, one
row a slot, row 0 the trash row, the slot's row index the LAST entry of
its page-table row.  K/V pool layers exist for the ATTENTION layers only.

This is a serving model: :meth:`Lfm2Model.decode` over the slot engine's
pools and a plain uncached forward (``__call__``).  It has no
``generate()`` cache and no training step (the dropless expert kernels
have no VJP).
"""

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.contract import SlotContract
from deepspeed_tpu.models.dots3 import _Mlp, _Norm
from deepspeed_tpu.models.latent_attention import _rms
from deepspeed_tpu.models.transformer import _rope, reference_attention
from deepspeed_tpu.moe.layer import MoE

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
    # what the attention registry reads off a config
    kv_cache_quant: bool = False
    decode_int8_matmuls: bool = False

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
    """The gated short convolution.  ``state`` is ``None`` (a sequence
    from its start, nothing kept) or ``(pool [conv layers, rows,
    ...short_conv.rows_shape], layer index in the pool, rows)`` — ``rows
    [N]`` for one token a lane, a scalar row for a chunk of one slot."""
    config: Lfm2Config

    @nn.compact
    def __call__(self, u, state=None, start=None, last=None):
        """``u [T, hidden]``.  A chunk (``start`` a scalar, or ``state``
        None): ``T`` consecutive positions of ONE sequence from ``start``;
        ``last`` is its last real row (the padded tail's ``z`` never
        reaches the state).  A step (``start`` None, ``state`` given): row
        ``n`` is lane ``n``'s one token.  Returns ``(out, pool)``."""
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
        return dense(h, "out_proj")(y), pool


class Lfm2Attention(nn.Module):
    """Grouped-query attention, per-head RMSNorm on q and k, rope on the
    whole head (half-split), no biases."""
    config: Lfm2Config

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
        gain = lambda name: self.param(name, nn.initializers.ones, (D,),
                                       jnp.float32)
        q = _rms(dense(H, "q_proj")(u), gain("q_norm"), cfg.norm_eps)
        k = _rms(dense(KVH, "k_proj")(u), gain("k_norm"), cfg.norm_eps)
        v = dense(KVH, "v_proj")(u)
        q, k = _rope(q, k, positions, D, cfg.rope_theta)
        if cache is None:
            out = reference_attention(q, k, v, causal=True)
        else:
            from deepspeed_tpu.ops.transformer.registry import (
                write_and_attend)
            out, cache = write_and_attend(cfg, q, k, v, positions, cache)
        return nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1), use_bias=False,
                               dtype=cfg.jnp_dtype, name="out_proj")(out), \
            cache


class Lfm2Layer(nn.Module):
    config: Lfm2Config
    layer_idx: int

    def setup(self):
        cfg, i = self.config, self.layer_idx
        self.operator_norm = _Norm(cfg.norm_eps)
        self.ffn_norm = _Norm(cfg.norm_eps)
        if cfg.layer_types[i] == "conv":
            self.conv = ShortConv(cfg)
        else:
            self.self_attn = Lfm2Attention(cfg)
        if i < cfg.num_dense_layers:
            self.feed_forward = _Mlp(cfg.intermediate_size, cfg.jnp_dtype)
        else:
            self.moe_mlp = MoE(
                hidden_size=cfg.hidden_size, num_experts=cfg.num_experts,
                k=cfg.moe_top_k, capacity_factor=None,
                norm_topk_prob=cfg.norm_topk_prob,
                ffn_hidden_size=cfg.moe_intermediate_size,
                dtype=cfg.jnp_dtype, gated=True, activation=nn.silu,
                scoring="sigmoid", routed_scaling=cfg.routed_scaling_factor,
                gate_sum_eps=GATE_SUM_EPS)

    def __call__(self, x, operate, live=None):
        """``operate(operator, normed x) -> (out, cache)``: the call form
        the model chose (chunk or step) with this layer's cache."""
        op = self.conv if self.config.layer_types[self.layer_idx] == "conv" \
            else self.self_attn
        a, cache = operate(op, self.operator_norm(x))
        x = x + a
        m = self.ffn_norm(x)
        if self.layer_idx < self.config.num_dense_layers:
            return x + self.feed_forward(m), cache
        y, _, _ = self.moe_mlp(m, train=False, live=live)
        return x + y, cache


class Lfm2Model(nn.Module):
    config: Lfm2Config

    def setup(self):
        cfg = self.config
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                                     dtype=cfg.jnp_dtype)
        self.layers = [Lfm2Layer(cfg, i) for i in range(cfg.num_layers)]
        self.embedding_norm = _Norm(cfg.norm_eps)

    def _head(self, h):
        emb = self.embed_tokens.embedding.astype(self.config.jnp_dtype)
        return self.embedding_norm(h) @ emb.T

    def __call__(self, batch):
        """Logits ``[B, S, V]`` of ``batch["input_ids"] [B, S]``: the plain
        causal forward, a row at a time, no cache."""
        cfg, rows = self.config, []
        for ids in batch["input_ids"]:
            x = self.embed_tokens(ids)
            positions = jnp.arange(ids.shape[0])[None]
            for i, layer in enumerate(self.layers):
                if cfg.layer_types[i] == "conv":
                    operate = lambda op, u: op(u, start=0)
                else:
                    operate = lambda op, u: (op(u[None], positions)[0][0],
                                             None)
                x, _ = layer(x, operate)
            rows.append(self._head(x))
        return jnp.stack(rows)

    # ---- the serving path ---- #
    def slot_contract(self):
        """For the slot engine (``models/contract.py``): the conv layers'
        state behind the slot's STATE ROW (``paging.SlotPages``) beside the
        attention layers' K/V pages; dropless experts after the dense
        layers."""
        cfg = self.config
        return SlotContract(
            vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
            dtype=cfg.dtype, num_layers=cfg.num_layers,
            state_kinds=("conv",), routes_experts=True,
            expert_layers=cfg.num_layers - cfg.num_dense_layers,
            experts=cfg.num_experts)

    def init_paged_cache(self, num_pages, page_size, dtype=None,
                         state_rows=1):
        """``k`` / ``v [attention layers, num_pages, page, KV heads x
        head_dim]`` behind the slot's page table, and ``conv [conv layers,
        state_rows, R, 128]`` behind its state row (``paging.SlotPages``
        sizes it: trash + one row a slot): a row's ``(conv_L_cache - 1) x
        hidden`` values as whole tiles under the row's index
        (``ops/transformer/short_conv.py::rows_shape``).  The index is a
        LEADING dimension because XLA tiles the last two: were it one of
        them, a slot's row would be a sublane of every tile it touches and
        a step's write-back a masked store a tile."""
        from deepspeed_tpu.ops.transformer.short_conv import rows_shape
        cfg = self.config
        dtype = dtype or cfg.jnp_dtype
        kv = (len(cfg.layers_of("full_attention")), int(num_pages),
              int(page_size), cfg.num_kv_heads * cfg.head_dim)
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
                "conv": jnp.zeros(
                    (len(cfg.layers_of("conv")), int(state_rows))
                    + rows_shape(cfg.conv_L_cache, cfg.hidden_size, dtype),
                    dtype)}

    def decode(self, input_ids, cache, start_pos, logits_at=None, live=None):
        """The slot programs' call: a prefill chunk of one slot
        (``input_ids [1, C]``, scalar ``start_pos``) or one token a lane
        (``[N, 1]``, ``start_pos [N]``).  ``cache["pages"]`` is the table
        row(s): the slot's pages, then its state row."""
        cfg = self.config
        per_row = jnp.ndim(start_pos) == 1
        table, rows = cache["pages"][:, :-1], cache["pages"][:, -1]
        kv = {"k": cache["k"], "v": cache["v"]}
        conv_pool = cache["conv"]
        attns, convs = cfg.layers_of("full_attention"), cfg.layers_of("conv")
        x = self.embed_tokens(input_ids[:, 0] if per_row else input_ids[0])
        flat_live = None if live is None else live.reshape(-1)
        if per_row:
            positions = start_pos[:, None]
            marker = {"per_row": jnp.zeros((), jnp.int32)}
        else:
            positions = (start_pos + jnp.arange(input_ids.shape[1]))[None]
            marker = {"page_runs": cache["page_runs"]} \
                if "page_runs" in cache else {}
        last = None if logits_at is None else logits_at[0].astype(jnp.int32)
        for i, layer in enumerate(self.layers):
            if cfg.layer_types[i] == "conv":
                at = convs.index(i)

                def operate(op, u, at=at):
                    if per_row:
                        return op(u, (conv_pool, at, rows))
                    return op(u, (conv_pool, at, rows[0]), start_pos, last)

                x, conv_pool = layer(x, operate, live=flat_live)
            else:
                layer_cache = {**kv, "pages": table, **marker,
                               "layer": jnp.asarray(attns.index(i),
                                                    jnp.int32)}

                def operate(op, u, layer_cache=layer_cache):
                    u = u[:, None] if per_row else u[None]
                    out, new = op(u, positions, layer_cache)
                    return (out[:, 0] if per_row else out[0]), new

                x, new = layer(x, operate, live=flat_live)
                kv = {"k": new["k"], "v": new["v"]}
        h = x[:, None] if per_row else x[None]
        if logits_at is not None:
            h = jnp.take_along_axis(
                h, logits_at.astype(jnp.int32)[:, None, None], axis=1)
        return self._head(h), {**kv, "conv": conv_pool}
