"""Trinity (Arcee, ``model_type: afmoe``) — HF ``config.json`` keys to
:class:`TrinityModel`.

The block is a SANDWICH: an RMSNorm before and after each sublayer, four
gains a layer — ``a = x + post_attn(Attn(input(x)))``, ``y = a +
post_mlp(FFN(pre_mlp(a)))``.  Attention is grouped-query with an RMSNorm
over each HEAD of q and k (one gain of ``head_dim`` each), and by
``layer_types[i]`` either ``sliding_attention`` — rope on the whole head
(half-split), the token and its ``sliding_window - 1`` predecessors — or
``full_attention`` with NO positional encoding (NoPE); in both the heads'
outputs are gated elementwise by ``sigmoid(x W_gate)`` before ``o_proj``.
The first ``num_dense_layers`` layers carry a dense SwiGLU, the rest a
routed expert layer: float32 sigmoid scores, the top
``num_experts_per_tok`` of score + a stored bias, gates the chosen scores
over their sum ``+ 1e-20`` (``route_norm``) times ``route_scale``, plus the
shared expert(s).  The embedding is multiplied by ``sqrt(hidden_size)``
(``mup_enabled``); final RMSNorm, untied head.

TWO kinds of K/V cache in the slot engine's one manager
(``paging.SlotPages``): the full layers' rows in LANE pages under the
slot's page table, growing with the context, and the sliding layers' in a
RING the slot owns for good — ``sliding_window`` rows a layer, position
``p`` in ring row ``p % sliding_window``.  Both go through the attention
module it shares with ``models/hybrid.py``'s families and
``ops/transformer/registry.py::write_and_attend``, which picks the paged
kernels for the lane pages and the ring modes for the ring.

This is a serving model: :meth:`TrinityModel.decode` over the slot engine's
pools and a plain uncached forward (``__call__``).  It has no
``generate()`` cache and no training step (the dropless expert kernels
have no VJP).
"""

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.contract import SlotContract
from deepspeed_tpu.models.hybrid import Attention, GroupedQueryAttention
from deepspeed_tpu.models.parts import _Mlp, _Norm, causal_pairs
from deepspeed_tpu.moe.layer import MoE

GATE_SUM_EPS = 1e-20         # the public router's guard under the division
CHUNK_CAP = 2048             # whole 512-query blocks of the chunk kernels


@dataclasses.dataclass(frozen=True)
class TrinityConfig:
    vocab_size: int
    hidden_size: int
    layer_types: Tuple[str, ...]
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    moe_top_k: int
    num_shared_experts: int
    num_dense_layers: int
    route_norm: bool
    route_scale: float
    sliding_window: int
    rope_theta: float
    max_seq_len: int
    mup_enabled: bool = True
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    def layers_of(self, kind):
        return [i for i, t in enumerate(self.layer_types) if t == kind]


def trinity_config(hf, **overrides):
    """``hf``: a dict of HF ``config.json`` keys."""
    if hf.get("rope_scaling") is not None:
        raise ValueError("rope scaling is not implemented")
    if hf.get("attention_bias") or hf.get("tie_word_embeddings"):
        raise ValueError("afmoe as released has no biases and an untied "
                         "head")
    if hf.get("score_func", "sigmoid") != "sigmoid" \
            or hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("the router is sigmoid scores + a stored bias "
                         "without expert groups")
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError("SwiGLU")
    kinds = tuple(hf["layer_types"])[:hf["num_hidden_layers"]]
    if len(kinds) != hf["num_hidden_layers"] or set(kinds) - {
            "full_attention", "sliding_attention"}:
        raise ValueError(f"layer_types {kinds!r}")
    if hf["num_attention_heads"] % hf["num_key_value_heads"]:
        raise ValueError("KV heads must divide the heads")
    base = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        layer_types=kinds, num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_experts=hf["num_experts"], moe_top_k=hf["num_experts_per_tok"],
        num_shared_experts=hf["num_shared_experts"],
        num_dense_layers=hf["num_dense_layers"],
        route_norm=bool(hf["route_norm"]),
        route_scale=float(hf["route_scale"]),
        sliding_window=hf["sliding_window"],
        rope_theta=float(hf["rope_theta"]),
        max_seq_len=hf["max_position_embeddings"],
        mup_enabled=bool(hf.get("mup_enabled", False)),
        norm_eps=hf["rms_norm_eps"])
    base.update(overrides)
    return TrinityConfig(**base)


def trinity_model(hf, **overrides):
    overrides.pop("scan_layers", None)       # the layers differ: unrolled
    return TrinityModel(trinity_config(hf, **overrides))


def attention_of(cfg, sliding):
    """A layer's attention: per-head RMSNorm on q and k and a sigmoid gate
    on the heads' outputs in both kinds, rope and the window on a SLIDING
    layer only."""
    return Attention(
        cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
        cfg.jnp_dtype, qk_norm_eps=cfg.norm_eps, out_gate=True,
        rope_theta=cfg.rope_theta if sliding else None,
        window=cfg.sliding_window if sliding else None)


class TrinityLayer(nn.Module):
    config: TrinityConfig
    layer_idx: int

    def setup(self):
        cfg, i = self.config, self.layer_idx
        self.input_layernorm = _Norm(cfg.norm_eps)
        self.post_attention_layernorm = _Norm(cfg.norm_eps)
        self.pre_mlp_layernorm = _Norm(cfg.norm_eps)
        self.post_mlp_layernorm = _Norm(cfg.norm_eps)
        self.self_attn = GroupedQueryAttention(attention_of(
            cfg, cfg.layer_types[i] == "sliding_attention"))
        if i < cfg.num_dense_layers:
            self.mlp = _Mlp(cfg.intermediate_size, cfg.jnp_dtype)
        else:
            self.moe_mlp = MoE(
                hidden_size=cfg.hidden_size, num_experts=cfg.num_experts,
                k=cfg.moe_top_k, capacity_factor=None,
                norm_topk_prob=cfg.route_norm,
                ffn_hidden_size=cfg.moe_intermediate_size,
                dtype=cfg.jnp_dtype, gated=True, activation=nn.silu,
                scoring="sigmoid", routed_scaling=cfg.route_scale,
                gate_sum_eps=GATE_SUM_EPS,
                shared_ffn_hidden_size=cfg.num_shared_experts
                * cfg.moe_intermediate_size)

    def __call__(self, x, positions, cache=None, live=None):
        """``x [B, S, hidden]``.  Returns ``(x, cache)``."""
        with jax.named_scope("norm.input"):
            u = self.input_layernorm(x)
        a, cache = self.self_attn(u, positions, cache)
        with jax.named_scope("norm.post_attn"):
            x = x + self.post_attention_layernorm(a)
        with jax.named_scope("norm.pre_mlp"):
            m = self.pre_mlp_layernorm(x)
        if self.layer_idx < self.config.num_dense_layers:
            y = self.mlp(m)
        else:
            y, _, _ = self.moe_mlp(m, train=False, live=live)
        with jax.named_scope("norm.post_mlp"):
            return x + self.post_mlp_layernorm(y), cache


class TrinityModel(nn.Module):
    config: TrinityConfig

    def setup(self):
        cfg = self.config
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                                     dtype=cfg.jnp_dtype)
        self.layers = [TrinityLayer(cfg, i) for i in range(cfg.num_layers)]
        self.norm = _Norm(cfg.norm_eps)
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                dtype=cfg.jnp_dtype)

    def _embed(self, ids):
        x = self.embed_tokens(ids)
        if not self.config.mup_enabled:
            return x
        with jax.named_scope("embed.scale"):
            return x * jnp.asarray(math.sqrt(self.config.hidden_size),
                                   x.dtype)

    def _head(self, h, at=None):
        """Logits of ``h [B, S, hidden]``, or of row ``at[b]`` of each."""
        with jax.named_scope("head.logits"):
            if at is not None:
                h = jnp.take_along_axis(
                    h, at.astype(jnp.int32)[:, None, None], axis=1)
            return self.lm_head(self.norm(h))

    def __call__(self, batch):
        """Logits ``[B, S, V]`` of ``batch["input_ids"] [B, S]``: the plain
        causal forward, no cache."""
        ids = batch["input_ids"]
        x = self._embed(ids)
        positions = jnp.broadcast_to(jnp.arange(ids.shape[1])[None],
                                     ids.shape)
        for layer in self.layers:
            x, _ = layer(x, positions)
        return self._head(x)

    # ---- the serving path ---- #
    def slot_contract(self):
        """For the slot engine (``models/contract.py``): K/V pages under
        the slot's table for the full layers, a K/V ring a slot for the
        sliding ones, the window chunk kernel's own chunk (up to 2,048:
        one a dispatch), dropless experts after the dense layers."""
        cfg = self.config
        return SlotContract(
            vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
            dtype=cfg.dtype, num_layers=cfg.num_layers,
            lane_layers=len(cfg.layers_of("full_attention")),
            kv_pages=True, ring_pages=self._ring_pages,
            row_kinds=("K/V rows", "ring rows"),
            ring_kinds=("k_ring", "v_ring"),
            chunk_cap=CHUNK_CAP, chunk_fault=self._chunk_fault,
            own_chunk_path=True, routes_experts=True,
            expert_layers=cfg.num_layers - cfg.num_dense_layers,
            experts=cfg.num_experts,
            chunk_work=self._chunk_work, block_work=self._block_work,
            work_counters=("window_keys", "full_keys"),
            work_levels=("window_pages", "window_ring_rows",
                         "window_chunk_rows"))

    def _ring_pages(self, page_size):
        """Pages of a slot's ring in each sliding layer: exactly the
        window, so that a full ring needs no mask."""
        cfg = self.config
        if not cfg.layers_of("sliding_attention"):
            return 0
        if cfg.sliding_window % page_size:
            raise ValueError(
                f"sliding_window {cfg.sliding_window} is no whole number of "
                f"pages of {page_size} rows: the ring holds exactly the "
                f"window")
        return cfg.sliding_window // page_size

    @staticmethod
    def _chunk_fault(chunk):
        from deepspeed_tpu.ops.transformer.paged_attention import (
            window_chunk_queries)
        if window_chunk_queries(chunk) is None:
            return (f"a chunk over 512 is whole 512-query blocks of the "
                    f"window chunk kernel; {chunk} is not")
        return None

    def _chunk_work(self, start, end, page_size, ring_pages, layers):
        """What a prefill chunk over positions ``start .. end - 1`` attends,
        as its dispatch span's args: ``window_keys`` / ``full_keys`` —
        (query, key) pairs in the sliding and in the full layers, summed
        over each kind's layers — and ``window_pages``, ring pages the
        sliding layers hold for the slot —, ``window_ring_rows`` — ring rows
        the chunk's queries can see, the positions before it inside the
        first query's band — and ``window_chunk_rows``, its own."""
        cfg = self.config
        full = len(cfg.layers_of("full_attention"))
        swa = len(cfg.layers_of("sliding_attention"))
        return {"window_keys": swa * causal_pairs(start, end,
                                                  cfg.sliding_window),
                "full_keys": full * causal_pairs(start, end, end),
                "window_pages": ring_pages * swa,
                "window_ring_rows": swa * min(start, cfg.sliding_window - 1),
                "window_chunk_rows": swa * (end - start)}

    def _block_work(self, live, ring_pages, layers):
        """The same for a decode block, from ``live`` — ``(context, steps)``
        a live slot."""
        cfg = self.config
        full = len(cfg.layers_of("full_attention"))
        swa = len(cfg.layers_of("sliding_attention"))
        contexts = [first + i for first, steps in live for i in range(steps)]
        return {"window_keys": swa * sum(min(c, cfg.sliding_window)
                                         for c in contexts),
                "full_keys": full * sum(contexts),
                "window_pages": ring_pages * len(live) * swa}

    def init_paged_cache(self, num_pages, page_size, dtype=None,
                         window_pages=1):
        """``k`` / ``v [full layers, num_pages, page, KV heads x head_dim]``
        behind the slot's page table, and ``k_ring`` / ``v_ring [sliding
        layers, window_pages, page, ...]`` holding each slot's ring
        (``paging.SlotPages`` sizes it: trash + slots x ring pages)."""
        cfg = self.config
        dtype = dtype or cfg.jnp_dtype
        shape = lambda kind, pages: (
            len(cfg.layers_of(kind)), int(pages), int(page_size),
            cfg.num_kv_heads * cfg.head_dim)
        lane = shape("full_attention", num_pages)
        ring = shape("sliding_attention", window_pages)
        return {"k": jnp.zeros(lane, dtype), "v": jnp.zeros(lane, dtype),
                "k_ring": jnp.zeros(ring, dtype),
                "v_ring": jnp.zeros(ring, dtype)}

    def decode(self, input_ids, cache, start_pos, logits_at=None, live=None):
        """The slot programs' call: a prefill chunk of one slot
        (``input_ids [1, C]``, scalar ``start_pos``) or one token a lane
        (``[N, 1]``, ``start_pos [N]``).  ``cache["pages"]`` is the table
        row(s): the slot's lane pages, then its ring pages."""
        cfg = self.config
        per_row = jnp.ndim(start_pos) == 1
        pools = {"full_attention": {"k": cache["k"], "v": cache["v"]},
                 "sliding_attention": {"k": cache["k_ring"],
                                       "v": cache["v_ring"]}}
        with jax.named_scope("slots.tables"):
            pages = cache["pages"]
            lane = pages.shape[1] \
                - self._ring_pages(cache["k_ring"].shape[2])
            tables = {"full_attention": pages[:, :lane],
                      "sliding_attention": pages[:, lane:]}
            if per_row:
                positions = start_pos[:, None]
            else:
                positions = (start_pos
                             + jnp.arange(input_ids.shape[1]))[None]
        zero = jnp.zeros((), jnp.int32)
        lane_marks = {"per_row": zero} if per_row \
            else {k: cache[k] for k in ("page_runs",) if k in cache}
        ring_marks = {"ring": zero}
        if not per_row and live is not None:
            ring_marks["live"] = live.reshape(-1)
        markers = {"full_attention": lane_marks,
                   "sliding_attention": ring_marks}
        x = self._embed(input_ids)
        for i, layer in enumerate(self.layers):
            kind = cfg.layer_types[i]
            layer_cache = {
                **pools[kind], **markers[kind], "pages": tables[kind],
                "layer": jnp.asarray(cfg.layers_of(kind).index(i),
                                     jnp.int32)}
            x, new = layer(x, positions, layer_cache, live=live)
            pools[kind] = {"k": new["k"], "v": new["v"]}
        full, ring = pools["full_attention"], pools["sliding_attention"]
        return self._head(x, logits_at), {"k": full["k"], "v": full["v"],
                               "k_ring": ring["k"], "v_ring": ring["v"]}
