"""dots3-note-prev (dots-studio, ``model_type: dots3_note``) — the language
model's HF ``config.json`` keys to :class:`Dots3Model`.

The block: pre-RMSNorm; latent (MLA) attention in one of two sizes by
``layer_types[i]`` — ``full_attention`` with the DSA indexer (top
``index_topk`` of the slot's positions) or ``sliding_attention`` at the
``swa_*`` sizes over the token and its ``sliding_window_size - 1``
predecessors — with a headwise output gate; then a dense SwiGLU MLP in the
first ``first_k_dense_replace`` layers and, in the rest, a routed expert
layer: sigmoid scores, the top ``num_experts_per_tok`` of score + a stored
bias (``noaux_tc``), gates the chosen scores over their sum, plus
``n_shared_experts`` shared experts.  Untied head.  The vision and audio
towers and the multi-token-prediction module are not built.  The block
itself lives in ``models/latent_block.py`` (``models/glm5.py`` is built from
the same one); this file holds the config keys, the two layer kinds' pools
and the model's call forms.

``held_experts=(first, count)`` gives the model one chip's share of each
expert layer (``moe/layer.py``); the router keeps its published width.

This is a serving model: :meth:`Dots3Model.decode` over the slot engine's
pools — by row kind, ``init_paged_cache`` — and a plain uncached forward
(``__call__``).  It has no ``generate()`` cache, no training step, and no
VJP through its kernels.
"""

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.contract import SlotContract
from deepspeed_tpu.models.latent_attention import (LatentSpec, flash_tiles,
                                                   live_block_rows, padded)
from deepspeed_tpu.models.latent_block import LatentBlock
from deepspeed_tpu.models.parts import _Norm, causal_pairs


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    vocab_size: int
    hidden_size: int
    layer_types: Tuple[str, ...]
    first_k_dense: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    moe_top_k: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    full: LatentSpec
    window: LatentSpec
    max_seq_len: int
    rms_norm_eps: float = 1e-5
    held_experts: Optional[Tuple[int, int]] = None
    dtype: str = "bfloat16"

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    def layers_of(self, kind):
        return [i for i, t in enumerate(self.layer_types) if t == kind]


def dots3_config(hf, held_experts=None, **overrides):
    """``hf``: a dict of HF ``config.json`` keys."""
    if hf.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not implemented")
    if hf.get("attention_bias") or hf.get("tie_word_embeddings"):
        raise ValueError("dots3_note as released has no attention biases "
                         "and an untied head")
    if hf.get("scoring_func") != "sigmoid" \
            or hf.get("topk_method") != "noaux_tc" or hf.get("n_group"):
        raise ValueError("the router is sigmoid + noaux_tc without expert "
                         "groups")
    if hf.get("attention_gate_type") != "headwise" \
            or hf.get("swa_attention_gate_type") != "headwise":
        raise ValueError("the output gate is headwise in both layer kinds")
    if hf.get("hidden_act", "silu") != "silu" \
            or hf.get("moe_layer_freq", 1) != 1:
        raise ValueError("SwiGLU, an expert layer in every layer past the "
                         "dense ones")
    kinds = tuple(hf["layer_types"])[:hf["num_hidden_layers"]]
    if len(kinds) != hf["num_hidden_layers"] or set(kinds) - {
            "full_attention", "sliding_attention"}:
        raise ValueError(f"layer_types {kinds!r}")
    for pre in ("", "swa_"):
        if hf[pre + "num_key_value_heads"] != hf[pre + "num_attention_heads"]:
            raise ValueError("latent attention has one latent for all heads")
    common = dict(hidden=hf["hidden_size"], eps=hf["rms_norm_eps"],
                  rescale=bool(hf["apply_mla_qkv_lora_rescale"]))
    full = LatentSpec(
        heads=hf["num_attention_heads"], q_rank=hf["q_lora_rank"],
        kv_rank=hf["kv_lora_rank"], nope=hf["qk_nope_head_dim"],
        rope=hf["qk_rope_head_dim"], v=hf["v_head_dim"],
        theta=float(hf["rope_theta"]), index_heads=hf["index_n_heads"],
        index_dim=hf["index_head_dim"], index_topk=hf["index_topk"],
        **common)
    window = LatentSpec(
        heads=hf["swa_num_attention_heads"], q_rank=hf["swa_q_lora_rank"],
        kv_rank=hf["swa_kv_lora_rank"], nope=hf["swa_qk_nope_head_dim"],
        rope=hf["swa_qk_rope_head_dim"], v=hf["swa_v_head_dim"],
        theta=float(hf["swa_rope_theta"]),
        window=hf["sliding_window_size"], **common)
    base = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        layer_types=kinds, first_k_dense=hf["first_k_dense_replace"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        n_routed_experts=hf.get("n_routed_experts_published",
                                hf["n_routed_experts"]),
        n_shared_experts=hf["n_shared_experts"],
        moe_top_k=hf["num_experts_per_tok"],
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        full=full, window=window,
        max_seq_len=hf["max_position_embeddings"],
        rms_norm_eps=hf["rms_norm_eps"],
        held_experts=tuple(held_experts) if held_experts else None)
    base.update(overrides)
    return Dots3Config(**base)


def dots3_model(hf, held_experts=None, **overrides):
    overrides.pop("scan_layers", None)       # the layers differ: unrolled
    return Dots3Model(dots3_config(hf, held_experts, **overrides))


class Dots3Model(nn.Module):
    config: Dots3Config

    def setup(self):
        cfg = self.config
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                                     dtype=cfg.jnp_dtype)
        self.layers = [LatentBlock(
            cfg, cfg.full if kind == "full_attention" else cfg.window,
            dense=i < cfg.first_k_dense)
            for i, kind in enumerate(cfg.layer_types)]
        self.final_norm = _Norm(cfg.rms_norm_eps)
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                dtype=cfg.jnp_dtype)

    def _head(self, h):
        return self.lm_head(self.final_norm(h))

    def __call__(self, batch):
        """Logits ``[B, S, V]`` of ``batch["input_ids"] [B, S]``: the plain
        causal forward, a row at a time, no cache."""
        rows = []
        for ids in batch["input_ids"]:
            x = self.embed_tokens(ids)
            for layer in self.layers:
                x, _ = layer(x, lambda attn, h: attn.chunk(h, jnp.int32(0)))
            rows.append(self._head(x))
        return jnp.stack(rows)

    # ---- the serving path ---- #
    def slot_contract(self):
        """For the slot engine (``models/contract.py``): pools by row kind,
        a ring a slot in the window layers, the latent kernels' own chunk
        (up to 2048), the load of the experts this model HOLDS a layer."""
        cfg = self.config
        return SlotContract(
            vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
            dtype=cfg.dtype, num_layers=cfg.num_layers,
            kv_pages=False, ring_pages=self._ring_pages,
            row_kinds=("latent + index rows", "window rows"),
            chunk_cap=2048, own_chunk_path=True,
            routes_experts=True, holds_share=cfg.held_experts is not None,
            expert_layers=cfg.num_layers - cfg.first_k_dense,
            experts=(cfg.held_experts or (0, cfg.n_routed_experts))[1],
            chunk_work=self._chunk_work, block_work=self._block_work,
            work_counters=("dsa_keys_scored", "dsa_keys_kept",
                           "latent_rows_read", "window_keys",
                           "flash_tiles_live", "flash_tiles_whole"),
            work_levels=("latent_rows_decompressed", "window_pages"))

    def _ring_pages(self, page_size):
        """Pages a slot's ring holds in each window layer: the window's
        ``W - 1`` predecessors, wherever they start in a page."""
        if not self.config.layers_of("sliding_attention"):
            return 0
        return -(-(self.config.window.window - 1) // page_size) + 1

    def _chunk_work(self, start, end, page_size, ring_pages, layers):
        """What a prefill chunk over positions ``start .. end - 1`` does
        in this model's attention, as its dispatch span's args (summed
        over the layers of each kind): ``dsa_keys_scored`` — (query, key)
        pairs the indexer scores, the causal ones —, ``dsa_keys_kept`` —
        pairs the softmax runs over —, ``latent_rows_read`` — latent rows
        fetched from the pool (the slot's live rows, once a layer) —,
        ``latent_rows_decompressed`` — rows the full layers up-project
        into every head's keys and values: the live key blocks, whole, not
        the lane (a padded last chunk's blocks past ``end`` run too and
        are not counted) —, ``window_pages`` — ring pages the
        window layers hold for the slot —, ``window_keys`` — pairs the
        window layers attend —, ``flash_tiles_live`` / ``flash_tiles_whole``
        — (query, key) tiles the chunk flash kernel walks in the layers of
        both kinds, and those of them its mask keeps whole
        (``latent_attention.flash_tiles``: past ``index_topk`` a lower
        bound)."""
        cfg = self.config
        full = len(cfg.layers_of("full_attention"))
        swa = len(cfg.layers_of("sliding_attention"))
        pairs = lambda limit: causal_pairs(start, end, limit)
        lane = flash_tiles(start, end, limit=cfg.full.index_topk)
        band = flash_tiles(start, end, window=cfg.window.window)
        return {"dsa_keys_scored": full * pairs(end),
                "dsa_keys_kept": full * pairs(cfg.full.index_topk),
                "latent_rows_read": full * -(-end // page_size) * page_size,
                "latent_rows_decompressed": full * live_block_rows(end),
                "window_pages": ring_pages * swa,
                "window_keys": swa * pairs(cfg.window.window),
                "flash_tiles_live": full * lane[0] + swa * band[0],
                "flash_tiles_whole": full * lane[1] + swa * band[1]}

    def _block_work(self, live, ring_pages, layers):
        """The same for a decode block, from ``live`` — ``(context, steps)``
        a live slot: a step scores its context and READS the kept rows
        only."""
        cfg = self.config
        full = len(cfg.layers_of("full_attention"))
        swa = len(cfg.layers_of("sliding_attention"))
        contexts = [first + i for first, steps in live for i in range(steps)]
        kept = sum(min(c, cfg.full.index_topk) for c in contexts)
        return {"dsa_keys_scored": full * sum(contexts),
                "dsa_keys_kept": full * kept,
                "latent_rows_read": full * kept,
                "window_pages": ring_pages * len(live) * swa,
                "window_keys": swa * sum(min(c, cfg.window.window)
                                         for c in contexts)}

    def init_paged_cache(self, num_pages, page_size, dtype=None,
                         window_pages=1):
        """The pools, by row kind: ``latent [full layers, num_pages, page,
        640]`` and ``index [.., 128]`` share the slot's page table; ``window
        [window layers, window_pages, page, 1152]`` holds each slot's ring
        (``paging.SlotPages`` sizes it: trash + slots x ring pages).  Rows
        are padded to whole 128-lane tiles."""
        cfg = self.config
        dtype = dtype or cfg.jnp_dtype
        nf = len(cfg.layers_of("full_attention"))
        nw = len(cfg.layers_of("sliding_attention"))
        shape = lambda n, pages, w: (n, int(pages), int(page_size), w)
        return {
            "latent": jnp.zeros(shape(nf, num_pages, padded(cfg.full.row)),
                                dtype),
            "index": jnp.zeros(shape(nf, num_pages, cfg.full.index_dim),
                               dtype),
            "window": jnp.zeros(shape(nw, window_pages,
                                      padded(cfg.window.row)), dtype)}

    def decode(self, input_ids, cache, start_pos, logits_at=None, live=None):
        """The slot programs' call: a prefill chunk of one slot
        (``input_ids [1, C]``, scalar ``start_pos``) or one token a lane
        (``[N, 1]``, ``start_pos [N]``).  ``cache["pages"]`` is the table
        row(s): the slot's lane pages, then its ring pages."""
        cfg = self.config
        per_row = jnp.ndim(start_pos) == 1
        pages = cache["pages"]
        ring = self._ring_pages(cache["window"].shape[2])
        lane = pages.shape[1] - ring
        full_pools, window_pool = (cache["latent"], cache["index"]), \
            cache["window"]
        fulls, windows = cfg.layers_of("full_attention"), \
            cfg.layers_of("sliding_attention")
        x = self.embed_tokens(input_ids[:, 0] if per_row else input_ids[0])
        flat_live = None if live is None else live.reshape(-1)
        for i, layer in enumerate(self.layers):
            is_full = cfg.layer_types[i] == "full_attention"
            pools = full_pools if is_full else window_pool
            at = (fulls if is_full else windows).index(i)
            table = pages[:, :lane] if is_full else pages[:, lane:]

            def attend(attn, h, pools=pools, at=at, table=table):
                if per_row:
                    return attn.step(h, start_pos, (pools, at, table))
                return attn.chunk(h, start_pos, flat_live,
                                  (pools, at, table[0]))

            x, pools = layer(x, attend, live=flat_live)
            if is_full:
                full_pools = pools
            else:
                window_pool = pools
        h = x[:, None] if per_row else x[None]
        if logits_at is not None:
            h = jnp.take_along_axis(
                h, logits_at.astype(jnp.int32)[:, None, None], axis=1)
        new = {"latent": full_pools[0], "index": full_pools[1],
               "window": window_pool}
        return self._head(h), new
