"""GLM-5 (zai-org, ``model_type: glm_moe_dsa``) — the HF ``config.json``
keys to :class:`Glm5Model`, with its multi-token-prediction module.

The block is ``models/latent_block.py``'s: pre-RMSNorm, latent (MLA)
attention with the DSA indexer in EVERY layer (no window layers, no output
gate, no latent rescale, rotary pairs ``(2i, 2i + 1)`` as
``rope_interleave`` says), then a dense SwiGLU MLP in the first
``first_k_dense_replace`` layers and the routed expert layer — sigmoid
scores, top ``num_experts_per_tok`` of score + bias (``noaux_tc``, one
group), gates the chosen scores over their sum times
``routed_scaling_factor``, plus one shared expert — in the rest.  Untied
head.  ``held_experts=(first, count)`` gives the model one chip's share of
each expert layer; the router keeps its published width.

**The multi-token-prediction module** (``num_nextn_predict_layers`` 1,
DeepSeek-V3's form): for position ``t`` with the main model's last hidden
state ``h_t`` (after the final norm) and the NEXT token ``x_{t+1}``::

    u_t = [RMSNorm_e(Emb(x_{t+1})) ; RMSNorm_h(h_t)] W_eh      (2h -> h)

one full block over the module's OWN cached rows at positions ``<= t``
(latent attention + indexer, the expert layer with the same held share),
its own final norm and the main model's head: logits for ``x_{t+2}``.
Embedding and head are the main model's.

This is a serving model.  Its pools hold ``num_hidden_layers + 1`` layers of
``latent`` and ``index`` rows: the module's rows are one more layer under
the slot's own page table.  Call forms:

* :meth:`Glm5Model.decode` — a prefill chunk of one slot (``input_ids [1,
  C]``, scalar ``start_pos``) or ``W`` rows a lane (``[N, W]``, ``start_pos
  [N]``: lane ``n``'s rows sit at ``start_pos[n] .. + W - 1``, each row its
  own kept set, a later row attending the earlier ones — ``W`` 1 is the
  decode step, 2 the verify window of self-drafting — through
  ``LatentAttention.window``, which takes the lane form while the slot's
  table spans a few ``index_topk``: the lane read once, the kept set a
  mask).  ``hidden=True``
  hands back ``h`` of every row beside the logits.
* :meth:`Glm5Model.draft` — the module over the same two forms, from
  ``next_ids`` and ``hidden``.  The slot programs that draft with it are
  ``serving/slots.py``'s (``docs/serving.md`` "Speculative decoding").
* ``__call__`` — the plain uncached forward; ``drafts=True`` adds the
  module's logits along the sequence.

No ``generate()`` cache, no training step, no VJP through its kernels.
"""

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.contract import SlotContract
from deepspeed_tpu.models.latent_attention import (LatentSpec, flash_tiles,
                                                   live_block_rows, padded)
from deepspeed_tpu.models.latent_block import LatentBlock
from deepspeed_tpu.models.parts import _Norm, causal_pairs


@dataclasses.dataclass(frozen=True)
class Glm5Config:
    vocab_size: int
    hidden_size: int
    num_layers: int
    first_k_dense: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    moe_top_k: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    attn: LatentSpec
    mtp_layers: int
    max_seq_len: int
    rms_norm_eps: float = 1e-5
    held_experts: Optional[Tuple[int, int]] = None
    dtype: str = "bfloat16"

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


def glm5_config(hf, held_experts=None, **overrides):
    """``hf``: a dict of HF ``config.json`` keys."""
    rope = hf.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError("rope scaling is not implemented")
    if hf.get("attention_bias") or hf.get("tie_word_embeddings"):
        raise ValueError("glm_moe_dsa as released has no attention biases "
                         "and an untied head")
    if hf.get("scoring_func") != "sigmoid" \
            or hf.get("topk_method") != "noaux_tc" \
            or hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("the router is sigmoid + noaux_tc in one group")
    if hf.get("hidden_act", "silu") != "silu" \
            or hf.get("moe_layer_freq", 1) != 1:
        raise ValueError("SwiGLU, an expert layer in every layer past the "
                         "dense ones")
    if hf["num_key_value_heads"] != hf["num_attention_heads"]:
        raise ValueError("latent attention has one latent for all heads")
    if hf.get("num_nextn_predict_layers", 0) not in (0, 1):
        raise ValueError("one multi-token-prediction module, or none")
    if bool(hf.get("indexer_rope_interleave", hf.get("rope_interleave"))) \
            != bool(hf.get("rope_interleave")):
        raise ValueError("one rotary pairing for attention and indexer")
    attn = LatentSpec(
        hidden=hf["hidden_size"], heads=hf["num_attention_heads"],
        q_rank=hf["q_lora_rank"], kv_rank=hf["kv_lora_rank"],
        nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
        v=hf["v_head_dim"], theta=float(rope["rope_theta"]),
        eps=hf["rms_norm_eps"], index_heads=hf["index_n_heads"],
        index_dim=hf["index_head_dim"], index_topk=hf["index_topk"],
        rescale=False, gated=False,
        interleaved=bool(hf.get("rope_interleave")))
    base = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        first_k_dense=hf["first_k_dense_replace"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        n_routed_experts=hf.get("n_routed_experts_published",
                                hf["n_routed_experts"]),
        n_shared_experts=hf["n_shared_experts"],
        moe_top_k=hf["num_experts_per_tok"],
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        attn=attn, mtp_layers=hf.get("num_nextn_predict_layers", 0),
        max_seq_len=hf["max_position_embeddings"],
        rms_norm_eps=hf["rms_norm_eps"],
        held_experts=tuple(held_experts) if held_experts else None)
    base.update(overrides)
    return Glm5Config(**base)


def glm5_model(hf, held_experts=None, **overrides):
    overrides.pop("scan_layers", None)       # the layers differ: unrolled
    return Glm5Model(glm5_config(hf, held_experts, **overrides))


class Glm5Mtp(nn.Module):
    """The module's own parameters: the two input norms, ``eh_proj``, one
    expert block, the norm before the (shared) head."""
    config: Glm5Config

    def setup(self):
        cfg = self.config
        self.embed_norm = _Norm(cfg.rms_norm_eps)
        self.hidden_norm = _Norm(cfg.rms_norm_eps)
        self.eh_proj = nn.Dense(cfg.hidden_size, use_bias=False,
                                dtype=cfg.jnp_dtype)
        self.block = LatentBlock(cfg, cfg.attn, dense=False)
        self.head_norm = _Norm(cfg.rms_norm_eps)

    def combine(self, embedded, hidden):
        return self.eh_proj(jnp.concatenate(
            [self.embed_norm(embedded), self.hidden_norm(hidden)], axis=-1))


class Glm5Model(nn.Module):
    config: Glm5Config

    def setup(self):
        cfg = self.config
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                                     dtype=cfg.jnp_dtype)
        self.layers = [LatentBlock(cfg, cfg.attn,
                                   dense=i < cfg.first_k_dense)
                       for i in range(cfg.num_layers)]
        self.final_norm = _Norm(cfg.rms_norm_eps)
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                dtype=cfg.jnp_dtype)
        if cfg.mtp_layers:
            self.mtp = Glm5Mtp(cfg)

    def __call__(self, batch, drafts=False):
        """Logits ``[B, S, V]`` of ``batch["input_ids"] [B, S]``: the plain
        causal forward, a row at a time, no cache.  ``drafts``: beside
        them the module's logits ``[B, S, V]`` — row ``t`` from ``h_t`` and
        token ``t + 1`` (the last row's next token reads as id 0)."""
        alone = lambda attn, h: attn.chunk(h, jnp.int32(0))
        drafts = drafts or (self.is_initializing()
                            and bool(self.config.mtp_layers))
        rows, guesses = [], []
        for ids in batch["input_ids"]:
            x = self.embed_tokens(ids)
            for layer in self.layers:
                x, _ = layer(x, alone)
            h = self.final_norm(x)
            rows.append(self.lm_head(h))
            if drafts:
                nxt = jnp.concatenate([ids[1:], jnp.zeros((1,), ids.dtype)])
                u = self.mtp.combine(self.embed_tokens(nxt), h)
                u, _ = self.mtp.block(u, alone)
                guesses.append(self.lm_head(self.mtp.head_norm(u)))
        if drafts:
            return jnp.stack(rows), jnp.stack(guesses)
        return jnp.stack(rows)

    # ---- the serving path ---- #
    def slot_contract(self):
        """For the slot engine (``models/contract.py``): latent rows and no
        K/V pages, the latent kernels' own chunk (up to 2048), the load of
        the experts this model HOLDS a layer, and the multi-token-prediction
        module's layers (expert layers, the pools' last)."""
        cfg = self.config
        return SlotContract(
            vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
            dtype=cfg.dtype, num_layers=cfg.num_layers,
            kv_pages=False, chunk_cap=2048, own_chunk_path=True,
            routes_experts=True, holds_share=cfg.held_experts is not None,
            expert_layers=cfg.num_layers - cfg.first_k_dense,
            experts=(cfg.held_experts or (0, cfg.n_routed_experts))[1],
            draft_layers=cfg.mtp_layers,
            chunk_work=self._chunk_work, block_work=self._block_work,
            work_counters=("dsa_keys_scored", "dsa_keys_kept",
                           "latent_rows_read", "flash_tiles_live",
                           "flash_tiles_whole"),
            work_levels=("latent_rows_decompressed",))

    def _chunk_work(self, start, end, page_size, ring_pages, layers):
        """What a prefill chunk over positions ``start .. end - 1`` does in
        this model's attention, as its dispatch span's args, summed over
        the ``layers`` the dispatch ran: ``dsa_keys_scored`` —
        (query, key) pairs the indexer scores, the causal ones —,
        ``dsa_keys_kept`` — pairs the softmax runs over —,
        ``latent_rows_read`` — latent rows fetched from the pool (the
        slot's live rows, once a layer) —, ``latent_rows_decompressed`` —
        rows up-projected into every head's keys and values: the live key
        blocks, whole, not the lane (a padded last chunk's blocks past
        ``end`` run too and are not counted) —, ``flash_tiles_live`` /
        ``flash_tiles_whole`` — (query, key) tiles the chunk flash kernel
        walks, and those of them its mask keeps whole
        (``latent_attention.flash_tiles``: past ``index_topk`` a lower
        bound)."""
        cfg = self.config
        pairs = lambda limit: causal_pairs(start, end, limit)
        live, whole = flash_tiles(start, end, limit=cfg.attn.index_topk)
        return {"dsa_keys_scored": layers * pairs(end),
                "dsa_keys_kept": layers * pairs(cfg.attn.index_topk),
                "latent_rows_read": layers * -(-end // page_size) * page_size,
                "latent_rows_decompressed": layers * live_block_rows(end),
                "flash_tiles_live": layers * live,
                "flash_tiles_whole": layers * whole}

    def _block_work(self, live, ring_pages, layers):
        """The same for the rows of a decode dispatch, from ``live`` —
        ``(context, rows)`` a live slot, the rows at consecutive positions:
        a row scores its context and attends its kept rows
        (``latent_rows_read``: what the per-row form reads; the lane form
        reads a lane's live rows once for all its rows, which the span's
        ``kv_pages`` counts — ``LatentAttention.window``)."""
        cfg = self.config
        contexts = [first + i for first, rows in live for i in range(rows)]
        kept = sum(min(c, cfg.attn.index_topk) for c in contexts)
        return {"dsa_keys_scored": layers * sum(contexts),
                "dsa_keys_kept": layers * kept,
                "latent_rows_read": layers * kept}

    def init_paged_cache(self, num_pages, page_size, dtype=None):
        """``latent [layers, num_pages, page, 640]`` and ``index [.., 128]``
        under the slot's page table, ``layers`` the main model's and then
        the multi-token-prediction module's.  Rows are padded to whole
        128-lane tiles."""
        cfg = self.config
        dtype = dtype or cfg.jnp_dtype
        n = cfg.num_layers + cfg.mtp_layers
        shape = lambda w: (n, int(num_pages), int(page_size), w)
        return {"latent": jnp.zeros(shape(padded(cfg.attn.row)), dtype),
                "index": jnp.zeros(shape(cfg.attn.index_dim), dtype)}

    def _blocks(self, blocks, first, x, cache, start_pos, live):
        """``x [T, h]`` through ``blocks`` — pool layers ``first ..`` —
        in the call form ``start_pos`` names: a chunk of one slot (scalar)
        or ``W`` rows a lane (``[N]``: ``T = N W``,
        ``LatentAttention.window``).  Returns ``(x, pools)``."""
        per_row = jnp.ndim(start_pos) == 1
        pools, pages = (cache["latent"], cache["index"]), cache["pages"]
        for i, block in enumerate(blocks):

            def attend(attn, h, pools=pools, at=first + i):
                if per_row:
                    return attn.window(h, start_pos, (pools, at, pages))
                return attn.chunk(h, start_pos, live, (pools, at, pages[0]))

            x, pools = block(x, attend, live=live)
        return x, pools

    @staticmethod
    def _rows(t, like):
        """``[T, ...]`` back to the caller's ``[B, S, ...]``."""
        return t.reshape(like.shape[:2] + t.shape[1:])

    def decode(self, input_ids, cache, start_pos, logits_at=None, live=None,
               hidden=False):
        """The slot programs' call (the forms are the module docstring's).
        ``cache["pages"]`` is the table row(s).  Returns ``(logits,
        pools)`` — ``(logits, h, pools)`` with ``hidden``, ``h [B, S, h]``
        the final-normed state of every row, what :meth:`draft` is fed."""
        flat_live = None if live is None else live.reshape(-1)
        x = self.embed_tokens(input_ids.reshape(-1))
        x, pools = self._blocks(self.layers, 0, x, cache, start_pos,
                                flat_live)
        h = self._rows(self.final_norm(x), input_ids)
        at = h
        if logits_at is not None:
            at = jnp.take_along_axis(
                h, logits_at.astype(jnp.int32)[:, None, None], axis=1)
        new = {"latent": pools[0], "index": pools[1]}
        logits = self.lm_head(at)
        return (logits, h, new) if hidden else (logits, new)

    def draft(self, next_ids, cache, start_pos, hidden, logits_at=None,
              live=None):
        """The multi-token-prediction module over ``next_ids [B, S]`` and
        ``hidden [B, S, h]`` — row ``(b, s)`` at the position of ``hidden[b,
        s]``, fed the token AFTER it — in :meth:`decode`'s two forms,
        writing its rows into the pools' last layer.  Returns ``(logits,
        pools)``: the row's guess at the token after ``next_ids``."""
        cfg = self.config
        flat_live = None if live is None else live.reshape(-1)
        with jax.named_scope("mtp.combine"):
            u = self.mtp.combine(
                self.embed_tokens(next_ids.reshape(-1)),
                hidden.reshape(-1, hidden.shape[-1]))
        with jax.named_scope("mtp.block"):
            u, pools = self._blocks([self.mtp.block], cfg.num_layers, u,
                                    cache, start_pos, flat_live)
        with jax.named_scope("mtp.head"):
            at = self._rows(self.mtp.head_norm(u), next_ids)
            if logits_at is not None:
                at = jnp.take_along_axis(
                    at, logits_at.astype(jnp.int32)[:, None, None], axis=1)
            logits = self.lm_head(at)
        return logits, {"latent": pools[0], "index": pools[1]}
