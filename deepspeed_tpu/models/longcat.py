"""LongCat-Flash-Chat (meituan-longcat) — the HF ``config.json`` keys, as
published (``num_layers``, ``ffn_hidden_size``, ``expert_ffn_hidden_size``,
``moe_topk``, ``zero_expert_num``, ``zero_expert_type``), to
:class:`LongcatModel`.

The block is a SHORTCUT-CONNECTED DOUBLE LAYER: two latent attentions and
two dense SwiGLU FFNs around ONE routed expert layer, whose input is taken
after the first attention and whose output joins the residual after the
second dense FFN — in a deployment the expert exchange runs beside the
first dense FFN and the whole second attention.  With RMSNorm (float32
gain) before every sublayer::

    a1 = x  + MLA_0(norm(x))            h1 = norm(a1)
    m  = Experts(h1)                     # the shortcut branch
    b1 = a1 + FFN_0(h1)
    a2 = b1 + MLA_1(norm(b1))           h2 = norm(a2)
    y  = a2 + FFN_1(h2) + m

``MLA`` is plain causal latent attention (``models/latent_attention.py`` at
``index_topk == 0``): no indexer, no output gate, both normed latents
scaled by ``sqrt(hidden / rank)`` (``mla_scale_q_lora`` /
``mla_scale_kv_lora``), rotary pairs ``(2i, 2i + 1)``.  ``Experts``: a
float32 softmax over ``n_routed_experts + zero_expert_num`` router outputs,
the top ``moe_topk`` of score + a stored bias, gates the chosen scores times
``routed_scaling_factor`` (no renormalisation); a chosen output past the
real experts is a zero-compute IDENTITY expert and adds its gate times the
layer's input (``moe/layer.py`` ``zero_experts``).  ``held_experts=(first,
count)`` gives the model one chip's share of each expert layer; the router
keeps its published width.  Embedding, the double layers, a final RMSNorm,
an untied head.

This is a serving model.  Its one pool, ``latent``, holds TWO layers a
double layer (the contract's ``num_layers`` is twice the model's,
``expert_layers`` once).  Call forms of :meth:`LongcatModel.decode`: a
prefill chunk of one slot (``input_ids [1, C]``, scalar ``start_pos``) or
``W`` rows a lane (``[N, W]``, ``start_pos [N]``; ``W`` 1 is the decode
step).  ``__call__`` is the plain uncached forward.  No ``generate()``
cache, no training step, no VJP through its kernels.
"""

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.contract import SlotContract
from deepspeed_tpu.models.latent_attention import (LatentAttention,
                                                   LatentSpec, flash_tiles,
                                                   padded)
from deepspeed_tpu.models.parts import _Mlp, _Norm, causal_pairs
from deepspeed_tpu.moe.layer import MoE


@dataclasses.dataclass(frozen=True)
class LongcatConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int              # double layers
    ffn_hidden_size: int
    expert_ffn_hidden_size: int
    n_routed_experts: int        # the router's real outputs, as published
    zero_expert_num: int
    moe_topk: int
    routed_scaling_factor: float
    attn: LatentSpec
    max_seq_len: int
    rms_norm_eps: float = 1e-5
    held_experts: Optional[Tuple[int, int]] = None
    dtype: str = "bfloat16"

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


def longcat_config(hf, held_experts=None, **overrides):
    """``hf``: a dict of HF ``config.json`` keys."""
    if hf.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not implemented")
    if hf.get("attention_bias") or hf.get("tie_word_embeddings"):
        raise ValueError("LongCat-Flash as released has no attention "
                         "biases and an untied head")
    if hf.get("zero_expert_type") != "identity":
        raise ValueError(f"zero_expert_type "
                         f"{hf.get('zero_expert_type')!r}: only 'identity' "
                         f"zero experts are implemented")
    if hf.get("attention_method", "MLA") != "MLA":
        raise ValueError("attention_method is MLA")
    if bool(hf["mla_scale_q_lora"]) != bool(hf["mla_scale_kv_lora"]):
        raise ValueError("mla_scale_q_lora and mla_scale_kv_lora are set "
                         "together (one rescale for both latents)")
    attn = LatentSpec(
        hidden=hf["hidden_size"], heads=hf["num_attention_heads"],
        q_rank=hf["q_lora_rank"], kv_rank=hf["kv_lora_rank"],
        nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
        v=hf["v_head_dim"], theta=float(hf["rope_theta"]),
        eps=hf["rms_norm_eps"], rescale=bool(hf["mla_scale_q_lora"]),
        gated=False, interleaved=True)
    base = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=hf["num_layers"], ffn_hidden_size=hf["ffn_hidden_size"],
        expert_ffn_hidden_size=hf["expert_ffn_hidden_size"],
        n_routed_experts=hf.get("n_routed_experts_published",
                                hf["n_routed_experts"]),
        zero_expert_num=hf["zero_expert_num"], moe_topk=hf["moe_topk"],
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        attn=attn, max_seq_len=hf["max_position_embeddings"],
        rms_norm_eps=hf["rms_norm_eps"],
        held_experts=tuple(held_experts) if held_experts else None)
    base.update(overrides)
    return LongcatConfig(**base)


def longcat_model(hf, held_experts=None, **overrides):
    overrides.pop("scan_layers", None)       # unrolled
    return LongcatModel(longcat_config(hf, held_experts, **overrides))


class ShortcutLayer(nn.Module):
    """One double layer (the module docstring's equations)."""
    config: LongcatConfig

    def setup(self):
        cfg = self.config
        pair = lambda make: [make() for _ in range(2)]
        self.attn = pair(lambda: LatentAttention(cfg.attn, cfg.jnp_dtype))
        self.input_norm = pair(lambda: _Norm(cfg.rms_norm_eps))
        self.post_attn_norm = pair(lambda: _Norm(cfg.rms_norm_eps))
        self.mlp = pair(lambda: _Mlp(cfg.ffn_hidden_size, cfg.jnp_dtype))
        self.moe_mlp = MoE(
            hidden_size=cfg.hidden_size, num_experts=cfg.n_routed_experts,
            k=cfg.moe_topk, capacity_factor=None, norm_topk_prob=False,
            ffn_hidden_size=cfg.expert_ffn_hidden_size, dtype=cfg.jnp_dtype,
            gated=True, activation=nn.silu, scoring="softmax", noaux_tc=True,
            routed_scaling=cfg.routed_scaling_factor,
            zero_experts=cfg.zero_expert_num, held_experts=cfg.held_experts)

    def __call__(self, x, pools, attend, live=None):
        """``attend(attn, normed x, pools, which) -> (out, pools)``: the
        call form the model chose with this layer's ``which``-th (0, 1)
        pool layer."""
        a, pools = attend(self.attn[0], self.input_norm[0](x), pools, 0)
        x = x + a
        h = self.post_attn_norm[0](x)
        with jax.named_scope("scmoe.experts"):
            m, _, _ = self.moe_mlp(h, train=False, live=live)
        with jax.named_scope("scmoe.dense_ffn"):
            x = x + self.mlp[0](h)
        a, pools = attend(self.attn[1], self.input_norm[1](x), pools, 1)
        x = x + a
        with jax.named_scope("scmoe.dense_ffn"):
            x = x + self.mlp[1](self.post_attn_norm[1](x))
        return x + m, pools


class LongcatModel(nn.Module):
    config: LongcatConfig

    def setup(self):
        cfg = self.config
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                                     dtype=cfg.jnp_dtype)
        self.layers = [ShortcutLayer(cfg) for _ in range(cfg.num_layers)]
        self.final_norm = _Norm(cfg.rms_norm_eps)
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                dtype=cfg.jnp_dtype)

    def __call__(self, batch):
        """Logits ``[B, S, V]`` of ``batch["input_ids"] [B, S]``: the plain
        causal forward, a row at a time, no cache."""
        alone = lambda attn, h, pools, which: attn.chunk(h, jnp.int32(0))
        rows = []
        for ids in batch["input_ids"]:
            x = self.embed_tokens(ids)
            for layer in self.layers:
                x, _ = layer(x, None, alone)
            rows.append(self.lm_head(self.final_norm(x)))
        return jnp.stack(rows)

    # ---- the serving path ---- #
    def slot_contract(self):
        """For the slot engine (``models/contract.py``): latent rows and no
        K/V pages, the latent kernels' own chunk (up to 2048), TWO pool
        layers a double layer and one expert layer, the load of the experts
        this model HOLDS and the choices that fell on zero experts."""
        cfg = self.config
        return SlotContract(
            vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
            dtype=cfg.dtype, num_layers=2 * cfg.num_layers,
            kv_pages=False, chunk_cap=2048, own_chunk_path=True,
            routes_experts=True, holds_share=cfg.held_experts is not None,
            zero_experts=cfg.zero_expert_num > 0,
            expert_layers=cfg.num_layers,
            experts=(cfg.held_experts or (0, cfg.n_routed_experts))[1],
            chunk_work=self._chunk_work, block_work=self._block_work,
            work_counters=("latent_rows_read", "causal_pairs",
                           "flash_tiles_live", "flash_tiles_whole"))

    @staticmethod
    def _chunk_work(start, end, page_size, ring_pages, layers):
        """What a prefill chunk over positions ``start .. end - 1`` does in
        this model's attention, as its dispatch span's args, summed over
        the pool ``layers``: ``causal_pairs`` — (query, key) pairs under
        the causal mask —, ``latent_rows_read`` — latent rows fetched from
        the pool (the slot's live rows, once a layer) —,
        ``flash_tiles_live`` / ``flash_tiles_whole`` — (query, key) tiles
        the chunk flash kernel walks, and those of them wholly under the
        diagonal (``latent_attention.flash_tiles``)."""
        live, whole = flash_tiles(start, end)
        return {"causal_pairs": layers * causal_pairs(start, end, end),
                "latent_rows_read": layers * -(-end // page_size) * page_size,
                "flash_tiles_live": layers * live,
                "flash_tiles_whole": layers * whole}

    @staticmethod
    def _block_work(live, ring_pages, layers):
        """The same for a decode dispatch, from ``live`` — ``(context,
        rows)`` a live slot: a row attends every row of its context, and a
        lane's live rows are read once a row and layer."""
        rows = sum(first + i for first, n in live for i in range(n))
        return {"causal_pairs": layers * rows,
                "latent_rows_read": layers * rows}

    def init_paged_cache(self, num_pages, page_size, dtype=None):
        """``latent [2 x double layers, num_pages, page, 640]`` under the
        slot's page table: double layer ``i``'s attentions are pool layers
        ``2i`` and ``2i + 1``.  Rows are padded to whole 128-lane tiles."""
        cfg = self.config
        return {"latent": jnp.zeros(
            (2 * cfg.num_layers, int(num_pages), int(page_size),
             padded(cfg.attn.row)), dtype or cfg.jnp_dtype)}

    def decode(self, input_ids, cache, start_pos, logits_at=None, live=None):
        """The slot programs' call (the forms are the module docstring's).
        ``cache["pages"]`` is the table row(s).  Returns ``(logits,
        pools)``."""
        per_row = jnp.ndim(start_pos) == 1
        pages = cache["pages"]
        flat_live = None if live is None else live.reshape(-1)
        x = self.embed_tokens(input_ids.reshape(-1))
        pool = cache["latent"]
        for i, layer in enumerate(self.layers):

            def attend(attn, h, pool, which, at=2 * i):
                if per_row:
                    return attn.window(h, start_pos,
                                       (pool, at + which, pages))
                return attn.chunk(h, start_pos, flat_live,
                                  (pool, at + which, pages[0]))

            x, pool = layer(x, pool, attend, live=flat_live)
        h = self.final_norm(x).reshape(input_ids.shape + x.shape[1:])
        if logits_at is not None:
            h = jnp.take_along_axis(
                h, logits_at.astype(jnp.int32)[:, None, None], axis=1)
        return self.lm_head(h), {"latent": pool}
