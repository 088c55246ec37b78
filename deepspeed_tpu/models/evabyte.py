"""EvaByte (EvaByte/EvaByte, ``model_type: evabyte``, ``attention_class:
"eva"``) — HF ``config.json`` keys to :class:`EvaByteModel`.

A byte-level decoder (vocabulary 320: 256 bytes + specials) whose
attention is EVA (Zheng et al., ICLR 2023, "Efficient Attention via
Control Variates") with the random feature replaced by a learned ``phi``
and the chunk key offset by a learned ``mu`` (``adaptive_phi`` /
``adaptive_mu_k``).  The block, on a float32 residual stream
(``fp32_skip_add``), RMSNorm with gain ``1 + g`` (``norm_add_unit_offset``)::

    h = x + Attn(RMSNorm_1(x));  y = h + W_down(silu(W_gate u') * (W_up u'))
    q, k, v = u W_q, u W_k, u W_v   32 heads of 128; rope(q, k) at absolute p
    chunk j = positions c j .. c j + c - 1  (c = chunk_size):
        a[j, t] = softmax over the chunk's t of  s * (k_t . phi)
        ksum_j = sum_t a[j, t] k_t + mu      vsum_j = sum_t a[j, t] v_t
    query at p, w = p // W  (W = window_size):
        L(p) = {t : W w <= t <= p}           its own window, causal, exact
        R(p) = {j : j < (W / c) w}           every chunk of every EARLIER window
        o_p = softmax over L(p) + R(p) of s q.k_t | s q.ksum_j, on v_t | vsum_j

and a head of ``num_pred_heads`` x 320 columns, float32 logits
(``fp32_logits``), columns ``0 .. 319`` the next byte's.

The config gives the sizes and not the code.  What is ASSUMED here (the
benchmark's configuration file lists the same): one set of pooling weights
``a`` for ``ksum`` and ``vsum``; ``mu`` added after pooling; rope before
pooling, at absolute positions, half-split layout; BLOCK windows (not a
sliding band); a window's summaries visible from the NEXT window on; head
0 = the next byte; the head one ``[hidden, heads x vocab]`` matrix; no
biases.

**The cache** (``paging.SlotPages``): two pools a layer behind the slot's
ONE table row.  ``k`` / ``v [layers, 1 + slots x W / page, page, H x D]`` is
a RING the slot owns for good (``ring_pages``): position ``p``'s row
is ring row ``p % W``, and the rows of the window before go dead ALL AT
ONCE when ``p`` crosses a multiple of ``W`` — by the position mask, never
by what was written.  ``ksum`` / ``vsum [layers, num_pages, page, H x D]``
is a lane whose row index is ``p // c`` (``lane_stride``): written as the
chunk completes, first READ a whole window later.  Three hand-overs:
(i) a prefill chunk pools only its ``c``-blocks whose positions are all
real — a partial last block and a padded tail write to the trash page;
(ii) the decode step that completes a ``c``-block (``(p + 1) % c == 0``)
pools it from the ring's last ``c`` rows and writes summary row ``p // c``
(any other lane's write goes to the trash page); (iii) both masks are
functions of the position alone, so a window's end needs no event.

This is a serving model: :meth:`EvaByteModel.decode` over the slot
engine's pools and a plain uncached forward (``__call__``, all prediction
heads).  The slot engine samples from head 0: multi-byte self-drafting
from the other seven needs a commit of a variable count and is not served.
No ``generate()`` cache, no training step.
"""

import dataclasses

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.contract import SlotContract
from deepspeed_tpu.models.parts import _Mlp, _rms
from deepspeed_tpu.models.transformer import _paged_write, _rope
from deepspeed_tpu.ops.transformer.eva_attention import (
    eva_chunk_attention, eva_decode_attention)
from deepspeed_tpu.ops.transformer.registry import paged_write_form


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    intermediate_size: int
    chunk_size: int
    window_size: int
    num_pred_heads: int
    rope_theta: float
    max_seq_len: int
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


def evabyte_config(hf, **overrides):
    """``hf``: a dict of HF ``config.json`` keys."""
    if hf.get("attention_class", "eva") != "eva" \
            or hf.get("rope_scaling") is not None:
        raise ValueError("evabyte as released: EVA attention, no rope scaling")
    if hf.get("attention_bias") or hf.get("tie_word_embeddings"):
        raise ValueError("evabyte as released has no biases and an untied "
                         "head")
    if not (hf.get("norm_add_unit_offset", True)
            and hf.get("fp32_skip_add", True)
            and hf.get("fp32_logits", True)
            and hf.get("hidden_act", "silu") == "silu"):
        raise ValueError("this model is evabyte as released: RMSNorm gain "
                         "1 + g, a float32 residual stream and logits, SwiGLU")
    heads = hf["num_attention_heads"]
    if hf["hidden_size"] % heads \
            or hf.get("num_key_value_heads", heads) != heads:
        raise ValueError("one K/V head a query head, heads divide the hidden "
                         "size")
    if hf["window_size"] % hf["chunk_size"]:
        raise ValueError("a window is whole chunks")
    base = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"], num_heads=heads,
        intermediate_size=hf["intermediate_size"],
        chunk_size=hf["chunk_size"], window_size=hf["window_size"],
        num_pred_heads=hf.get("num_pred_heads", 1),
        rope_theta=float(hf["rope_theta"]),
        max_seq_len=hf["max_position_embeddings"],
        norm_eps=hf["rms_norm_eps"])
    base.update(overrides)
    return EvaByteConfig(**base)


def evabyte_model(hf, **overrides):
    overrides.pop("scan_layers", None)       # unrolled, as every slot model
    return EvaByteModel(evabyte_config(hf, **overrides))


class _Norm(nn.Module):
    """RMSNorm with gain ``1 + g`` (``norm_add_unit_offset``)."""
    eps: float

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                       jnp.float32)
        return _rms(x, 1.0 + g, self.eps)


def summarise(k, v, phi, mu, scale):
    """Pool whole chunks: ``k`` / ``v [..., c, H, D]`` (keys after rope) to
    ``(ksum, vsum) [..., H, D]`` — a softmax over the chunk's ``c`` rows of
    ``scale * (k . phi)`` weighs both, ``mu`` offsets the pooled key."""
    with jax.named_scope("eva.summarise"):
        kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
        a = jax.nn.softmax(
            scale * jnp.sum(kf * phi.astype(jnp.float32), axis=-1), axis=-2)
        ksum = jnp.sum(a[..., None] * kf, axis=-3) + mu.astype(jnp.float32)
        vsum = jnp.sum(a[..., None] * vf, axis=-3)
        return ksum.astype(k.dtype), vsum.astype(v.dtype)


class EvaAttention(nn.Module):
    config: EvaByteConfig

    def setup(self):
        cfg = self.config
        H, D = cfg.num_heads, cfg.head_dim
        dense = lambda name: nn.DenseGeneral(
            (H, D), use_bias=False, dtype=cfg.jnp_dtype, name=name)
        self.q_proj, self.k_proj, self.v_proj = \
            dense("q_proj"), dense("k_proj"), dense("v_proj")
        self.o_proj = nn.DenseGeneral(
            cfg.hidden_size, axis=(-2, -1), use_bias=False,
            dtype=cfg.jnp_dtype, name="o_proj")
        init = nn.initializers.normal(0.02)
        self.phi = self.param("phi", init, (H, D), jnp.float32)
        self.mu = self.param("mu", init, (H, D), jnp.float32)

    def _project(self, u, positions):
        """``u [B, S, hidden]``, ``positions [B, S]`` -> q, k, v ``[B, S, H,
        D]``, q and k after rope."""
        cfg = self.config
        q, k, v = self.q_proj(u), self.k_proj(u), self.v_proj(u)
        q, k = _rope(q, k, positions, cfg.head_dim, cfg.rope_theta)
        return q, k, v

    @property
    def _scale(self):
        return self.config.head_dim ** -0.5

    def __call__(self, u):
        """Plain EVA attention of ONE sequence ``u [S, hidden]`` from
        position 0 (``S`` whole chunks or not): dense masks, no cache."""
        cfg = self.config
        S, c, W = u.shape[0], cfg.chunk_size, cfg.window_size
        q, k, v = self._project(u[None], jnp.arange(S)[None])
        q, k, v = q[0], k[0], v[0]
        n = S // c                               # whole chunks
        ksum, vsum = summarise(
            k[:n * c].reshape(n, c, *k.shape[1:]),
            v[:n * c].reshape(n, c, *v.shape[1:]), self.phi, self.mu,
            self._scale)
        return self.o_proj(self._attend_dense(q, k, v, ksum, vsum, S, c, W))

    def _attend_dense(self, q, k, v, ksum, vsum, S, c, W):
        f32 = lambda t: t.astype(jnp.float32)
        p, t = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        local = (t <= p) & (t // W == p // W)
        remote = jnp.arange(ksum.shape[0])[None, :] < (W // c) * (p // W)
        scores = self._scale * jnp.concatenate(
            [jnp.einsum("phd,thd->hpt", f32(q), f32(k)),
             jnp.einsum("phd,jhd->hpj", f32(q), f32(ksum))], axis=-1)
        mask = jnp.concatenate([local, remote], axis=-1)
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        out = jnp.einsum("hpt,thd->phd", probs,
                         jnp.concatenate([f32(v), f32(vsum)]))
        return out.astype(q.dtype)

    # ---- the serving path ---- #
    def chunk(self, u, start, last, cache):
        """A prefill chunk of one slot: ``u [C, hidden]`` at positions
        ``start ..`` (inside one window), ``last`` its last real row.
        ``cache``: ``(pools, layer, ring table [1, n], lane table [1, m],
        page_runs marker or None)``.  Returns ``(out, pools)``."""
        cfg = self.config
        pools, li, ring, lane, runs = cache
        C, c, W = u.shape[0], cfg.chunk_size, cfg.window_size
        runs = paged_write_form(C, pools["k"].shape[2],
                                page_runs=runs) == "page_runs"
        q, k, v = self._project(u[None], (start + jnp.arange(C))[None])
        flat = lambda t: t.reshape(1, C, -1)
        with jax.named_scope("cache.write"):
            ringed = _paged_write(
                {"k": pools["k"], "v": pools["v"], "pages": ring,
                 "layer": li}, flat(k), flat(v), None, None,
                (start % W + jnp.arange(C))[None], per_row=False,
                page_runs=runs)
        out = self._eva_attend_chunk(q, ringed, pools, start, ring, lane, li)
        # (i) whole REAL chunks are pooled; the rest go to the trash page
        ksum, vsum = summarise(k[0].reshape(C // c, c, *k.shape[2:]),
                               v[0].reshape(C // c, c, *v.shape[2:]),
                               self.phi, self.mu, self._scale)
        rows = start // c + jnp.arange(C // c)
        real = (jnp.arange(C // c) + 1) * c - 1 <= last
        pools = {**ringed, **self._write_summaries(
            pools, li, lane[0][rows // pools["ksum"].shape[2]], rows, real,
            ksum, vsum)}
        return self.o_proj(out)[0], pools

    def _eva_attend_chunk(self, q, ringed, pools, start, ring, lane, li):
        cfg = self.config
        return eva_chunk_attention(
            q, ringed["k"], ringed["v"], pools["ksum"], pools["vsum"], start,
            ring, lane, layer=li, window=cfg.window_size,
            chunk_size=cfg.chunk_size, scale=self._scale)

    def step(self, u, pos, cache):
        """One token a lane: ``u [N, hidden]`` at positions ``pos [N]``.
        ``cache``: ``(pools, layer, ring table [N, n], lane table [N, m])``.
        Returns ``(out, pools)``."""
        cfg = self.config
        pools, li, ring, lane = cache
        c, W, page = cfg.chunk_size, cfg.window_size, pools["k"].shape[2]
        q, k, v = self._project(u[:, None], pos[:, None])
        out, k_ring, v_ring = self._eva_attend_step(
            q[:, 0], k[:, 0], v[:, 0], pools, pos, ring, lane, li)
        # (ii) the step that completes a chunk pools it from the ring's
        # last c rows (its own among them: the kernel wrote it)
        r = pos % W
        first = r - r % c                        # the chunk's first ring row
        ring_page = jnp.take_along_axis(ring, (r // page)[:, None], axis=1)
        at = (first % page)[:, None] + jnp.arange(c)
        ksum, vsum = summarise(
            self._heads(k_ring[li, ring_page, at]),
            self._heads(v_ring[li, ring_page, at]), self.phi, self.mu,
            self._scale)
        rows = pos // c
        lane_page = jnp.take_along_axis(
            lane, jnp.minimum(rows // page, lane.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        pools = {"k": k_ring, "v": v_ring, **self._write_summaries(
            pools, li, lane_page, rows, (pos + 1) % c == 0, ksum, vsum)}
        return self.o_proj(out[:, None])[:, 0], pools

    def _eva_attend_step(self, q, k, v, pools, pos, ring, lane, li):
        cfg = self.config
        return eva_decode_attention(
            q, pools["k"], pools["v"], pools["ksum"], pools["vsum"], pos,
            ring, lane, layer=li, window=cfg.window_size,
            chunk_size=cfg.chunk_size, new_k=k, new_v=v, scale=self._scale)

    def _heads(self, rows):
        cfg = self.config
        return rows.reshape(*rows.shape[:-1], cfg.num_heads, cfg.head_dim)

    def _write_summaries(self, pools, li, pages, rows, keep, ksum, vsum):
        """Summary rows ``rows [T]`` onto lane pages ``pages [T]``; where
        ``keep`` is false the row goes to the trash page."""
        with jax.named_scope("cache.write"):
            page = pools["ksum"].shape[2]
            pages = jnp.where(keep, pages, 0)
            put = lambda pool, new: pool.at[li, pages, rows % page].set(
                new.reshape(new.shape[0], -1).astype(pool.dtype))
            return {"ksum": put(pools["ksum"], ksum),
                    "vsum": put(pools["vsum"], vsum)}


class EvaByteLayer(nn.Module):
    config: EvaByteConfig

    def setup(self):
        cfg = self.config
        self.input_norm = _Norm(cfg.norm_eps)
        self.post_attn_norm = _Norm(cfg.norm_eps)
        self.attn = EvaAttention(cfg)
        self.mlp = _Mlp(cfg.intermediate_size, cfg.jnp_dtype)

    def __call__(self, x, attend):
        """``x`` float32; ``attend(attn, normed x) -> (out, pools)``: the
        call form the model chose (plain, chunk or step)."""
        a, pools = attend(self.attn, self.input_norm(x))
        x = x + a.astype(jnp.float32)
        return x + self.mlp(self.post_attn_norm(x)).astype(jnp.float32), pools


class _Head(nn.Module):
    """``[hidden, heads x vocab]``, float32 logits off bfloat16 operands."""
    features: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h, columns=None):
        w = self.param("kernel", nn.initializers.lecun_normal(),
                       (h.shape[-1], self.features), jnp.float32)
        w = w if columns is None else w[:, :columns]
        return jnp.dot(h.astype(self.dtype), w.astype(self.dtype),
                       preferred_element_type=jnp.float32)


class EvaByteModel(nn.Module):
    config: EvaByteConfig

    def setup(self):
        cfg = self.config
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                                     dtype=cfg.jnp_dtype)
        self.layers = [EvaByteLayer(cfg) for _ in range(cfg.num_layers)]
        self.final_norm = _Norm(cfg.norm_eps)
        self.lm_head = _Head(cfg.num_pred_heads * cfg.vocab_size,
                             cfg.jnp_dtype)

    def _head(self, h, columns=None):
        return self.lm_head(self.final_norm(h), columns)

    def __call__(self, batch):
        """Logits ``[B, S, heads x V]`` of ``batch["input_ids"] [B, S]``,
        float32, every prediction head: the plain forward, a row at a time,
        no cache."""
        rows = []
        for ids in batch["input_ids"]:
            x = self.embed_tokens(ids).astype(jnp.float32)
            for layer in self.layers:
                x, _ = layer(x, lambda attn, u: (attn(u), None))
            rows.append(self._head(x))
        return jnp.stack(rows)

    # ---- the serving path ---- #
    def slot_contract(self):
        """For the slot engine (``models/contract.py``): one SUMMARY row a
        chunk in the lane, one window's K/V ring a slot, a prefill chunk of
        at most a window that straddles none."""
        cfg = self.config
        return SlotContract(
            vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
            dtype=cfg.dtype, num_layers=cfg.num_layers,
            lane_stride=cfg.chunk_size, ring_pages=self._ring_pages,
            row_kinds=("summary rows", "ring rows"),
            chunk_cap=cfg.window_size, chunk_fault=self._chunk_fault,
            own_chunk_path=True,
            chunk_work=self._chunk_work, block_work=self._block_work,
            work_counters=("eva_ring_rows", "eva_summary_rows",
                           "eva_local_pairs", "eva_remote_pairs",
                           "eva_summaries_written"),
            work_levels=("ring_bytes_held", "summary_bytes_mapped"))

    def _chunk_fault(self, chunk):
        """Why ``chunk`` cannot be the prefill chunk, or None."""
        c, W = self.config.chunk_size, self.config.window_size
        if W % chunk or chunk % c:
            return (f"an evabyte prefill chunk divides window_size={W} and "
                    f"is whole chunks of chunk_size={c}; got {chunk}")
        return None

    def _ring_pages(self, page_size):
        """Pages of a slot's K/V ring in each layer: one window."""
        cfg = self.config
        if cfg.window_size % page_size or page_size % cfg.chunk_size:
            raise ValueError(
                f"serving.page_size={page_size} must divide evabyte's "
                f"window_size={cfg.window_size} and be whole chunks of "
                f"{cfg.chunk_size} (a chunk is pooled from ONE ring page)")
        return cfg.window_size // page_size

    def _pairs(self, lo, hi):
        """(query, key) pairs of queries at positions ``lo .. hi - 1``, a
        layer: ``(local, remote)`` — ring rows ``<= p % W`` and summary
        rows ``< (W / c) (p // W)``."""
        cfg = self.config
        W, per = cfg.window_size, cfg.window_size // cfg.chunk_size
        local = remote = 0
        p = lo
        while p < hi:                       # a window's stretch at a time
            w, end = p // W, min(hi, (p // W + 1) * W)
            n = end - p
            local += n * (p % W + 1) + n * (n - 1) // 2
            remote += n * per * w
            p = end
        return local, remote

    def _chunk_work(self, start, end, page_size, ring_pages, layers):
        """What a prefill chunk over REAL positions ``start .. end - 1``
        does in EVA attention, as its dispatch span's args (summed over the
        layers): ``eva_ring_rows`` / ``eva_summary_rows`` — K/V rows the
        chunk kernel fetches a query block (ring rows to the block's last,
        whole pages; the window's visible summaries) —, ``eva_local_pairs``
        / ``eva_remote_pairs`` — (query, key) pairs under the softmax —,
        ``eva_summaries_written`` — whole real chunks pooled."""
        cfg, L = self.config, layers
        c, W = cfg.chunk_size, cfg.window_size
        local, remote = self._pairs(start, end)
        return {"eva_ring_rows": L * ((end - 1) % W + 1),
                "eva_summary_rows": L * (W // c) * (start // W),
                "eva_local_pairs": L * local,
                "eva_remote_pairs": L * remote,
                "eva_summaries_written": L * (end // c - start // c)}

    def _block_work(self, live, ring_pages, layers):
        """The same for a decode block, from ``live`` — ``(context,
        steps)`` a live slot, ``context`` the positions the first step
        attends (its own among them) — plus the cache's split:
        ``ring_bytes_held`` — the rings of the live slots, held whole —
        and ``summary_bytes_mapped`` — the lane pages their summaries
        reach."""
        cfg, L = self.config, layers
        c, W = cfg.chunk_size, cfg.window_size
        page = W // max(ring_pages, 1)
        page_bytes = 2 * page * cfg.hidden_size * cfg.jnp_dtype.itemsize
        local = remote = written = pages = 0
        for first, steps in live:
            lo, hi = first - 1, first - 1 + steps        # positions fed
            a, b = self._pairs(lo, hi)
            local, remote = local + a, remote + b
            written += hi // c - lo // c
            pages += -(-hi // (c * page))      # lane pages hi positions reach
        # one query a step: the rows a step reads are its pairs
        return {"eva_ring_rows": L * local, "eva_summary_rows": L * remote,
                "eva_local_pairs": L * local, "eva_remote_pairs": L * remote,
                "eva_summaries_written": L * written,
                "ring_bytes_held": L * len(live) * ring_pages * page_bytes,
                "summary_bytes_mapped": L * pages * page_bytes}

    def init_paged_cache(self, num_pages, page_size, dtype=None,
                         window_pages=1):
        """``k`` / ``v [layers, window_pages, page, H x D]`` — each slot's
        ring (``paging.SlotPages`` sizes it: trash + slots x ring pages) —
        and ``ksum`` / ``vsum [layers, num_pages, page, H x D]``, one row a
        chunk, behind the slot's lane pages."""
        cfg = self.config
        dtype = dtype or cfg.jnp_dtype
        shape = lambda pages: (cfg.num_layers, int(pages), int(page_size),
                               cfg.hidden_size)
        ring, lane = shape(window_pages), shape(num_pages)
        return {"k": jnp.zeros(ring, dtype), "v": jnp.zeros(ring, dtype),
                "ksum": jnp.zeros(lane, dtype), "vsum": jnp.zeros(lane, dtype)}

    def decode(self, input_ids, cache, start_pos, logits_at=None, live=None):
        """The slot programs' call: a prefill chunk of one slot
        (``input_ids [1, C]``, scalar ``start_pos``) or one token a lane
        (``[N, 1]``, ``start_pos [N]``).  ``cache["pages"]`` is the table
        row(s): the slot's lane pages, then its ring pages.  Logits are
        head 0's, float32."""
        cfg = self.config
        per_row = jnp.ndim(start_pos) == 1
        if per_row and input_ids.shape[1] != 1:
            raise NotImplementedError(
                "evabyte serves one token a lane a step: a speculative "
                "verify window would write ring rows and summaries for "
                "positions it may reject (serving.speculative)")
        pages = cache["pages"]
        n_ring = self._ring_pages(cache["k"].shape[2])
        lane, ring = pages[:, :-n_ring], pages[:, -n_ring:]
        pools = {n: cache[n] for n in ("k", "v", "ksum", "vsum")}
        x = self.embed_tokens(input_ids[:, 0] if per_row else input_ids[0]) \
            .astype(jnp.float32)
        last = input_ids.shape[1] - 1 if logits_at is None \
            else logits_at[0].astype(jnp.int32)
        for i, layer in enumerate(self.layers):
            li = jnp.asarray(i, jnp.int32)

            def attend(attn, u, li=li, pools=pools):
                if per_row:
                    return attn.step(u, start_pos, (pools, li, ring, lane))
                return attn.chunk(u, start_pos, last,
                                  (pools, li, ring, lane,
                                   "page_runs" in cache))

            x, pools = layer(x, attend)
        h = x[:, None] if per_row else x[None]
        if logits_at is not None:
            h = jnp.take_along_axis(
                h, logits_at.astype(jnp.int32)[:, None, None], axis=1)
        return self._head(h, cfg.vocab_size), pools
