"""Latent (MLA) attention for the serving path: one module, two sizes.

A layer caches ONE row a token, ``[c_kv | k_r]`` — the RMS-normed latent
and the roped key part shared by all heads — instead of per-head keys and
values; every head's key and value are up-projections of the latent.

* A **full** layer (``window == 0``) with ``index_topk > 0`` carries the
  DSA indexer (DeepSeek-V3.2): a second, small cached key a token,
  per-query index scores over the slot's live positions, an exact
  top-``index_topk``, and a softmax over the kept positions only.
* A **dense** full layer (``window == 0``, ``index_topk == 0``) is plain
  causal MLA (``models/longcat.py``): no indexer parameter, no index pool
  — its cache is the latent pool alone —, a chunk under the causal mask,
  a decode row over every row ``<= position`` of its lane.  The kernels
  are the indexed layer's, handed a mask that keeps every visible key
  (scopes ``attn.mla_dense_chunk`` / ``attn.mla_dense_decode``).
* A **window** layer attends the token and its ``window - 1`` predecessors
  and keeps its rows in a bounded ring a slot (``paging.SlotPages``).

Call forms over the same parameters (``ops/transformer/
latent_attention.py`` has the kernels):

* :meth:`LatentAttention.chunk` — a prefill chunk of one slot, keys and
  values decompressed from the slot's cached rows — a full layer's lane
  gathered through the slot's table, its key blocks up to the chunk's last
  position decompressed and no others (``attn.mla_decompress``) —, flash
  attention under the kept-set (or band) mask; with no cache it is the
  plain causal forward over the chunk alone (init, tests).
* :meth:`LatentAttention.window` — a few rows a lane at consecutive
  positions (a verify window; one row is a decode step), in the absorbed
  form: the query goes through the key up-projection, attends latent rows
  directly, and the value up-projection follows.  A full layer takes one
  of two forms, chosen from what the module sees — the slot's table
  against ``index_topk`` (:data:`LANE_FORM_KEPT_SETS`): the LANE form
  reads a lane's rows once for all its rows and heads, each row's kept
  set a mask over them (``attn.mla_lane_decode``); the per-row form sorts
  a row's scores and READS only its kept rows.
* :meth:`LatentAttention.step` — one token a lane: a window layer's ring,
  or :meth:`window` at one row.

What differs between the models that share this module is a size of the
:class:`LatentSpec`, fixed when the module is built: ``gated`` — the
headwise output gate ``sigmoid(x W_g)`` on each head's output before
``o_proj`` (``models/dots3.py`` has it, ``models/glm5.py`` has neither the
gate nor its parameter) —, ``rescale`` and ``interleaved``, the rotary
pairing (``(i, i + d/2)`` or ``(2i, 2i + 1)``).
"""

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from deepspeed_tpu.models.parts import _rms
from deepspeed_tpu.ops.transformer import latent_attention as ops

LANES = ops.LANES
# A full layer's decode takes the LANE form — a lane's rows read once for
# all its rows and heads, a row's kept set a mask over them — while the
# slot's table spans at most this many kept sets (``index_topk``), and
# beyond that the per-row form, a sort and a gather of the kept rows:
# the lane form reads ``table`` rows a lane, the per-row one writes and
# reads ``index_topk`` gathered rows a ROW, after a sort of the whole row.
# (GLM-5's cell: 4,672 / 2,048 = 2.3, lane; dots3's: 16,448 / 2,048 = 8,
# per row.  The crossing was not measured: PERF.md section 7.)
LANE_FORM_KEPT_SETS = 4


def padded(width):
    """A pool row's width: ``width`` rounded up to whole 128-lane tiles
    (576 -> 640, 1088 -> 1152)."""
    return -(-width // LANES) * LANES


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """One layer kind's sizes (HF key names in ``models/dots3.py``)."""
    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    eps: float = 1e-5
    window: int = 0              # 0: full attention
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0          # 0 (full): no indexer, plain causal
    rescale: bool = True
    gated: bool = True           # the headwise output gate and its W_g
    interleaved: bool = False    # rotary pairs (2i, 2i + 1), not (i, i + d/2)

    @property
    def row(self):
        return self.kv_rank + self.rope

    @property
    def scale(self):
        return float(1.0 / np.sqrt(self.nope + self.rope))


def rope(x, positions, theta, dims=None, interleaved=False):
    """Rotary positions on the first ``dims`` features of ``x [..., T,
    D]`` (default all); ``positions [T]``.  Feature ``i`` pairs with
    ``i + dims/2`` (the half-split layout) or, ``interleaved``, ``2i``
    with ``2i + 1``."""
    dims = dims or x.shape[-1]
    half = dims // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    if interleaved:
        pairs = xf[..., :dims].reshape(xf.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        rot = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(xf.shape[:-1] + (dims,))
        out = jnp.concatenate([rot, xf[..., dims:]], axis=-1)
    else:
        x1, x2 = xf[..., :half], xf[..., half:dims]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                               xf[..., dims:]], axis=-1)
    return out.astype(x.dtype)


def _layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def live_block_rows(end):
    """Rows a full layer's cached chunk decompresses when its last
    position is ``end - 1``: the live key blocks, whole — the host-side
    count behind ``chunk_work``'s ``latent_rows_decompressed``."""
    return -(-end // ops.KEY_BLOCK) * ops.KEY_BLOCK


def flash_tiles(start, end, limit=None, window=0):
    """``(live, whole)``: how many of the chunk flash kernel's ``KEY_BLOCK
    x KEY_BLOCK`` (query, key) tiles one layer walks for a chunk's real
    positions ``start .. end - 1`` (every head block walks each), and how
    many of those its mask keeps WHOLE — the host-side count behind
    ``chunk_work``'s ``flash_tiles_live`` / ``flash_tiles_whole``.  Query
    tiles lie from ``start``; key tiles from position 0 (a full layer's
    lane) or, ``window`` set, from the band's first key ``start - window +
    1`` — the kernel's own tiles wherever the chunk and the keys are whole
    512-row tiles, or fewer than 512 rows.  A tile is live where a pair is
    visible.  ``limit`` (a selecting layer's ``index_topk``): a query past
    it keeps a subset nobody knows here, so a tile counts as whole only
    where every query keeps all it sees — a LOWER bound on the whole tiles
    of the contexts past ``limit``, exact before it."""
    tile = ops.KEY_BLOCK
    first = start - window + 1 if window else 0
    live = whole = 0
    for a in range(start, end, tile):
        b = min(a + tile, end)                 # queries a .. b - 1
        for c in range(first, b, tile):        # keys c .. d - 1, some <= b - 1
            d = c + tile
            if d <= 0 or (window and d - 1 <= a - window):
                continue
            live += 1
            whole += c >= 0 and d - 1 <= a \
                and (not window or c > b - 1 - window) \
                and (limit is None or b <= limit)
    return live, whole


def lane_rows(pool, layer, table_row, multiple):
    """One slot's virtual lane of ``pool [layers, pages, page, W]`` at
    ``layer`` through its table row ``[n]``: ``[n' * page, W]`` in position
    order, ``n'`` rounded up to ``multiple`` pages with trash-page entries.
    ONE gather by ``(layer, page)``: the pool's layer is never sliced out
    (a copy of every slot's pages to read one slot's)."""
    pad = -table_row.shape[0] % multiple
    rows = pool[layer, jnp.pad(table_row, (0, pad))]
    return rows.reshape(-1, rows.shape[-1])


def write_rows(pool, layer, table, positions, rows, keep=None):
    """Scatter ``rows [T, W']`` at ``positions [T]`` through ``table``
    (``[n]`` for one slot's chunk, ``[T, n]`` for one row a lane) into
    ``pool [layers, pages, page, W]``.  ``keep [T]`` false: not written.
    ``n`` pages form a RING where it is shorter than the positions
    (a window layer's): position ``p`` lives in table entry
    ``(p // page) % n``."""
    page, n = pool.shape[2], table.shape[-1]
    slot = (positions // page) % n
    phys = table[slot] if table.ndim == 1 \
        else jnp.take_along_axis(table, slot[:, None], axis=1)[:, 0]
    if keep is not None:
        phys = jnp.where(keep, phys, pool.shape[1])       # out of range
    rows = jnp.pad(rows, ((0, 0), (0, pool.shape[-1] - rows.shape[-1])))
    return pool.at[layer, phys, positions % page].set(
        rows.astype(pool.dtype), mode="drop")


class LatentAttention(nn.Module):
    spec: LatentSpec
    dtype: Any = jnp.bfloat16

    def setup(self):
        z = self.spec
        init = nn.initializers.lecun_normal()
        p = lambda name, *shape: self.param(name, init, shape, jnp.float32)
        ones = lambda name, n: self.param(name, nn.initializers.ones, (n,),
                                          jnp.float32)
        self.q_a = p("q_a", z.hidden, z.q_rank)
        self.q_a_norm = ones("q_a_norm", z.q_rank)
        self.q_b = p("q_b", z.q_rank, z.heads * (z.nope + z.rope))
        self.kv_a = p("kv_a", z.hidden, z.row)
        self.kv_a_norm = ones("kv_a_norm", z.kv_rank)
        self.kv_b = p("kv_b", z.kv_rank, z.heads * (z.nope + z.v))
        self.o_proj = p("o_proj", z.heads * z.v, z.hidden)
        if z.gated:
            self.gate = p("gate", z.hidden, z.heads)
        if z.index_topk:
            self.index_q = p("index_q", z.q_rank,
                             z.index_heads * z.index_dim)
            self.index_k = p("index_k", z.hidden, z.index_dim)
            self.index_k_scale = ones("index_k_norm_scale", z.index_dim)
            self.index_k_bias = self.param(
                "index_k_norm_bias", nn.initializers.zeros, (z.index_dim,),
                jnp.float32)
            self.index_w = p("index_w", z.hidden, z.index_heads)

    # ---- projections shared by both call forms ---- #
    def _w(self, w):
        return w.astype(self.dtype)

    def _project(self, x, positions):
        """``x [T, h]`` -> ``(q [H, T, nope + rope]`` with the rope part
        roped, ``row [T, kv_rank + rope]`` — what the cache holds —,
        ``c_q [T, q_rank])``."""
        z = self.spec
        up = lambda rank: float(np.sqrt(z.hidden / rank)) if z.rescale \
            else 1.0
        c_q = _rms(x @ self._w(self.q_a), self.q_a_norm, z.eps,
                   up(z.q_rank))
        q = jnp.einsum("tr,rhd->htd", c_q, self._w(self.q_b).reshape(
            z.q_rank, z.heads, z.nope + z.rope))
        turn = lambda t: rope(t, positions, z.theta,
                              interleaved=z.interleaved)
        q = jnp.concatenate([q[..., :z.nope], turn(q[..., z.nope:])], -1)
        kv = x @ self._w(self.kv_a)
        row = jnp.concatenate([
            _rms(kv[:, :z.kv_rank], self.kv_a_norm, z.eps, up(z.kv_rank)),
            turn(kv[:, z.kv_rank:])], axis=-1)
        return q, row, c_q

    def _index(self, x, c_q, positions):
        """The indexer's ``(q [T, J, D], k [T, D], w [T, J] float32)`` —
        ``w`` carries the score's constant factors."""
        z = self.spec
        q = (c_q @ self._w(self.index_q)).reshape(-1, z.index_heads,
                                                  z.index_dim)
        q = rope(q.transpose(1, 0, 2), positions, z.theta, z.rope,
                 z.interleaved).transpose(1, 0, 2)
        k = _layer_norm(x @ self._w(self.index_k), self.index_k_scale,
                        self.index_k_bias, z.eps)
        k = rope(k, positions, z.theta, z.rope, z.interleaved)
        w = (x @ self._w(self.index_w)).astype(jnp.float32) \
            * float(z.index_heads ** -0.5 * z.index_dim ** -0.5)
        return q, k, w

    def _kv_up(self):
        z = self.spec
        w = self._w(self.kv_b).reshape(z.kv_rank, z.heads, z.nope + z.v)
        return w[..., :z.nope], w[..., z.nope:]

    def _out(self, x, o):
        """``o [T, H, v]``, gated head by head where the spec has the
        gate, through ``o_proj``."""
        if self.spec.gated:
            g = jax.nn.sigmoid((x @ self._w(self.gate)).astype(jnp.float32))
            o = o.astype(jnp.float32) * g[..., None]
        o = o.astype(self.dtype)
        return o.reshape(o.shape[0], -1) @ self._w(self.o_proj)

    # ---- a chunk of one slot ---- #
    def chunk(self, x, start, live=None, cache=None):
        """``x [C, h]`` at positions ``start .. start + C - 1`` of one
        slot.  ``cache``: ``None`` (the chunk alone, ``start`` 0) or
        ``(pools, layer, table_row)`` — ``pools`` the layer kind's pool(s),
        ``layer`` its index there.  Returns ``(out [C, h], pools)``."""
        z = self.spec
        C = x.shape[0]
        positions = start + jnp.arange(C, dtype=jnp.int32)
        q, row, c_q = self._project(x, positions)
        if z.window:
            out, cache = self._chunk_window(q, row, positions, live, cache)
        elif z.index_topk:
            out, cache = self._chunk_full(x, q, row, c_q, positions, cache)
        else:
            out, cache = self._chunk_dense(q, row, positions, cache)
        return self._out(x, out.transpose(1, 0, 2)), cache

    def _attend(self, q, keys, mask, name, live_keys=None):
        """Decompress ``keys [L, >= row]`` and attend under ``mask``.
        ``live_keys``: the mask keeps no key at or past it, and only the
        key blocks before it are decompressed (``ops.decompress``) — the
        blocks the flash kernel can fetch."""
        z = self.spec
        with jax.named_scope("attn.mla_decompress"):      # c_kv W_kvb
            if live_keys is None:
                w_k, w_v = self._kv_up()
                lat = keys[:, :z.kv_rank]
                k_nope = jnp.einsum("lr,rhd->hld", lat, w_k)
                v = jnp.einsum("lr,rhd->hld", lat, w_v)
            else:
                k_nope, v = ops.decompress(keys, self._w(self.kv_b), z.heads,
                                           z.nope, live_keys)
        return ops.masked_flash(
            q[..., :z.nope], q[..., z.nope:], k_nope,
            keys[:, z.kv_rank:z.row], v, mask, z.scale, name)

    def _chunk_full(self, x, q, row, c_q, positions, cache):
        z = self.spec
        C = x.shape[0]
        qi, ki, w = self._index(x, c_q, positions)
        if cache is None:
            pools, keys, index_keys = None, row, ki
        else:
            (latent, index), layer, table = cache
            with jax.named_scope("cache.write"):
                latent = write_rows(latent, layer, table, positions, row)
                index = write_rows(index, layer, table, positions, ki)
            pools = (latent, index)
            # the slot's whole lane, in position order, in whole key blocks
            pages = max(1, ops.KEY_BLOCK // latent.shape[2])
            keys = lane_rows(latent, layer, table, pages)
            index_keys = lane_rows(index, layer, table, pages)
        live_keys = positions[-1] + 1
        scores = ops.index_scores(qi, w, index_keys, live_keys)
        mask = ops.kept_mask(scores, positions, z.index_topk)
        return self._attend(q, keys, mask, "attn.mla_chunk_prefill",
                            None if cache is None else live_keys), pools

    def _chunk_dense(self, q, row, positions, cache):
        """A dense full layer's chunk: the slot's lane under the causal
        mask, the live key blocks decompressed; ``cache = (latent pool,
        layer, table_row)``."""
        pool, keys = None, row
        if cache is not None:
            pool, layer, table = cache
            with jax.named_scope("cache.write"):
                pool = write_rows(pool, layer, table, positions, row)
            keys = lane_rows(pool, layer, table,
                             max(1, ops.KEY_BLOCK // pool.shape[2]))
        with jax.named_scope("attn.mla_dense_chunk"):
            mask = jnp.arange(keys.shape[0])[None, :] <= positions[:, None]
            return self._attend(
                q, keys, mask.astype(jnp.int8), "attn.mla_chunk_prefill",
                None if cache is None else positions[-1] + 1), pool

    def _chunk_window(self, q, row, positions, live, cache):
        z = self.spec
        C, back = row.shape[0], z.window - 1
        start = positions[0]
        before = start - back + jnp.arange(back, dtype=jnp.int32)
        if cache is None:
            pool, prev = None, jnp.zeros((back, row.shape[1]), row.dtype)
        else:
            pool, layer, ring = cache
            page, n = pool.shape[2], ring.shape[0]
            at = jnp.maximum(before, 0)
            prev = pool[layer, ring[(at // page) % n], at % page][:, :z.row]
            # the ring keeps the chunk's last live rows; a padded tail
            # must not push out rows the next step still attends
            keep = jnp.ones((C,), bool) if live is None else live
            last = jnp.max(jnp.where(keep, positions, -1))
            keep = keep & (positions > last - n * page)
            with jax.named_scope("cache.write"):
                pool = write_rows(pool, layer, ring, positions, row, keep)
        key_pos = jnp.concatenate([before, positions])
        mask = (key_pos[None, :] >= 0) \
            & (key_pos[None, :] <= positions[:, None]) \
            & (key_pos[None, :] > positions[:, None] - z.window)
        keys = jnp.concatenate([prev.astype(row.dtype), row])
        return self._attend(q, keys, mask.astype(jnp.int8),
                            "attn.mla_window"), pool

    # ---- a few rows a lane ---- #
    def window(self, x, start, cache):
        """``x [N W, h]``: lane ``n``'s ``W`` rows at positions ``start[n]
        .. + W - 1`` (``W`` 1: a decode step; 2: a verify window, the later
        row attending the earlier); ``cache = (pools, layer, table [N,
        n])`` of a full layer.  Every row's cache rows are written first;
        then each row's kept set is the exact top ``index_topk`` of its
        scores and the absorbed softmax runs over it, in the form the
        slot's table decides (:data:`LANE_FORM_KEPT_SETS`).  Returns
        ``(out [N W, h], pools)``.  A dense full layer (``cache``'s pool
        the latent pool alone) takes the lane form with every row
        ``<= position`` kept."""
        z = self.spec
        N, W = start.shape[0], x.shape[0] // start.shape[0]
        positions = start if W == 1 else (
            start[:, None] + jnp.arange(W, dtype=start.dtype)).reshape(-1)
        # every row has its own position: rope row by row
        q, row, c_q = jax.vmap(
            lambda xr, p: self._project(xr[None], p[None]))(x, positions)
        q, row, c_q = q[:, :, 0], row[:, 0], c_q[:, 0]    # [T, H, D] ...
        w_k, w_v = self._kv_up()
        q_lat = jnp.einsum("thd,rhd->thr", q[..., :z.nope], w_k)
        if not z.index_topk:
            lat, pools = self._lanes_dense(q_lat, q[..., z.nope:], row,
                                           start, positions, cache)
            out = jnp.einsum("thr,rhd->thd", lat.astype(self.dtype), w_v)
            return self._out(x, out), pools
        (latent, index), layer, table = cache
        qi, ki, w = jax.vmap(
            lambda xr, cr, p: self._index(xr[None], cr[None], p[None]))(
                x, c_q, positions)
        per_row = table if W == 1 else jnp.repeat(table, W, axis=0)
        with jax.named_scope("cache.write"):
            latent = write_rows(latent, layer, per_row, positions, row)
            index = write_rows(index, layer, per_row, positions, ki[:, 0])
        page = latent.shape[2]
        if table.shape[1] * page <= LANE_FORM_KEPT_SETS * z.index_topk:
            lat, latent, index = self._lanes(
                q_lat, q[..., z.nope:], qi[:, 0], w[:, 0], latent, index,
                layer, table, start, positions)
        else:
            lat = self._kept_rows(q_lat, q[..., z.nope:], qi[:, 0], w[:, 0],
                                  latent, index, layer, per_row, positions)
        out = jnp.einsum("thr,rhd->thd", lat.astype(self.dtype), w_v)
        return self._out(x, out), (latent, index)

    def _lanes(self, q_lat, q_rope, qi, w, latent, index, layer, table,
               start, positions):
        """The lane form: the lane's index keys and latent rows are read
        through the table ONCE for all its rows and heads, a row's kept
        set is a MASK over them (``ops.kept_mask``, the chunk's) — no
        sort, no gather of kept rows a row.  Returns ``(attended latent,
        latent pool, index pool)``, the pools as the kernels hand them
        through."""
        z = self.spec
        N, W = start.shape[0], positions.shape[0] // start.shape[0]
        # the lane in 512-key blocks (the kept-set kernel's rows are whole
        # lane tiles), trash-page entries past the table
        lane, bp = ops.lane_pages(table, latent.shape[2])
        L = lane.shape[1] * latent.shape[2]
        ctx = jnp.minimum(start + W, L)
        by_lane = lambda t: t.reshape((N, W) + t.shape[1:])
        scores, index = ops.lane_index_scores(by_lane(qi), by_lane(w), index,
                                              layer, lane, bp, ctx)
        kept = ops.kept_mask(scores.reshape(N * W, L), positions,
                             min(z.index_topk, L))
        lat, latent = self._lane_attend(q_lat, q_rope, kept, latent, layer,
                                        lane, bp, ctx)
        return lat, latent, index

    def _lane_attend(self, q_lat, q_rope, kept, latent, layer, lane, bp,
                     ctx):
        """``ops.lane_decode`` of ``[T, H, ..]`` query rows under ``kept
        [T, L]``, the query laid out as a pool row."""
        z = self.spec
        N = lane.shape[0]
        by_lane = lambda t: t.reshape((N, -1) + t.shape[1:])
        q_row = jnp.concatenate([q_lat, q_rope], axis=-1)
        q_row = jnp.pad(q_row, ((0, 0), (0, 0),
                                (0, latent.shape[-1] - q_row.shape[-1])))
        lat, latent = ops.lane_decode(by_lane(q_row), by_lane(kept), latent,
                                      layer, lane, bp, ctx, z.kv_rank,
                                      z.scale)
        return lat.reshape((-1,) + lat.shape[2:]), latent

    def _lanes_dense(self, q_lat, q_rope, row, start, positions, cache):
        """A dense full layer's rows: written, then each lane's rows read
        once for all its rows and heads, every row ``<= position`` kept.
        Returns ``(attended latent, latent pool)``."""
        latent, layer, table = cache
        W = positions.shape[0] // start.shape[0]
        per_row = table if W == 1 else jnp.repeat(table, W, axis=0)
        with jax.named_scope("cache.write"):
            latent = write_rows(latent, layer, per_row, positions, row)
        with jax.named_scope("attn.mla_dense_decode"):
            lane, bp = ops.lane_pages(table, latent.shape[2])
            L = lane.shape[1] * latent.shape[2]
            kept = jnp.arange(L)[None, :] <= positions[:, None]
            return self._lane_attend(q_lat, q_rope, kept, latent, layer,
                                     lane, bp, jnp.minimum(start + W, L))

    def _kept_rows(self, q_lat, q_rope, qi, w, latent, index, layer, table,
                   positions):
        """The per-row form (``table [T, n]``, a row's own): a sort of
        the row's scores and a gather of its kept rows, which are all it
        READS of the latent pool."""
        z = self.spec
        T, page = positions.shape[0], latent.shape[2]
        index_keys = index[layer, table].reshape(T, -1, index.shape[-1])
        L = index_keys.shape[1]
        scores = ops.index_scores_rows(qi, w, index_keys)
        visible = jnp.arange(L)[None, :] <= positions[:, None]
        kept, valid = ops.kept_indices(scores, visible, min(z.index_topk, L))
        flat = jnp.take_along_axis(table, kept // page, axis=1) * page \
            + kept % page
        rows = latent[layer].reshape(-1, latent.shape[-1])[flat]
        return ops.sparse_decode(q_lat, q_rope, rows, valid, z.kv_rank,
                                 z.scale)

    # ---- one token a lane ---- #
    def step(self, x, positions, cache):
        """``x [N, h]``, lane ``n`` at ``positions[n]``; ``cache =
        (pools, layer, table [N, n])``.  Returns ``(out [N, h], pools)``.
        A full layer's step is :meth:`window` at one row a lane."""
        z = self.spec
        if not z.window:
            return self.window(x, positions, cache)
        N = x.shape[0]
        # every lane has its own position: rope row by row
        q, row, c_q = jax.vmap(
            lambda xr, p: self._project(xr[None], p[None]))(x, positions)
        q, row = q[:, :, 0], row[:, 0]                    # [N, H, D] ...
        w_k, w_v = self._kv_up()
        q_lat = jnp.einsum("nhd,rhd->nhr", q[..., :z.nope], w_k)
        pool, layer, table = cache
        with jax.named_scope("cache.write"):
            pool = write_rows(pool, layer, table, positions, row)
        page, n = pool.shape[2], table.shape[1]
        rows = pool[layer, table].reshape(N, n * page, -1)
        r = jnp.arange(n * page, dtype=jnp.int32)[None, :]
        held = positions[:, None] - (positions[:, None] - r) % (n * page)
        valid = (held >= 0) & (held > positions[:, None] - z.window)
        with jax.named_scope("attn.mla_window"):
            lat = ops.sparse_decode(q_lat, q[..., z.nope:], rows, valid,
                                    z.kv_rank, z.scale)
        out = jnp.einsum("nhr,rhd->nhd", lat.astype(self.dtype), w_v)
        return self._out(x, out), pool
