"""Latent (MLA) attention for the serving path: one module, two sizes.

A layer caches ONE row a token, ``[c_kv | k_r]`` — the RMS-normed latent
and the roped key part shared by all heads — instead of per-head keys and
values; every head's key and value are up-projections of the latent.

* A **full** layer (``window == 0``) carries the DSA indexer (DeepSeek-
  V3.2): a second, small cached key a token, per-query index scores over
  the slot's live positions, an exact top-``index_topk``, and a softmax
  over the kept positions only.
* A **window** layer attends the token and its ``window - 1`` predecessors
  and keeps its rows in a bounded ring a slot (``paging.SlotPages``).

Three call forms over the same parameters (``ops/transformer/
latent_attention.py`` has the kernels):

* :meth:`LatentAttention.chunk` — a prefill chunk of one slot, keys and
  values decompressed from the slot's cached rows, flash attention under
  the kept-set (or band) mask; with no cache it is the plain causal
  forward over the chunk alone (init, tests).
* :meth:`LatentAttention.step` — one token a lane, in the absorbed form:
  the query goes through the key up-projection, attends latent rows
  directly, and the value up-projection follows.  A full layer READS only
  the kept rows of the latent pool.

The headwise output gate ``sigmoid(x W_g)`` scales each head's output
before ``o_proj``.
"""

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from deepspeed_tpu.ops.transformer import latent_attention as ops

LANES = 128


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
    window: int = 0              # 0: full attention with the indexer
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    rescale: bool = True

    @property
    def row(self):
        return self.kv_rank + self.rope

    @property
    def scale(self):
        return float(1.0 / np.sqrt(self.nope + self.rope))


def rope(x, positions, theta, dims=None):
    """Rotary positions on the first ``dims`` features of ``x [..., T,
    D]`` (default all), half-split layout; ``positions [T]``."""
    dims = dims or x.shape[-1]
    half = dims // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:dims]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           xf[..., dims:]], axis=-1)
    return out.astype(x.dtype)


def _rms(x, scale, eps, factor=1.0):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32) * factor).astype(x.dtype)


def _layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def lane_rows(pool, table_row, multiple):
    """One slot's virtual lane of a pool layer ``[pages, page, W]``
    through its table row ``[n]``: ``[n' * page, W]`` in position order,
    ``n'`` rounded up to ``multiple`` pages with trash-page entries."""
    pad = -table_row.shape[0] % multiple
    rows = pool[jnp.pad(table_row, (0, pad))]
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
        q = jnp.concatenate([q[..., :z.nope],
                             rope(q[..., z.nope:], positions, z.theta)], -1)
        kv = x @ self._w(self.kv_a)
        row = jnp.concatenate([
            _rms(kv[:, :z.kv_rank], self.kv_a_norm, z.eps, up(z.kv_rank)),
            rope(kv[:, z.kv_rank:], positions, z.theta)], axis=-1)
        return q, row, c_q

    def _index(self, x, c_q, positions):
        """The indexer's ``(q [T, J, D], k [T, D], w [T, J] float32)`` —
        ``w`` carries the score's constant factors."""
        z = self.spec
        q = (c_q @ self._w(self.index_q)).reshape(-1, z.index_heads,
                                                  z.index_dim)
        q = rope(q.transpose(1, 0, 2), positions, z.theta,
                 z.rope).transpose(1, 0, 2)
        k = _layer_norm(x @ self._w(self.index_k), self.index_k_scale,
                        self.index_k_bias, z.eps)
        k = rope(k, positions, z.theta, z.rope)
        w = (x @ self._w(self.index_w)).astype(jnp.float32) \
            * float(z.index_heads ** -0.5 * z.index_dim ** -0.5)
        return q, k, w

    def _kv_up(self):
        z = self.spec
        w = self._w(self.kv_b).reshape(z.kv_rank, z.heads, z.nope + z.v)
        return w[..., :z.nope], w[..., z.nope:]

    def _out(self, x, o):
        """``o [T, H, v]`` gated head by head, through ``o_proj``."""
        g = jax.nn.sigmoid((x @ self._w(self.gate)).astype(jnp.float32))
        o = (o.astype(jnp.float32) * g[..., None]).astype(self.dtype)
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
        else:
            out, cache = self._chunk_full(x, q, row, c_q, positions, cache)
        return self._out(x, out.transpose(1, 0, 2)), cache

    def _attend(self, q, keys, mask, name):
        """Decompress ``keys [L, row]`` and attend under ``mask``."""
        z = self.spec
        w_k, w_v = self._kv_up()
        lat = keys[:, :z.kv_rank]
        with jax.named_scope("attn.mla_decompress"):      # c_kv W_kvb
            k_nope = jnp.einsum("lr,rhd->hld", lat, w_k)
            v = jnp.einsum("lr,rhd->hld", lat, w_v)
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
            # the slot's whole lane, in position order, in 512-key blocks
            pages = max(1, 512 // latent.shape[2])
            keys = lane_rows(latent[layer], table, pages)
            index_keys = lane_rows(index[layer], table, pages)
        live_keys = positions[-1] + 1
        scores = ops.index_scores(qi, w, index_keys, live_keys)
        mask = ops.kept_mask(scores, positions, z.index_topk)
        return self._attend(q, keys, mask, "attn.mla_chunk_prefill"), pools

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

    # ---- one token a lane ---- #
    def step(self, x, positions, cache):
        """``x [N, h]``, lane ``n`` at ``positions[n]``; ``cache =
        (pools, layer, table [N, n])``.  Returns ``(out [N, h], pools)``."""
        z = self.spec
        N = x.shape[0]
        # every lane has its own position: rope row by row
        q, row, c_q = jax.vmap(
            lambda xr, p: self._project(xr[None], p[None]))(x, positions)
        q, row, c_q = q[:, :, 0], row[:, 0], c_q[:, 0]    # [N, H, D] ...
        w_k, w_v = self._kv_up()
        q_lat = jnp.einsum("nhd,rhd->nhr", q[..., :z.nope], w_k)
        pools, layer, table = cache
        if z.window:
            with jax.named_scope("cache.write"):
                pool = write_rows(pools, layer, table, positions, row)
            page, n = pool.shape[2], table.shape[1]
            rows = pool[layer, table].reshape(N, n * page, -1)
            r = jnp.arange(n * page, dtype=jnp.int32)[None, :]
            held = positions[:, None] - (positions[:, None] - r) % (n * page)
            valid = (held >= 0) & (held > positions[:, None] - z.window)
            with jax.named_scope("attn.mla_window"):
                lat = ops.sparse_decode(q_lat, q[..., z.nope:], rows, valid,
                                        z.kv_rank, z.scale)
            pools = pool
        else:
            latent, index = pools
            qi, ki, w = jax.vmap(
                lambda xr, cr, p: self._index(xr[None], cr[None], p[None]))(
                    x, c_q, positions)
            with jax.named_scope("cache.write"):
                latent = write_rows(latent, layer, table, positions, row)
                index = write_rows(index, layer, table, positions, ki[:, 0])
            page = latent.shape[2]
            index_keys = index[layer, table].reshape(N, -1, index.shape[-1])
            L = index_keys.shape[1]
            scores = ops.index_scores_rows(qi[:, 0], w[:, 0], index_keys)
            visible = jnp.arange(L)[None, :] <= positions[:, None]
            kept, valid = ops.kept_indices(scores, visible,
                                           min(z.index_topk, L))
            flat = jnp.take_along_axis(table, kept // page, axis=1) * page \
                + kept % page
            rows = latent[layer].reshape(-1, latent.shape[-1])[flat]
            lat = ops.sparse_decode(q_lat, q[..., z.nope:], rows, valid,
                                    z.kv_rank, z.scale)
            pools = (latent, index)
        out = jnp.einsum("nhr,rhd->nhd", lat.astype(self.dtype), w_v)
        return self._out(x, out), pools
