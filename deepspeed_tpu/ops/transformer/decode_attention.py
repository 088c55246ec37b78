"""Pallas decode attention — the KV-cache generation kernel.

TPU-native equivalent of the reference's ``softmax_context`` inference op
(``csrc/transformer/inference/csrc/pt_binding.cpp:1934-``; the attention
half of its decode pipeline).  Single-token decode: one query row per
(batch, head) attends over the cache.

Kernel layout (v4, bandwidth-first): decode attention moves ~1.6 GB of KV
cache per token-step at OPT-1.3B/bs16 and does almost no math, so everything
is shaped for DMA efficiency, not MXU occupancy:

* The cache is **S-major with flattened heads** — ``[B, S_max, KVH*D]``
  (optionally layer-stacked ``[L, ...]``).  A KV block is then a fully
  contiguous ``[block_k, KVH*D]`` slab whose minor dim (e.g. 2048) is a
  whole number of 128-lane tiles, so the HBM→VMEM DMA streams at full
  width.  The previous head-major ``[B, KVH, S, D]`` layout produced
  D(=64)-lane-minor blocks that pad to 128 lanes in VMEM — half the
  effective bandwidth — and its per-(batch, head) grid added ~0.6 µs of
  overhead per 64 KB sliver.  Bonus: the decode-step cache write is the raw
  projection output (no per-token transpose at all).
* One grid cell covers ALL kv heads of one (batch row, kv block).  Per-head
  score matmuls are fused into ONE MXU matmul via a **block-diagonal Q**:
  rows = query heads, row h*G+g carries q[h,g] in columns h*D:(h+1)*D and
  zeros elsewhere, so ``Q_bd @ K_slab^T`` lands exactly the per-head scores
  [H, block_k] (the MXU multiplies zeros for free — it is idle here anyway).
  ``P @ V_slab`` similarly yields [H, KVH*D] from which each head's D-column
  diagonal block is accumulated.
* Online softmax runs once per cell over the whole [H, block_k] score tile
  in fp32 scratch, so the cache never materializes an S_max-wide
  probability row in fp32 HBM.

The KV length mask (cache tail + causality for a single new token collapse
to ``pos < length``) is applied per block, and blocks entirely past the
live cache region are skipped: their block index is pinned to the last live
block (Mosaic elides the repeated DMA) and their compute is pl.when-gated.

Single-token decode is one algorithm under two iteration maps: the row
state and the per-block update (``_init_row`` / ``_block_update`` /
``_finish_row`` / ``_write_stripe``) are shared, the drivers are separate —
``_decode_kernel`` here walks a contiguous cache on a ``(B, nk)`` grid;
``paged_attention._paged_decode_kernel`` walks a page table with an
in-kernel loop over the row's live pages.  Chunked prefill is split the
same way (``_init_chunk`` / ``_chunk_block_update`` / ``_finish_chunk``):
``_chunk_prefill_kernel`` walks a contiguous cache on a ``(B, nk)`` grid,
``paged_attention._paged_chunk_kernel`` folds the chunk's reachable pages
in blocks of ~512 keys inside one grid step, and the sliding-window and EVA
chunk kernels (``paged_attention._window_chunk_kernel``,
``eva_attention._eva_chunk_kernel``) their rings.  The chunk fold keeps its
running max and sum LANE-REPLICATED — ``[C, STAT_LANES]`` float32 tiles a
head — and selects a masked score once (``_ChunkState``, ``STAT_FLOOR``).
"""

import functools
import math
import os as _os
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import (LSE_LANES, NEG_INF,
                                                           _interpret)

DEFAULT_BLOCK_K_DECODE = int(_os.environ.get("DSTPU_DECODE_BLOCK_K", "512"))


def _new_rows(kn_ref, vn_ref, quant):
    """This step's K/V rows, quantized the same way the cache stores
    them (payload+scale when ``quant``), plus the DEQUANTIZED values
    this step's attention must see — write-then-read parity with the
    unfused path."""
    kn = kn_ref[0].astype(jnp.float32)                   # [KVH, D]
    vn = vn_ref[0].astype(jnp.float32)
    if not quant:
        return kn, vn, kn, vn, None, None
    ks_n = jnp.max(jnp.abs(kn), axis=1, keepdims=True) / 127.0
    vs_n = jnp.max(jnp.abs(vn), axis=1, keepdims=True) / 127.0
    ks_safe = jnp.where(ks_n == 0.0, 1.0, ks_n)
    vs_safe = jnp.where(vs_n == 0.0, 1.0, vs_n)
    kq = jnp.clip(jnp.round(kn / ks_safe), -127, 127)
    vq = jnp.clip(jnp.round(vn / vs_safe), -127, 127)
    return kq, vq, kq * ks_safe, vq * vs_safe, ks_safe, vs_safe


def _expand_scales(st, g):
    # [bk, KVH] per-(position, kv-head) scales → [H, bk]: row r of the
    # block-diagonal Q belongs to kv head r // g, so its score column j
    # dequantizes by scales[j, r // g].  Only this [bk, KVH]-sized tile
    # is ever transposed — the KV slabs stay in their DMA layout.
    st = st.astype(jnp.float32).T                        # [KVH, bk]
    if g == 1:
        return st
    return jnp.repeat(st, g, axis=0)                     # [H, bk]


class _RowState(NamedTuple):
    """One batch row's online-softmax state plus the per-row inputs the
    per-block update reads: the refs both decode drivers (the grid walk
    over a contiguous cache here, the in-kernel page loop of
    ``paged_attention``) hand to :func:`_init_row`,
    :func:`_block_update`, :func:`_finish_row` and :func:`_write_stripe`.
    ``qs_scr`` is present only in the int8-MXU variant, ``kn_ref`` /
    ``vn_ref`` only with the fused write."""
    q_ref: Any
    m_scr: Any
    l_scr: Any
    acc_scr: Any
    qbd_scr: Any
    qs_scr: Any = None
    kn_ref: Any = None
    vn_ref: Any = None


def _init_row(st, *, kvh, g, d):
    m_scr, l_scr, acc_scr, qbd_scr = (st.m_scr, st.l_scr, st.acc_scr,
                                      st.qbd_scr)
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    # build the block-diagonal Q once per batch row
    qbd_scr[:] = jnp.zeros_like(qbd_scr)
    q = st.q_ref[0]                                      # [H, D]
    if st.qs_scr is not None:
        # quantize q per head so the score matmul runs int8×int8 on
        # the MXU — the [bk, KVH*D] slabs then never get cast
        qf = q.astype(jnp.float32)
        qs = jnp.max(jnp.abs(qf), axis=1, keepdims=True) / 127.0
        qs = jnp.where(qs == 0.0, 1.0, qs)
        st.qs_scr[:] = jnp.broadcast_to(qs, st.qs_scr.shape)
        q = jnp.clip(jnp.round(qf / qs), -127, 127)
    for h in range(kvh):
        qbd_scr[h * g:(h + 1) * g, h * d:(h + 1) * d] = \
            q[h * g:(h + 1) * g].astype(qbd_scr.dtype)


def _block_update(st, ik, length, k, v, ks, vs, *, scale, block_k, kvh, g,
                  d, window):
    """Fold KV block ``ik`` of one row into its online-softmax state.
    ``k``/``v``: the block's [bk, KVH*D] slabs as loaded; ``ks``/``vs``:
    its [bk, KVH] dequant scales (None for an unquantized cache).  The
    one per-block update of single-token decode, whichever driver
    supplies the block sequence."""
    m_scr, l_scr, acc_scr, qbd_scr = (st.m_scr, st.l_scr, st.acc_scr,
                                      st.qbd_scr)
    quant = ks is not None
    mxu_int8 = st.qs_scr is not None
    fused_write = st.kn_ref is not None
    if quant and not mxu_int8:
        # int8 payloads: cast for the MXU; the per-entry scale applies
        # to SCORES (k) and to P (v) — never to the big slabs, so no
        # [bk, KVH*D]-sized reshape/relayout happens in-kernel
        k = k.astype(qbd_scr.dtype)
        v = v.astype(qbd_scr.dtype)
    # all heads' scores in ONE matmul (see module docstring)
    if mxu_int8:
        # int8×int8 MXU path: the slabs go to the matmul untouched
        s = jax.lax.dot_general(
            qbd_scr[:], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
        s = s * (st.qs_scr[:, 0:1] * scale)
    else:
        s = jax.lax.dot_general(
            qbd_scr[:], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
    if quant:
        s = s * _expand_scales(ks, g)
    pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)                      # [1, bk]
    live = pos < length                                  # cache tail mask
    if window is not None:
        # sliding-window decode (mistral-style): the single query sits
        # at position length-1, so the live window is
        # [length - window, length)
        live = jnp.logical_and(live, pos >= length - window)
    if fused_write:
        # the cache does NOT yet hold this step's token: its column
        # (global position length-1, which only occurs in this — the
        # last live — block) is recomputed from the fresh row and
        # substituted into the score tile.  Dequantized values keep
        # write-then-read parity with the unfused path.
        _, _, kn_used, vn_used, _, _ = _new_rows(st.kn_ref, st.vn_ref,
                                                 quant)
        kn_rep = kn_used if g == 1 else jnp.repeat(kn_used, g, axis=0)
        q_f32 = st.q_ref[0].astype(jnp.float32)          # [H, D]
        col = jnp.sum(q_f32 * kn_rep, axis=1,
                      keepdims=True) * scale             # [H, 1]
        sel_col = (pos == length - 1)                    # [1, bk]
        s = jnp.where(sel_col, col, s)
    s = jnp.where(live, s, NEG_INF)                      # [H, bk]
    m_prev = m_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(live, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[:] = jnp.broadcast_to(
        l_scr[:, 0:1] * corr + jnp.sum(p, axis=1, keepdims=True),
        l_scr.shape)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    pv = p * _expand_scales(vs, g) if quant else p
    if fused_write:
        # the V slab's row at the write column is stale too: zero that
        # probability column for the big PV matmul and add its rank-1
        # contribution from the fresh (dequantized) V row per head.
        # p_col comes from the RAW probabilities — the fresh row's
        # scale is already folded into vn_used, the slab's stale
        # v-scale must not touch it.
        p_col = jnp.sum(jnp.where(sel_col, p, 0.0), axis=1,
                        keepdims=True)                   # [H, 1]
        pv = jnp.where(sel_col, 0.0, pv)
    if mxu_int8:
        # fold the v-scale into P, then quantize P per row: the PV
        # matmul also runs int8×int8 with a per-row rescale after
        rmax = jnp.max(pv, axis=1, keepdims=True) / 127.0
        rsafe = jnp.where(rmax == 0.0, 1.0, rmax)
        pv_i8 = jnp.clip(jnp.round(pv / rsafe), -127, 127) \
            .astype(jnp.int8)
        o_flat = jax.lax.dot_general(
            pv_i8, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
        o_flat = o_flat * rmax
    else:
        o_flat = jax.lax.dot_general(pv.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    # accumulate each head's D-column diagonal block of [H, KVH*D]
    for h in range(kvh):
        rows = slice(h * g, (h + 1) * g)
        contrib = o_flat[rows, h * d:(h + 1) * d]
        if fused_write:
            contrib = contrib + p_col[rows] * vn_used[h:h + 1]
        acc_scr[rows] = acc_scr[rows] * corr[rows] + contrib


def _finish_row(st, o_ref):
    l = st.l_scr[:, 0:1]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (st.acc_scr[:] / safe_l).astype(o_ref.dtype)


def _write_stripe(st, length, block_k, load8, ko, vo, kso, vso, *, kvh, d):
    """The fused cache write: this step's row goes out through the
    ALIASED, 8-ROW-STRIPE outputs ``ko``/``vo`` (and ``kso``/``vso``
    for a quantized cache) — [8, ...] views of the output blocks, which
    cover only the 8-sublane-aligned stripe containing the write row
    (pinned by index map), so per step the flush is 8 rows — not a
    whole block (a full-block write-back measured ~1.8x on the whole
    decode step at bs64).  The stripe's other 7 rows are merged from
    the raw input block (loaded for scores anyway): ``load8(base)``
    returns its rows ``base .. base+8`` as ``(k, v, k_scale,
    v_scale)``; Mosaic accepts the dynamic 8-aligned ref read.
    Clamp: a zero-length row (invalid input — lengths INCLUDE this
    step's token, so the minimum is 1) would compute
    row = (-1) % block_k = block_k-1 and merge the slab's FAR stripe
    into the pinned rows 0-7 of the output (the output index map clamps
    to stripe 0), silently corrupting the cache head.  Clamped,
    length=0 degenerates to the benign length=1 write at row 0."""
    quant = kso is not None
    row = jnp.maximum(length - 1, 0) % block_k
    base = (row // 8) * 8
    off = row - base
    sel = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0) == off  # [8, 1]
    kq, vq, _, _, ks_n, vs_n = _new_rows(st.kn_ref, st.vn_ref, quant)
    kraw8, vraw8, ks_raw8, vs_raw8 = load8(base)         # [8, KVH*D] raw
    # per-kv-head merges: Mosaic cannot shape-cast a computed
    # [KVH, D] f32 tile to [1, KVH*D], so each head's D-column
    # stripe merges separately
    for hk in range(kvh):
        cols = slice(hk * d, (hk + 1) * d)
        km = jnp.where(sel, kq[hk:hk + 1],
                       kraw8[:, cols].astype(jnp.float32))
        vm = jnp.where(sel, vq[hk:hk + 1],
                       vraw8[:, cols].astype(jnp.float32))
        ko[:, cols] = km.astype(ko.dtype)
        vo[:, cols] = vm.astype(vo.dtype)
    if quant:
        ksm = ks_raw8.astype(jnp.float32)                # [8, KVH]
        vsm = vs_raw8.astype(jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, kvh), 1)
        for hk in range(kvh):
            m = jnp.logical_and(sel, lane == hk)         # [8, KVH]
            ksm = jnp.where(m, ks_n[hk, 0], ksm)
            vsm = jnp.where(m, vs_n[hk, 0], vsm)
        kso[...] = ksm.astype(kso.dtype)
        vso[...] = vsm.astype(vso.dtype)


# What a decode-side kernel asks of VMEM past the buffers and values it is
# counted to hold: the compiler's own scratch and what it spills.  (The least
# limit each call compiles under at the serving cells' shapes is within
# 1 MiB of its declared buffers, and under its count: PERF.md, PR 61.)
VMEM_HEADROOM = 4 * 1024 * 1024


def vmem_bytes(shape, dtype):
    """Bytes an array of ``shape`` takes in VMEM: its last two dims in
    whole tiles of 128 lanes by the dtype's sublanes (8 rows of 32 bits, 16
    of bfloat16, 32 of int8)."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 32 // itemsize
    *lead, rows, lanes = (1, 1) + tuple(shape)
    return (math.prod(lead) * -(-rows // sublanes) * sublanes
            * -(-lanes // 128) * 128 * itemsize)


def _decode_vmem_bytes(rows, h, d, bk, kvhd, kv_dtype, q_dtype, *, buffers=2,
                       quant=False):
    """The VMEM a single-token decode kernel asks for — ``attn.decode``,
    ``attn.paged_decode`` in both its forms, ``attn.eva_decode`` — from the
    buffers the call declares and the values one per-block update
    (:func:`_block_update`) makes, and no more:

    * ``buffers`` K and as many V blocks of ``[bk, KVH*D]`` — the block
      loop's and the grid pipeline's two, ``attn.eva_decode``'s ring of
      three — and, for an int8 cache (``quant``), as many ``[bk, KVH]``
      float32 scale blocks (whole 128-lane tiles) and a K and a V block
      cast to the query's dtype for the MXU;
    * the fused write's 8-row stripes, two a pool — staged by the block
      loop, output blocks of the grid forms; counted whether or not the
      call writes (128 bytes a lane);
    * the q and output blocks ``[rows, H, D]`` and the new K/V rows
      ``[rows, KVH, D]``, each double-buffered by the pipeline: ``rows`` is
      1 a grid step, and EVERY lane of the block loop — LFM2's 256 lanes
      are 12 MiB and Nemotron's 192 are 9, nothing a headroom hides;
    * the online-softmax scratch: the running max and sum (and the int8
      MXU form's q scales), the accumulator, the block-diagonal q;
    * the update's ``[H, bk]`` float32 score-side tiles — the scores, their
      masked, exponentiated, scaled and cast forms: six — and its ``[H,
      KVH*D]`` float32 product;
    * ``VMEM_HEADROOM``.

    What a kernel is granted XLA cannot use across it: under the ``max(96
    MiB, ...)`` floor this ask had, the decode block's next weights could
    not be prefetched into VMEM while the kernel ran (PERF.md, PR 61)."""
    kvh = kvhd // d
    f32 = jnp.float32
    ask = 2 * buffers * vmem_bytes((bk, kvhd), kv_dtype)
    ask += 2 * 2 * vmem_bytes((8, kvhd), kv_dtype)
    if quant:
        ask += 2 * buffers * vmem_bytes((bk, kvh), f32)
        ask += 2 * 2 * vmem_bytes((8, kvh), f32)
        ask += 2 * vmem_bytes((bk, kvhd), q_dtype)
    ask += 2 * 2 * (vmem_bytes((rows, h, d), q_dtype)
                    + vmem_bytes((rows, kvh, d), q_dtype))
    ask += (3 * vmem_bytes((h, LSE_LANES), f32) + vmem_bytes((h, d), f32)
            + vmem_bytes((h, kvhd), q_dtype))
    ask += 6 * vmem_bytes((h, bk), f32) + vmem_bytes((h, kvhd), f32)
    return ask + VMEM_HEADROOM


def _decode_kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, *rest,
                   scale, block_k, nk, kvh, g, d, stacked, quant, window,
                   mxu_int8, fused_write=False):
    """The grid-walk driver: grid ``(B, nk)`` over a contiguous cache,
    the block location resolved by the BlockSpec index maps."""
    ks_ref = vs_ref = kn_ref = vn_ref = qs_scr = None
    ko_ref = vo_ref = kso_ref = vso_ref = None
    rest = list(rest)
    if quant:
        ks_ref, vs_ref = rest[:2]
        del rest[:2]
    if fused_write:
        # in-kernel cache write (see decode_attention new_k/new_v): the
        # new token's raw K/V rows ride extra inputs and the caches come
        # BACK as aliased outputs pinned at each row's write block
        kn_ref, vn_ref = rest[:2]
        del rest[:2]
    o_ref = rest.pop(0)
    if fused_write:
        ko_ref, vo_ref = rest[:2]
        del rest[:2]
        if quant:
            kso_ref, vso_ref = rest[:2]
            del rest[:2]
    m_scr, l_scr, acc_scr, qbd_scr = rest[:4]
    if mxu_int8:
        qs_scr = rest[4]
    st = _RowState(q_ref, m_scr, l_scr, acc_scr, qbd_scr, qs_scr,
                   kn_ref, vn_ref)
    lead = (0, 0) if stacked else (0,)       # this cell's block
    b = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        _init_row(st, kvh=kvh, g=g, d=d)

    length = len_ref[b]
    run = ik * block_k < length
    if window is not None:
        # blocks entirely below the live window are skipped (their DMA is
        # elided by the matching index-map pin) — decode cost is O(window)
        run = jnp.logical_and(run, (ik + 1) * block_k > length - window)

    # skip KV blocks entirely past the live cache region (and, with a
    # window, entirely before it)
    @pl.when(run)
    def _body():
        _block_update(st, ik, length, k_ref[lead], v_ref[lead],
                      ks_ref[lead] if quant else None,
                      vs_ref[lead] if quant else None,
                      scale=scale, block_k=block_k, kvh=kvh, g=g, d=d,
                      window=window)

    @pl.when(ik == nk - 1)
    def _finish():
        _finish_row(st, o_ref)
        if fused_write:
            def load8(base):
                rows = lead + (pl.dslice(base, 8),)
                return (k_ref[rows], v_ref[rows],
                        ks_ref[rows] if quant else None,
                        vs_ref[rows] if quant else None)

            _write_stripe(st, length, block_k, load8,
                          ko_ref.at[lead], vo_ref.at[lead],
                          kso_ref.at[lead] if quant else None,
                          vso_ref.at[lead] if quant else None,
                          kvh=kvh, d=d)


# Head groups one iteration of the chunk update's group loop folds in:
# two let one group's softmax passes overlap the other's matmuls.  Measured
# on v5e (PERF.md PR 28), us a layer-chunk at OPT-1.3B's widths, 13 and 28
# pages reached: one 48.8 / 82.9, two 46.0 / 77.2, four 46.1 / 76.1 (and
# 13 s to compile at a 512-token chunk against 6).
_CHUNK_GROUP_UNROLL = 2


# Lanes of the chunk fold's running max and sum: one whole lane tile, every
# lane its row's value.  The fold's own width — ``flash_attention.LSE_LANES``
# (8) stays the flash kernels' and the decode kernels' ``[H, LSE_LANES]``.
STAT_LANES = 128

# The chunk fold's running max starts at the FLOOR and a masked score is
# NEG_INF, a decade under it: the max never falls under the floor, so a masked
# score's ``exp(NEG_INF - m)`` is 0 whatever its row has kept — ONE select a
# masked score, none on the probabilities (``latent_attention``'s ``FLOOR`` /
# ``NEG`` pair).  A row that has kept nothing keeps ``l == 0``.
STAT_FLOOR = -1e29


class _ChunkState(NamedTuple):
    """One batch row's chunk-prefill state: the refs both chunk drivers
    (the grid walk over a contiguous cache here, the in-kernel block loop
    of ``paged_attention``) hand to :func:`_init_chunk`,
    :func:`_chunk_block_update` and :func:`_finish_chunk`.  The running
    max and sum are kept per head LANE-REPLICATED, as whole ``[C,
    STAT_LANES]`` float32 tiles every lane of which holds its row's value
    (``[H, C, STAT_LANES]`` scratch): a head's update reads, computes and
    writes whole tiles, and ``s - m`` / ``acc * corr`` take the tile laid
    side by side (:func:`_across`) — kept as ``[C, 1]`` columns the two
    cost a lane broadcast for every vreg of the ``[C, bk]`` score tile and
    of the accumulator slice (PERF.md PR 51, PR 53)."""
    q_ref: Any                               # [1, C, H*D]
    m_scr: Any                               # [H, C, STAT_LANES]
    l_scr: Any                               # [H, C, STAT_LANES]
    acc_scr: Any                             # [C, H*D]


def _chunk_scratch(c, h, d):
    return [pltpu.VMEM((h, c, STAT_LANES), jnp.float32),  # running max
            pltpu.VMEM((h, c, STAT_LANES), jnp.float32),  # running sum
            pltpu.VMEM((c, h * d), jnp.float32)]          # per-head acc


def _chunk_scratch_bytes(c, h, d):
    # the running max and sum are whole 128-lane float32 tiles — what the
    # [C, LSE_LANES] tiles they replaced (PR 53) padded to in VMEM: the
    # reckoning did not change with the layout
    return 2 * h * c * STAT_LANES * 4 + c * h * d * 4


def _chunk_grid_vmem_bytes(c, h, d, block_k, kvhd, itemsize):
    """The VMEM the grid-walk chunk kernel asks for: K and V blocks
    (double-buffered by the pipeline), the q and output blocks, the
    online-softmax scratch, and headroom."""
    return max(64 * 1024 * 1024,
               4 * block_k * kvhd * itemsize + c * h * d * 4
               + _chunk_scratch_bytes(c, h, d) + 16 * 1024 * 1024)


def _across(x, n):
    """``x [rows, STAT_LANES]``, every lane its row's value, as ``[rows,
    n]``: whole vregs side by side where ``n`` is whole lane tiles — no
    lane broadcast a vreg, which a ``[rows, 1]`` column costs; the column
    form where it is not (the tiny test models), decided from the static
    shape (``latent_attention._across``, whose module imports this one)."""
    if n % STAT_LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return x if n == STAT_LANES else jnp.tile(x, (1, n // STAT_LANES))


def _side_by_side(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _heads_a_tile(heads, d):
    """How many of ``heads`` consecutive heads of size ``d`` share one lane
    tile whole (two of 64), or 1."""
    per = STAT_LANES // d if d < STAT_LANES and STAT_LANES % d == 0 else 1
    return per if heads % per == 0 else 1


def _per_head(tiles, d):
    """One lane-replicated statistic a head (``[rows, STAT_LANES]`` each, of
    consecutive heads) as ``[rows, len(tiles) * d]``, head ``t``'s value in
    its ``d`` columns — what rescales the heads' slice of the accumulator
    in one multiply.  By the static head size: whole lane tiles (D = 128)
    are the tiles side by side; heads that share a lane tile (D = 64: two)
    are ONE tile built by a lane select, where the columns' form took the
    slice apart into half-filled vregs and put it together again; a size
    that tiles no lane takes the column form."""
    per = _heads_a_tile(len(tiles), d)
    if per == 1:
        return _side_by_side([_across(t, d) for t in tiles])
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, STAT_LANES), 1)
    parts = []
    for i in range(0, len(tiles), per):
        tile = tiles[i]
        for u in range(1, per):
            tile = jnp.where(lane >= u * d, tiles[i + u], tile)
        parts.append(tile)
    return _side_by_side(parts)


def _init_chunk(st):
    st.m_scr[...] = jnp.full_like(st.m_scr, STAT_FLOOR)
    st.l_scr[...] = jnp.zeros_like(st.l_scr)
    st.acc_scr[...] = jnp.zeros_like(st.acc_scr)


def _chunk_block_update(st, ik, start, k_ref, v_ref, ks, vs, *, scale,
                        block_k, c, kvh, g, d, masked=True, prefix=False,
                        pos0=None, window=None, limit=None):
    """Fold KV block ``ik`` (virtual positions ``ik*block_k ..``) into the
    chunk's online-softmax state: per head ONE ``[C, D] x [D, bk]`` score
    matmul, a ``[C, bk]`` float32 tile for max / exp / sum, ONE
    ``[C, bk] x [bk, D]`` value matmul and one rescale of the head's
    accumulator slice.  ``k_ref``/``v_ref``: the block's ``[bk, KVH*D]``
    slabs (refs); ``ks``/``vs``: its ``[bk, KVH]`` dequant scales (None
    for an unquantized cache).  ``masked=False`` is for a block wholly
    under the causal diagonal (every position ``<= start``): the same
    numbers without the mask's compares and selects.  ``prefix=True``
    (with ``masked``): the block's keys are no positions of the chunk's
    sequence but rows every query sees alike up to a bound — ``start`` is
    then that bound, and row ``r`` is live while ``ik*block_k + r <
    start`` (EVA's chunk summaries, ``eva_attention.py``).  A BAND
    (``paged_attention``'s window chunk kernel; with ``masked``): the
    block's row 0 stands at position ``pos0`` (not ``ik*block_k``), a query
    sees the ``window`` positions up to its own, and rows at ``limit`` and
    past it hold no position of the sequence yet.  The one per-block update
    of chunked prefill, whichever driver supplies the block sequence.

    Heads are walked in GROUPS of whole 128-lane tiles of the slabs (two
    kv heads of 64, one of 128, with the ``g`` query heads of each): a
    group's columns are a tile-aligned slice that Mosaic takes at a
    dynamic offset, so the walk is a ``fori_loop`` whose body is traced
    and compiled once — unrolled over 32 heads of [C, 512] tiles the
    kernel took 10 s to compile.  A head size that does not tile 128
    lanes (tiny test models) unrolls statically, one head a group."""
    quant = ks is not None
    if quant:
        kst = ks.astype(jnp.float32).T                   # [KVH, bk]
        vst = vs.astype(jnp.float32).T
    if masked:
        pos = (ik * block_k if pos0 is None else pos0) \
            + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)  # [1, bk]
        if prefix:
            live = pos < start                           # [1, bk], all rows
        else:
            qpos = start + jax.lax.broadcasted_iota(
                jnp.int32, (c, 1), 0)                    # [C, 1]
            live = pos <= qpos                           # [C, bk] causal+tail
            if window is not None:
                live = jnp.logical_and(live, pos > qpos - window)
            if limit is not None:
                live = jnp.logical_and(live, pos < limit)
    hpg = 128 // d if 128 % d == 0 and kvh % (128 // d) == 0 else 1
    lanes, qlanes = hpg * d, hpg * g * d
    tiled = lanes % 128 == 0 and not quant

    def aligned(x):
        return pl.multiple_of(x, 128) if tiled else x

    def group(j):
        # kv heads j*hpg .. (j+1)*hpg and their hpg*g query heads
        kcols = pl.ds(aligned(j * lanes), lanes)
        qcols = pl.ds(aligned(j * qlanes), qlanes)
        qg = st.q_ref[0, :, qcols]                       # [C, hpg*g*D]
        kg = k_ref[:, kcols]                             # [bk, hpg*D]
        vg = v_ref[:, kcols]
        accg = st.acc_scr[:, qcols]
        corrs, outs = [], []
        for t in range(hpg * g):
            h, hk = j * hpg * g + t, j * hpg + t // g
            cols = slice(t * d, (t + 1) * d)
            kvcols = slice(t // g * d, (t // g + 1) * d)
            qh, kh, vh = qg[:, cols], kg[:, kvcols], vg[:, kvcols]
            if quant:
                kh = kh.astype(qh.dtype)
                vh = vh.astype(qh.dtype)
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if quant:
                s = s * kst[hk:hk + 1]                   # [1, bk] k-scales
            if masked:
                # ONE select: the running max never falls under
                # STAT_FLOOR, so exp(NEG_INF - m) is 0 without a second
                s = jnp.where(live, s, NEG_INF)
            m_prev = st.m_scr[h]                         # [C, STAT_LANES]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _across(m_new, block_k))
            corr = jnp.exp(m_prev - m_new)
            st.l_scr[h] = st.l_scr[h] * corr + jnp.sum(p, axis=1,
                                                       keepdims=True)
            st.m_scr[h] = m_new
            if quant:
                p = p * vst[hk:hk + 1]                   # v-scales on P
            outs.append(jax.lax.dot_general(
                p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))     # [C, D]
            corrs.append(corr)
        st.acc_scr[:, qcols] = accg * _per_head(corrs, d) \
            + _side_by_side(outs)

    if tiled:
        n_groups = kvh // hpg
        per = _CHUNK_GROUP_UNROLL if n_groups % _CHUNK_GROUP_UNROLL == 0 \
            else 1

        def body(jj, carry):
            for u in range(per):
                group(jj * per + u)
            return carry
        jax.lax.fori_loop(0, n_groups // per, body, None)
    else:
        for j in range(kvh // hpg):
            group(j)


def _finish_chunk(st, o_ref, *, heads, d):
    # the heads of one lane tile together (two of 64), as the update
    # rescales them
    per = _heads_a_tile(heads, d)
    for h in range(0, heads, per):
        cols = slice(h * d, (h + per) * d)
        l = _per_head([st.l_scr[h + u] for u in range(per)], d)
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :, cols] = (st.acc_scr[:, cols] / safe_l).astype(o_ref.dtype)


def _chunk_prefill_kernel(start_ref, layer_ref, q_ref, k_ref, v_ref, *rest,
                          scale, block_k, nk, c, kvh, g, d, stacked, quant):
    """Multi-token (chunk) prefill against the cache: rows ``iq`` of the
    chunk attend causally to cache positions ``<= start_b + iq``.  Same
    slab layout + online softmax as ``_decode_kernel``, but with a [C, bk]
    score tile per head instead of the block-diagonal all-heads trick
    (C×H rows would not fit one matmul).  The grid-walk driver: grid
    ``(B, nk)``, the block location resolved by the BlockSpec index
    maps."""
    ks_ref = vs_ref = None
    rest = list(rest)
    if quant:
        ks_ref, vs_ref = rest[:2]
        del rest[:2]
    o_ref, m_scr, l_scr, acc_scr = rest
    st = _ChunkState(q_ref, m_scr, l_scr, acc_scr)
    lead = (0, 0) if stacked else (0,)       # this cell's block
    b = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        _init_chunk(st)

    start = start_ref[b]
    limit = start + c                       # rows reach pos <= start+c-1

    @pl.when(ik * block_k < limit)
    def _body():
        _chunk_block_update(
            st, ik, start, k_ref.at[lead], v_ref.at[lead],
            ks_ref[lead] if quant else None,
            vs_ref[lead] if quant else None,
            scale=scale, block_k=block_k, c=c, kvh=kvh, g=g, d=d)

    @pl.when(ik == nk - 1)
    def _finish():
        _finish_chunk(st, o_ref, heads=kvh * g, d=d)


def chunk_prefill_attention(q, k_cache, v_cache, starts, scale=None,
                            block_k=DEFAULT_BLOCK_K_DECODE, layer=None,
                            k_scale=None, v_scale=None):
    """Chunked-prefill attention: a block of C fresh query tokens (already
    written to the cache at positions ``starts[b] .. starts[b]+C-1``)
    attends causally over the cache.  The memory-bounding half of chunked
    prefill (reference analog: the workspace-resident incremental prefill
    of ``inference_context.h`` + ``softmax_context``'s arbitrary-length
    cache path, ``pt_binding.cpp:456``): score/probability tiles are
    [C, block_k] regardless of prompt or cache length, so a 4k-prompt
    prefill no longer materializes multi-GB per-layer transients.

    q: [B, C, H, D]; caches as in :func:`decode_attention` (S-major slabs,
    optionally layer-stacked + quantized).  starts: [B] int32 — each row's
    chunk start position (cache positions beyond ``starts[b]+iq`` are
    masked per query row ``iq``).  Returns [B, C, H, D].
    """
    B, C, H, D = q.shape
    stacked = k_cache.ndim == 4
    if stacked and layer is None:
        raise ValueError("stacked [L, ...] caches require layer=")
    quant = k_scale is not None
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    S_max, KVHD = k_cache.shape[-2], k_cache.shape[-1]
    KVH = KVHD // D
    G = H // KVH
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    block_k = min(block_k, S_max)
    nk = pl.cdiv(S_max, block_k)
    layer_arr = jnp.asarray([layer if layer is not None else 0], jnp.int32)

    def _live_block(ik, starts_arr, b):
        # pin blocks past the chunk's furthest reachable position
        # (starts[b] + C - 1) to the last live block — their DMA is elided
        # and their compute pl.when-gated off, like decode's dead tail
        last = jnp.maximum((starts_arr[b] + C + block_k - 1) // block_k - 1,
                           0)
        return jnp.minimum(ik, last)

    if stacked:
        kv_spec = pl.BlockSpec(
            (1, 1, block_k, KVHD),
            lambda b, ik, st, li: (li[0], b, _live_block(ik, st, b), 0))
        sc_spec = pl.BlockSpec(
            (1, 1, block_k, KVH),
            lambda b, ik, st, li: (li[0], b, _live_block(ik, st, b), 0))
    else:
        kv_spec = pl.BlockSpec(
            (1, block_k, KVHD),
            lambda b, ik, st, li: (b, _live_block(ik, st, b), 0))
        sc_spec = pl.BlockSpec(
            (1, block_k, KVH),
            lambda b, ik, st, li: (b, _live_block(ik, st, b), 0))

    in_specs = [
        # q flattened to [B, C, H*D] — Mosaic blocks want at most two
        # non-unit trailing dims, and the flat layout matches the cache
        # slabs' full-lane-width tiling anyway
        pl.BlockSpec((1, C, H * D), lambda b, ik, st, li: (b, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    operands = [q.reshape(B, C, H * D), k_cache, v_cache]
    if quant:
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale, v_scale]

    out = pl.pallas_call(
        functools.partial(_chunk_prefill_kernel, scale=float(scale),
                          block_k=block_k, nk=nk, c=C, kvh=KVH, g=G, d=D,
                          stacked=stacked, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, C, H * D),
                                   lambda b, ik, st, li: (b, 0, 0)),
            scratch_shapes=_chunk_scratch(C, H, D)),
        out_shape=jax.ShapeDtypeStruct((B, C, H * D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_chunk_grid_vmem_bytes(
                C, H, D, block_k, KVHD, q.dtype.itemsize)),
        interpret=_interpret(),
        name="attn.chunk_prefill",
    )(jnp.asarray(starts, jnp.int32), layer_arr, *operands)
    return out.reshape(B, C, H, D)


def decode_attention(q, k_cache, v_cache, lengths,
                     scale=None, block_k=DEFAULT_BLOCK_K_DECODE, layer=None,
                     k_scale=None, v_scale=None, window=None,
                     int8_matmuls=False, new_k=None, new_v=None):
    """Single-token decode attention.

    q: [B, H, D] (this step's query); caches: [B, S_max, KVH*D]
    (S-major, heads flattened into lanes — the layout the model stores, so
    the cache write is the raw projection output and the kernel's KV DMAs
    are contiguous full-lane-width slabs), or the FULL layer-stacked
    [L, B, S_max, KVH*D] cache with ``layer`` a (traced) layer index — the
    kernel's index maps then DMA only this layer's blocks, so the caller
    never materializes a per-layer slice of the stacked cache.
    lengths: [B] int32 — number of valid cache entries INCLUDING this
    step's freshly-written position.  Returns [B, H, D].

    ``k_scale``/``v_scale`` ([..., S_max, KVH]) switch the caches to int8
    payloads with per-(position, kv-head) dequant scales: decode is
    HBM-bound on the KV stream, so halving its bytes nearly halves the
    cache-dominated share of the step.  Dequantization never touches the
    [block_k, KVH*D] slabs — the k-scale lands on the score tile and the
    v-scale on the probability tile (both [H, block_k]).

    ``new_k``/``new_v`` ([B, KVH, D], raw projection rows) switch on the
    FUSED CACHE WRITE: the kernel quantizes (when the cache is int8) and
    writes this step's row at each row's position ``lengths[b]-1`` into
    the caches, returned as ALIASED outputs (``input_output_aliases`` —
    the in-place workspace write of the reference's ``inference_context``)
    — and substitutes the fresh row into this step's own attention.  The
    caller must then NOT pre-write the cache, and every ``lengths[b]``
    must be >= 1 (it counts the fresh row); a zero-length row is clamped
    to the length-1 write position in-kernel instead of corrupting cache
    rows 0-7.  Returns
    ``(out, k_cache, v_cache[, k_scale, v_scale])`` instead of ``out``.
    Measured: the out-of-kernel dynamic-update-slice chain interacting
    with the kernel's cache reads makes XLA copy the multi-GB cache
    per step above ~bs12 x 4k (129 ms/step); the fused write runs at
    kernel-only speed (12.7 ms/step at bs16 x 4k x 24 layers).
    ``int8_matmuls`` is unsupported with the fused write.
    """
    B, H, D = q.shape
    stacked = k_cache.ndim == 4
    if stacked and layer is None:
        raise ValueError("stacked [L, ...] caches require layer=")
    quant = k_scale is not None
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if int8_matmuls and not quant:
        raise ValueError("int8_matmuls requires quantized caches "
                         "(k_scale/v_scale)")
    fused_write = new_k is not None
    if (new_k is None) != (new_v is None):
        raise ValueError("new_k and new_v must be given together")
    if fused_write and int8_matmuls:
        raise ValueError("int8_matmuls is unsupported with the fused "
                         "cache write (new_k/new_v)")
    if fused_write and k_cache.shape[-2] % 8 != 0:
        raise ValueError(
            f"fused cache write needs S_max % 8 == 0 (8-sublane-aligned "
            f"write stripes); got {k_cache.shape[-2]} — round the cache "
            f"length up (required_cache_len does)")
    if fused_write and min(block_k, k_cache.shape[-2]) % 8 != 0:
        raise ValueError(
            f"fused cache write needs block_k % 8 == 0 (the in-block "
            f"stripe base assumes 8-aligned blocks); got block_k="
            f"{min(block_k, k_cache.shape[-2])}")
    mxu_int8 = bool(int8_matmuls)
    S_max, KVHD = k_cache.shape[-2], k_cache.shape[-1]
    KVH = KVHD // D
    G = H // KVH                                         # query heads per kv head
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    block_k = min(block_k, S_max)
    nk = pl.cdiv(S_max, block_k)
    layer_arr = jnp.asarray([layer if layer is not None else 0], jnp.int32)

    def _live_block(ik, lens, b):
        # pin indices past the live cache region to the last live block:
        # Mosaic skips the DMA when a block index repeats, so dead-region
        # grid steps fetch nothing (their compute is pl.when-gated off
        # too).  With a sliding window, blocks entirely BELOW the window
        # pin to its first block the same way — decode DMA is O(window)
        last = jnp.maximum((lens[b] + block_k - 1) // block_k - 1, 0)
        idx = ik
        if window is not None:
            first = jnp.maximum((lens[b] - window) // block_k, 0)
            idx = jnp.maximum(idx, first)
        return jnp.minimum(idx, last)

    if stacked:
        kv_spec = pl.BlockSpec(
            (1, 1, block_k, KVHD),
            lambda b, ik, lens, li: (li[0], b, _live_block(ik, lens, b), 0))
        sc_spec = pl.BlockSpec(
            (1, 1, block_k, KVH),
            lambda b, ik, lens, li: (li[0], b, _live_block(ik, lens, b), 0))
    else:
        kv_spec = pl.BlockSpec(
            (1, block_k, KVHD),
            lambda b, ik, lens, li: (b, _live_block(ik, lens, b), 0))
        sc_spec = pl.BlockSpec(
            (1, block_k, KVH),
            lambda b, ik, lens, li: (b, _live_block(ik, lens, b), 0))

    in_specs = [
        pl.BlockSpec((1, H, D), lambda b, ik, lens, li: (b, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    operands = [q, k_cache, v_cache]
    if quant:
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale, v_scale]

    out_specs = [pl.BlockSpec((1, H, D), lambda b, ik, lens, li: (b, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, H, D), q.dtype)]
    io_aliases = {}
    if fused_write:
        # pinned write-STRIPE output specs: the output block is only the
        # 8-sublane-aligned stripe containing each row's write position
        # (block index in 8-row units), constant per batch row, so Mosaic
        # flushes 8 rows once after the final (writing) grid step;
        # input_output_aliases makes the returned caches the SAME buffers
        # the caller passed in (no copy, no extra HBM)
        def _write_stripe(lens, b):
            return jnp.maximum(lens[b] - 1, 0) // 8

        if stacked:
            kvo_spec = pl.BlockSpec(
                (1, 1, 8, KVHD),
                lambda b, ik, lens, li: (li[0], b, _write_stripe(lens, b), 0))
            sco_spec = pl.BlockSpec(
                (1, 1, 8, KVH),
                lambda b, ik, lens, li: (li[0], b, _write_stripe(lens, b), 0))
        else:
            kvo_spec = pl.BlockSpec(
                (1, 8, KVHD),
                lambda b, ik, lens, li: (b, _write_stripe(lens, b), 0))
            sco_spec = pl.BlockSpec(
                (1, 8, KVH),
                lambda b, ik, lens, li: (b, _write_stripe(lens, b), 0))
        nspec = pl.BlockSpec((1, KVH, D), lambda b, ik, lens, li: (b, 0, 0))
        in_specs += [nspec, nspec]
        operands += [new_k, new_v]
        out_specs += [kvo_spec, kvo_spec]
        out_shape += [jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                      jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)]
        # operand indices INCLUDE the two scalar-prefetch args
        io_aliases = {3: 1, 4: 2}
        if quant:
            out_specs += [sco_spec, sco_spec]
            out_shape += [jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
                          jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype)]
            io_aliases = {3: 1, 4: 2, 5: 3, 6: 4}

    res = pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale),
                          block_k=block_k, nk=nk, kvh=KVH, g=G, d=D,
                          stacked=stacked, quant=quant,
                          window=None if window is None else int(window),
                          mxu_int8=mxu_int8, fused_write=fused_write),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nk),
            in_specs=in_specs,
            out_specs=out_specs if fused_write else out_specs[0],
            scratch_shapes=[
                pltpu.VMEM((H, LSE_LANES), jnp.float32),
                pltpu.VMEM((H, LSE_LANES), jnp.float32),
                pltpu.VMEM((H, D), jnp.float32),
                pltpu.VMEM((H, KVHD),
                           jnp.int8 if mxu_int8 else q.dtype),
            ] + ([pltpu.VMEM((H, LSE_LANES), jnp.float32)]
                 if mxu_int8 else [])),
        out_shape=out_shape if fused_write else out_shape[0],
        input_output_aliases=io_aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_decode_vmem_bytes(
                1, H, D, block_k, KVHD, k_cache.dtype, q.dtype, quant=quant)),
        interpret=_interpret(),
        name="attn.decode",
    )(jnp.asarray(lengths, jnp.int32), layer_arr, *operands)
    return res
