"""Pallas paged attention — decode & chunked prefill over block-table pools.

TPU-native analog of vLLM's PagedAttention kernel: the KV cache is a shared
page pool ``[L, num_pages, page_size, KVH*D]`` and each batch row owns a
block table ``pages[b, virtual_page] -> physical_page``.  Before these
kernels, the paged serving path materialized a per-layer virtual view with
``take_along_axis`` (``models/transformer._paged_gather``) and ran dense
attention over it — one full gathered cache copy per layer per step.

A paged cache is the monolithic split-K online-softmax computation of
``decode_attention.py`` under another address map: virtual page ``i`` of
row ``b`` lives at pool page ``pages[b, i]``, a block is a whole number of
pages, and the kernels' virtual position math (``pos = i*block_k +
iota``, length masks, write row ``(length-1) % block_k``) transfers
verbatim.  The per-block update — block-diagonal Q, cross-block max/sum
merge, int8-KV dequant on the score/probability tiles, the fused write's
column substitution — is the monolithic kernel's own (``_block_update``);
what differs is who supplies the block sequence.

**Decode** (:func:`paged_decode_attention`) is ONE software pipeline over
the call's sequence of (live row, block of pages) pairs:

* ``grid=(1,)`` — the K/V pools stay whole in HBM
  (``memory_space=pl.ANY``), every row's q, new K/V row and output sit in
  VMEM, and a loop walks the LIVE rows.  A row's ``ceil(length /
  page_size)`` pages are folded ``min(ceil(512 / page_size), pages a
  slot)`` at a time (``_decode_block_pages``, a shape-derived constant):
  each page of a block is fetched through the table with
  ``make_async_copy`` into its rows of a double-buffered ``[pages *
  page_size, KVH*D]`` VMEM block, all of a block's copies in flight at
  once and block i+1's started before block i is folded in, one
  per-block update a block.  DMAs and arithmetic are O(live pages); the
  table's width costs nothing.  (The page-an-update form this replaces
  paid a fetch, a wait and a whole online-softmax update — a half-vreg
  score tile, matmuls with an N / a K of 64, a sub-vreg accumulator
  update a KV head — for every 64-key page: 0.62 us a page where the
  DMA of LFM2's 128 KiB page takes 0.16, PERF.md PR 41.)
* **The pipeline outlives the row.**  While a row folds its LAST block,
  the first block of the NEXT LIVE row is already on its way into the
  other buffer, so no row begins with a wait for a fetch nobody had asked
  for; only the call's first block is exposed.
* The tail block is partly filled: rows of pages past the live ones keep
  what an earlier block or an earlier row left (or nobody wrote), and
  the last page's rows past ``length`` hold what the slot's previous
  tenant wrote.  Scores there are masked by the update; the VALUE rows
  at ``pos >= length`` are zeroed in the buffer before the matmul (a
  probability of 0 against a NaN is a NaN).
* A DEAD row — one whose table points at the reserved trash page
  (``pages[b, 0] == 0``; page 0 is never allocated), which is how the
  serving decode block presents free, retired and still-prefilling
  lanes whatever their position counter says — is skipped by the row
  loop and by the hand-over: a table lookup, a zero output row, no
  write.  (As a grid step of its own it cost 0.35 us: 8.4 of the chat
  cell's 51 us a call.)
* The fused decode write targets the pool through the table: the row's
  8-row write stripe — ``(layer, pages[b, (len-1)//page], 8-row stripe
  of (len-1)%page)`` — is merged from the last block's buffer (before
  its tail is zeroed: the stripe's other rows keep the pool's bits),
  staged in VMEM and sent to the aliased pool by ``make_async_copy``,
  two stripes in flight.  A row's write page is private to its slot and
  shared prefix pages are full and read-only, so a block fetched ahead
  of a stripe write reads nothing that is being written.
* int8 pools keep the grid walk over virtual pages (the monolithic
  ``_decode_kernel`` body behind table index maps, ``grid=(B, pages a
  slot)``, ``block_k = page_size``): a page of dequant scales is
  ``[page_size, KVH]`` with ``KVH < 128`` lanes, which Mosaic refuses to
  slice out of an HBM ref by hand ("slice shape must be aligned to
  tiling (128)") — only its own BlockSpec pipeline can fetch it.  Dead
  rows are presented to it with length 0, so they skip the arithmetic
  (not the grid steps), and their stripes go to the trash page.

**Chunked prefill** (:func:`paged_chunk_prefill_attention`) folds the
REACHABLE pages of each row — those up to the chunk's furthest position,
``ceil((start + C) / page_size)`` — in wide blocks:

* ``grid=(B,)`` (B = 1 in the serving chunk step), the pools whole in
  HBM.  Inside the step a ``fori_loop`` over KV blocks of
  ``min(ceil(512 / page_size), pages a slot)`` pages — a shape-derived
  constant — fetches each page of a block through the table with
  ``make_async_copy`` into its rows of a ``[pages * page_size, KVH*D]``
  VMEM buffer, double-buffered so block i+1 arrives while block i is
  folded in.  Grid steps, DMAs and arithmetic are O(reachable pages).
* Per block and head: ONE ``[C, D] x [D, 512]`` score matmul, a
  lane-dense ``[C, 512]`` float32 tile for max / exp / sum, ONE
  ``[C, 512] x [512, D]`` value matmul and one rescale of the head's
  accumulator slice (``decode_attention._chunk_block_update``, the
  monolithic chunk kernel's own update); the running max and sum are
  lane-replicated ``[C, 128]`` tiles a head (``STAT_LANES``: every lane
  its row's value, so ``s - m`` and the accumulator's rescale take whole
  vregs and no lane broadcast — PERF.md PR 53).  Heads are walked by a
  ``fori_loop`` over 128-lane groups of the slab, so the body compiles
  once.  (The
  grid-per-page form this replaces did two 128x64x64 matmuls, a
  half-vreg score tile, two single-lane column updates and a rescale for
  every (head, 64-key page): ~20 us a reachable page, 3% of the chip's
  roofline — PERF.md PR 28.)
* The causal mask is applied only in blocks that reach ``start``; blocks
  wholly under the diagonal run the same arithmetic without its compares
  and selects.
* The tail block is partly filled: rows of pages past the reachable
  ones keep what an earlier block left, and the last page's rows past
  the chunk hold whatever the slot's previous tenant wrote.  Scores
  there are masked by the update; the VALUE rows are zeroed in the
  buffer before the matmul (a probability of 0 against a NaN is a NaN).
* The call itself is jitted with the layer index a TRACED operand
  (``_paged_chunk_call``): an unrolled model calls it once a layer with
  the same shapes, so the kernel is traced and lowered to Mosaic once a
  program — the 24-layer chunk step builds in 14 s where it took 29
  (PERF.md PR 28).
* int8 pools, and pages that are not whole sublane tiles of the pool's
  dtype, keep the grid walk over virtual pages (the monolithic
  ``_chunk_prefill_kernel`` driver behind table index maps; pages past
  the reachable ones pin to the last, so their DMA is elided and their
  body ``pl.when``-gated off): the scale page cannot be fetched by hand
  (above).

**A sliding window over a K/V ring** (:func:`window_chunk_attention`,
``attn.gqa_window_chunk``): a windowed layer's K/V lie in a ring a slot of
exactly the window's rows (``models/trinity.py``; position ``t`` in ring row
``t % window``), read through the slot's RING table.  A prefill chunk's
queries go ``_WINDOW_CHUNK_QUERIES`` a grid step; a step folds first the
ring's rows its queries can see — the positions before the chunk from its
first query's band on, in the chunk loop's 512-key blocks through the
table, double-buffered — and then the chunk's own keys up to the diagonal,
which are a VMEM input (the ring is read as the chunk FOUND it; the chunk's
rows go in after).  Blocks wholly outside a query block's band are never
walked, blocks wholly inside it take the unmasked update, the others
``_chunk_block_update``'s band mask (``pos0`` / ``window`` / ``limit``).  A
decode step over the ring needs no kernel of its own: every row of a full
ring is in the band, so it is :func:`paged_decode_attention` over the ring's
table at ``min(pos + 1, window)`` rows (``registry``'s
``pallas_ring_decode``).

How far the live-page walks engage in serving is on the dispatch spans:
``dstpu.sched.dispatch.decode`` carries ``kv_pages`` (pages the block's
steps walk) against ``kv_pages_table`` (slots x pages a slot x steps)
and ``kv_folds`` (the per-block updates made of those pages:
``kv_pages / kv_folds`` is the pages an update really carried),
``dstpu.sched.dispatch.prefill_chunk`` ``kv_pages`` (pages the chunk's
layers fetch) against ``kv_pages_table`` (pages a slot x layers).

Numerics: decode's block sequence and arithmetic per block are those of
``decode_attention(block_k=pages-a-block * page_size)`` over the gathered
virtual view (partial last block included), so the two are BITWISE
equal; chunked prefill's are those of
``chunk_prefill_attention(block_k=pages-a-block * page_size)`` over the
gathered view, bitwise again (an int8 pool, either kernel:
``block_k=page_size``) — both regression-tested in
tests/unit/test_paged_attention.py.  Greedy
serving outputs stay equal to solo ``generate()`` token for token.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.decode_attention import (
    _ChunkState, _RowState, _block_update, _chunk_block_update,
    _chunk_grid_vmem_bytes, _chunk_prefill_kernel, _chunk_scratch,
    _chunk_scratch_bytes, _decode_kernel, _decode_vmem_bytes, _finish_chunk,
    _finish_row, _init_chunk, _init_row, _write_stripe)
from deepspeed_tpu.ops.transformer.flash_attention import LSE_LANES, _interpret

# VMEM ring of a decode loop that folds a page an update (``attn.eva_decode``
# still does; this module's decode loop did until PR 41): the page being
# folded in plus two fetches behind it.  Measured on v5e at the serving
# cells' shapes (PERF.md PR 26): two buffers 132 us a layer-step, three 117,
# four 117.
_DECODE_PAGE_BUFFERS = 3

# Keys one block of the chunk-prefill loop folds at once: the score tile is
# [C, 512] float32 (whole vregs along lanes) and the two matmuls a head get
# an N / a K of 512, where a 64-key page gave them 64.
_CHUNK_BLOCK_KEYS = 512

# Keys one block of the decode loop folds at once.  Measured on v5e, us a
# call at the four serving cells' shapes (PERF.md PR 41; a page an update
# and a row a grid step read 58 / 128 / 443 / 1771): 256 keys 43 / 119 /
# 383 / 782, 512 keys 44 / 119 / 381 / 662 — pages of 512 lanes (the last)
# are bound by the updates, not the bytes, and want them wide.
_DECODE_BLOCK_KEYS = 512


def _live_pages(lens, pages, b, page, nk):
    """Pages row ``b`` of the decode kernel walks: ``ceil(length /
    page)`` (at most the table's width), and 0 for a DEAD row — one
    whose table points at the reserved trash page (``pages[b, 0] == 0``;
    page 0 is never allocated) or whose length is not positive."""
    n = jnp.clip((lens[b] + page - 1) // page, 0, nk)
    return jnp.where(pages[b, 0] == 0, 0, n)


def _decode_block_pages(page, nk, itemsize):
    """Pages a block of the decode loop folds at once — the ONE rule, from
    the shapes the call sees (the dispatch span's ``kv_folds`` reads it
    too, ``serving/paging.py``): ~512 keys' worth, at most the table's
    width — and one where a page is not whole sublane tiles of the
    pool's dtype, so that it cannot land on its rows of a wider buffer."""
    if page % (32 // itemsize):
        return 1
    return min(-(-_DECODE_BLOCK_KEYS // page), nk)


def _page_rows(j, page, bp):
    """Rows of a ``[bp * page, lanes]`` block buffer that page ``j`` of
    the block lands on."""
    if bp == 1:
        return slice(None)
    return pl.ds(pl.multiple_of(j * page, page), page)


def _each_page(fn, pages_ref, li, row, first, count, slot, pools, bufs, sem,
               *, page, bp):
    """``fn`` over the async copies that bring virtual pages ``first ..
    first + count`` of table row ``row`` through the table to rows
    ``0.., page.., ..`` of block buffer ``slot`` — K and V of layer
    ``li``, every copy of a buffer on that buffer's semaphore.  A wait
    rebuilds the descriptors its start used."""
    def one(j, carry):
        pg = pages_ref[row, first + j]
        rows = _page_rows(j, page, bp)
        for n, (src, dst) in enumerate(zip(pools, bufs)):
            fn(pltpu.make_async_copy(src.at[li, pg], dst.at[slot, rows],
                                     sem.at[n, slot]))
        return carry

    jax.lax.fori_loop(0, count, one, None)


def _zero_value_tail(vbuf, slot, base, limit, *, page, bp):
    """Zero the rows of value block ``slot`` (row 0 at position
    ``base``) at positions ``>= limit``.  Rows nobody attends — the last
    page's tail and the pages of a partly filled block that were not
    fetched — hold what the slot's previous tenant, an earlier block, an
    earlier row or nobody wrote; the score side is masked by the update,
    but a probability of 0 against a NaN is a NaN."""
    def one(j, carry):
        rows = _page_rows(j, page, bp)
        pos = base + j * page + jax.lax.broadcasted_iota(
            jnp.int32, (page, 1), 0)
        v = vbuf[slot, rows]
        vbuf[slot, rows] = jnp.where(pos < limit, v, jnp.zeros_like(v))
        return carry

    jax.lax.fori_loop(jnp.clip((limit - base) // page, 0, bp), bp, one, None)


class _Row:
    """Row ``r`` of a ``[B, ...]`` ref as the ``[1, ...]`` block the row
    state reads and writes at ``[0]`` (Mosaic refuses the ref slice
    where the row's lanes are not whole tiles; the indexed load and
    store it takes)."""

    def __init__(self, ref, r):
        self.ref, self.r, self.dtype = ref, r, ref.dtype

    def __getitem__(self, _):
        return self.ref[self.r]

    def __setitem__(self, _, value):
        self.ref[self.r] = value


def _paged_decode_kernel(len_ref, layer_ref, pages_ref, q_ref, k_hbm, v_hbm,
                         *rest, scale, page, nk, bp, nb, kvh, g, d,
                         fused_write):
    """The block-loop driver: ONE grid step a call, the pools whole in
    HBM, and one software pipeline over the call's sequence of (live
    row, block of pages) pairs.  A loop over the LIVE rows (a dead row
    costs a table lookup); a row's pages are fetched through the table,
    ``bp`` at a time, into the rows of a double-buffered ``[bp * page,
    KVH*D]`` block and each block goes through the monolithic kernel's
    per-block update; while a row folds its LAST block the first block
    of the next live row is already on its way into the other buffer.
    The fused write's 8-row stripes go out to the aliased pools by hand,
    two in flight."""
    kn_ref = vn_ref = None
    rest = list(rest)
    if fused_write:
        kn_ref, vn_ref = rest[:2]
        del rest[:2]
    o_ref = rest.pop(0)
    if fused_write:
        pools_out = rest[:2]
        del rest[:2]
    m_scr, l_scr, acc_scr, qbd_scr, kbuf, vbuf, sem = rest[:7]
    if fused_write:
        kstage, vstage, wsem = rest[7:]
    li = layer_ref[0]
    bk = bp * page

    def live_pages(r):
        return _live_pages(len_ref, pages_ref, jnp.minimum(r, nb - 1), page,
                           nk)

    def next_live(r):
        # the first live row from ``r`` on, ``nb`` where there is none
        return jax.lax.while_loop(
            lambda r: jnp.logical_and(r < nb, live_pages(r) == 0),
            lambda r: r + 1, r)

    def each_page(fn, row, first, count, slot):
        _each_page(fn, pages_ref, li, row, first, count, slot,
                   (k_hbm, v_hbm), (kbuf, vbuf), sem, page=page, bp=bp)

    def start(cp):
        cp.start()

    def wait(cp):
        cp.wait()

    def each_stripe(fn, stage, wpage=0, base=0):
        # staged stripe ``stage`` → rows ``base .. base + 8`` of pool page
        # ``wpage``; a wait needs the descriptors' shapes alone
        for n, (src, dst) in enumerate(zip((kstage, vstage), pools_out)):
            fn(pltpu.make_async_copy(src.at[stage],
                                     dst.at[li, wpage, pl.ds(base, 8)],
                                     wsem.at[n, stage]))

    def one_row(carry):
        # ``slot0``: the buffer this row's first block is on its way
        # into; ``done``: live rows before this one
        r, slot0, done = carry
        length = len_ref[r]
        n_pages = live_pages(r)
        n_blocks = (n_pages + bp - 1) // bp
        nxt = next_live(r + 1)
        nxt_pages = jnp.where(nxt < nb, jnp.minimum(live_pages(nxt), bp), 0)
        st = _RowState(_Row(q_ref, r), m_scr, l_scr, acc_scr, qbd_scr,
                       kn_ref=_Row(kn_ref, r) if fused_write else None,
                       vn_ref=_Row(vn_ref, r) if fused_write else None)
        _init_row(st, kvh=kvh, g=g, d=d)

        def fold(i, carry):
            slot = (slot0 + i) % 2
            last = i == n_blocks - 1
            # what follows block i in the call's sequence — this row's
            # block i + 1, or the next live row's first — starts into
            # the buffer block i - 1 (or the row before) has been folded
            # out of.  A row's write page is private to its slot and
            # shared prefix pages are full and read-only, so a fetch
            # ahead of this row's stripe write reads nothing that is
            # being written.
            each_page(start, jnp.where(last, jnp.minimum(nxt, nb - 1), r),
                      jnp.where(last, 0, (i + 1) * bp),
                      jnp.where(last, nxt_pages,
                                jnp.minimum(n_pages - (i + 1) * bp, bp)),
                      1 - slot)
            each_page(wait, r, i * bp, jnp.minimum(n_pages - i * bp, bp),
                      slot)

            @pl.when(last)
            def _tail():
                if fused_write:
                    # the write row lies in the last live page: rows of
                    # THIS buffer, read before the tail below is zeroed
                    # so that the stripe's other rows keep the pool's
                    # bits.  The stripe is staged and sent on its way;
                    # its stage is free once the stripe two rows back
                    # has landed.
                    stage = done % 2

                    @pl.when(done >= 2)
                    def _():
                        each_stripe(wait, stage)

                    def load8(base):
                        rows = pl.ds(base, 8)
                        return kbuf[slot, rows], vbuf[slot, rows], None, None

                    _write_stripe(st, length, bk, load8, kstage.at[stage],
                                  vstage.at[stage], None, None, kvh=kvh, d=d)
                    pos = jnp.maximum(length - 1, 0)
                    each_stripe(
                        start, stage,
                        pages_ref[r, jnp.minimum(pos // page, nk - 1)],
                        pl.multiple_of((pos % page) // 8 * 8, 8))
                _zero_value_tail(vbuf, slot, i * bk, length, page=page,
                                 bp=bp)

            _block_update(st, i, length, kbuf[slot], vbuf[slot], None, None,
                          scale=scale, block_k=bk, kvh=kvh, g=g, d=d,
                          window=None)
            return carry

        jax.lax.fori_loop(0, n_blocks, fold, None)
        _finish_row(st, _Row(o_ref, r))
        return nxt, (slot0 + n_blocks) % 2, done + 1

    o_ref[...] = jnp.zeros_like(o_ref)      # dead rows return zeros
    first = next_live(0)
    each_page(start, jnp.minimum(first, nb - 1), 0,
              jnp.where(first < nb, jnp.minimum(live_pages(first), bp), 0),
              0)
    _, _, done = jax.lax.while_loop(lambda c: c[0] < nb, one_row,
                                    (first, 0, 0))
    if fused_write:
        for back in (1, 2):                 # the stripes still in flight
            @pl.when(done >= back)
            def _():
                each_stripe(wait, (done - back) % 2)


def _paged_grid_decode_body(len_ref, layer_ref, pages_ref, *args, **kw):
    # the page table is consumed entirely by the BlockSpec index maps;
    # the kernel body is the monolithic decode kernel, verbatim
    del pages_ref
    _decode_kernel(len_ref, layer_ref, *args, **kw)


def _paged_chunk_body(start_ref, layer_ref, pages_ref, *args, **kw):
    del pages_ref
    _chunk_prefill_kernel(start_ref, layer_ref, *args, **kw)


def _chunk_block_pages(page, nk):
    """Pages a block of the chunk-prefill loop holds: ~512 keys' worth,
    at most the table's width."""
    return min(-(-_CHUNK_BLOCK_KEYS // page), nk)


def _reachable_pages(start, c, page, nk):
    """Pages of a row a chunk of ``c`` queries starting at ``start`` can
    reach: its furthest position is ``start + c - 1``."""
    return jnp.clip((start + c + page - 1) // page, 1, nk)


def _paged_chunk_kernel(start_ref, layer_ref, pages_ref, q_ref, k_hbm, v_hbm,
                        o_ref, m_scr, l_scr, acc_scr, kbuf, vbuf, sem, *,
                        scale, page, nk, bp, c, kvh, g, d):
    """The block-loop driver: one grid step a batch row, the pools whole
    in HBM.  The row's REACHABLE pages are fetched through the table,
    ``bp`` at a time, into the rows of a double-buffered ``[bp * page,
    KVH*D]`` block (block i+1 arrives while block i is folded in) and
    each block goes through the monolithic kernel's per-block update —
    unmasked while the block lies wholly under the causal diagonal."""
    st = _ChunkState(q_ref, m_scr, l_scr, acc_scr)
    b = pl.program_id(0)
    li = layer_ref[0]
    start = start_ref[b]
    limit = start + c                       # rows reach pos <= limit - 1
    bk = bp * page
    n_pages = _reachable_pages(start, c, page, nk)
    n_blocks = (n_pages + bp - 1) // bp
    # blocks whose every position is <= start need no causal mask
    n_under = jnp.minimum((start + 1) // bk, n_blocks)

    def each_page(i, fn):
        # block i of this row → buffer i % 2.  Pages past the reachable
        # ones are not fetched: the tail block's rows there keep what an
        # earlier block left.
        _each_page(fn, pages_ref, li, b, i * bp,
                   jnp.clip(n_pages - i * bp, 0, bp), i % 2, (k_hbm, v_hbm),
                   (kbuf, vbuf), sem, page=page, bp=bp)

    def fold(masked):
        def body(i, carry):
            each_page(i + 1, lambda cp: cp.start())
            each_page(i, lambda cp: cp.wait())
            slot = i % 2
            if masked:
                # rows no query reaches: the score side is masked by the
                # update, the value side here
                @pl.when((i + 1) * bk > limit)
                def _zero_tail():
                    _zero_value_tail(vbuf, slot, i * bk, limit, page=page,
                                     bp=bp)
            _chunk_block_update(st, i, start, kbuf.at[slot], vbuf.at[slot],
                                None, None, scale=scale, block_k=bk, c=c,
                                kvh=kvh, g=g, d=d, masked=masked)
            return carry
        return body

    _init_chunk(st)
    each_page(0, lambda cp: cp.start())
    jax.lax.fori_loop(0, n_under, fold(False), None)
    jax.lax.fori_loop(n_under, n_blocks, fold(True), None)
    _finish_chunk(st, o_ref, heads=kvh * g, d=d)


def _pool_dims(q, k_pool):
    if k_pool.ndim != 4:
        raise ValueError(
            f"paged attention expects a layer-stacked pool "
            f"[L, num_pages, page_size, KVH*D]; got shape {k_pool.shape}")
    D = q.shape[-1]
    page, KVHD = k_pool.shape[-2], k_pool.shape[-1]
    KVH = KVHD // D
    return page, KVHD, KVH


def paged_decode_attention(q, k_pool, v_pool, lengths, pages, *, scale=None,
                           layer=None, k_scale=None, v_scale=None,
                           int8_matmuls=False, new_k=None, new_v=None):
    """Single-token decode attention over a paged KV pool.

    q: [B, H, D]; pools: [L, num_pages, page_size, KVH*D] (the
    ``init_paged_cache`` layout — page-major S-major slabs, heads
    flattened into lanes, so each page is one contiguous full-lane-width
    DMA).  ``pages``: [B, n_virtual_pages] int32 block tables (virtual
    page ``pos // page_size`` → physical pool page).  ``lengths``: [B]
    int32 — valid virtual positions INCLUDING this step's token.
    ``layer``: the (traced) layer index into the stacked pools.  Returns
    [B, H, D].

    A row whose table points at the reserved trash page
    (``pages[b, 0] == 0``) is DEAD whatever its length says: it reads no
    page, its output row is zeros, and (fused write) it writes no
    allocated page.  Live rows cost O(ceil(length / page_size)) pages —
    see the module docstring for the two drivers.

    ``k_scale``/``v_scale`` ([L, num_pages, page_size, KVH]) switch the
    pools to int8 payloads with per-(position, kv-head) dequant scales,
    applied to score/probability tiles exactly as in
    :func:`~deepspeed_tpu.ops.transformer.decode_attention.decode_attention`.

    ``new_k``/``new_v`` ([B, KVH, D]) switch on the FUSED CACHE WRITE:
    the kernel quantizes (when the pool is int8) and writes this step's
    row at virtual position ``lengths[b]-1`` THROUGH the block table
    into the pool, returned as aliased outputs — the caller must then
    NOT pre-scatter the row.  Requires ``page_size % 8 == 0`` (the
    8-sublane-aligned write stripe) and is unsupported with
    ``int8_matmuls`` (same restriction as the monolithic kernel).
    Returns ``(out, k_pool, v_pool[, k_scale, v_scale])`` instead of
    ``out``.
    """
    B, H, D = q.shape
    page, KVHD, KVH = _pool_dims(q, k_pool)
    G = H // KVH
    if layer is None:
        raise ValueError("layer-stacked pools require layer=")
    quant = k_scale is not None
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if int8_matmuls and not quant:
        raise ValueError("int8_matmuls requires quantized pools "
                         "(k_scale/v_scale)")
    fused_write = new_k is not None
    if (new_k is None) != (new_v is None):
        raise ValueError("new_k and new_v must be given together")
    if fused_write and int8_matmuls:
        raise ValueError("int8_matmuls is unsupported with the fused "
                         "cache write (new_k/new_v)")
    if fused_write and page % 8 != 0:
        raise ValueError(
            f"fused paged write needs page_size % 8 == 0 (8-sublane-"
            f"aligned write stripes); got {page}")
    mxu_int8 = bool(int8_matmuls)
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    nk = pages.shape[1]                     # virtual pages per row
    layer_arr = jnp.asarray([layer], jnp.int32)
    pages_arr = jnp.asarray(pages, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)

    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    if quant:
        # the grid walk, a row a grid step.  Index maps: (grid
        # indices..., lengths, layer, pages).  Virtual pages past the
        # live region pin to the LAST live page, so its physical index
        # repeats across the dead tail and Mosaic elides the DMA (compute
        # is pl.when-gated off in the body); dead rows get length 0,
        # which gates every page
        lengths = jnp.where(pages_arr[:, 0] == 0, 0, lengths)
        grid = (B, nk)

        def rows(*tail):
            return pl.BlockSpec((1,) + tail, lambda b, *refs: (b, 0, 0))

        def stripe(b, *refs):
            # table-resolved write stripe: virtual write position
            # lens[b]-1 lands on pool page pages[b, (lens[b]-1)//page] at
            # in-page row (lens[b]-1) % page; the output block covers
            # only that row's 8-sublane-aligned stripe (index in 8-row
            # units), constant per batch row, so Mosaic flushes 8 rows
            # once after the row's last grid step — same stripe economics
            # as the monolithic fused write.  A dead row's stripe goes to
            # the trash page.
            lens, li, pg = refs[-3:]
            pos = jnp.maximum(lens[b] - 1, 0)
            wpage = jnp.where(_live_pages(lens, pg, b, page, nk) == 0, 0,
                              pg[b, jnp.minimum(pos // page, nk - 1)])
            return (li[0], wpage, (pos % page) // 8, 0)

        def write_spec(lanes):
            return pl.BlockSpec((1, 1, 8, lanes), stripe)

        def kv(b, ik, lens, li, pg):
            last = jnp.maximum(_live_pages(lens, pg, b, page, nk) - 1, 0)
            return (li[0], pg[b, jnp.minimum(ik, last)], 0, 0)

        kv_spec = pl.BlockSpec((1, 1, page, KVHD), kv)
        sc_spec = pl.BlockSpec((1, 1, page, KVH), kv)
        in_specs = [rows(H, D), kv_spec, kv_spec, sc_spec, sc_spec]
        operands = [q, k_pool, v_pool, k_scale, v_scale]
        kernel = functools.partial(
            _paged_grid_decode_body, scale=float(scale), block_k=page,
            nk=nk, kvh=KVH, g=G, d=D, stacked=True, quant=True,
            window=None, mxu_int8=mxu_int8, fused_write=fused_write)
        loop_scratch = []
        q_rows, block_keys = 1, page        # a row and a page a grid step
    else:
        # the block loop: ONE grid step, every row's q / new rows / output
        # in VMEM, the pools whole in HBM on both sides (the kernel sends
        # the write stripes itself)
        grid = (1,)
        bp = _decode_block_pages(page, nk, k_pool.dtype.itemsize)

        def rows(*tail):
            return pl.BlockSpec((B,) + tail, lambda i, *refs: (0, 0, 0))

        def write_spec(lanes):
            return pool_spec

        in_specs = [rows(H, D), pool_spec, pool_spec]
        operands = [q, k_pool, v_pool]
        kernel = functools.partial(
            _paged_decode_kernel, scale=float(scale), page=page, nk=nk,
            bp=bp, nb=B, kvh=KVH, g=G, d=D, fused_write=fused_write)
        # two K and two V blocks; with the fused write, two staged
        # stripes a pool
        loop_scratch = [pltpu.VMEM((2, bp * page, KVHD), k_pool.dtype),
                        pltpu.VMEM((2, bp * page, KVHD), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))]
        if fused_write:
            loop_scratch += [pltpu.VMEM((2, 8, KVHD), k_pool.dtype),
                             pltpu.VMEM((2, 8, KVHD), v_pool.dtype),
                             pltpu.SemaphoreType.DMA((2, 2))]
        q_rows, block_keys = B, bp * page

    out_specs = [rows(H, D)]
    out_shape = [jax.ShapeDtypeStruct((B, H, D), q.dtype)]
    io_aliases = {}
    if fused_write:
        in_specs += [rows(KVH, D)] * 2
        operands += [new_k, new_v]
        out_specs += [write_spec(KVHD)] * 2
        out_shape += [jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                      jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)]
        # operand indices INCLUDE the three scalar-prefetch args
        io_aliases = {4: 1, 5: 2}
        if quant:
            out_specs += [write_spec(KVH)] * 2
            out_shape += [jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
                          jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype)]
            io_aliases = {4: 1, 5: 2, 6: 3, 7: 4}

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs if fused_write else out_specs[0],
            scratch_shapes=[
                pltpu.VMEM((H, LSE_LANES), jnp.float32),
                pltpu.VMEM((H, LSE_LANES), jnp.float32),
                pltpu.VMEM((H, D), jnp.float32),
                pltpu.VMEM((H, KVHD),
                           jnp.int8 if mxu_int8 else q.dtype),
            ] + ([pltpu.VMEM((H, LSE_LANES), jnp.float32)]
                 if mxu_int8 else []) + loop_scratch),
        out_shape=out_shape if fused_write else out_shape[0],
        input_output_aliases=io_aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")[:len(grid)],
            vmem_limit_bytes=_decode_vmem_bytes(
                q_rows, H, D, block_keys, KVHD, k_pool.dtype, q.dtype,
                quant=quant)),
        interpret=_interpret(),
        name="attn.paged_decode",
    )(lengths, layer_arr, pages_arr, *operands)


def _chunk_loop_vmem_bytes(c, h, d, bk, kvhd, kv_itemsize, q_itemsize):
    """The VMEM the chunk kernel's block loop asks for: two K and two V
    blocks, a head group's [C, bk] float32 score tiles, the q and output
    blocks (double-buffered by the pipeline), the online-softmax
    scratch, and headroom — and no more (29 MiB at OPT-1.3B's chunk of
    128).  What the kernel is granted XLA cannot use across it: under a
    64 MiB floor the chunk step's next weights could not be prefetched
    into VMEM while the kernel ran, 0.66 ms of a 4.70 ms chunk
    (PERF.md, PR 36)."""
    ask = (4 * bk * kvhd * kv_itemsize + 6 * c * bk * 4
           + 4 * c * h * d * q_itemsize + _chunk_scratch_bytes(c, h, d)
           + 16 * 1024 * 1024)
    # a KV head is walked with ALL its query heads: their q columns, their
    # accumulator columns and their outputs before they are joined.  Up to
    # eight heads a KV head that is inside the headroom; Nemotron-H's
    # sixteen of 128 at a 512-query block are 14.7 MB and were not (72.9 MB
    # wanted of 63 asked, once two rows double-buffer q and the output)
    group = h * d // kvhd
    if group > 8:
        ask += c * group * d * (q_itemsize + 12)
    return ask


def paged_chunk_prefill_attention(q, k_pool, v_pool, starts, pages, *,
                                  scale=None, layer=None, k_scale=None,
                                  v_scale=None):
    """Chunked-prefill attention over a paged KV pool: a block of C fresh
    query tokens (already scattered into the pool at virtual positions
    ``starts[b] .. starts[b]+C-1``) attends causally over each row's
    paged cache.  Same [C, page_size] score-tile economics as
    :func:`~deepspeed_tpu.ops.transformer.decode_attention.chunk_prefill_attention`
    — paged admission prefill never materializes the gathered virtual
    view (previously one ``take_along_axis`` pool copy per layer per
    chunk).

    q: [B, C, H, D]; pools/pages/layer as in
    :func:`paged_decode_attention`.  starts: [B] int32 per-row chunk
    start (query row ``iq`` masks virtual positions ``> starts[b]+iq``).
    Returns [B, C, H, D].
    """
    _pool_dims(q, k_pool)
    if layer is None:
        raise ValueError("layer-stacked pools require layer=")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    scales = () if k_scale is None else (k_scale, v_scale)
    interpret = _interpret()                # a bool: static by value
    return _paged_chunk_call(
        q, k_pool, v_pool, jnp.asarray(starts, jnp.int32),
        jnp.asarray([layer], jnp.int32), jnp.asarray(pages, jnp.int32),
        *scales, scale=float(scale), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _paged_chunk_call(q, k_pool, v_pool, starts, layer_arr, pages_arr,
                      k_scale=None, v_scale=None, *, scale, interpret):
    """The kernel call, jitted with the layer as a traced operand: an
    unrolled model calls it once a layer with the same shapes, so the
    kernel is traced to a jaxpr and lowered to Mosaic ONCE a program and
    not once a layer (two thirds of a serving program's set-up is Python
    tracing and lowering — PERF.md §5 `setup_s`)."""
    B, C, H, D = q.shape
    page, KVHD, KVH = _pool_dims(q, k_pool)
    G = H // KVH
    quant = k_scale is not None
    nk = pages_arr.shape[1]

    q_spec = pl.BlockSpec((1, C, H * D), lambda b, *refs: (b, 0, 0))
    itemsize = k_pool.dtype.itemsize
    # the block loop lands each page on its rows of a VMEM block by hand:
    # the page must be whole sublane tiles of the pool's dtype, and a
    # quantized pool's [page, KVH] scale page cannot be sliced out of HBM
    # at all (see the module docstring) — those keep the grid walk
    loop = not quant and page % (32 // itemsize) == 0
    if loop:
        bp = _chunk_block_pages(page, nk)
        grid = (B,)
        pool_spec = pl.BlockSpec(memory_space=pl.ANY)
        in_specs = [q_spec, pool_spec, pool_spec]
        operands = [k_pool, v_pool]
        kernel = functools.partial(
            _paged_chunk_kernel, scale=scale, page=page, nk=nk,
            bp=bp, c=C, kvh=KVH, g=G, d=D)
        scratch = [pltpu.VMEM((2, bp * page, KVHD), k_pool.dtype),
                   pltpu.VMEM((2, bp * page, KVHD), v_pool.dtype),
                   pltpu.SemaphoreType.DMA((2, 2))]
        vmem = _chunk_loop_vmem_bytes(C, H, D, bp * page, KVHD, itemsize,
                                      q.dtype.itemsize)
    else:
        def _live_page(ik, st, b):
            # the chunk's furthest reachable virtual position is st[b]+C-1
            return jnp.minimum(ik, _reachable_pages(st[b], C, page, nk) - 1)

        def kv(b, ik, st, li, pg):
            return (li[0], pg[b, _live_page(ik, st, b)], 0, 0)

        grid = (B, nk)
        kv_spec = pl.BlockSpec((1, 1, page, KVHD), kv)
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = [k_pool, v_pool]
        if quant:
            sc_spec = pl.BlockSpec((1, 1, page, KVH), kv)
            in_specs += [sc_spec, sc_spec]
            operands += [k_scale, v_scale]
        kernel = functools.partial(
            _paged_chunk_body, scale=scale, block_k=page, nk=nk,
            c=C, kvh=KVH, g=G, d=D, stacked=True, quant=quant)
        scratch = []
        vmem = _chunk_grid_vmem_bytes(C, H, D, page, KVHD,
                                      q.dtype.itemsize)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, C, H * D), lambda b, *refs: (b, 0, 0)),
            scratch_shapes=_chunk_scratch(C, H, D) + scratch),
        out_shape=jax.ShapeDtypeStruct((B, C, H * D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")[:len(grid)],
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="attn.paged_chunk_prefill",
    )(starts, layer_arr, pages_arr, q.reshape(B, C, H * D), *operands)
    return out.reshape(B, C, H, D)


# ---- a sliding window over a K/V ring ------------------------------------ #

# queries one grid step of the window chunk kernel holds: the chunk kernel's
# own bound (``registry.MAX_CHUNK_S``)
_WINDOW_CHUNK_QUERIES = 512


def _window_chunk_kernel(start_ref, layer_ref, ring_ref, q_ref, kn_ref,
                         vn_ref, k_hbm, v_hbm, o_ref, m_scr, l_scr, acc_scr,
                         kbuf, vbuf, sem, *, scale, page, n_ring, bp, cq,
                         window, kvh, g, d):
    """One block of ``cq`` queries of a prefill chunk under a sliding
    window of ``window`` keys: first the ring's rows the block can see —
    the positions before the chunk, ``q0 - window + 1 .. start - 1``,
    fetched through the slot's ring table ``bp`` pages at a time as
    ``_paged_chunk_kernel`` fetches a lane's —, then the chunk's own keys up
    to the diagonal, from VMEM.  Blocks wholly outside the band are not
    walked; blocks wholly inside it run unmasked."""
    st = _ChunkState(q_ref, m_scr, l_scr, acc_scr)
    li = layer_ref[0]
    start = start_ref[0]
    q0 = start + pl.program_id(0) * cq      # the block's first query
    low1 = q0 + cq - window                 # its LAST query's first key
    bk = bp * page
    update = functools.partial(_chunk_block_update, st, 0, q0, ks=None,
                               vs=None, scale=scale, c=cq, kvh=kvh, g=g, d=d)

    # ---- the ring: positions a0 .. start - 1, a0 the page of the first
    # query's first key; position t lies in ring page (t // page) % n_ring
    a0 = jnp.maximum(q0 - window + 1, 0) // page * page
    n_pages = jnp.maximum((start - a0 + page - 1) // page, 0)
    n_blocks = (n_pages + bp - 1) // bp
    # blocks under every query's band whole: from the last query's first
    # key on, and before ``start``
    first_in = jnp.clip((low1 - a0 + bk - 1) // bk, 0, n_blocks)
    end_in = jnp.clip((start - a0) // bk, first_in, n_blocks)

    def each_page(i, fn):
        # block i of the ring walk -> buffer i % 2; pages past ``start``
        # are not fetched
        vp, slot = a0 // page + i * bp, i % 2

        def one(j, carry):
            pg = ring_ref[(vp + j) % n_ring]
            rows = _page_rows(j, page, bp)
            for n, (src, dst) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                fn(pltpu.make_async_copy(src.at[li, pg], dst.at[slot, rows],
                                         sem.at[n, slot]))
            return carry

        jax.lax.fori_loop(0, jnp.clip(n_pages - i * bp, 0, bp), one, None)

    def fold_ring(masked):
        def body(i, carry):
            each_page(i + 1, lambda cp: cp.start())
            each_page(i, lambda cp: cp.wait())
            slot = i % 2
            if masked:
                # rows at ``start`` and past it hold what the ring kept of
                # a window ago, or nothing: masked on the score side by
                # ``limit``, zeroed on the value side
                @pl.when(a0 + (i + 1) * bk > start)
                def _zero_tail():
                    _zero_value_tail(vbuf, slot, a0 + i * bk, start,
                                     page=page, bp=bp)
            update(k_ref=kbuf.at[slot], v_ref=vbuf.at[slot], block_k=bk,
                   masked=masked, pos0=a0 + i * bk, window=window,
                   limit=start)
            return carry
        return body

    _init_chunk(st)
    each_page(0, lambda cp: cp.start())
    jax.lax.fori_loop(0, first_in, fold_ring(True), None)
    jax.lax.fori_loop(first_in, end_in, fold_ring(False), None)
    jax.lax.fori_loop(end_in, n_blocks, fold_ring(True), None)

    # ---- the chunk's own keys, ``cq`` rows a block, up to the diagonal
    diag = pl.program_id(0)
    first = jnp.maximum(q0 - window + 1 - start, 0) // cq
    whole = jnp.clip((low1 - start + cq - 1) // cq, first, diag)

    def fold_chunk(masked):
        def body(t, carry):
            rows = pl.ds(pl.multiple_of(t * cq, cq), cq)
            update(k_ref=kn_ref.at[rows], v_ref=vn_ref.at[rows], block_k=cq,
                   masked=masked, pos0=start + t * cq, window=window)
            return carry
        return body

    jax.lax.fori_loop(first, whole, fold_chunk(True), None)
    jax.lax.fori_loop(whole, diag, fold_chunk(False), None)
    fold_chunk(True)(diag, None)
    _finish_chunk(st, o_ref, heads=kvh * g, d=d)


def window_chunk_queries(c):
    """Queries a grid step of the window chunk kernel holds at a chunk of
    ``c``, or ``None`` where ``c`` is no whole number of them."""
    cq = min(c, _WINDOW_CHUNK_QUERIES)
    return None if c % cq else cq


def window_chunk_attention(q, k_new, v_new, k_ring, v_ring, start, ring, *,
                           window, layer, scale=None):
    """A prefill chunk's attention under a sliding window over a K/V RING
    (``attn.gqa_window_chunk``): query ``i`` at position ``start + i`` sees
    keys ``start + i - window + 1 .. start + i`` — those before ``start``
    from the slot's ring, where position ``t`` lies in row ``t % page`` of
    ring page ``(t // page) % n_ring`` (``n_ring * page == window``: the
    ring holds exactly the window), those of the chunk from ``k_new`` /
    ``v_new``.  The ring is read as the chunk FOUND it: the chunk's own
    rows go in after (``registry``'s ring write).

    q ``[C, H, D]``; k_new / v_new ``[C, KVH*D]`` (normed, roped: as
    cached); rings ``[window layers, pages, page, KVH*D]``; ``ring``
    ``[n_ring]`` int32, the slot's ring pages; ``start`` a scalar.
    Returns ``[C, H, D]``."""
    C, H, D = q.shape
    page, KVHD, KVH = _pool_dims(q, k_ring)
    n_ring = ring.shape[0]
    if n_ring * page != window:
        raise ValueError(f"a ring of {n_ring} pages of {page} rows holds "
                         f"{n_ring * page} positions, not the window's "
                         f"{window}")
    cq = window_chunk_queries(C)
    if cq is None or page % (32 // k_ring.dtype.itemsize):
        raise ValueError(
            f"the window chunk kernel takes a chunk of whole "
            f"{_WINDOW_CHUNK_QUERIES}-query blocks (got {C}) and pages of "
            f"whole sublane tiles (got {page})")
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    interpret = _interpret()                # a bool: static by value
    return _window_chunk_call(
        q.reshape(1, C, H * D), k_new, v_new, k_ring, v_ring,
        jnp.asarray(start, jnp.int32).reshape(1),
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(ring, jnp.int32), scale=float(scale), window=int(window),
        cq=cq, heads=H, interpret=interpret).reshape(C, H, D)


@functools.partial(jax.jit, static_argnames=("scale", "window", "cq",
                                             "heads", "interpret"))
def _window_chunk_call(q, k_new, v_new, k_ring, v_ring, start, layer_arr,
                       ring, *, scale, window, cq, heads, interpret):
    """Jitted with the layer traced, like :func:`_paged_chunk_call`: the
    window layers of an unrolled model lower the kernel once."""
    _, C, HD = q.shape
    D = HD // heads
    page, KVHD = k_ring.shape[-2:]
    KVH = KVHD // D
    n_ring = ring.shape[0]
    bp = _chunk_block_pages(page, n_ring)
    itemsize = k_ring.dtype.itemsize
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    q_spec = pl.BlockSpec((1, cq, HD), lambda j, *refs: (0, j, 0))
    new_spec = pl.BlockSpec((C, KVHD), lambda j, *refs: (0, 0))
    kernel = functools.partial(
        _window_chunk_kernel, scale=scale, page=page, n_ring=n_ring, bp=bp,
        cq=cq, window=window, kvh=KVH, g=heads // KVH, d=D)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(C // cq,),
            in_specs=[q_spec, new_spec, new_spec, pool_spec, pool_spec],
            out_specs=q_spec,
            scratch_shapes=_chunk_scratch(cq, heads, D) + [
                pltpu.VMEM((2, bp * page, KVHD), k_ring.dtype),
                pltpu.VMEM((2, bp * page, KVHD), v_ring.dtype),
                pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_chunk_loop_vmem_bytes(
                cq, heads, D, max(bp * page, cq), KVHD, itemsize,
                q.dtype.itemsize) + 4 * C * KVHD * itemsize),
        interpret=interpret,
        name="attn.gqa_window_chunk",
    )(start, layer_arr, ring, q, k_new, v_new, k_ring, v_ring)
