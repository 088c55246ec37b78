"""Pallas paged attention — decode & chunked prefill over block-table pools.

TPU-native analog of vLLM's PagedAttention kernel: the KV cache is a shared
page pool ``[L, num_pages, page_size, KVH*D]`` and each batch row owns a
block table ``pages[b, virtual_page] -> physical_page``.  Before these
kernels, the paged serving path materialized a per-layer virtual view with
``take_along_axis`` (``models/transformer._paged_gather``) and ran dense
attention over it — one full gathered cache copy per layer per step.

A paged cache is the monolithic split-K online-softmax computation of
``decode_attention.py`` under another address map: virtual page ``i`` of
row ``b`` lives at pool page ``pages[b, i]``, ``block_k = page_size``, and
the kernels' virtual position math (``pos = i*block_k + iota``, length
masks, write row ``(length-1) % block_k``) transfers verbatim.  The
per-block update — block-diagonal Q, cross-page max/sum merge, int8-KV
dequant on the score/probability tiles, the fused write's column
substitution — is the monolithic kernel's own (``_block_update``); what
differs is who supplies the block sequence.

**Decode** (:func:`paged_decode_attention`) follows the LIVE pages of
each row:

* ``grid=(B,)`` — one grid step a slot.  The K/V pools stay whole in HBM
  (``memory_space=pl.ANY``); inside the step a ``fori_loop`` over the
  row's ``ceil(length / page_size)`` pages fetches page ``pages[b, i]`` of
  the layer with ``make_async_copy`` into a three-deep VMEM ring (two
  fetches in flight behind the page being folded in) and applies the
  per-block update.  Grid steps, DMAs and arithmetic are all
  O(live pages); the table's width costs nothing.  (The grid-per-page
  form this replaces paid ~0.35 us for every (slot, virtual page) pair,
  live or not — three fifths of both serving cells' device time, PERF.md
  PR 26.)
* A DEAD row — one whose table points at the reserved trash page
  (``pages[b, 0] == 0``; page 0 is never allocated), which is how the
  serving decode block presents free, retired and still-prefilling
  lanes whatever their position counter says — walks no page: no
  block-diagonal Q, no fetch, no arithmetic, a zero output row, and its
  fused-write stripe goes to the trash page.  A dead row costs one empty
  grid step.
* The fused decode write targets the pool through the table: the
  aliased output's 8-row write stripe pins to ``(layer,
  pages[b, (len-1)//page], ((len-1)%page)//8, 0)`` and is merged from
  the last live page, which the loop leaves in its buffer.
* int8 pools keep the grid walk over virtual pages (the monolithic
  ``_decode_kernel`` body behind table index maps): a page of dequant
  scales is ``[page_size, KVH]`` with ``KVH < 128`` lanes, which Mosaic
  refuses to slice out of an HBM ref by hand ("slice shape must be
  aligned to tiling (128)") — only its own BlockSpec pipeline can fetch
  it.  Dead rows are presented to it with length 0, so they skip the
  arithmetic (not the grid steps).

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
  ``[C, LSE_LANES]`` tiles a head.  Heads are walked by a ``fori_loop``
  over 128-lane groups of the slab, so the body compiles once.  (The
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

How far the live-page walks engage in serving is on the dispatch spans:
``dstpu.sched.dispatch.decode`` carries ``kv_pages`` (pages the block's
steps walk) against ``kv_pages_table`` (slots x pages a slot x steps),
``dstpu.sched.dispatch.prefill_chunk`` ``kv_pages`` (pages the chunk's
layers fetch) against ``kv_pages_table`` (pages a slot x layers).

Numerics: decode's page sequence and arithmetic per page are those of
``decode_attention(block_k=page_size)`` over the gathered virtual view,
so the two are BITWISE equal; chunked prefill's are those of
``chunk_prefill_attention(block_k=pages-a-block * page_size)`` over the
gathered view, bitwise again (an int8 pool: ``block_k=page_size``) —
both regression-tested in tests/unit/test_paged_attention.py.  Greedy
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
    _chunk_scratch_bytes,
    _decode_kernel, _finish_chunk, _finish_row, _init_chunk, _init_row,
    _write_stripe)
from deepspeed_tpu.ops.transformer.flash_attention import LSE_LANES, _interpret

# VMEM ring of the decode loop: the page being folded in plus two fetches
# behind it.  Measured on v5e at the serving cells' shapes (PERF.md PR 26):
# two buffers 132 us a layer-step, three 117, four 117.
_DECODE_PAGE_BUFFERS = 3

# Keys one block of the chunk-prefill loop folds at once: the score tile is
# [C, 512] float32 (whole vregs along lanes) and the two matmuls a head get
# an N / a K of 512, where a 64-key page gave them 64.
_CHUNK_BLOCK_KEYS = 512


def _live_pages(lens, pages, b, page, nk):
    """Pages row ``b`` of the decode kernel walks: ``ceil(length /
    page)`` (at most the table's width), and 0 for a DEAD row — one
    whose table points at the reserved trash page (``pages[b, 0] == 0``;
    page 0 is never allocated) or whose length is not positive."""
    n = jnp.clip((lens[b] + page - 1) // page, 0, nk)
    return jnp.where(pages[b, 0] == 0, 0, n)


def _paged_decode_kernel(len_ref, layer_ref, pages_ref, q_ref, k_hbm, v_hbm,
                         *rest, scale, page, nk, kvh, g, d, fused_write):
    """The page-loop driver: one grid step a batch row, the pools whole
    in HBM.  A live row fetches its pages through the table into the
    VMEM ring and folds each into the online-softmax state with the
    monolithic kernel's per-block update; a dead row writes a zero
    output row and does nothing else."""
    kn_ref = vn_ref = ko_ref = vo_ref = None
    rest = list(rest)
    if fused_write:
        kn_ref, vn_ref = rest[:2]
        del rest[:2]
    o_ref = rest.pop(0)
    if fused_write:
        ko_ref, vo_ref = rest[:2]
        del rest[:2]
    m_scr, l_scr, acc_scr, qbd_scr, kbuf, vbuf, sem = rest
    st = _RowState(q_ref, m_scr, l_scr, acc_scr, qbd_scr,
                   kn_ref=kn_ref, vn_ref=vn_ref)
    nbuf = _DECODE_PAGE_BUFFERS
    b = pl.program_id(0)
    li = layer_ref[0]
    length = len_ref[b]
    n_pages = _live_pages(len_ref, pages_ref, b, page, nk)

    def copies(i):
        # virtual page i of this row → ring slot i % nbuf; a wait
        # rebuilds the descriptors its start used
        pg, slot = pages_ref[b, i], i % nbuf
        return [pltpu.make_async_copy(src.at[li, pg], dst.at[slot],
                                      sem.at[j, slot])
                for j, (src, dst) in enumerate([(k_hbm, kbuf),
                                                (v_hbm, vbuf)])]

    def start(i):
        @pl.when(i < n_pages)
        def _():
            for c in copies(i):
                c.start()

    @pl.when(n_pages == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_pages > 0)
    def _live():
        _init_row(st, kvh=kvh, g=g, d=d)
        for i in range(nbuf - 1):
            start(i)

        def fold(i, carry):
            start(i + nbuf - 1)
            for c in copies(i):
                c.wait()
            _block_update(st, i, length, kbuf[i % nbuf], vbuf[i % nbuf],
                          None, None, scale=scale, block_k=page, kvh=kvh,
                          g=g, d=d, window=None)
            return carry

        jax.lax.fori_loop(0, n_pages, fold, None)
        _finish_row(st, o_ref)
        if fused_write:
            # the write row lies in the LAST live page, which the loop
            # left in the ring
            last = (n_pages - 1) % nbuf

            def load8(base):
                rows = pl.dslice(base, 8)
                return kbuf[last, rows], vbuf[last, rows], None, None

            _write_stripe(st, length, page, load8, ko_ref.at[0, 0],
                          vo_ref.at[0, 0], None, None, kvh=kvh, d=d)


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
        # virtual page i*bp + j of this row → rows j*page.. of buffer
        # i % 2; a wait rebuilds the descriptors its start used.  Pages
        # past the reachable ones are not fetched: the tail block's rows
        # there keep what an earlier block left.
        def one(j, carry):
            pg, slot = pages_ref[b, i * bp + j], i % 2
            rows = pl.ds(pl.multiple_of(j * page, page), page)
            for n, (src, dst) in enumerate([(k_hbm, kbuf), (v_hbm, vbuf)]):
                fn(pltpu.make_async_copy(src.at[li, pg], dst.at[slot, rows],
                                         sem.at[n, slot]))
            return carry

        jax.lax.fori_loop(0, jnp.clip(n_pages - i * bp, 0, bp), one, None)

    def fold(masked):
        def body(i, carry):
            each_page(i + 1, lambda cp: cp.start())
            each_page(i, lambda cp: cp.wait())
            slot = i % 2
            if masked:
                # rows no query reaches — the last page's tail and the
                # tail block's unfetched pages — may hold anything, and
                # a probability of 0 against a NaN is a NaN: the score
                # side is masked by the update, the value side here
                @pl.when((i + 1) * bk > limit)
                def _zero_tail():
                    pos = i * bk + jax.lax.broadcasted_iota(
                        jnp.int32, (bk, 1), 0)
                    v = vbuf[slot]
                    vbuf[slot] = jnp.where(pos < limit, v,
                                           jnp.zeros_like(v))
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
    page, its output row is zeros, and (fused write) its stripe lands in
    the trash page.  Live rows cost O(ceil(length / page_size)) pages —
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

    # index maps: (grid indices..., lengths, layer, pages); the row is
    # always the first grid index
    def row(b, *refs):
        return (b, 0, 0)

    def stripe(b, *refs):
        # table-resolved write stripe: virtual write position lens[b]-1
        # lands on pool page pages[b, (lens[b]-1)//page] at in-page row
        # (lens[b]-1) % page; the output block covers only that row's
        # 8-sublane-aligned stripe (index in 8-row units), constant per
        # batch row, so Mosaic flushes 8 rows once after the row's last
        # grid step — same stripe economics as the monolithic fused
        # write.  A dead row's stripe goes to the trash page.
        lens, li, pg = refs[-3:]
        pos = jnp.maximum(lens[b] - 1, 0)
        wpage = jnp.where(_live_pages(lens, pg, b, page, nk) == 0, 0,
                          pg[b, jnp.minimum(pos // page, nk - 1)])
        return (li[0], wpage, (pos % page) // 8, 0)

    if quant:
        # the grid walk: virtual pages past the live region pin to the
        # LAST live page, so its physical index repeats across the dead
        # tail and Mosaic elides the DMA (compute is pl.when-gated off
        # in the body); dead rows get length 0, which gates every page
        lengths = jnp.where(pages_arr[:, 0] == 0, 0, lengths)
        grid = (B, nk)

        def kv(b, ik, lens, li, pg):
            last = jnp.maximum(_live_pages(lens, pg, b, page, nk) - 1, 0)
            return (li[0], pg[b, jnp.minimum(ik, last)], 0, 0)

        kv_spec = pl.BlockSpec((1, 1, page, KVHD), kv)
        sc_spec = pl.BlockSpec((1, 1, page, KVH), kv)
        in_specs = [pl.BlockSpec((1, H, D), row), kv_spec, kv_spec,
                    sc_spec, sc_spec]
        operands = [q, k_pool, v_pool, k_scale, v_scale]
        kernel = functools.partial(
            _paged_grid_decode_body, scale=float(scale), block_k=page,
            nk=nk, kvh=KVH, g=G, d=D, stacked=True, quant=True,
            window=None, mxu_int8=mxu_int8, fused_write=fused_write)
        ring = []
    else:
        grid = (B,)
        pool_spec = pl.BlockSpec(memory_space=pl.ANY)
        in_specs = [pl.BlockSpec((1, H, D), row), pool_spec, pool_spec]
        operands = [q, k_pool, v_pool]
        kernel = functools.partial(
            _paged_decode_kernel, scale=float(scale), page=page, nk=nk,
            kvh=KVH, g=G, d=D, fused_write=fused_write)
        ring = [pltpu.VMEM((_DECODE_PAGE_BUFFERS, page, KVHD), k_pool.dtype),
                pltpu.VMEM((_DECODE_PAGE_BUFFERS, page, KVHD), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, _DECODE_PAGE_BUFFERS))]

    out_specs = [pl.BlockSpec((1, H, D), row)]
    out_shape = [jax.ShapeDtypeStruct((B, H, D), q.dtype)]
    io_aliases = {}
    if fused_write:
        nspec = pl.BlockSpec((1, KVH, D), row)
        in_specs += [nspec, nspec]
        operands += [new_k, new_v]
        out_specs += [pl.BlockSpec((1, 1, 8, KVHD), stripe)] * 2
        out_shape += [jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                      jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)]
        # operand indices INCLUDE the three scalar-prefetch args
        io_aliases = {4: 1, 5: 2}
        if quant:
            out_specs += [pl.BlockSpec((1, 1, 8, KVH), stripe)] * 2
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
                 if mxu_int8 else []) + ring),
        out_shape=out_shape if fused_write else out_shape[0],
        input_output_aliases=io_aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")[:len(grid)],
            # pages are small (<= a monolithic block_k) — the monolithic
            # slab-sized floor is comfortably enough headroom
            vmem_limit_bytes=max(
                96 * 1024 * 1024,
                6 * page * KVHD * q.dtype.itemsize + 16 * 1024 * 1024)),
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
    return (4 * bk * kvhd * kv_itemsize + 6 * c * bk * 4
            + 4 * c * h * d * q_itemsize + _chunk_scratch_bytes(c, h, d)
            + 16 * 1024 * 1024)


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
