"""Pallas EVA attention — ONE softmax over two key sets of different kinds.

EVA (Zheng et al., ICLR 2023; ``models/evabyte.py``) gives a query at
position ``p`` of window ``w = p // W`` two sets of keys:

* the RING — the K/V rows of its own window, ring row ``t % W`` for
  position ``t``, live while ``t % W <= p % W`` (block-causal: the rows of
  the window before still lie in the ring's upper part and are masked by
  position, never by "what was written");
* the SUMMARIES — one pooled K/V row a chunk of ``c`` positions, row
  ``t // c`` of the slot's lane pages, live for chunks of EARLIER windows
  only: rows ``< (W // c) * w``, the same bound for every query of a
  window.

Both are paged pools of the paged kernels' layout (``[L, pages, page,
H*D]``) behind two block tables (the slot's ring pages; its lane pages),
and both kernels here are the paged kernels' page loops
(``paged_attention.py``) run over the two block sequences one after the
other into ONE online-softmax state — the per-block updates are the
monolithic kernels' own (``decode_attention._block_update`` /
``_chunk_block_update``), so a ring block is folded exactly as a page of
``attn.paged_decode`` / ``attn.paged_chunk_prefill`` is, and a summary block
is the same arithmetic under a prefix mask.

**Decode** (:func:`eva_decode_attention`, ``attn.eva_decode``): ``grid=(B,)``,
one step a lane; the lane's ``2 w`` summary pages (``W // c`` rows a
window) and then its ring pages ``0 .. (p % W) // page`` are fetched
through the tables into one three-deep VMEM ring of pages, so the second
sequence's first fetches fly while the first's last pages are folded in.
The step's own K/V row is written by the kernel (the fused 8-row stripe of
``attn.paged_decode``) at ring row ``p % W``.  A DEAD lane (table row on the
trash page) walks nothing.

**Chunk** (:func:`eva_chunk_attention`, ``attn.eva_chunk``): the chunk's
``C`` queries in blocks of ``min(C, 512)`` along the grid — a chunk may be a
whole window (2,048), whose scores would not fit VMEM at once —; a query
block at ring rows ``r0 ..`` folds the summary rows below its window's
bound in 512-key blocks (prefix mask) and then ring rows ``0 .. r0 + 511``
(unmasked under the diagonal, causal on it), double-buffered like
``attn.paged_chunk_prefill``.  The chunk's own rows are in the ring already
(the page-run write of ``models/transformer._paged_write``).
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.decode_attention import (
    _ChunkState, _RowState, _block_update, _chunk_block_update,
    _chunk_scratch, _decode_vmem_bytes, _finish_chunk, _finish_row,
    _init_chunk, _init_row, _write_stripe)
from deepspeed_tpu.ops.transformer.flash_attention import LSE_LANES, _interpret
from deepspeed_tpu.ops.transformer.paged_attention import (
    _CHUNK_BLOCK_KEYS, _DECODE_PAGE_BUFFERS, _chunk_loop_vmem_bytes)

# queries one grid step of the chunk kernel holds (its [Cq, 512] score
# tiles, q / output blocks and accumulator are what VMEM bounds)
_CHUNK_QUERIES = 512


def _eva_decode_kernel(pos_ref, layer_ref, ring_ref, lane_ref, q_ref,
                       kr_hbm, vr_hbm, ks_hbm, vs_hbm, kn_ref, vn_ref,
                       o_ref, ko_ref, vo_ref, m_scr, l_scr, acc_scr, qbd_scr,
                       kbuf, vbuf, sem, *, scale, page, window, per_window,
                       n_lane, h, d):
    """One lane: its visible summary pages, then its live ring pages,
    through one VMEM ring of pages into one online-softmax state."""
    st = _RowState(q_ref, m_scr, l_scr, acc_scr, qbd_scr,
                   kn_ref=kn_ref, vn_ref=vn_ref)
    plain = st._replace(kn_ref=None, vn_ref=None)   # summaries: no new row
    nbuf = _DECODE_PAGE_BUFFERS
    b = pl.program_id(0)
    li = layer_ref[0]
    p = pos_ref[b]
    r = p % window
    sum_rows = jnp.minimum((p // window) * per_window, n_lane * page)
    n_sum = (sum_rows + page - 1) // page
    n_all = n_sum + r // page + 1

    def copies(i, fn):
        # block i of the lane's sequence -> ring slot i % nbuf; a wait
        # rebuilds a descriptor of its start's shape
        slot = i % nbuf

        def fetch(k_src, v_src, pg):
            for j, (src, dst) in enumerate([(k_src, kbuf), (v_src, vbuf)]):
                fn(pltpu.make_async_copy(src.at[li, pg], dst.at[slot],
                                         sem.at[j, slot]))

        @pl.when(i < n_sum)
        def _():
            fetch(ks_hbm, vs_hbm, lane_ref[b, jnp.minimum(i, n_lane - 1)])

        @pl.when(jnp.logical_and(i >= n_sum, i < n_all))
        def _():
            fetch(kr_hbm, vr_hbm, ring_ref[b, jnp.maximum(i - n_sum, 0)])

    @pl.when(ring_ref[b, 0] == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(ring_ref[b, 0] != 0)
    def _live():
        _init_row(st, kvh=h, g=1, d=d)
        for i in range(nbuf - 1):
            copies(i, lambda c: c.start())

        def fold(state, first, length):
            def body(i, carry):
                copies(i + nbuf - 1, lambda c: c.start())
                copies(i, lambda c: c.wait())
                _block_update(state, i - first, length, kbuf[i % nbuf],
                              vbuf[i % nbuf], None, None, scale=scale,
                              block_k=page, kvh=h, g=1, d=d, window=None)
                return carry
            return body

        jax.lax.fori_loop(0, n_sum, fold(plain, 0, sum_rows), None)
        jax.lax.fori_loop(n_sum, n_all, fold(st, n_sum, r + 1), None)
        _finish_row(st, o_ref)
        # the write row lies in the LAST ring page, which the loop left in
        # the ring of buffers
        last = (n_all - 1) % nbuf

        def load8(base):
            rows = pl.dslice(base, 8)
            return kbuf[last, rows], vbuf[last, rows], None, None

        _write_stripe(st, r + 1, page, load8, ko_ref.at[0, 0],
                      vo_ref.at[0, 0], None, None, kvh=h, d=d)


def eva_decode_attention(q, k_ring, v_ring, k_sum, v_sum, positions,
                         ring_pages, lane_pages, *, layer, window,
                         chunk_size, new_k, new_v, scale=None):
    """One query a lane over its ring and its visible summaries, and the
    lane's new K/V row written at ring row ``positions[b] % window``.

    q ``[B, H, D]``; ``k_ring`` / ``v_ring [L, ring pool pages, page,
    H*D]`` behind ``ring_pages [B, window // page]``; ``k_sum`` / ``v_sum
    [L, pages, page, H*D]`` behind ``lane_pages [B, n]`` (summary row
    ``j`` on lane page ``j // page``); ``positions [B]`` the lanes'
    positions; ``new_k`` / ``new_v [B, H, D]``.  Returns ``(out [B, H, D],
    k_ring, v_ring)`` — the ring pools aliased through.  A lane whose ring
    table points at the trash page is dead: zero output, its stripe to the
    trash page."""
    B, H, D = q.shape
    page, HD = k_ring.shape[-2], k_ring.shape[-1]
    if H * D != HD or k_sum.shape[-2:] != (page, HD):
        raise ValueError("the ring and summary pools hold [page, H*D] pages "
                         f"of one size; got {k_ring.shape}, {k_sum.shape}")
    if window % page or page % 8 or window % chunk_size:
        raise ValueError(f"window {window} must be whole pages of {page} "
                         f"rows (a multiple of 8) and whole chunks of "
                         f"{chunk_size}")
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    n_ring, n_lane = window // page, lane_pages.shape[1]
    positions = jnp.asarray(positions, jnp.int32)
    ring_pages = jnp.asarray(ring_pages, jnp.int32)

    def row(b, *refs):
        return (b, 0, 0)

    def stripe(b, pos, li, ring, lane):
        r = pos[b] % window
        pg = jnp.where(ring[b, 0] == 0, 0,
                       ring[b, jnp.minimum(r // page, n_ring - 1)])
        return (li[0], pg, (r % page) // 8, 0)

    pool = pl.BlockSpec(memory_space=pl.ANY)
    vec = pl.BlockSpec((1, H, D), row)
    out, k_ring, v_ring = pl.pallas_call(
        functools.partial(
            _eva_decode_kernel, scale=float(scale), page=page, window=window,
            per_window=window // chunk_size, n_lane=n_lane, h=H, d=D),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[vec, pool, pool, pool, pool, vec, vec],
            out_specs=[vec, pl.BlockSpec((1, 1, 8, HD), stripe),
                       pl.BlockSpec((1, 1, 8, HD), stripe)],
            scratch_shapes=[
                pltpu.VMEM((H, LSE_LANES), jnp.float32),
                pltpu.VMEM((H, LSE_LANES), jnp.float32),
                pltpu.VMEM((H, D), jnp.float32),
                pltpu.VMEM((H, HD), q.dtype),
                pltpu.VMEM((_DECODE_PAGE_BUFFERS, page, HD), k_ring.dtype),
                pltpu.VMEM((_DECODE_PAGE_BUFFERS, page, HD), v_ring.dtype),
                pltpu.SemaphoreType.DMA((2, _DECODE_PAGE_BUFFERS))]),
        out_shape=[jax.ShapeDtypeStruct((B, H, D), q.dtype),
                   jax.ShapeDtypeStruct(k_ring.shape, k_ring.dtype),
                   jax.ShapeDtypeStruct(v_ring.shape, v_ring.dtype)],
        # operand indices INCLUDE the four scalar-prefetch args
        input_output_aliases={5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_decode_vmem_bytes(
                1, H, D, page, HD, k_ring.dtype, q.dtype,
                buffers=_DECODE_PAGE_BUFFERS)),
        interpret=_interpret(),
        name="attn.eva_decode",
    )(positions, jnp.asarray([layer], jnp.int32), ring_pages,
      jnp.asarray(lane_pages, jnp.int32), q, k_ring, v_ring, k_sum, v_sum,
      new_k, new_v)
    return out, k_ring, v_ring


def _eva_chunk_kernel(start_ref, layer_ref, ring_ref, lane_ref, q_ref,
                      kr_hbm, vr_hbm, ks_hbm, vs_hbm, o_ref, m_scr, l_scr,
                      acc_scr, kbuf, vbuf, sem, *, scale, page, window,
                      per_window, n_lane, bp, cq, h, d):
    """One block of ``cq`` queries at ring rows ``r0 ..``: the summary rows
    under its window's bound, then ring rows ``0 .. r0 + cq - 1``, ``bp``
    pages a block, double-buffered."""
    st = _ChunkState(q_ref, m_scr, l_scr, acc_scr)
    li = layer_ref[0]
    start = start_ref[0] + pl.program_id(0) * cq
    r0 = start % window
    bk = bp * page
    n_ring = window // page
    sum_rows = jnp.minimum((start // window) * per_window, n_lane * page)
    sum_pages = (sum_rows + page - 1) // page
    n_sum = (sum_pages + bp - 1) // bp               # blocks
    ring_pages = jnp.minimum((r0 + cq + page - 1) // page, n_ring)
    n_loc = (ring_pages + bp - 1) // bp
    # ring blocks whose every row is <= r0 need no causal mask
    n_under = jnp.minimum((r0 + 1) // bk, n_loc)

    def each_page(i, fn):
        # page j of block i -> rows j*page.. of buffer i % 2; pages past
        # the sequence's last are not fetched
        slot = i % 2

        def fetch(k_src, v_src, table, n_tab, first, count):
            def one(j, carry):
                pg = table[0, jnp.minimum(first + j, n_tab - 1)]
                rows = pl.ds(pl.multiple_of(j * page, page), page)
                for n, (src, dst) in enumerate([(k_src, kbuf),
                                                (v_src, vbuf)]):
                    fn(pltpu.make_async_copy(src.at[li, pg],
                                             dst.at[slot, rows],
                                             sem.at[n, slot]))
                return carry
            jax.lax.fori_loop(0, jnp.clip(count, 0, bp), one, None)

        @pl.when(i < n_sum)
        def _():
            fetch(ks_hbm, vs_hbm, lane_ref, n_lane, i * bp,
                  sum_pages - i * bp)

        @pl.when(jnp.logical_and(i >= n_sum, i < n_sum + n_loc))
        def _():
            fetch(kr_hbm, vr_hbm, ring_ref, n_ring, (i - n_sum) * bp,
                  ring_pages - (i - n_sum) * bp)

    def fold(first, bound, limit, **mask):
        def body(i, carry):
            each_page(i + 1, lambda cp: cp.start())
            each_page(i, lambda cp: cp.wait())
            slot, ik = i % 2, i - first
            if mask.get("masked", True):
                # rows no query reaches — past the bound, or pages the
                # tail block did not fetch — may hold anything, and a
                # probability of 0 against a NaN is a NaN
                @pl.when((ik + 1) * bk > limit)
                def _zero_tail():
                    pos = ik * bk + jax.lax.broadcasted_iota(
                        jnp.int32, (bk, 1), 0)
                    v = vbuf[slot]
                    vbuf[slot] = jnp.where(pos < limit, v, jnp.zeros_like(v))
            _chunk_block_update(st, ik, bound, kbuf.at[slot], vbuf.at[slot],
                                None, None, scale=scale, block_k=bk, c=cq,
                                kvh=h, g=1, d=d, **mask)
            return carry
        return body

    _init_chunk(st)
    each_page(0, lambda cp: cp.start())
    jax.lax.fori_loop(0, n_sum,
                      fold(0, sum_rows, sum_rows, prefix=True), None)
    jax.lax.fori_loop(n_sum, n_sum + n_under,
                      fold(n_sum, r0, r0 + cq, masked=False), None)
    jax.lax.fori_loop(n_sum + n_under, n_sum + n_loc,
                      fold(n_sum, r0, r0 + cq), None)
    _finish_chunk(st, o_ref, heads=h, d=d)


def eva_chunk_attention(q, k_ring, v_ring, k_sum, v_sum, start, ring_pages,
                        lane_pages, *, layer, window, chunk_size, scale=None):
    """A chunk of ``C`` queries of ONE slot at positions ``start .. start +
    C - 1`` (inside one window: ``C`` divides ``window`` and ``start`` is a
    multiple of ``C``), whose K/V rows are in the ring already, over the
    ring and the window's visible summaries.  q ``[1, C, H, D]``; pools as
    :func:`eva_decode_attention`; ``ring_pages [1, window // page]``,
    ``lane_pages [1, n]``.  Returns ``[1, C, H, D]``."""
    _, C, H, D = q.shape
    page = k_ring.shape[-2]
    if window % C or window % page or C % min(C, _CHUNK_QUERIES):
        raise ValueError(f"a chunk of {C} must divide the window {window} "
                         f"(whole pages of {page}) and be whole blocks of "
                         f"{_CHUNK_QUERIES} queries")
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    interpret = _interpret()                # a bool: static by value
    return _eva_chunk_call(
        q, k_ring, v_ring, k_sum, v_sum,
        jnp.asarray(start, jnp.int32).reshape(1),
        jnp.asarray([layer], jnp.int32), jnp.asarray(ring_pages, jnp.int32),
        jnp.asarray(lane_pages, jnp.int32), scale=float(scale),
        window=int(window), chunk_size=int(chunk_size), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "window", "chunk_size",
                                             "interpret"))
def _eva_chunk_call(q, k_ring, v_ring, k_sum, v_sum, start, layer_arr,
                    ring_pages, lane_pages, *, scale, window, chunk_size,
                    interpret):
    """The kernel call, jitted with the layer a traced operand: traced and
    lowered to Mosaic once a program, not once a layer
    (``paged_attention._paged_chunk_call``)."""
    _, C, H, D = q.shape
    page, HD = k_ring.shape[-2], k_ring.shape[-1]
    n_lane = lane_pages.shape[1]
    cq = min(C, _CHUNK_QUERIES)
    bp = max(1, min(_CHUNK_BLOCK_KEYS // page, window // page))
    block = pl.BlockSpec((1, cq, HD), lambda i, *refs: (0, i, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(
            _eva_chunk_kernel, scale=scale, page=page, window=window,
            per_window=window // chunk_size, n_lane=n_lane, bp=bp, cq=cq,
            h=H, d=D),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(C // cq,),
            in_specs=[block, pool, pool, pool, pool],
            out_specs=block,
            scratch_shapes=_chunk_scratch(cq, H, D) + [
                pltpu.VMEM((2, bp * page, HD), k_ring.dtype),
                pltpu.VMEM((2, bp * page, HD), v_ring.dtype),
                pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((1, C, HD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_chunk_loop_vmem_bytes(
                cq, H, D, bp * page, HD, k_ring.dtype.itemsize,
                q.dtype.itemsize)),
        interpret=interpret,
        name="attn.eva_chunk",
    )(start, layer_arr, ring_pages, lane_pages, q.reshape(1, C, HD),
      k_ring, v_ring, k_sum, v_sum)
    return out.reshape(1, C, H, D)
