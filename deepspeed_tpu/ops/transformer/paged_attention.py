"""Pallas paged attention — decode & chunked prefill over block-table pools.

TPU-native analog of vLLM's PagedAttention kernel: the KV cache is a shared
page pool ``[L, num_pages, page_size, KVH*D]`` and each batch row owns a
block table ``pages[b, virtual_page] -> physical_page``.  Before this
kernel, the paged serving path materialized a per-layer virtual view with
``take_along_axis`` (``models/transformer._paged_gather``) and ran dense
attention over it — one full gathered cache copy per layer per step, which
is the BENCH_r04 bs128 decode cliff (8,673 → 1,193 tok/s/chip).

Design: the monolithic decode/chunk kernels in ``decode_attention.py`` are
already split-K online-softmax kernels whose grid walks KV blocks of one
batch row in order, with the block location resolved by a BlockSpec index
map from scalar-prefetch operands.  A paged cache is the SAME computation
with a different address map: virtual page ``ik`` of row ``b`` lives at
pool page ``pages[b, ik]``.  So this module reuses the kernel BODIES
(``_decode_kernel`` / ``_chunk_prefill_kernel``) unchanged — online
softmax with cross-page max/sum merge, block-diagonal Q, int8-KV dequant
fused onto the score/probability tiles, fused aliased cache write — and
only swaps the index maps:

* ``block_k = page_size`` and the grid's KV dimension walks VIRTUAL pages
  in order, so the kernels' virtual position math (``pos = ik*block_k +
  iota``, length masks, write row ``(length-1) % block_k``) transfers
  verbatim.
* The page table rides as a THIRD scalar-prefetch operand; input index
  maps resolve ``(layer, pages[b, virt], 0, 0)``.  Pages past the live
  region pin to the last live page — Mosaic elides the repeated-index
  DMA, so dead-tail grid steps fetch nothing (split-K cost is
  O(ceil(length/page_size)) pages, not O(table width)).
* The fused decode write targets the pool through the table too: the
  aliased output's 8-row write stripe pins to ``(layer,
  pages[b, (len-1)//page], ((len-1)%page)//8, 0)``.  Dead lanes (length
  0, table redirected to the reserved trash page 0 by the caller) write
  their garbage stripe into the trash page — the paged analog of the
  monolithic "dead lanes write into their own lane" safety argument.

Numerics: with ``block_k = page_size`` the online-softmax block sequence
is identical to ``decode_attention(block_k=page_size)`` over the gathered
virtual view, so the two are BITWISE equal (regression-tested in
tests/unit/test_paged_attention.py); greedy serving outputs stay bitwise
equal to the monolithic engine as before.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.decode_attention import (
    _chunk_prefill_kernel, _decode_kernel)
from deepspeed_tpu.ops.transformer.flash_attention import LSE_LANES, _interpret


def _paged_decode_body(len_ref, layer_ref, pages_ref, *args, **kw):
    # the page table is consumed entirely by the BlockSpec index maps;
    # the kernel body is the monolithic decode kernel, verbatim
    del pages_ref
    _decode_kernel(len_ref, layer_ref, *args, **kw)


def _paged_chunk_body(start_ref, layer_ref, pages_ref, *args, **kw):
    del pages_ref
    _chunk_prefill_kernel(start_ref, layer_ref, *args, **kw)


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
    page ``pos // page_size`` → physical pool page; dead/unmapped rows
    must point at the reserved trash page 0).  ``lengths``: [B] int32 —
    valid virtual positions INCLUDING this step's token.  ``layer``: the
    (traced) layer index into the stacked pools.  Returns [B, H, D].

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

    def _live_page(ik, lens, b):
        # pin virtual pages past the live region to the LAST live page:
        # its physical index then repeats across the dead tail and Mosaic
        # elides the DMA (compute is pl.when-gated off in the body)
        last = jnp.maximum((lens[b] + page - 1) // page - 1, 0)
        return jnp.minimum(ik, last)

    kv_spec = pl.BlockSpec(
        (1, 1, page, KVHD),
        lambda b, ik, lens, li, pg: (li[0], pg[b, _live_page(ik, lens, b)],
                                     0, 0))
    sc_spec = pl.BlockSpec(
        (1, 1, page, KVH),
        lambda b, ik, lens, li, pg: (li[0], pg[b, _live_page(ik, lens, b)],
                                     0, 0))

    in_specs = [
        pl.BlockSpec((1, H, D), lambda b, ik, lens, li, pg: (b, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    operands = [q, k_pool, v_pool]
    if quant:
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale, v_scale]

    out_specs = [pl.BlockSpec((1, H, D),
                              lambda b, ik, lens, li, pg: (b, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, H, D), q.dtype)]
    io_aliases = {}
    if fused_write:
        # table-resolved write stripe: virtual write position lens[b]-1
        # lands on pool page pages[b, (lens[b]-1)//page] at in-page row
        # (lens[b]-1) % page; the output block covers only that row's
        # 8-sublane-aligned stripe (index in 8-row units), constant per
        # batch row, so Mosaic flushes 8 rows once after the final grid
        # step — same stripe economics as the monolithic fused write
        def _wpage(lens, pg, b):
            return pg[b, jnp.maximum(lens[b] - 1, 0) // page]

        def _wstripe(lens, b):
            return (jnp.maximum(lens[b] - 1, 0) % page) // 8

        kvo_spec = pl.BlockSpec(
            (1, 1, 8, KVHD),
            lambda b, ik, lens, li, pg: (li[0], _wpage(lens, pg, b),
                                         _wstripe(lens, b), 0))
        sco_spec = pl.BlockSpec(
            (1, 1, 8, KVH),
            lambda b, ik, lens, li, pg: (li[0], _wpage(lens, pg, b),
                                         _wstripe(lens, b), 0))
        nspec = pl.BlockSpec((1, KVH, D),
                             lambda b, ik, lens, li, pg: (b, 0, 0))
        in_specs += [nspec, nspec]
        operands += [new_k, new_v]
        out_specs += [kvo_spec, kvo_spec]
        out_shape += [jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                      jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)]
        # operand indices INCLUDE the three scalar-prefetch args
        io_aliases = {4: 1, 5: 2}
        if quant:
            out_specs += [sco_spec, sco_spec]
            out_shape += [jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
                          jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype)]
            io_aliases = {4: 1, 5: 2, 6: 3, 7: 4}

    res = pl.pallas_call(
        functools.partial(_paged_decode_body, scale=float(scale),
                          block_k=page, nk=nk, kvh=KVH, g=G, d=D,
                          stacked=True, quant=quant, window=None,
                          mxu_int8=mxu_int8, fused_write=fused_write),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
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
            # pages are small (<= a monolithic block_k) — the monolithic
            # slab-sized floor is comfortably enough headroom
            vmem_limit_bytes=max(
                96 * 1024 * 1024,
                6 * page * KVHD * q.dtype.itemsize + 16 * 1024 * 1024)),
        interpret=_interpret(),
        name="attn.paged_decode",
    )(jnp.asarray(lengths, jnp.int32), layer_arr, pages_arr, *operands)
    return res


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
    B, C, H, D = q.shape
    page, KVHD, KVH = _pool_dims(q, k_pool)
    G = H // KVH
    if layer is None:
        raise ValueError("layer-stacked pools require layer=")
    quant = k_scale is not None
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    nk = pages.shape[1]
    layer_arr = jnp.asarray([layer], jnp.int32)
    pages_arr = jnp.asarray(pages, jnp.int32)

    def _live_page(ik, st, b):
        # the chunk's furthest reachable virtual position is st[b]+C-1
        last = jnp.maximum((st[b] + C + page - 1) // page - 1, 0)
        return jnp.minimum(ik, last)

    kv_spec = pl.BlockSpec(
        (1, 1, page, KVHD),
        lambda b, ik, st, li, pg: (li[0], pg[b, _live_page(ik, st, b)],
                                   0, 0))
    sc_spec = pl.BlockSpec(
        (1, 1, page, KVH),
        lambda b, ik, st, li, pg: (li[0], pg[b, _live_page(ik, st, b)],
                                   0, 0))

    in_specs = [
        pl.BlockSpec((1, C, H * D), lambda b, ik, st, li, pg: (b, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    operands = [q.reshape(B, C, H * D), k_pool, v_pool]
    if quant:
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale, v_scale]

    out = pl.pallas_call(
        functools.partial(_paged_chunk_body, scale=float(scale),
                          block_k=page, nk=nk, c=C, kvh=KVH, g=G, d=D,
                          stacked=True, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, C, H * D),
                                   lambda b, ik, st, li, pg: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((C, H), jnp.float32),         # running max
                pltpu.VMEM((C, H), jnp.float32),         # running sum
                pltpu.VMEM((C, H * D), jnp.float32),     # per-head acc
            ]),
        out_shape=jax.ShapeDtypeStruct((B, C, H * D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(
                64 * 1024 * 1024,
                4 * page * KVHD * q.dtype.itemsize
                + 2 * C * H * D * 4 + 16 * 1024 * 1024)),
        interpret=_interpret(),
        name="attn.paged_chunk_prefill",
    )(jnp.asarray(starts, jnp.int32), layer_arr, pages_arr, *operands)
    return out.reshape(B, C, H, D)
