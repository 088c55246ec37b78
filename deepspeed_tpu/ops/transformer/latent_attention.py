"""Kernels for latent (MLA) attention with a learned sparse selection
(DeepSeek-V3.2's DSA indexer) and for windowed latent attention — what
``models/latent_attention.py`` calls on the serving path.

Pallas kernels, each findable in a device trace by its own name:

* ``attn.dsa_index`` (:func:`index_scores`) — the indexer's scores of a
  chunk of queries against a slot's cached indexer keys:
  ``I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])`` in float32, the heads
  stacked along the rows of ONE matmul per tile and summed back in groups.
  Key blocks past the chunk's last position are skipped, DMA and body.
* ``attn.mla_chunk_prefill`` / ``attn.mla_window`` (:func:`masked_flash`)
  — flash attention of a chunk's queries over decompressed keys and values
  under an explicit ``[queries, keys]`` int8 mask (the per-query kept set
  of a full layer, the band of a window layer).  The key has two parts —
  a per-head ``nope`` part and ONE ``rope`` part shared by all heads — so
  the shared part is never copied per head.  Tiles in which the mask
  keeps nothing are skipped, DMA and body.  A grid step holds eight
  heads; the running max and sum live lane-replicated (no lane broadcast
  a vreg of the score tile), and a masked score is selected ONCE: the
  running max starts at a floor above the masked value.

* ``attn.mla_decompress`` (:func:`decompress`) — a cached full layer's
  ``c_kv W_kvb``: every head's keys and values of the lane's key blocks up
  to the chunk's last position, written head-major as :func:`masked_flash`
  takes them.  A block past them is neither read, computed nor written
  (no flash tile lies in one); the weights stay in VMEM along the keys.

* ``attn.dsa_topk`` (:func:`kth_largest`) — a chunk's exact per-query
  top-k threshold: the k-th largest score by bisection over the scores'
  bit patterns, 32 compare-and-count passes over a row that stays in VMEM,
  no sort (:func:`kept_mask` makes the mask ``score >= it``).

* ``attn.dsa_lane_index`` (:func:`lane_index_scores`) and
  ``attn.mla_lane_decode`` (:func:`lane_decode`) — a decode step's or a
  verify window's LANE form: one grid step a lane, the pool whole in HBM,
  the lane's pages fetched through its table in blocks of ~512 keys ONCE
  for all the lane's rows and heads — the index scores of its ``W`` rows,
  then the absorbed softmax of ``W x heads`` query rows under each row's
  kept mask, the latent row key and value at once.  A call walks its
  (live lane, block) pairs as ONE pipeline through a two-block ring that
  outlives the grid step: while a lane folds its last block the first
  block of the next live lane is on its way, so the call's first block
  alone is waited for with nothing folding.  Pages past the lane's last
  position, and a dead lane's, are not fetched.

and three parts left to XLA, each under a ``jax.named_scope`` of its name
(the per-row form of a decode step, where the context is many times the
kept set):

* ``attn.dsa_topk`` (:func:`kept_indices`) — ``lax.top_k``'s indices.
* ``attn.dsa_index`` (:func:`index_scores_rows`) — one query a lane
  against its lane's gathered keys.
* ``attn.mla_sparse_decode`` (:func:`sparse_decode`) — attention over the
  KEPT rows only, in the absorbed form: the latent row is key and value at
  once (the value is its first ``rank`` columns), so a row reads ``kept x
  row`` bytes a layer, not ``context x row``.

No VJP: training through latent attention is not implemented.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import _interpret
from deepspeed_tpu.ops.transformer.paged_attention import (_live_pages,
                                                           _zero_value_tail)

NEG = -1e30
# where a flash kernel's running max starts: finite and ABOVE the masked
# score, so exp(NEG - max) is 0 from a row's first tile on and a masked
# weight needs no second select; a row that keeps nothing ends with a sum
# of 0 and returns zeros
FLOOR = -1e29
LANES = 128
# what this file's grid kernels ask of VMEM, flat.  The two lane kernels
# hold 3 MiB of it, and keep the rest ON PURPOSE: asked for what they hold,
# XLA keeps ~100 MB of LongCat's dense FFN weights in flight across each
# ``attn.mla_lane_decode`` call, and the kernel — bound by its page copies'
# latency, at 53% of its bytes — loses more to that traffic than the
# weights gain: the decode block read 137.0 -> 140.0 ms (PERF.md, PR 61)
VMEM_ASK = 64 * 1024 * 1024
# heads of a flash grid step unrolled into one block of code (the rest of
# the step's heads loop over such groups)
HEAD_GROUP = 2
# a chunk's keys are scored, decompressed and attended in blocks of this
# many rows: a lane is whole blocks, and what one kernel leaves out past
# the live ones (``decompress``) no other fetches (``masked_flash``)
KEY_BLOCK = 512


def _block(n, want):
    """The largest block <= ``want`` that divides ``n`` (a toy size takes
    the whole axis)."""
    b = min(want, n)
    while n % b:
        b //= 2
    return max(b, 1)


# --------------------------------------------------------------------- #
# attn.dsa_index
# --------------------------------------------------------------------- #
def _index_kernel(live_ref, q_ref, w_ref, k_ref, o_ref, *, heads, lanes):
    @pl.when(pl.program_id(1) < live_ref[0])
    def _scores():
        s = jax.lax.dot_general(q_ref[...], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        bq = s.shape[0] // heads
        w = w_ref[...]
        for c in range(s.shape[1] // lanes):
            part = jnp.maximum(s[:, c * lanes:(c + 1) * lanes], 0.0) * w
            o_ref[:, c * lanes:(c + 1) * lanes] = jnp.sum(
                part.reshape(bq, heads, lanes), axis=1)

    @pl.when(pl.program_id(1) >= live_ref[0])
    def _dead():
        o_ref[...] = jnp.full(o_ref.shape, NEG, o_ref.dtype)


def index_scores(q, w, k, live_keys, block_q=32, block_k=KEY_BLOCK):
    """``I [C, L]`` float32 from ``q [C, J, D]``, ``w [C, J]`` (float32,
    the score's constant factors folded in) and the cached keys
    ``k [L, D]``.  Keys at or past ``live_keys`` (a traced scalar, rounded
    up to a key block) are not scored: their entries read ``NEG``."""
    C, J, D = q.shape
    L = k.shape[0]
    bq, bk = _block(C, block_q), _block(L, block_k)
    lanes = 128 if bk % 128 == 0 else bk
    live = jnp.reshape(-(-live_keys // bk), (1,)).astype(jnp.int32)
    key_block = lambda i, j, live: (jnp.minimum(j, live[0] - 1), 0)
    return pl.pallas_call(
        functools.partial(_index_kernel, heads=J, lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(C // bq, L // bk),
            in_specs=[pl.BlockSpec((bq * J, D), lambda i, j, live: (i, 0)),
                      pl.BlockSpec((bq * J, lanes),
                                   lambda i, j, live: (i, 0)),
                      pl.BlockSpec((bk, D), key_block)],
            out_specs=pl.BlockSpec((bq, bk), lambda i, j, live: (i, j))),
        out_shape=jax.ShapeDtypeStruct((C, L), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_ASK),
        interpret=_interpret(),
        name="attn.dsa_index",
    )(live, q.reshape(C * J, D),
      jnp.broadcast_to(w.astype(jnp.float32).reshape(C * J, 1),
                       (C * J, lanes)), k)


def index_scores_rows(q, w, k):
    """A decode step's scores ``[N, L]``: ``q [N, J, D]``, ``w [N, J]``,
    each lane's own keys ``k [N, L, D]``."""
    with jax.named_scope("attn.dsa_index"):
        s = jnp.einsum("njd,nld->njl", q, k,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("njl,nj->nl", jnp.maximum(s, 0.0),
                          w.astype(jnp.float32))


def _lane_walk(ctx_ref, layer_ref, pages_ref, pool, buf, sem, slot_ref, fold,
               *, page, bp):
    """This grid step's part of the call's ONE pipeline over its (live
    lane, block of ``bp`` pages) pairs: ``fold(i, slot)`` on each block
    ``i`` of lane ``program_id(0)``, in ``buf[slot]``, while what follows
    it in the call's sequence is on its way into the other buffer — this
    lane's block ``i + 1`` or, behind its LAST block, the first block of
    the next LIVE lane (a scalar look-ahead over the table; a DEAD lane,
    its table at the trash page, is walked by nobody).  The buffer a
    lane's first block lands in is carried from grid step to grid step in
    ``slot_ref`` (SMEM), so only the call's very first block is waited for
    with nothing folding.  A block copies its live pages alone — whole
    pages up to the lane's last position; start and wait count the same
    descriptors —: the other rows of ``buf[slot]`` keep what an earlier
    block, an earlier lane or nobody wrote."""
    n, lanes, width = pl.program_id(0), pages_ref.shape[0], pages_ref.shape[1]

    def live_pages(r):
        return _live_pages(ctx_ref, pages_ref, jnp.minimum(r, lanes - 1),
                           page, width)

    def next_live(r):
        # the first live lane from ``r`` on, ``lanes`` where there is none
        return jax.lax.while_loop(
            lambda r: jnp.logical_and(r < lanes, live_pages(r) == 0),
            lambda r: r + 1, r)

    def each_page(fn, lane, first, count, slot):
        # the copies of pages ``first .. first + count`` of ``lane``'s
        # table into the rows of ``buf[slot]``.  A whole block — every
        # block of a lane but its last — is straight-line code: the
        # counted loop costs each descriptor a branch, more than the
        # pages it leaves out save a lane of many blocks
        def one(j, carry=None):
            fn(pltpu.make_async_copy(
                pool.at[layer_ref[0], pages_ref[lane, first + j]],
                buf.at[slot, pl.ds(pl.multiple_of(j * page, page), page)],
                sem.at[slot]))

        @pl.when(count == bp)
        def _whole():
            for j in range(bp):
                one(j)

        @pl.when(count < bp)
        def _some():
            jax.lax.fori_loop(0, count, one, None)

    def first_block(fn, lane, slot):
        each_page(fn, jnp.minimum(lane, lanes - 1), 0,
                  jnp.where(lane < lanes,
                            jnp.minimum(live_pages(lane), bp), 0), slot)

    def start(cp):
        cp.start()

    def wait(cp):
        cp.wait()

    @pl.when(n == 0)
    def _exposed():
        slot_ref[0] = 0
        first_block(start, next_live(0), 0)

    n_pages = live_pages(n)

    @pl.when(n_pages > 0)
    def _live():
        slot0 = slot_ref[0]
        n_blocks = (n_pages + bp - 1) // bp
        nxt = next_live(n + 1)

        def body(i, carry):
            slot = (slot0 + i) % 2

            @pl.when(i + 1 < n_blocks)
            def _next_block():
                each_page(start, n, (i + 1) * bp,
                          jnp.minimum(n_pages - (i + 1) * bp, bp), 1 - slot)

            @pl.when(i + 1 == n_blocks)
            def _hand_over():
                first_block(start, nxt, 1 - slot)

            each_page(wait, n, i * bp, jnp.minimum(n_pages - i * bp, bp),
                      slot)
            fold(i, slot)
            return carry

        jax.lax.fori_loop(0, n_blocks, body, None)
        slot_ref[0] = (slot0 + n_blocks) % 2


def _lane_index_kernel(ctx_ref, layer_ref, pages_ref, q_ref, w_ref, pool,
                       o_ref, _pool_out, buf, sem, slot_ref, *, page, bp,
                       rows, heads):
    bk = bp * page
    # positions in the lane's live pages: the rows past them are not fetched
    live = (ctx_ref[pl.program_id(0)] + page - 1) // page * page
    o_ref[...] = jnp.full(o_ref.shape, NEG, o_ref.dtype)

    def fold(i, slot):
        s = jax.lax.dot_general(q_ref[0], buf[slot],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        part = jnp.maximum(s, 0.0) * w_ref[0][:, :1]
        scores = jnp.concatenate(
            [jnp.sum(part[r * heads:(r + 1) * heads], axis=0, keepdims=True)
             for r in range(rows)], axis=0)
        pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        o_ref[0, :, pl.ds(pl.multiple_of(i * bk, bk), bk)] = jnp.where(
            pos < live, scores, NEG)

    _lane_walk(ctx_ref, layer_ref, pages_ref, pool, buf, sem, slot_ref, fold,
               page=page, bp=bp)


def _lane_call(kernel, name, ctx, layer, table, operands, pool, out_width,
               scratch, bp):
    """One grid step a lane, the pool whole in HBM, the table and the
    lanes' contexts in SMEM; ``operands [N, ...]`` a lane's block each,
    brought and taken by the grid's own pipeline while the kernel walks
    the call's (live lane, block) pairs through a two-block ring that
    outlives the grid step (:func:`_lane_walk`: the ring, its semaphores
    and the buffer the next lane starts in are the last three scratch
    operands).  Returns ``(out [N, *out_width] float32, pool)`` — the
    pool handed through as an aliased output nothing writes: the next
    cache write then follows the kernel in the program's dataflow, and
    XLA has no earlier value of the pool to keep or recompute beside
    it."""
    N = table.shape[0]
    page = pool.shape[2]
    lane = lambda n, *refs: (n, 0, 0)
    whole = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N,),
            in_specs=[pl.BlockSpec((1,) + t.shape[1:], lane)
                      for t in operands] + [whole],
            out_specs=[pl.BlockSpec((1,) + out_width, lane), whole],
            scratch_shapes=scratch + [
                pltpu.VMEM((2, bp * page, pool.shape[-1]), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((N,) + out_width, jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={3 + len(operands): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_ASK),
        interpret=_interpret(),
        name=name,
    )(ctx.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      table.astype(jnp.int32), *operands, pool)


def lane_pages(table, page, block_keys=512):
    """``(table, pages a key block)`` for the lane kernels: the table
    padded with trash-page entries to whole blocks of ~``block_keys``
    keys."""
    bp = min(max(1, block_keys // page), table.shape[1])
    return jnp.pad(table, ((0, 0), (0, -table.shape[1] % bp))), bp


def lane_index_scores(q, w, pool, layer, table, bp, ctx):
    """The scores ``[N, W, L]`` float32 of ``W`` rows a lane against the
    lane's cached index keys, read THROUGH the table (``table [N, n]``,
    ``n`` a multiple of ``bp``; ``L = n x page``) from ``pool [layers,
    pages, page, D]``, each lane's keys fetched once for its rows: ``q
    [N, W, J, D]``, ``w [N, W, J]`` (the score's constant factors folded
    in).  Pages past ``ctx[n]`` positions, and every page of a dead lane
    (its table at the trash page), are not fetched: ``NEG``."""
    N, W, J, D = q.shape
    page = pool.shape[2]
    L = table.shape[1] * page
    return _lane_call(
        functools.partial(_lane_index_kernel, page=page, bp=bp, rows=W,
                          heads=J),
        "attn.dsa_lane_index", ctx, layer, table,
        [q.reshape(N, W * J, D),
         jnp.broadcast_to(w.astype(jnp.float32).reshape(N, W * J, 1),
                          (N, W * J, 128))],
        pool, (W, L), [], bp)


# --------------------------------------------------------------------- #
# attn.dsa_topk
# --------------------------------------------------------------------- #
_INT_MIN = -2 ** 31


def _ordered(x):
    """float32 -> int32 whose (signed) order is the floats' order."""
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


def _kth_kernel(s_ref, pos_ref, kth_ref, count_ref, *, k):
    key = _ordered(s_ref[...])
    col = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
    visible = col <= pos_ref[:, :1]
    key = jnp.where(visible, key, jnp.int32(_INT_MIN))

    def at_least(cand):
        """Visible keys >= ``cand``, a bit pattern in the order of the
        uint32 ``key ^ 0x80000000`` (an exact float32 count: a row is
        short)."""
        return jnp.sum((visible & (key >= (cand ^ jnp.int32(_INT_MIN))))
                       .astype(jnp.float32), axis=1, keepdims=True)

    def bit(i, kth):
        cand = kth | jnp.left_shift(jnp.int32(1), jnp.int32(31) - i)
        return jnp.where(at_least(cand) >= k, cand, kth)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros((key.shape[0], 1), jnp.int32))
    count_ref[...] = jnp.broadcast_to(at_least(kth).astype(jnp.int32),
                                      count_ref.shape)
    kth_ref[...] = jnp.broadcast_to(kth ^ jnp.int32(_INT_MIN),
                                    kth_ref.shape)


def kth_largest(scores, positions, k, block_q=32):
    """For each query ``t`` the ``k``-th largest of ``scores[t, :
    positions[t] + 1]`` as an ordered int32 (:func:`_ordered`; the
    smallest int32 where fewer than ``k`` keys are visible), and how many
    visible keys are at least that.  Exact, with no sort: a bisection
    over the 32 bits of the scores' patterns, each pass a compare and a
    count over the query's row, which stays in VMEM for all of them."""
    C, L = scores.shape
    bq = _block(C, block_q)
    rows = lambda width: pl.BlockSpec((bq, width), lambda i: (i, 0))
    kth, count = pl.pallas_call(
        functools.partial(_kth_kernel, k=k),
        grid=(C // bq,),
        in_specs=[rows(L), rows(128)],
        out_specs=[rows(128), rows(128)],
        out_shape=[jax.ShapeDtypeStruct((C, 128), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # the row block in, double-buffered, its keys and a pass's
            # compare beside them
            vmem_limit_bytes=min(100 * 1024 * 1024,
                                 6 * bq * L * 4 + 8 * 1024 * 1024)),
        interpret=_interpret(),
        name="attn.dsa_topk",
    )(scores, jnp.broadcast_to(positions.astype(jnp.int32)[:, None],
                               (C, 128)))
    return kth[:, :1], count[:, :1]


def kept_mask(scores, positions, k):
    """``[C, L]`` int8: for query ``t`` the ``k`` keys ``s <= positions[t]``
    of largest score — all of them where fewer are visible.  Exact: the
    k-th largest score comes from :func:`kth_largest` (Pallas,
    ``attn.dsa_topk``) and a key is kept iff its score is at least that.
    Scores that tie AT the k-th value go to the lower positions, as
    ``lax.top_k`` breaks ties — a running count over the ties, taken only
    where a row has any (with float32 sums of 64 products it has none;
    four toy heads whose products are all negative give exact zeros)."""
    kth, count = kth_largest(scores, positions, k)
    with jax.named_scope("attn.dsa_topk"):
        visible = jnp.arange(scores.shape[1])[None, :] <= positions[:, None]
        key = _ordered(scores)
        at_least = visible & (key >= kth)

        def lower_ties_first(_):
            above = visible & (key > kth)
            tie = visible & (key == kth)
            room = k - jnp.sum(above, axis=1, dtype=jnp.int32, keepdims=True)
            return above | (tie & (jnp.cumsum(tie, axis=1,
                                              dtype=jnp.int32) <= room))

        return jax.lax.cond(jnp.any(count > k), lower_ties_first,
                            lambda _: at_least, None).astype(jnp.int8)


def kept_indices(scores, visible, k):
    """A decode step's kept set: ``(idx [N, k] int32, valid [N, k])`` —
    the ``k`` visible positions of largest score (``lax.top_k``: ties to
    the lower index), ``valid`` false past the visible ones."""
    with jax.named_scope("attn.dsa_topk"):
        vals, idx = jax.lax.top_k(jnp.where(visible, scores, NEG), k)
        return idx.astype(jnp.int32), vals > NEG / 2


# --------------------------------------------------------------------- #
# attn.mla_sparse_decode
# --------------------------------------------------------------------- #
def sparse_decode(q_lat, q_rope, rows, valid, rank, scale):
    """Absorbed latent attention of one query a lane over its kept rows.
    ``q_lat [N, H, rank]`` (the query's nope part through the key
    up-projection), ``q_rope [N, H, R]``, ``rows [N, K, >= rank + R]``
    (``[latent | rope key | padding]``), ``valid [N, K]``.  Returns the
    attended latent ``[N, H, rank]`` (the caller applies the value
    up-projection)."""
    with jax.named_scope("attn.mla_sparse_decode"):
        R = q_rope.shape[-1]
        lat, kr = rows[..., :rank], rows[..., rank:rank + R]
        s = jnp.einsum("nhr,nkr->nhk", q_lat, lat,
                       preferred_element_type=jnp.float32) \
            + jnp.einsum("nhd,nkd->nhk", q_rope, kr,
                         preferred_element_type=jnp.float32)
        s = jnp.where(valid[:, None, :], s * scale, NEG)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid[:, None, :], p, 0.0)
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        return jnp.einsum("nhk,nkr->nhr", p.astype(rows.dtype), lat,
                          preferred_element_type=jnp.float32)


def _lane_decode_kernel(ctx_ref, layer_ref, pages_ref, q_ref, keep_ref, pool,
                        o_ref, _pool_out, m_ref, l_ref, acc_ref, buf, sem,
                        slot_ref, *, page, bp, rows, heads, rank, scale):
    bk = bp * page
    ctx = ctx_ref[pl.program_id(0)]
    m_ref[...] = jnp.full(m_ref.shape, NEG, m_ref.dtype)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(i, slot):
        # a row is key AND value: the mask leaves a row past the lane's
        # last position — stale where its page was not fetched — a
        # probability of 0, and 0 against a NaN is a NaN
        _zero_value_tail(buf, slot, i * bk, ctx, page=page, bp=bp)
        keys = buf[slot]
        s = jax.lax.dot_general(q_ref[0], keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        keep = keep_ref[0, :, pl.ds(pl.multiple_of(i * bk, bk), bk)] != 0
        on = jnp.concatenate(
            [jnp.broadcast_to(keep[r:r + 1], (heads, bk))
             for r in range(rows)], axis=0)
        s = jnp.where(on, s * scale, NEG)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(on, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(keys.dtype), keys[:, :rank],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _lane_walk(ctx_ref, layer_ref, pages_ref, pool, buf, sem, slot_ref, fold,
               page=page, bp=bp)
    o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def lane_decode(q, kept, pool, layer, table, bp, ctx, rank, scale):
    """Absorbed latent attention of ``W`` rows a lane over the lane's
    rows under each row's kept mask, the rows read THROUGH the table from
    ``pool [layers, pages, page, width]`` once a lane for all its rows and
    heads — Pallas, ``attn.mla_lane_decode``; nothing is sorted or
    gathered row by row (:func:`sparse_decode` reads the kept rows only,
    which wins where the context is many times the kept set).  ``q [N,
    W, H, width]`` — ``[the nope part through the key up-projection |
    rope part | zeros]``, laid out as a pool row, so a score is ONE
    product —, ``kept [N, W, L]`` (nonzero: attended; ``L = n x page``,
    ``table [N, n]``, ``n`` a multiple of ``bp``), ``ctx [N]`` the
    positions a lane has (pages past them, and a dead lane's, are not
    fetched).  Returns ``(the attended latent [N, W, H, rank] float32 —
    zeros for a row that keeps nothing —, pool)``."""
    N, W, H, width = q.shape
    out, pool = _lane_call(
        functools.partial(_lane_decode_kernel, page=pool.shape[2], bp=bp,
                          rows=W, heads=H, rank=rank, scale=scale),
        "attn.mla_lane_decode", ctx, layer, table,
        [q.reshape(N, W * H, width), kept.astype(jnp.int32)], pool,
        (W * H, rank),
        [pltpu.VMEM((W * H, 1), jnp.float32),
         pltpu.VMEM((W * H, 1), jnp.float32),
         pltpu.VMEM((W * H, rank), jnp.float32)], bp)
    return out.reshape(N, W, H, rank), pool


# --------------------------------------------------------------------- #
# attn.mla_decompress
# --------------------------------------------------------------------- #
def _decompress_kernel(live_ref, lat_ref, w_ref, k_ref, v_ref):
    @pl.when(pl.program_id(1) < live_ref[0])
    def _block():
        lat = lat_ref[...]
        heads, nope = k_ref.shape[0], k_ref.shape[2]
        per = w_ref.shape[1] // heads
        for h in range(heads):
            kv = jnp.dot(lat, w_ref[:, h * per:(h + 1) * per],
                         preferred_element_type=jnp.float32)
            k_ref[h] = kv[:, :nope].astype(k_ref.dtype)
            v_ref[h] = kv[:, nope:].astype(v_ref.dtype)


def decompress(rows, w, heads, nope, live_keys, block_k=KEY_BLOCK,
               block_h=8):
    """Every head's keys and values of a lane's LIVE rows: ``(k_nope [H,
    L, nope], v [H, L, Dv])`` — head-major, as :func:`masked_flash` takes
    them — from the cached ``rows [L, >= rank]`` (the latent ``c_kv`` its
    first ``rank`` columns: on the chip whole 128-lane tiles, or the row)
    and the up-projection ``w [rank, H x (nope + Dv)]`` in its parameter's
    own layout.  Key blocks at or past ``live_keys`` (a traced scalar,
    rounded up to a key block) are neither read nor computed NOR WRITTEN:
    what the result holds there is not defined.  A head block's weights
    stay in VMEM along the key axis."""
    L, (rank, width) = rows.shape[0], w.shape
    per = width // heads
    bk, bh = _block(L, block_k), _block(heads, block_h)
    live = jnp.reshape(-(-live_keys // bk), (1,)).astype(jnp.int32)
    # a dead step names the last live block on both sides: a block already
    # fetched, and an output block that has not changed — no DMA either way
    key_block = lambda h, j, live: (jnp.minimum(j, live[0] - 1), 0)
    out_block = lambda h, j, live: (h, jnp.minimum(j, live[0] - 1), 0)
    return pl.pallas_call(
        _decompress_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(heads // bh, L // bk),
            in_specs=[pl.BlockSpec((bk, rank), key_block),
                      pl.BlockSpec((rank, bh * per),
                                   lambda h, j, live: (0, h))],
            out_specs=[pl.BlockSpec((bh, bk, nope), out_block),
                       pl.BlockSpec((bh, bk, per - nope), out_block)]),
        out_shape=[jax.ShapeDtypeStruct((heads, L, nope), rows.dtype),
                   jax.ShapeDtypeStruct((heads, L, per - nope), rows.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_ASK),
        interpret=_interpret(),
        name="attn.mla_decompress",
    )(live, rows, w)


# --------------------------------------------------------------------- #
# attn.mla_chunk_prefill / attn.mla_window
# --------------------------------------------------------------------- #
def _across(x, n):
    """``x [rows, LANES]``, every lane its row's value, as ``[rows, n]``:
    whole vregs side by side where ``n`` is whole lane tiles — no lane
    broadcast a vreg, which a ``[rows, 1]`` column costs."""
    if n % LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return x if n == LANES else jnp.tile(x, (1, n // LANES))


def _flash_kernel(live_ref, fetch_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
                  mask_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, group):
    j = pl.program_id(2)
    tile = pl.program_id(1) * pl.num_programs(2) + j
    heads, dv = o_ref.shape[0], o_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, FLOOR, m_ref.dtype)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live_ref[tile] > 0)
    def _tile():
        keep = mask_ref[...] != 0
        contract = (((1,), (1,)), ((), ()))
        kr = kr_ref[...]

        def head(h):
            s = jax.lax.dot_general(qn_ref[h], kn_ref[h], contract,
                                    preferred_element_type=jnp.float32) \
                + jax.lax.dot_general(qr_ref[h], kr, contract,
                                      preferred_element_type=jnp.float32)
            # ONE select: the running max never falls under FLOOR, so a
            # masked score's exp(NEG - m) is 0 whatever its row has kept
            s = jnp.where(keep, s * scale, NEG)
            m_old = m_ref[h]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _across(m_new, s.shape[1]))
            alpha = jnp.exp(m_old - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = _across(alpha, dv) * acc_ref[h] + jnp.dot(
                p.astype(v_ref.dtype), v_ref[h],
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

        # ``group`` heads are one block of code — one head's matmuls fill
        # the MXU while its neighbour's softmax walks the VPU — and the
        # groups a loop: the compile time is the group's, not the block's
        def heads_of(g, carry):
            for u in range(group):
                head(g * group + u)
            return carry

        if heads == group:
            heads_of(0, None)
        else:
            jax.lax.fori_loop(0, heads // group, heads_of, None)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        for h in range(heads):
            o_ref[h] = (acc_ref[h] / jnp.maximum(_across(l_ref[h], dv), 1e-30)
                        ).astype(o_ref.dtype)


def _tile_plan(mask, bq, bk):
    """Which ``[bq, bk]`` tiles of ``mask [C, L]`` keep anything, and for
    each tile the key block to name: its own where it is live, else the
    live one before it in its row of tiles (the first live one ahead of the
    row's first) — a block already fetched, so a dead tile costs no DMA.
    Both flat ``[C / bq * L / bk]`` int32."""
    nq, nk = mask.shape[0] // bq, mask.shape[1] // bk
    live = jnp.any(mask.reshape(nq, bq, nk, bk) != 0, axis=(1, 3))
    ids = jnp.arange(nk, dtype=jnp.int32)[None, :]
    before = jax.lax.cummax(jnp.where(live, ids, -1), axis=1)
    first = jnp.argmax(live, axis=1).astype(jnp.int32)[:, None]
    fetch = jnp.where(before < 0, first, before)
    return live.astype(jnp.int32).reshape(-1), fetch.reshape(-1)


def _flash_vmem_bytes(bh, bq, bk, dn, dr, dv, itemsize):
    """The VMEM a grid step of :func:`masked_flash` needs: its blocks —
    queries, keys, values, mask, output — double-buffered by the pipeline,
    the running max and sum (a 128-lane float32 tile a head each) and the
    accumulator, and a group of heads' score tiles (float32 scores and
    weights, the weights again in the pool's dtype)."""
    blocks = bh * bq * (dn + dr) + bh * bk * (dn + dv) + bk * dr \
        + bh * bq * dv
    scratch = bh * bq * (2 * LANES + dv) * 4
    return 2 * (blocks * itemsize + bq * bk) + scratch \
        + HEAD_GROUP * bq * bk * (4 + 4 + itemsize)


def masked_flash(q_nope, q_rope, k_nope, k_rope, v, mask, scale, name,
                 block_q=512, block_k=KEY_BLOCK, block_h=8):
    """``out [H, C, Dv]``: softmax over the keys ``mask [C, L]`` keeps of
    ``(q_nope . k_nope + q_rope . k_rope) * scale``, times ``v``.
    ``q_nope [H, C, Dn]``, ``q_rope [H, C, Dr]``, ``k_nope [H, L, Dn]``,
    ``k_rope [L, Dr]`` (one for all heads), ``v [H, L, Dv]``.  A tile of
    ``block_q`` queries by ``block_k`` keys in which the mask keeps nothing
    is skipped, DMA and body — the keys past a chunk's last position, the
    chunk's own upper triangle, everything off a window layer's band.  A
    query whose mask keeps nothing gets zeros.  A grid step holds
    ``block_h`` heads (one mask tile unpacked for all of them) and walks
    them in groups of :data:`HEAD_GROUP`; the running max and sum live
    lane-replicated, ``[.., LANES]`` wide."""
    H, C, Dn = q_nope.shape
    L, Dr = k_rope.shape
    Dv = v.shape[-1]
    bq, bk, bh = _block(C, block_q), _block(L, block_k), _block(H, block_h)
    nk = L // bk
    live, fetch = _tile_plan(mask, bq, bk)
    kb = lambda i, j, fetch: fetch[i * nk + j]
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale,
                          group=_block(bh, HEAD_GROUP)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(H // bh, C // bq, nk),
            in_specs=[
                pl.BlockSpec((bh, bq, Dn), lambda h, i, j, lv, f: (h, i, 0)),
                pl.BlockSpec((bh, bq, Dr), lambda h, i, j, lv, f: (h, i, 0)),
                pl.BlockSpec((bh, bk, Dn),
                             lambda h, i, j, lv, f: (h, kb(i, j, f), 0)),
                pl.BlockSpec((bk, Dr),
                             lambda h, i, j, lv, f: (kb(i, j, f), 0)),
                pl.BlockSpec((bh, bk, Dv),
                             lambda h, i, j, lv, f: (h, kb(i, j, f), 0)),
                pl.BlockSpec((bq, bk),
                             lambda h, i, j, lv, f: (i, kb(i, j, f)))],
            out_specs=pl.BlockSpec((bh, bq, Dv),
                                   lambda h, i, j, lv, f: (h, i, 0)),
            scratch_shapes=[pltpu.VMEM((bh, bq, LANES), jnp.float32),
                            pltpu.VMEM((bh, bq, LANES), jnp.float32),
                            pltpu.VMEM((bh, bq, Dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((H, C, Dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_ASK),
        interpret=_interpret(),
        name=name,
    )(live, fetch, q_nope, q_rope, k_nope, k_rope, v, mask)
