"""Pallas paged-attention kernel tests (``ops/transformer/paged_attention.py``)
and the attention-kernel registry (``ops/transformer/registry.py``).

The kernel contract: paged decode/chunk-prefill over the page pool is
BITWISE equal to the ``take_along_axis`` gather reference — the gathered
virtual view fed to the monolithic kernel at ``block_k = pages-a-block *
page_size`` (an int8 pool: ``block_k = page_size``), which walks the
identical online-softmax block sequence — across page sizes {16, 64,
128}, fp32, bf16 and int8-KV pools, ragged batches with dead rows
between live ones, and NaN-poisoned pages outside the live regions.  (Serving-level mid-stream EOS / slot-churn / greedy-bitwise
coverage rides ``test_serving_paged.py``, which now exercises these
kernels end to end.)  The registry contract: one static dispatch table,
probed identically by the traced programs and the host-side attribution,
reference fallback warns instead of silently re-creating the gather
cliff, and the traced paged decode step stays host-callback-free with
its fused write aliased in the jaxpr.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.decode_attention import (
    chunk_prefill_attention, decode_attention)
from deepspeed_tpu.ops.transformer import paged_attention as paged_mod
from deepspeed_tpu.ops.transformer.paged_attention import (
    _chunk_block_pages, _decode_block_pages, paged_chunk_prefill_attention,
    paged_decode_attention)
from deepspeed_tpu.ops.transformer.registry import (
    MAX_CHUNK_S, kernel_modes, select_kernel)

L, B, H, KVH, D = 2, 3, 4, 2, 8
KVHD = KVH * D
LAYER = 1


def _pool_fixture(page, *, int8=False, seed=0):
    """A small pool + block tables with a dead lane (length 0, table all
    trash page 0) and unaligned live lengths."""
    rng = np.random.RandomState(seed)
    nvirt = 4
    P = 3 * nvirt + 1                       # worst case + trash page 0
    shape = (L, P, page, KVHD)
    if int8:
        k = jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
        v = jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
        ks = jnp.asarray(rng.rand(L, P, page, KVH) * 0.1 + 0.01, jnp.float32)
        vs = jnp.asarray(rng.rand(L, P, page, KVH) * 0.1 + 0.01, jnp.float32)
    else:
        k = jnp.asarray(rng.randn(*shape), jnp.float32)
        v = jnp.asarray(rng.randn(*shape), jnp.float32)
        ks = vs = None
    # non-contiguous, non-monotone physical pages; row 2 is a dead lane
    pages = jnp.asarray([[3, 5, 2, 7], [1, 4, 6, 8], [0, 0, 0, 0]],
                        jnp.int32)
    lengths = jnp.asarray([2 * page + 5, 4 * page, 0], jnp.int32)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    return q, k, v, ks, vs, pages, lengths, nvirt


def _gather(buf, pages, nvirt):
    """The take_along_axis reference view: [B, nvirt*page, last-dim]."""
    return buf[LAYER, pages].reshape(pages.shape[0], nvirt * buf.shape[2],
                                     buf.shape[-1])


def _decode_block_k(pool, nvirt, int8=False):
    """Keys one online-softmax update of paged decode folds: the block
    loop's pages a block (an int8 pool keeps the grid walk, a page a
    step)."""
    page = pool.shape[2]
    if int8:
        return page
    return page * _decode_block_pages(page, nvirt, pool.dtype.itemsize)


@pytest.mark.parametrize("page", [16, 64, 128])
@pytest.mark.parametrize("int8", [False, True], ids=["fp32", "int8"])
def test_paged_decode_bitwise_vs_gather(page, int8):
    """Decode over the pool == decode over the gathered virtual view,
    BITWISE (live rows; the dead lane's output is garbage either way)."""
    q, k, v, ks, vs, pages, lengths, nvirt = _pool_fixture(page, int8=int8)
    ref = decode_attention(
        q, _gather(k, pages, nvirt), _gather(v, pages, nvirt), lengths,
        block_k=_decode_block_k(k, nvirt, int8),
        k_scale=None if ks is None else _gather(ks, pages, nvirt),
        v_scale=None if vs is None else _gather(vs, pages, nvirt))
    out = paged_decode_attention(q, k, v, lengths, pages, layer=LAYER,
                                 k_scale=ks, v_scale=vs)
    np.testing.assert_array_equal(np.asarray(ref[:2]), np.asarray(out[:2]))


def _ragged_fixture(page, variant, *, seed=0):
    """A ragged batch over one pool: lengths 1, ``page``, ``page + 1``
    and the full table, with DEAD rows (trash table) between the live
    ones.  The dead rows carry plausible lengths — a retired serving
    lane keeps counting — so it is the table, not the length, that must
    mark them.  Returns the operands, the live rows and, per live row,
    the pool pages its live region covers."""
    rng = np.random.RandomState(seed)
    nvirt = 4
    lengths = np.asarray([1, 2 * page + 3, page, page + 1, nvirt * page,
                          nvirt * page], np.int32)
    live = np.asarray([0, 2, 3, 5])
    nb = len(lengths)
    P = nb * nvirt + 1
    pages = np.zeros((nb, nvirt), np.int32)
    free = list(rng.permutation(np.arange(1, P)))
    for b in live:                          # whole rows allocated, like a
        pages[b] = [free.pop() for _ in range(nvirt)]   # reserved slot
    shape = (L, P, page, KVHD)
    if variant == "bf16":
        k = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
        v = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
        ks = vs = None
        qdt = jnp.bfloat16
    else:
        k = jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
        v = jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
        ks = jnp.asarray(rng.rand(L, P, page, KVH) * 0.1 + 0.01, jnp.float32)
        vs = jnp.asarray(rng.rand(L, P, page, KVH) * 0.1 + 0.01, jnp.float32)
        qdt = jnp.float32
    q = jnp.asarray(rng.randn(nb, H, D), qdt)
    new_k = jnp.asarray(rng.randn(nb, KVH, D), qdt)
    new_v = jnp.asarray(rng.randn(nb, KVH, D), qdt)
    live_pages = {int(b): pages[b, :-(-int(lengths[b]) // page)]
                  for b in live}
    return (q, k, v, ks, vs, jnp.asarray(pages), jnp.asarray(lengths),
            new_k, new_v, nvirt, live, live_pages)


@pytest.mark.parametrize("variant,fused", [
    ("bf16", False), ("bf16", True), ("int8", False), ("int8", True),
    ("int8_mxu", False)])
def test_paged_decode_ragged_bitwise_vs_gather(variant, fused):
    """Every variant of paged decode, over a ragged batch with dead rows
    between live ones, is BITWISE the monolithic kernel at the same
    ``block_k`` over the gathered view — outputs, and with the fused
    write the row each live slot writes; dead rows return exactly
    zero."""
    page = 16
    (q, k, v, ks, vs, pages, lengths, new_k, new_v, nvirt, live,
     _) = _ragged_fixture(page, variant)
    quant = ks is not None
    kw = dict(int8_matmuls=variant == "int8_mxu")
    g = lambda buf: None if buf is None else _gather(buf, pages, nvirt)
    if fused:
        kw.update(new_k=new_k, new_v=new_v)
    ref = decode_attention(q, g(k), g(v), lengths,
                           block_k=_decode_block_k(k, nvirt, quant),
                           k_scale=g(ks), v_scale=g(vs), **kw)
    out = paged_decode_attention(q, k, v, lengths, pages, layer=LAYER,
                                 k_scale=ks, v_scale=vs, **kw)
    if fused:
        ref, *ref_caches = ref
        out, *pools = out
    np.testing.assert_array_equal(np.asarray(ref, np.float32)[live],
                                  np.asarray(out, np.float32)[live])
    dead = np.setdiff1d(np.arange(q.shape[0]), live)
    assert not np.asarray(out, np.float32)[dead].any()
    if fused:
        pos = np.asarray(lengths) - 1
        phys = np.asarray(pages)[live, pos[live] // page]
        for mono, pool in zip(ref_caches, pools):
            np.testing.assert_array_equal(
                np.asarray(mono, np.float32)[live, pos[live]],
                np.asarray(pool, np.float32)[LAYER, phys, pos[live] % page])
        assert len(pools) == (4 if quant else 2)


@pytest.mark.parametrize("variant", ["bf16", "int8"])
def test_paged_decode_reads_and_writes_live_pages_only(variant):
    """Poison everything a live-page walk must not touch — the trash
    page, the pages no row owns, and the pages a live row owns past its
    length — with NaN (int8 pools: NaN scales under extreme payloads).
    The fused-write decode stays finite, returns exactly zero for dead
    rows and the unpoisoned run's bits for live ones, and changes no
    allocated page except each live row's write row.  (Positions past
    the length INSIDE the last live page are not poisoned: their
    probabilities are zero, and 0 x NaN through the PV matmul is NaN in
    the gather reference too.)"""
    page = 16
    (q, k, v, ks, vs, pages, lengths, new_k, new_v, _, live,
     live_pages) = _ragged_fixture(page, variant, seed=5)
    quant = ks is not None
    keep = np.zeros((k.shape[1],), bool)
    keep[np.concatenate(list(live_pages.values()))] = True
    kw = dict(layer=LAYER, new_k=new_k, new_v=new_v)

    def poison(buf, bad):
        return jnp.where(jnp.asarray(keep)[None, :, None, None], buf,
                         jnp.asarray(bad, buf.dtype))

    if quant:
        pk, pv = poison(k, 127), poison(v, -127)
        pks, pvs = poison(ks, np.nan), poison(vs, np.nan)
    else:
        pk, pv, pks, pvs = poison(k, np.nan), poison(v, np.nan), None, None
    clean = paged_decode_attention(q, k, v, lengths, pages, k_scale=ks,
                                   v_scale=vs, **kw)
    got = paged_decode_attention(q, pk, pv, lengths, pages, k_scale=pks,
                                 v_scale=pvs, **kw)
    out = np.asarray(got[0], np.float32)
    assert np.isfinite(out).all()
    dead = np.setdiff1d(np.arange(q.shape[0]), live)
    assert not out[dead].any()
    np.testing.assert_array_equal(np.asarray(clean[0], np.float32)[live], out[live])
    pos = np.asarray(lengths) - 1
    for before, after in zip((pk, pv, pks, pvs)[:len(got) - 1], got[1:]):
        before = np.array(before, np.float32)
        after = np.array(after, np.float32)
        for b in live:                      # the write row, then mask it
            wp, wr = int(np.asarray(pages)[b, pos[b] // page]), pos[b] % page
            assert np.isfinite(after[LAYER, wp, wr]).all()
            before[LAYER, wp, wr] = after[LAYER, wp, wr] = 0
        # every page but the trash page: same bits (NaN == NaN here)
        np.testing.assert_array_equal(before[:, 1:], after[:, 1:])


# The block loop's cases, at a page of 16 and blocks of TWO pages (the
# rule's keys a block shrunk to 32 for the test): per row its length, 0 a
# DEAD row (trash table, a plausible length all the same).
_PIPELINE_CASES = {
    # 3, 5 and 2 pages: blocks of 2 + 1, 2 + 2 + 1 and 2
    "pages_not_a_multiple_of_the_block": [3 * 16 - 2, 5 * 16, 16 + 1],
    "rows_shorter_than_one_block": [7, 16, 1],
    # the hand-over skips the dead rows between live ones
    "dead_rows_between_live_ones": [40, 0, 0, 70, 0, 5],
    # nothing to hand over to: dead rows end the call
    "last_live_row_then_dead_rows": [0, 33, 80, 0, 0],
    # row 0's last block (pages 2, 3) leaves its NaN tail in buffer 1;
    # row 1's second block lands ONE page there, under that tail
    "nan_tail_of_an_earlier_row": [3 * 16 + 5, 2 * 16 + 9, 16 + 3],
}


@pytest.mark.parametrize("lanes", [512, 2048])
@pytest.mark.parametrize("case", list(_PIPELINE_CASES))
def test_paged_decode_block_pipeline(case, lanes, monkeypatch):
    """The decode kernel as ONE pipeline over (live row, block of pages)
    pairs: whatever the rows' lengths, the dead rows between them and
    what an earlier row left in the block buffers, the outputs are
    BITWISE the monolithic kernel's at ``block_k = pages-a-block * page``
    over the gathered view, fused write or not; dead rows return zeros;
    and after the fused write the pools equal the unfused
    write-then-read's — each live row's write row holds its fresh K/V,
    every other row of every allocated page keeps its bits (dead rows'
    stripes land in the trash page), although a row's successor was
    fetched before its stripe was flushed.  Lanes of 512 are LFM2's
    grouped KV heads (32 query heads over 8), 2048 OPT's."""
    monkeypatch.setattr(paged_mod, "_DECODE_BLOCK_KEYS", 32)
    page, nvirt, d, h = 16, 5, 64, 32
    kvh = lanes // d
    bp = _decode_block_pages(page, nvirt, 2)
    assert bp == 2
    lengths = np.asarray(_PIPELINE_CASES[case], np.int32)
    live = np.flatnonzero(lengths)
    nb = len(lengths)
    rng = np.random.RandomState(len(case) + lanes)
    P = nb * nvirt + 1
    pages = np.zeros((nb, nvirt), np.int32)
    free = list(rng.permutation(np.arange(1, P)))
    for b in live:
        pages[b] = [free.pop() for _ in range(nvirt)]
    lengths = np.where(lengths == 0, 37, lengths)       # dead by the TABLE
    shape = (L, P, page, lanes)
    k = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    v = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    q = jnp.asarray(rng.randn(nb, h, d), jnp.bfloat16)
    new_k = jnp.asarray(rng.randn(nb, kvh, d), jnp.bfloat16)
    new_v = jnp.asarray(rng.randn(nb, kvh, d), jnp.bfloat16)

    # poison what the walk must not read or must not let through: the
    # trash page, every page past a row's live ones and — stricter than
    # the gather reference, which lets a stale NaN of the last page
    # through as 0 x NaN — every position past a live row's length
    bad = np.ones((P, page), bool)
    for b in live:
        n = int(lengths[b])
        for i in range(-(-n // page)):
            bad[pages[b, i]] = np.arange(page) + i * page >= n
    poison = lambda buf: jnp.where(jnp.asarray(bad)[None, :, :, None],
                                   jnp.asarray(np.nan, buf.dtype), buf)
    pk, pv = poison(k), poison(v)

    def view(buf):                          # gathered, padded to whole blocks
        g = _gather(buf, jnp.asarray(pages), nvirt)
        return jnp.pad(g, ((0, 0), (0, -nvirt % bp * page), (0, 0)))

    dead = np.setdiff1d(np.arange(nb), live)
    pos = lengths - 1
    phys, off = pages[live, pos[live] // page], pos[live] % page
    for fused in (False, True):
        kw = dict(new_k=new_k, new_v=new_v) if fused else {}
        ref = decode_attention(q, view(k), view(v), lengths,
                               block_k=bp * page, **kw)
        got = paged_decode_attention(q, pk, pv, lengths, jnp.asarray(pages),
                                     layer=LAYER, **kw)
        if fused:
            ref, got, pools = ref[0], got[0], got[1:]
        out = np.asarray(got, np.float32)
        np.testing.assert_array_equal(np.asarray(ref, np.float32)[live],
                                      out[live])
        assert not out[dead].any()
    for before, fresh, after in zip((pk, pv), (new_k, new_v), pools):
        want = before.at[LAYER, phys, off].set(
            fresh[live].reshape(len(live), lanes))
        np.testing.assert_array_equal(np.asarray(want, np.float32)[:, 1:],
                                      np.asarray(after, np.float32)[:, 1:])


def test_paged_decode_fused_write_pool_contents():
    """The fused aliased write: the step's K/V row lands BITWISE at the
    table-resolved (page, offset), every untouched pool page is bitwise
    untouched (the dead lane's garbage stripe goes to trash page 0), and
    the attend output matches the pre-scattered reference within the
    fused kernel's score-column tolerance (VPU row-sum vs MXU dot —
    the same bound the monolithic fused tests use)."""
    page = 16
    q, k, v, _, _, pages, lengths, nvirt = _pool_fixture(page)
    rng = np.random.RandomState(7)
    new_k = jnp.asarray(rng.randn(B, KVH, D), jnp.float32)
    new_v = jnp.asarray(rng.randn(B, KVH, D), jnp.float32)
    # reference: pre-scatter the row through the table, then attend
    pos = jnp.maximum(lengths - 1, 0)
    phys = pages[jnp.arange(B), pos // page]
    off = pos % page
    kw = k.at[LAYER, phys, off].set(new_k.reshape(B, KVHD))
    vw = v.at[LAYER, phys, off].set(new_v.reshape(B, KVHD))
    ref = decode_attention(q, _gather(kw, pages, nvirt),
                           _gather(vw, pages, nvirt), lengths,
                           block_k=_decode_block_k(k, nvirt))
    out, ko, vo = paged_decode_attention(q, k, v, lengths, pages,
                                         layer=LAYER, new_k=new_k,
                                         new_v=new_v)
    np.testing.assert_allclose(np.asarray(ref[:2]), np.asarray(out[:2]),
                               rtol=2e-5, atol=2e-5)
    live = np.arange(2)                     # rows 0, 1 are live
    np.testing.assert_array_equal(np.asarray(kw[LAYER, phys[live], off[live]]),
                                  np.asarray(ko[LAYER, phys[live], off[live]]))
    np.testing.assert_array_equal(np.asarray(vw[LAYER, phys[live], off[live]]),
                                  np.asarray(vo[LAYER, phys[live], off[live]]))
    untouched = np.setdiff1d(np.arange(k.shape[1]), np.asarray(phys))
    np.testing.assert_array_equal(np.asarray(k[:, untouched]),
                                  np.asarray(ko[:, untouched]))
    np.testing.assert_array_equal(np.asarray(v[:, untouched]),
                                  np.asarray(vo[:, untouched]))


def test_paged_decode_fused_write_int8_quantizes_like_cache():
    """Fused write on an int8 pool: the kernel's in-kernel quantization
    of the fresh row (per-kv-head symmetric, max/127) writes the SAME
    payload bytes and scales the out-of-kernel quantize-then-scatter
    path would."""
    page = 16
    q, k, v, ks, vs, pages, lengths, _ = _pool_fixture(page, int8=True)
    rng = np.random.RandomState(11)
    new_k = jnp.asarray(rng.randn(B, KVH, D), jnp.float32)
    new_v = jnp.asarray(rng.randn(B, KVH, D), jnp.float32)
    out, ko, vo, kso, vso = paged_decode_attention(
        q, k, v, lengths, pages, layer=LAYER, k_scale=ks, v_scale=vs,
        new_k=new_k, new_v=new_v)
    assert bool(jnp.all(jnp.isfinite(out[:2])))
    pos = jnp.maximum(lengths - 1, 0)
    phys = np.asarray(pages[jnp.arange(B), pos // page])
    off = np.asarray(pos % page)
    for b in range(2):                      # live rows only
        row = np.asarray(new_k[b], np.float32)          # [KVH, D]
        s = np.abs(row).max(axis=1, keepdims=True) / 127.0
        s = np.where(s == 0.0, 1.0, s)
        qrow = np.clip(np.round(row / s), -127, 127).astype(np.int8)
        np.testing.assert_array_equal(
            np.asarray(ko[LAYER, phys[b], off[b]]).reshape(KVH, D), qrow)
        np.testing.assert_allclose(
            np.asarray(kso[LAYER, phys[b], off[b]]), s[:, 0], rtol=1e-6)


@pytest.mark.parametrize("int8", [False, True], ids=["fp32", "int8"])
def test_paged_chunk_prefill_bitwise_vs_gather(int8):
    """Chunked prefill over the pool == the monolithic chunk kernel over
    the gathered view, bitwise — per-row starts including 0 and an
    unaligned mid-page start.  An int8 pool keeps the grid walk, one
    page a step: the monolithic kernel at ``block_k = page``.  An
    unquantized pool folds its reachable pages in blocks of
    ``_chunk_block_pages`` pages: the monolithic kernel at that block
    size (here the whole table)."""
    page = 16
    q0, k, v, ks, vs, pages, _, nvirt = _pool_fixture(page, int8=int8)
    del q0
    C = 24
    rng = np.random.RandomState(3)
    qc = jnp.asarray(rng.randn(B, C, H, D), jnp.float32)
    starts = jnp.asarray([13, 0, 0], jnp.int32)
    block_k = page if int8 else page * _chunk_block_pages(page, nvirt)
    ref = chunk_prefill_attention(
        qc, _gather(k, pages, nvirt), _gather(v, pages, nvirt), starts,
        block_k=block_k,
        k_scale=None if ks is None else _gather(ks, pages, nvirt),
        v_scale=None if vs is None else _gather(vs, pages, nvirt))
    out = paged_chunk_prefill_attention(qc, k, v, starts, pages,
                                        layer=LAYER, k_scale=ks, v_scale=vs)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


# page 64 throughout: a block of the loop is 8 pages = 512 keys
CHUNK_LOOP_CASES = {
    # one block, masked, seven of its eight pages never fetched
    "start0": dict(starts=[0]),
    # a start inside a page: one unmasked block, one masked
    "start_mid_page": dict(starts=[717]),
    # the second (tail) block holds ONE live page
    "tail_one_page": dict(starts=[448]),
    # 29 pages in the table, 3 reachable
    "table_wider_than_reach": dict(starts=[40], nk=29),
    # the table's width is no multiple of the block: the last block ends
    # with the table
    "table_end_mid_block": dict(starts=[1700], nk=29),
    # two rows whose first page is the SAME pool page (a shared prefix)
    "shared_first_page": dict(starts=[64, 576], share=True),
    "c128_h32_d64": dict(starts=[704], heads=(32, 32, 64), nk=32),
    "c128_h16_d128": dict(starts=[300], heads=(16, 16, 128)),
    "c512_h32_d64": dict(starts=[512], c=512, heads=(32, 32, 64)),
    "c512_h16_d128": dict(starts=[0], c=512, heads=(16, 16, 128)),
    "gqa": dict(starts=[130, 1000], heads=(8, 2, 16), nk=24),
    "fp32_pool": dict(starts=[5, 600], dtype=jnp.float32),
    # every page the chunk cannot reach, the trash page and the last
    # reachable page's rows past the chunk hold NaN
    "nan_outside_reach": dict(starts=[200, 717], poison=True),
}


@pytest.mark.parametrize("case", sorted(CHUNK_LOOP_CASES))
def test_paged_chunk_prefill_block_loop(case):
    """The block-loop driver (unquantized pools): bitwise the monolithic
    chunk kernel at the loop's block size over the gathered view —
    whatever the start, the table's width, the head shape or the chunk
    size — and untouched by what lies in pages and rows no query
    reaches."""
    kw = dict(CHUNK_LOOP_CASES[case])
    starts = np.asarray(kw.pop("starts"), np.int32)
    C = kw.pop("c", 128)
    Hq, KVHq, Dq = kw.pop("heads", (4, 4, 16))
    nk = kw.pop("nk", 16)
    dtype = kw.pop("dtype", jnp.bfloat16)
    share, poison = kw.pop("share", False), kw.pop("poison", False)
    assert not kw
    page, nb = 64, len(starts)
    bp = _chunk_block_pages(page, nk)
    assert bp == 8
    rng = np.random.RandomState(7)
    n_pool = nb * nk + 1
    shape = (L, n_pool, page, KVHq * Dq)
    k = rng.randn(*shape).astype(np.float32)
    v = rng.randn(*shape).astype(np.float32)
    pages = (rng.permutation(n_pool - 1) + 1).reshape(nb, nk) \
        .astype(np.int32)
    if share:
        pages[1, 0] = pages[0, 0]
    q = jnp.asarray(rng.randn(nb, C, Hq, Dq), dtype)

    def run(k_np, v_np):
        return paged_chunk_prefill_attention(
            q, jnp.asarray(k_np, dtype), jnp.asarray(v_np, dtype),
            jnp.asarray(starts), jnp.asarray(pages), layer=LAYER)

    out = run(k, v)
    # the reference view: the table padded with the trash page to whole
    # blocks, so the monolithic kernel walks the same block sequence
    wide = np.zeros((nb, -(-nk // bp) * bp), np.int32)
    wide[:, :nk] = pages
    ref = chunk_prefill_attention(
        q, _gather(jnp.asarray(k, dtype), wide, wide.shape[1]),
        _gather(jnp.asarray(v, dtype), wide, wide.shape[1]),
        jnp.asarray(starts), block_k=bp * page)
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    np.testing.assert_array_equal(np.asarray(ref, np.float32),
                                  np.asarray(out, np.float32))
    if poison:
        kp, vp = np.full_like(k, np.nan), np.full_like(v, np.nan)
        for b in range(nb):
            limit = int(starts[b]) + C
            for i in range(-(-limit // page)):
                rows = min(page, limit - i * page)
                kp[:, pages[b, i], :rows] = k[:, pages[b, i], :rows]
                vp[:, pages[b, i], :rows] = v[:, pages[b, i], :rows]
        np.testing.assert_array_equal(
            np.asarray(out, np.float32), np.asarray(run(kp, vp), np.float32))


def test_paged_chunk_prefill_4k_prompt_matches_dense_one_pass():
    """A 4k-prompt tail chunk through the paged kernel == the dense
    one-pass softmax over the full 4096-token history (the path 4k+
    prompts used to OOM through): same numbers, never a [S, S] score
    tensor.  Tolerance is fp32 online-softmax vs dense re-association."""
    page, nvirt = 64, 64                    # 4096 virtual positions
    S_virt, C = page * nvirt, 128
    start = S_virt - C                      # the last prefill chunk
    Hq, KVHq, Dq = 2, 1, 8
    rng = np.random.RandomState(5)
    k = jnp.asarray(rng.randn(1, nvirt + 1, page, KVHq * Dq), jnp.float32)
    v = jnp.asarray(rng.randn(1, nvirt + 1, page, KVHq * Dq), jnp.float32)
    pages = jnp.asarray(rng.permutation(nvirt) + 1, jnp.int32)[None]
    qc = jnp.asarray(rng.randn(1, C, Hq, Dq), jnp.float32)
    out = paged_chunk_prefill_attention(
        qc, k, v, jnp.asarray([start], jnp.int32), pages, layer=0)
    # dense one-pass reference over the gathered history, in float64 —
    # plain loops keep it obviously correct
    kv_g = k[0, pages[0]].reshape(S_virt, KVHq, Dq)
    vv_g = v[0, pages[0]].reshape(S_virt, KVHq, Dq)
    q_np = np.asarray(qc[0], np.float64)                 # [C, Hq, Dq]
    k_np = np.asarray(kv_g, np.float64)                  # [S, KVHq, Dq]
    v_np = np.asarray(vv_g, np.float64)
    ref = np.zeros((C, Hq, Dq))
    for h in range(Hq):
        kh = k_np[:, h // (Hq // KVHq)]                  # GQA group share
        vh = v_np[:, h // (Hq // KVHq)]
        s = (q_np[:, h] / np.sqrt(Dq)) @ kh.T            # [C, S]
        mask = np.arange(S_virt)[None, :] > (start + np.arange(C))[:, None]
        s[mask] = -np.inf
        p = np.exp(s - s.max(axis=1, keepdims=True))
        ref[:, h] = (p / p.sum(axis=1, keepdims=True)) @ vh
    np.testing.assert_allclose(np.asarray(out[0]), ref, rtol=2e-5,
                               atol=2e-5)


# --------------------------------------------------------------------- #
# The registry: one static dispatch table, host attribution included
# --------------------------------------------------------------------- #

def test_registry_dispatch_table(monkeypatch):
    """The capability probes, in table order: paged decode only without
    bias/window, and with Pallas; monolithic decode masks windows
    in-kernel; the
    chunk kernel covers 1 < S <= MAX_CHUNK_S; everything else is the
    reference fallback."""
    assert select_kernel(s=1, paged=True) == "pallas_paged_decode"
    assert select_kernel(s=1, paged=False) == "pallas_decode"
    assert select_kernel(s=1, paged=False,
                         has_window=True) == "pallas_decode"
    assert select_kernel(s=1, paged=True,
                         has_window=True) == "reference_fallback"
    assert select_kernel(s=1, paged=True,
                         has_bias=True) == "reference_fallback"
    for s in (2, 8, MAX_CHUNK_S):
        assert select_kernel(s=s, paged=True) == "pallas_chunked_prefill"
        assert select_kernel(s=s, paged=False) == "pallas_chunked_prefill"
    assert select_kernel(s=MAX_CHUNK_S + 1,
                         paged=True) == "reference_fallback"
    # host-side attribution probes the SAME table
    assert kernel_modes(paged=True) == {
        "decode": "pallas_paged_decode",
        "prefill_chunk": "pallas_chunked_prefill"}
    assert kernel_modes(paged=False) == {
        "decode": "pallas_decode",
        "prefill_chunk": "pallas_chunked_prefill"}
    monkeypatch.setenv("DSTPU_DISABLE_FLASH", "1")
    assert select_kernel(s=1, paged=True) == "reference_fallback"
    assert kernel_modes(paged=True) == {
        "decode": "reference_fallback",
        "prefill_chunk": "reference_fallback"}


def test_registry_backend_gate(monkeypatch):
    """DSTPU_DISABLE_FLASH=1 drops every mode to the reference fallback —
    the probe consults live backend capability, not a cached answer."""
    monkeypatch.setenv("DSTPU_DISABLE_FLASH", "1")
    assert select_kernel(s=1, paged=True) == "reference_fallback"
    assert select_kernel(s=8, paged=False) == "reference_fallback"
    monkeypatch.delenv("DSTPU_DISABLE_FLASH")
    assert select_kernel(s=1, paged=True) == "pallas_paged_decode"


def test_prefill_plan_reasons_name_kernel_modes():
    """prefill_plan() reasons carry the registry attribution so bench
    records say which kernel path actually ran."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.transformer import (Transformer,
                                                  TransformerConfig)
    cfg = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=1,
                            num_heads=2, max_seq_len=4096)
    eng = InferenceEngine(Transformer(cfg),
                          DeepSpeedInferenceConfig(prefill_chunk_size="auto"))
    mode, chunk, why = eng.prefill_plan(16, 4096)
    assert mode == "chunked"
    assert "prefill=pallas_chunked_prefill" in why
    assert "decode=pallas_decode" in why
    _, _, why_paged = eng.prefill_plan(16, 4096, paged=True)
    assert "decode=pallas_paged_decode" in why_paged


def test_paged_decode_jaxpr_callback_free_and_aliased():
    """The traced paged decode step: no host callbacks anywhere in the
    jaxpr, and the fused kernel's pool write is declared as
    input_output_aliases on the pallas_call — the in-place pool update
    the whole paged design rests on.  (The full entry-point donation
    proof lives in the PROGRAMS.lock harness.)"""
    page = 16
    q, k, v, _, _, pages, lengths, _ = _pool_fixture(page)
    rng = np.random.RandomState(13)
    new_k = jnp.asarray(rng.randn(B, KVH, D), jnp.float32)
    new_v = jnp.asarray(rng.randn(B, KVH, D), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda *a: paged_decode_attention(*a, layer=LAYER, new_k=new_k,
                                          new_v=new_v))(
        q, k, v, lengths, pages)
    text = str(jaxpr)
    assert "callback" not in text
    eqns = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert eqns, "paged decode did not lower to a pallas_call"
    aliases = eqns[0].params.get("input_output_aliases")
    assert aliases, "fused paged write lost its input/output aliasing"
