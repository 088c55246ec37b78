"""KV-cache tests of the slot engine, the chunk's K/V WRITE and the chunk
rows of a dispatch (``docs/serving.md`` "Paged attention kernels", "Prefill
dispatches").  A file of its own beside ``test_serving_paged.py`` — moved
out of it, test for test — because under ``--dist loadfile`` a file is one
worker's and that file was the longest of the run's tail."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.transformer import Transformer, TransformerConfig


def tiny_cfg(**over):
    base = dict(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
                max_seq_len=64, use_flash_attention=False, dtype="float32")
    base.update(over)
    return TransformerConfig(**base)


PAGED = {"enabled": True, "num_slots": 3, "max_cache_len": 64,
         "prefill_chunk": 8, "prefill_token_budget": 16,
         "decode_block": 2, "paged": True, "page_size": 16}


def _build_engine(model_cfg=None, serving=None):
    model = Transformer(model_cfg or tiny_cfg())
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 97, (2, 12)),
                      jnp.int32)
    params = model.init(jax.random.key(0), {"input_ids": ids})
    eng = deepspeed_tpu.init_inference(
        model, config={"dtype": "float32", "prefill_chunk_size": 8,
                       "serving": serving or PAGED})
    eng.set_params(params)
    return eng


@pytest.fixture(scope="module")
def paged_engine():
    return _build_engine()


def _assert_bitwise(eng, outs, rids, prompts, news, eos=None):
    for i, (rid, p, n) in enumerate(zip(rids, prompts, news)):
        e = -1 if eos is None else eos[i]
        want = np.asarray(eng.generate(p[None], max_new_tokens=n,
                                       eos_token_id=e))[0]
        np.testing.assert_array_equal(
            outs[rid], want,
            err_msg=f"request {rid} (P={len(p)}, new={n}) diverges from "
                    f"its solo generate() run")


# --------------------------------------------------------------------- #
# The chunk's K/V write as page runs (docs/serving.md "Paged attention
# kernels"): whole pages, or one run inside a page, go into the pool by
# dynamic_update_slice where the caller marks its starts run-aligned —
# bitwise the row scatter's pool.
# --------------------------------------------------------------------- #

def _prims_under(jaxpr, scope):
    """Primitive names of every equation whose name stack holds ``scope``,
    through nested jaxprs (pjit, remat, scan)."""
    found = []
    for eqn in jaxpr.eqns:
        if scope in str(eqn.source_info.name_stack):
            found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _prims_under(sub, scope)
    return found


def _pool_and_block(page, block, batch, quant, seed, table, layers=3,
                    num_pages=40, feat=16, kvh=2):
    rng = np.random.default_rng(seed)
    dt = jnp.int8 if quant else jnp.bfloat16
    pool = lambda w=feat, d=dt: jnp.asarray(
        rng.integers(-9, 9, (layers, num_pages, page, w)), d)
    cache = {"k": pool(), "v": pool(), "layer": jnp.asarray(1, jnp.int32),
             "pages": jnp.asarray(table, jnp.int32)}
    new = lambda w=feat: jnp.asarray(
        rng.standard_normal((batch, block, w)) * 50, jnp.float32)
    rows = [new(), new(), None, None]
    if quant:
        cache["k_scale"], cache["v_scale"] = pool(kvh, jnp.float32), \
            pool(kvh, jnp.float32)
        rows[2:] = [new(kvh), new(kvh)]
    return cache, rows


def _write(cache, rows, start, marked):
    """``start``: one for all rows, or a list — a start a row (per-row)."""
    from deepspeed_tpu.ops.transformer.registry import _write_cache
    if marked:
        cache = {**cache, "page_runs": jnp.zeros((), jnp.int32)}
    block, batch = rows[0].shape[1], rows[0].shape[0]
    if isinstance(start, list):
        cache = {**cache, "per_row": jnp.zeros((), jnp.int32)}
        start = jnp.asarray(start, jnp.int32)[:, None]
    positions = start + jnp.broadcast_to(jnp.arange(block), (batch, block))
    fn = lambda c, r, p: _write_cache(c, *r, p)
    prims = _prims_under(jax.make_jaxpr(fn)(cache, rows, positions).jaxpr, "")
    return jax.jit(fn)(cache, rows, positions), prims


# distinct live pages a row; 0 is the trash page
_ROW_A = [7, 3, 12, 9, 30, 5, 21, 14, 2, 38]
_ROW_B = [11, 25, 4, 33, 8, 19, 1, 27, 16, 6]

PAGE_RUN_CASES = {
    # name: (page, block, start, table rows, int8 pool)
    "two_whole_pages": (64, 128, 128, [_ROW_A], False),
    "eight_whole_pages": (64, 512, 0, [_ROW_A], False),
    "half_page_at_0": (64, 32, 192, [_ROW_A], False),
    "half_page_at_32": (64, 32, 224, [_ROW_A], False),
    "two_rows_two_tables": (64, 128, 256, [_ROW_A, _ROW_B], False),
    "two_rows_half_page_at_32": (64, 32, 96, [_ROW_A, _ROW_B], False),
    # a final chunk past the mapped pages: the trash page takes the tail
    "tail_pages_unmapped": (64, 512, 0, [_ROW_A[:3] + [0] * 7], False),
    # the lane's last chunk ends exactly at the table row's end
    "ends_at_the_rows_end": (64, 128, 512, [_ROW_A], False),
    "ends_at_the_rows_end_half_page": (64, 32, 608, [_ROW_A], False),
    "int8_pool_with_scale_pages": (64, 128, 128, [_ROW_A, _ROW_B], True),
    "int8_pool_half_page_at_32": (64, 32, 32, [_ROW_A], True),
    # the chunk program's rows: a start a ROW — consecutive chunks of one
    # prompt (one table), two prompts, a dead row (all trash, start 0)
    "per_row_one_prompts_chunks": (64, 128, [0, 128, 256, 384],
                                   [_ROW_A] * 4, False),
    "per_row_two_prompts": (64, 128, [256, 0, 384, 128],
                            [_ROW_A, _ROW_B, _ROW_A, _ROW_B], False),
    "per_row_dead_row": (64, 128, [128, 0], [_ROW_A, [0] * 10], False),
    "per_row_half_pages": (64, 32, [96, 32, 0], [_ROW_A, _ROW_B, _ROW_B],
                           False),
    "per_row_int8_pool": (64, 128, [128, 384], [_ROW_A, _ROW_B], True),
}


@pytest.mark.parametrize("case", sorted(PAGE_RUN_CASES))
def test_page_runs_write_is_bitwise_the_row_scatter(case):
    """Marked, the block goes in by dynamic_update_slice alone — one a
    page run a buffer — and the pool is bit for bit the one the row
    scatter leaves: every live page of the run written, no other page
    touched (an unmapped tail lands on trash page 0 in both)."""
    page, block, start, table, quant = PAGE_RUN_CASES[case]
    cache, rows = _pool_and_block(page, block, len(table), quant,
                                  seed=len(case), table=table)
    got, prims = _write(cache, rows, start, marked=True)
    want, ref_prims = _write(cache, rows, start, marked=False)
    buffers = 4 if quant else 2
    runs = len(table) * max(1, block // page)
    assert prims.count("dynamic_update_slice") == buffers * runs
    assert "scatter" not in prims and "scatter" in ref_prims
    for key in ("k", "v", "k_scale", "v_scale")[:buffers]:
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]), err_msg=key)
    assert "page_runs" in got and "page_runs" not in want
    # what the write may touch: the run's own pages of this layer
    starts = start if isinstance(start, list) else [start] * len(table)
    mine = {row[first // page + j] for row, first in zip(table, starts)
            for j in range(max(1, block // page))}
    before, after = np.asarray(cache["k"]), np.asarray(got["k"])
    changed = {int(p) for layer, p in zip(*np.nonzero(
        (before != after).any(axis=(2, 3))))}
    assert changed <= mine, (changed, mine)
    assert (before[[0, 2]] == after[[0, 2]]).all()
    assert {p for p in mine if p} <= changed


@pytest.mark.parametrize("page,block,marked,per_row", [
    (64, 128, False, False),        # a hand-built pool: nobody promised
    (64, 96, True, False),          # neither multiple nor divisor
    (64, 160, True, False),
    (64, 1, True, False),           # one token is a row, not a run
    (64, 128, False, True),         # an unmarked per-row block (speculative
                                    # verify) starts where its slot stands
])
def test_what_is_not_page_runs_keeps_the_scatter(page, block, marked,
                                                 per_row):
    from deepspeed_tpu.ops.transformer.registry import paged_write_form
    assert paged_write_form(block, page, page_runs=marked) == "row_scatter"
    cache, rows = _pool_and_block(page, block, 1, False, seed=block,
                                  table=[_ROW_A])
    _, prims = _write(cache, rows, [64] if per_row else 64, marked=marked)
    assert "scatter" in prims and "dynamic_update_slice" not in prims


def _strip_page_runs(monkeypatch):
    """The chunk program's cache reaches the model without the marker."""
    plain = Transformer.decode

    def decode(self, input_ids, cache, start_pos, **kw):
        cache = {k: v for k, v in cache.items() if k != "page_runs"}
        return plain(self, input_ids, cache, start_pos, **kw)
    monkeypatch.setattr(Transformer, "decode", decode)


def _chunk_write_prims(srv):
    """What the server's own chunk program traces under ``cache.write``."""
    pool = jax.eval_shape(
        lambda: srv._pages.new_pools(srv.engine.compute_dtype))
    args = (srv.engine._params, pool,
            jnp.zeros((1, srv._pages.table_width), jnp.int32),
            jnp.zeros((1, srv.chunk), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((1,), jnp.int32))
    return _prims_under(jax.make_jaxpr(srv._chunk_fn)(*args).jaxpr,
                        "cache.write")


@pytest.mark.parametrize("page,chunk,form", [
    (16, 8, "page_runs"), (8, 16, "page_runs"), (16, 24, "row_scatter")])
def test_served_tokens_with_the_marker_equal_those_without(
        monkeypatch, page, chunk, form):
    """One request end to end, prompt over several chunks: the tokens a
    server's page-run chunk program gives are those the same server
    gives with the marker stripped (the row scatter), and both are the
    solo generate() run's.  The report — ``stats["chunk_write"]``, the
    ``prefill_plan`` reason — says what the traced program did."""
    serving = {**PAGED, "page_size": page, "prefill_chunk": chunk}
    eng = _build_engine(serving=serving)
    prompt = np.random.default_rng(5).integers(1, 97, (29,)).astype(np.int32)
    outs = {}
    for tag in ("marked", "stripped"):
        if tag == "stripped":
            _strip_page_runs(monkeypatch)
        srv = eng.serve()
        assert srv.stats["chunk_write"] == form
        assert "chunk_write=" + form in eng.prefill_plan(1, 29, paged=True)[2]
        prims = _chunk_write_prims(srv)
        runs = tag == "marked" and form == "page_runs"
        assert ("scatter" in prims) == (not runs), (tag, prims)
        assert ("dynamic_update_slice" in prims) == runs, (tag, prims)
        rid = srv.submit(prompt, max_new_tokens=9)
        outs[tag] = srv.drain()[rid]
        srv.close()
    np.testing.assert_array_equal(outs["marked"], outs["stripped"])
    monkeypatch.undo()
    _assert_bitwise(eng, {0: outs["marked"]}, [0], [prompt], [9])


def test_kernel_modes_keeps_exactly_its_two_keys(paged_engine):
    """``benchmark/serving.py`` compares ``srv.kernel_modes`` WHOLE with
    these two keys and refuses a server on any difference: the write
    form is a stat, never a third key here."""
    srv = paged_engine.serve()
    try:
        assert dict(srv.kernel_modes) == {
            "decode": "pallas_paged_decode",
            "prefill_chunk": "pallas_chunked_prefill"}
        assert srv.stats["chunk_write"] == "page_runs"     # page 16, chunk 8
        assert "chunk_write" not in paged_engine.prefill_plan(1, 29)[2]
    finally:
        srv.close()


# --------------------------------------------------------------------- #
# The chunk program takes rows (docs/serving.md "Prefill dispatches"): a
# dispatch of R chunk rows — of one prompt or of several, each with its
# own table row, start and last real position — leaves the pool and each
# row's selected logits what R one-row dispatches leave.
# --------------------------------------------------------------------- #
_RC, _RPAGE, _RSLOT_PAGES = 16, 16, 6
_TABLE_A = [1, 2, 3, 4, 5, 6]
_TABLE_B = [7, 8, 9, 10, 11, 12]


def _row_prompts():
    rng = np.random.default_rng(41)
    a = rng.integers(1, 97, (58,)).astype(np.int32)     # 4 chunks, 10 real
    b = rng.integers(1, 97, (40,)).astype(np.int32)     # 3 chunks, 8 real
    # shares a's first two pages, then its own 20 tokens: starts at 32
    c = np.concatenate([a[:32], rng.integers(1, 97, (20,))]).astype(np.int32)
    return {"a": (a, _TABLE_A, 0), "b": (b, _TABLE_B, 0),
            "c": (c, _TABLE_A[:2] + _TABLE_B[2:], 32)}


ROW_CASES = {
    # name: (rows run one at a time beforehand, the dispatch's rows);
    # a row is (prompt, chunk index), None a dead row
    "four_chunks_of_one_prompt": ([], [("a", 0), ("a", 1), ("a", 2),
                                       ("a", 3)]),
    "two_prompts_interleaved": ([], [("a", 0), ("b", 0), ("a", 1),
                                     ("b", 1)]),
    "padded_last_chunk_beside_anothers_first": (
        [("a", 0), ("a", 1), ("a", 2)],
        [("a", 3), ("b", 0), ("b", 1), ("b", 2)]),
    "dead_rows": ([], [("a", 0), None, ("a", 1), None]),
    "shared_prefix_start": ([], [("a", 0), ("a", 1), ("c", 0), ("c", 1)]),
}


def _row_args(rows):
    """``(tables, ids, starts, last)`` of a dispatch: one entry a row."""
    prompts = _row_prompts()
    tables = np.zeros((len(rows), _RSLOT_PAGES), np.int32)
    ids = np.zeros((len(rows), _RC), np.int32)
    starts = np.zeros((len(rows),), np.int32)
    last = np.zeros((len(rows),), np.int32)
    for r, row in enumerate(rows):
        if row is None:
            continue
        (fill, table, s0), ci = prompts[row[0]], row[1]
        starts[r] = s0 + ci * _RC
        tables[r] = table
        part = fill[starts[r]:starts[r] + _RC]
        ids[r, :len(part)] = part
        last[r] = min(max(len(fill) - 1 - starts[r], 0), _RC - 1)
    return tables, ids, starts, last


@pytest.mark.parametrize("case", sorted(ROW_CASES))
def test_a_dispatch_of_rows_equals_one_row_dispatches(case, paged_engine,
                                                      monkeypatch):
    """The R-row program over the Pallas chunk kernel against the one-row
    program (scalar start) on the gather reference, row after row: the
    same selected logits a live row, the same pool outside the trash
    page.  Rows of one prompt attend what earlier rows wrote in the same
    dispatch; a shared-prefix row attends another slot's pages written in
    it; a dead row (all-trash table, start 0) touches nothing live."""
    from deepspeed_tpu.inference.serving.slots import make_chunk_fn
    eng = paged_engine
    before, rows = ROW_CASES[case]
    new_pool = lambda: eng.module.init_paged_cache(
        1 + 2 * _RSLOT_PAGES, _RPAGE, dtype=eng.compute_dtype)

    def one_at_a_time(fn, pool, todo):
        out = {}
        for r, row in enumerate(todo):
            if row is None:
                continue
            tables, ids, starts, last = _row_args([row])
            out[r], pool = fn(eng._params, pool, jnp.asarray(tables),
                              jnp.asarray(ids), jnp.asarray(starts[0]),
                              jnp.asarray(last))
        return out, pool

    kernel = make_chunk_fn(eng.module, eng.module.slot_contract(), None)
    _, pool = one_at_a_time(kernel, new_pool(), before)
    tables, ids, starts, last = _row_args(rows)
    got, got_pool = kernel(eng._params, pool, jnp.asarray(tables),
                           jnp.asarray(ids), jnp.asarray(starts),
                           jnp.asarray(last))
    assert got.shape == (len(rows), 1, 97)

    monkeypatch.setenv("DSTPU_DISABLE_FLASH", "1")
    gather = make_chunk_fn(eng.module, eng.module.slot_contract(), None)
    _, pool = one_at_a_time(gather, new_pool(), before)
    want, want_pool = one_at_a_time(gather, pool, rows)
    for r, logits in want.items():
        np.testing.assert_allclose(np.asarray(got[r]), np.asarray(logits[0]),
                                   rtol=2e-5, atol=2e-5, err_msg=f"row {r}")
    for key in ("k", "v"):
        np.testing.assert_allclose(np.asarray(got_pool[key])[:, 1:],
                                   np.asarray(want_pool[key])[:, 1:],
                                   rtol=2e-5, atol=2e-5, err_msg=key)
    # every page a live row covers was written; nothing else was
    covered = {_row_prompts()[row[0]][1][(s // _RPAGE)]
               for row, s in zip(rows, starts) if row is not None}
    written = {int(p) for p in np.nonzero(
        np.asarray(got_pool["k"])[0].any(axis=(1, 2)))[0]} - {0}
    assert written == covered | {_row_prompts()[r[0]][1][
        (_row_args([r])[2][0]) // _RPAGE] for r in before}
