"""KV-cache tests of the slot engine (``inference/serving/paging.py``,
``docs/serving.md`` "KV cache").

The acceptance contract: over the shared page pool + block tables,
greedy serving outputs stay BITWISE-identical to solo ``generate()``
runs, tokens are invariant to the page size, a
shared prompt prefix is prefilled exactly once (copy-on-write at page
granularity), pool exhaustion degrades into admission backpressure
(``QueueFull`` / stalls — never corruption), paged snapshots
preempt→restore bitwise, and the whole lifecycle still mints exactly ONE
decode executable per server."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.serving.paging import (PagePool, PrefixIndex,
                                                    SlotPages,
                                                    compact_page_str,
                                                    expand_page_str)
from deepspeed_tpu.inference.serving.slo import QueueFull, RequestStatus
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


def _mixed_workload(rng, n=7):
    lens = rng.integers(9, 21, (n,))
    news = rng.integers(3, 13, (n,))
    prompts = [rng.integers(1, 97, (int(p),)).astype(np.int32)
               for p in lens]
    return prompts, [int(x) for x in news]


def _assert_bitwise(eng, outs, rids, prompts, news, eos=None):
    for i, (rid, p, n) in enumerate(zip(rids, prompts, news)):
        e = -1 if eos is None else eos[i]
        want = np.asarray(eng.generate(p[None], max_new_tokens=n,
                                       eos_token_id=e))[0]
        np.testing.assert_array_equal(
            outs[rid], want,
            err_msg=f"request {rid} (P={len(p)}, new={n}) diverges from "
                    f"its solo generate() run")


def test_paged_serving_matches_solo_generate(paged_engine):
    """The PR 4 equivalence contract in paged mode: num_slots(3) <
    num_requests(7), mid-stream EOS retirements, slot churn — every
    output bitwise-equal to solo generate(), ONE decode executable."""
    eng = paged_engine
    rng = np.random.default_rng(3)
    prompts, news = _mixed_workload(rng)
    eos_ids = []
    for i, (p, n) in enumerate(zip(prompts, news)):
        if i % 2 == 0:
            probe = np.asarray(eng.generate(p[None], max_new_tokens=n))[0]
            eos_ids.append(int(probe[len(p) + n // 2]))
        else:
            eos_ids.append(-1)
    srv = eng.serve()
    assert srv.page == 16
    rids = [srv.submit(p, max_new_tokens=n, eos_token_id=e)
            for p, n, e in zip(prompts, news, eos_ids)]
    outs = srv.drain()
    assert sorted(outs) == sorted(rids)
    _assert_bitwise(eng, outs, rids, prompts, news, eos_ids)
    # every slot's pages returned to the pool; only the prefix index may
    # still hold references
    assert not srv._pages._rows
    assert (srv._pages.table() == 0).all()
    n_decode_sigs = sum(1 for sig in eng._aot
                        if sig and sig[0] == id(srv._decode_fn))
    assert n_decode_sigs == 1, n_decode_sigs


def test_paged_decode_span_counts_live_pages(paged_engine, tmp_path):
    """The counter that says how far the live-page walk engages: each
    ``dstpu.sched.dispatch.decode`` span carries ``kv_pages`` — pages
    the block's steps walk, ``ceil(context / page_size)`` a live slot
    and step — and ``kv_pages_table`` (slots x pages a slot x steps).
    A hand-built schedule: more requests than slots, contexts crossing
    page boundaries, budgets that end mid-block (so a slot retires and
    is re-occupied inside the run) — the spans' sum is exact, and the
    outputs stay bitwise equal to solo ``generate()``."""
    import json
    from deepspeed_tpu.monitor import trace as span_trace
    eng = paged_engine
    rng = np.random.default_rng(11)
    # (prompt length, new tokens): block 4, so 7 / 6 / 10 new tokens end
    # two / one / one step into a block; 15 -> 16 -> 17 and 30 -> 33
    # cross page boundaries
    plan = [(15, 7), (30, 6), (9, 10), (17, 3), (31, 5)]
    prompts = [rng.integers(1, 97, (p,)).astype(np.int32) for p, _ in plan]
    news = [n for _, n in plan]
    srv = eng.serve(tracing=True, decode_block=4)
    try:
        rids = [srv.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        outs = srv.drain()
        path = srv.dump_trace(str(tmp_path / "trace.json"))
    finally:
        srv.close()
        span_trace.disable()
    _assert_bitwise(eng, outs, rids, prompts, news)
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    decodes = [e["args"] for e in evs
               if e["name"] == "dstpu.sched.dispatch.decode"]
    page = srv.page
    # the step that makes a request's token i attends prompt + i positions
    want = sum(-(-(p + i) // page) for p, n in plan for i in range(1, n))
    assert sum(a["kv_pages"] for a in decodes) == want
    # a 4-page lane is one block of the kernel's loop: a fold a slot-step
    assert srv._pages.fold_pages == 4
    assert sum(a["kv_folds"] for a in decodes) \
        == sum(n - 1 for _, n in plan)
    table = srv.num_slots * srv.pages_per_slot * srv.block
    assert {a["kv_pages_table"] for a in decodes} == {table}
    assert all(0 <= a["kv_pages"] <= table for a in decodes)
    # a slot that retires mid-block walks fewer steps than the block:
    # some dispatch counts a slot for less than `block` steps
    assert any(a["kv_pages"] < a["live_slots"] * srv.block for a in decodes)
    assert sum(a["kv_positions"] for a in decodes) \
        == sum(p + i for p, n in plan for i in range(1, n))


def test_decode_span_kv_folds_is_the_kernels_own_fold_count(monkeypatch):
    """``kv_folds`` beside ``kv_pages`` on the decode dispatch span is
    the online-softmax updates the paged-decode kernel makes of those
    pages — ``ceil(pages / pages a block)`` a live slot and step, the
    pages a block read from the kernel's own rule.  A hand-made set of
    live slots over a 40-page lane (blocks of 32 pages at a page of
    16): contexts that stay inside one block, cross into the second
    between two steps, and fill the lane; the KERNEL, run a step at a
    time over the same contexts with its per-block update counted,
    makes exactly that many."""
    from deepspeed_tpu.ops.transformer import paged_attention as paged_mod
    model = Transformer(tiny_cfg(max_seq_len=640))
    sp = SlotPages(model, model.slot_contract(), 5, cache_len=640,
                   page_size=16, num_pages=0, chunk=64, share_prefixes=False,
                   stats={})
    pools = sp.new_pools(jnp.float32)
    assert sp.fold_pages == paged_mod._decode_block_pages(16, 40, 4) == 32
    # (context the first step attends, steps); slot 3 is dead
    live = {0: (500, 2), 1: (511, 3), 2: (5, 1), 4: (639, 2)}
    reach = sp.block_reach(2, list(live.values()), 4)
    assert reach["kv_pages"] == 32 + 32 + 32 + 32 + 33 + 1 + 40 + 40
    assert reach["kv_folds"] == 1 + 1 + 1 + 1 + 2 + 1 + 2 + 2
    assert reach["kv_pages_table"] == 5 * 40 * 4

    folds = []
    update = paged_mod._block_update

    def counted(*args, **kw):
        jax.debug.callback(lambda: folds.append(1))
        return update(*args, **kw)

    monkeypatch.setattr(paged_mod, "_block_update", counted)
    table = np.zeros((5, 40), np.int32)
    for slot in live:
        table[slot] = 1 + slot * 40 + np.arange(40)
    q = jnp.ones((5, 4, 16), jnp.float32)
    for step in range(4):
        rows = {s: first + step for s, (first, steps) in live.items()
                if step < steps}
        lengths = np.full((5,), 77, np.int32)           # dead by the table
        lengths[list(rows)] = list(rows.values())
        paged_mod.paged_decode_attention(
            q, pools["k"], pools["v"], jnp.asarray(lengths),
            jnp.asarray(np.where(np.isin(np.arange(5), list(rows))[:, None],
                                 table, 0)), layer=0)
    jax.effects_barrier()
    assert len(folds) == reach["kv_folds"]


def test_paged_prefill_chunk_span_counts_reachable_pages(paged_engine,
                                                         tmp_path):
    """The counter that says how far the chunk kernel's block loop
    engages: each ``dstpu.sched.dispatch.prefill_chunk`` span carries
    ``kv_pages`` — the pages its layers fetch, every page of the slot's
    table up to the chunk's furthest position — and ``kv_pages_table``
    (pages a slot x layers).  Known prompts: chunk 8, page 16, two
    layers, four pages a slot — chunk ``ci`` of a prompt reaches
    ``ceil(8 * (ci + 1) / 16)`` pages a layer."""
    import json
    from deepspeed_tpu.monitor import trace as span_trace
    eng = paged_engine
    rng = np.random.default_rng(12)
    plan = [(5, 2), (16, 3), (17, 2), (40, 2), (57, 3)]
    prompts = [rng.integers(1, 97, (p,)).astype(np.int32) for p, _ in plan]
    news = [n for _, n in plan]
    srv = eng.serve(tracing=True)
    try:
        rids = [srv.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        outs = srv.drain()
        path = srv.dump_trace(str(tmp_path / "trace.json"))
    finally:
        srv.close()
        span_trace.disable()
    _assert_bitwise(eng, outs, rids, prompts, news)
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    chunks = [e["args"] for e in evs
              if e["name"] == "dstpu.sched.dispatch.prefill_chunk"]
    layers, page, C = 2, srv.page, srv.chunk
    assert (page, C, srv.pages_per_slot) == (16, 8, 4)
    table = layers * srv.pages_per_slot
    # a dispatch sums its live rows (rows_cap = 512 // 8 of them at most)
    assert {a["rows_cap"] for a in chunks} == {srv.chunk_rows} == {64}
    assert all(a["kv_pages_table"] == a["rows"] * table for a in chunks)
    assert all(0 < a["kv_pages"] <= a["rows"] * table for a in chunks)
    reach = [layers * -(-(C * (ci + 1)) // page)
             for plen, _ in plan for ci in range(-(-plen // C))]
    assert sum(a["rows"] for a in chunks) == len(reach) \
        == srv.stats["prefill_rows"]
    assert len(chunks) == srv.stats["prefill_dispatches"] < len(reach)
    assert sum(a["kv_pages"] for a in chunks) == sum(reach)
    # the first dispatch starts with the 5-token prompt: one page of four
    assert (chunks[0]["rid"], chunks[0]["chunk"]) == (rids[0], 0)
    assert reach[0] == 2 and reach[-1] == table


def test_paged_page_size_invariance(paged_engine):
    """Same tokens for page_size in {16, 64, 128}: the page size only
    changes where K/V rows physically live, never what is attended."""
    eng = paged_engine
    rng = np.random.default_rng(5)
    prompts, news = _mixed_workload(rng, n=5)
    ref = None
    for ps in (16, 64, 128):
        srv = eng.serve(page_size=ps)
        rids = [srv.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        outs = srv.drain()
        got = [outs[r] for r in rids]
        if ref is None:
            ref = got
            _assert_bitwise(eng, outs, rids, prompts, news)
        else:
            for a, b in zip(ref, got):
                np.testing.assert_array_equal(a, b)


def test_paged_prefix_cow_divergence(paged_engine):
    """Copy-on-write prefix sharing: requests with a common 2-page
    prefix and divergent tails share the prefix pages (prefilled once —
    later admissions hit the index) yet produce bitwise-solo outputs;
    the divergent tail re-prefills at most one page of tokens."""
    eng = paged_engine
    rng = np.random.default_rng(11)
    pre = rng.integers(1, 97, (32,)).astype(np.int32)      # 2 full pages
    reqs = [np.concatenate([pre,
                            rng.integers(1, 97, (5,)).astype(np.int32)])
            for _ in range(4)]
    srv = eng.serve()
    rids = [srv.submit(q, max_new_tokens=6) for q in reqs]
    outs = srv.drain()
    _assert_bitwise(eng, outs, rids, reqs, [6] * 4)
    # request 1..3 each matched the 2 shared pages request 0 registered
    assert srv.stats["prefix_hits"] >= 3, srv.stats
    assert srv.stats["prefix_tokens_reused"] >= 3 * 32
    # the shared prefill really was skipped: without sharing 4 requests
    # of 37 tokens cost 4*ceil(37/8)*8 = 160 prefill tokens; with 2
    # shared pages the 3 hits each saved 32 tokens
    assert srv.stats["prefill_tokens"] <= 160 - 3 * 32


def test_paged_prefix_chunk_unaligned_boundary():
    """A prefix match whose page boundary is NOT chunk-aligned must be
    rounded DOWN to a chunk-aligned start: chunk ci writes the full
    padded span [s0+ci*C, s0+(ci+1)*C), so a page-aligned-only s0 can
    pad past the table row (page 16, chunk 64, P=120, m=7 matched pages:
    112 + 64 = 176 > the 8-page lane — a host-side broadcast crash
    mid-admission before the fix).  Outputs stay bitwise-solo."""
    eng = _build_engine(
        model_cfg=tiny_cfg(max_seq_len=128),
        serving={"enabled": True, "num_slots": 2, "max_cache_len": 128,
                 "prefill_chunk": 64, "prefill_token_budget": 128,
                 "decode_block": 2, "paged": True, "page_size": 16})
    eng._config.prefill_chunk_size = 64      # solo replays the same chunk
    rng = np.random.default_rng(31)
    p = rng.integers(1, 97, (120,)).astype(np.int32)
    want = np.asarray(eng.generate(p[None], max_new_tokens=8))[0]
    srv = eng.serve()
    r1 = srv.submit(p, max_new_tokens=8)     # registers the prefix
    outs = srv.drain()
    r2 = srv.submit(p, max_new_tokens=8)     # matches 7 pages -> round to 4
    outs.update(srv.drain())
    np.testing.assert_array_equal(outs[r1], want)
    np.testing.assert_array_equal(outs[r2], want)
    assert srv.stats["prefix_hits"] == 1
    # the trimmed match really started the second prefill chunk-aligned
    assert srv.stats["prefix_tokens_reused"] == 64


def test_paged_prefix_stats_count_admissions_not_stalls():
    """Prefix stats count ADMISSIONS: a request stalled at the queue
    head under pool pressure retries _start_prefill_paged every step and
    must not record a lookup/hit per retry (hit-rate inflation)."""
    eng = _build_engine()
    rng = np.random.default_rng(37)
    pre = rng.integers(1, 97, (32,)).astype(np.int32)      # 2 pages
    reqs = [np.concatenate([pre,
                            rng.integers(1, 97, (5,)).astype(np.int32)])
            for _ in range(4)]
    # 4 allocatable pages vs 3 pages/request: concurrency is page-bound,
    # so admissions stall while earlier requests decode
    srv = eng.serve(num_pages=5)
    rids = [srv.submit(q, max_new_tokens=6) for q in reqs]
    outs = srv.drain()
    assert srv.stats["admission_stalls"] > 0
    assert srv.stats["prefix_lookups"] == 4, srv.stats
    _assert_bitwise(eng, outs, rids, reqs, [6] * 4)


def test_paged_pool_exhaustion_backpressure(paged_engine):
    """Refcount/pool exhaustion shows up as admission BACKPRESSURE —
    a bounded queue rejects with QueueFull, an unbounded one stalls
    admission until retirements free pages — and everything admitted
    still completes bitwise-correct (no corruption, no deadlock)."""
    eng = paged_engine
    rng = np.random.default_rng(13)
    prompts, news = _mixed_workload(rng, n=8)

    # bounded queue: pool of 8 allocatable pages fills, queue backs up,
    # submit() rejects with QueueFull
    srv = eng.serve(num_pages=9, max_queue_depth=2, queue_policy="reject")
    accepted = []
    with pytest.raises(QueueFull):
        for i in range(8):
            accepted.append(
                (srv.submit(prompts[i], max_new_tokens=news[i]), i))
    outs = srv.drain()
    _assert_bitwise(eng, outs, [r for r, _ in accepted],
                    [prompts[i] for _, i in accepted],
                    [news[i] for _, i in accepted])

    # unbounded queue: admission stalls at the queue head under pool
    # pressure and resumes as slots retire — all 8 complete.  3
    # allocatable pages vs (mostly) 2-page requests: two can never run
    # concurrently even though 3 slots are free
    srv = eng.serve(num_pages=4, prefix_cache=False)
    rids = [srv.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, news)]
    outs = srv.drain()
    assert srv.stats["admission_stalls"] > 0
    _assert_bitwise(eng, outs, rids, prompts, news)
    # nothing leaked: the pool drains back to empty
    assert srv._pages.in_use == 0

    # a request the pool can NEVER hold is rejected at submit, not
    # queued into a deadlock
    with pytest.raises(ValueError, match="pages"):
        srv.submit(rng.integers(1, 97, (40,)).astype(np.int32),
                   max_new_tokens=20)


def test_paged_preempt_restore_bitwise(paged_engine, tmp_path):
    """Graceful preemption of a paged server: snapshot mid-flight,
    restore on a fresh paged server, stitched outputs bitwise-identical
    to uninterrupted runs; the snapshot stores page tables as compact
    range strings (diagnostics), never one JSON int per entry."""
    import json
    import os
    eng = paged_engine
    rng = np.random.default_rng(17)
    prompts, news = _mixed_workload(rng, n=6)
    srv = eng.serve()
    rids = [srv.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, news)]
    outs = {}
    for _ in range(4):
        outs.update(srv.step())
    tag, snapped, fin = srv.preempt(str(tmp_path), drain_budget_s=0.0)
    outs.update(fin)
    assert snapped, "nothing was left to snapshot — weak test setup"
    with open(os.path.join(str(tmp_path), tag, "serving_state.json")) as f:
        state = json.load(f)
    in_slot = [r for r in state["requests"] if r.get("pages")]
    assert in_slot, "no in-slot request carried a compact page table"
    for r in in_slot:
        assert isinstance(r["pages"], str)
        assert expand_page_str(r["pages"])          # parses back
    srv2 = eng.serve()
    restored = srv2.restore(str(tmp_path))
    assert sorted(restored) == sorted(snapped)
    outs.update(srv2.drain())
    _assert_bitwise(eng, outs, rids, prompts, news)


def test_paged_restore_onto_smaller_pool_aborts(paged_engine, tmp_path):
    """A snapshot from a big-pool server restored onto a server whose
    pool can never hold a request ABORTs it with a clear reason (the
    paged mirror of the PR 5 lane-capacity check)."""
    eng = paged_engine
    rng = np.random.default_rng(19)
    big = rng.integers(1, 97, (20,)).astype(np.int32)
    srv = eng.serve()
    rid = srv.submit(big, max_new_tokens=20)        # 40 positions
    srv.preempt(str(tmp_path), drain_budget_s=0.0)
    srv2 = eng.serve(num_pages=3)                   # 2 pages = 32 positions
    assert srv2.restore(str(tmp_path)) == []
    res = srv2.result(rid)
    assert res.status == RequestStatus.ABORTED
    assert "page" in res.detail and "num_pages" in res.detail


def test_paged_int8_kv_serving_matches_solo(tmp_path):
    """int8 KV quantization through the paged pool: quantized page
    writes/gathers reproduce solo generate() (which quantizes the same
    rows into a monolithic cache) bitwise."""
    eng = _build_engine(model_cfg=tiny_cfg(kv_cache_quant=True))
    rng = np.random.default_rng(23)
    prompts, news = _mixed_workload(rng, n=5)
    srv = eng.serve()
    assert "k_scale" in srv._pages.take(eng.compute_dtype)
    srv._pages.drop_buffer()
    rids = [srv.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, news)]
    outs = srv.drain()
    _assert_bitwise(eng, outs, rids, prompts, news)


def test_paged_overload_cycle_zero_new_decode_executables(paged_engine,
                                                         tmp_path):
    """The zero-new-executables invariant extended to paged mode
    (acceptance): an overload burst + deadline shed + cancel + preempt +
    restarted-server resume mints exactly ONE paged decode signature per
    server — page allocation, sharing, eviction and table churn all ride
    traced arguments."""
    eng = paged_engine
    rng = np.random.default_rng(29)
    prompts, news = _mixed_workload(rng, n=7)
    srv1 = eng.serve()
    rids = [srv1.submit(p, max_new_tokens=n)
            for p, n in zip(prompts[:5], news[:5])]
    r_shed = srv1.submit(prompts[5], max_new_tokens=4, deadline_s=0.0)
    r_cancel = srv1.submit(prompts[6], max_new_tokens=4)
    srv1.cancel(r_cancel)
    early = {}
    for _ in range(4):
        early.update(srv1.step())
    tag, snapped, fin = srv1.preempt(str(tmp_path), drain_budget_s=0.0)
    early.update(fin)
    assert srv1.result(r_shed).status == RequestStatus.SHED_DEADLINE
    assert srv1.result(r_cancel).status == RequestStatus.CANCELLED
    srv2 = eng.serve()
    restored = srv2.restore(str(tmp_path))
    assert sorted(restored) == sorted(snapped)
    outs = dict(early)
    outs.update(srv2.drain())
    _assert_bitwise(eng, outs, rids, prompts[:5], news[:5])
    for srv in (srv1, srv2):
        n_decode = sum(1 for sig in eng._aot
                       if sig and sig[0] == id(srv._decode_fn))
        assert n_decode == 1, n_decode


@pytest.mark.parametrize("gone", ["paged", "paged_kernel"])
def test_removed_layout_switch_refused_by_name(gone):
    """The lane layout and both of its switches are gone: neither is a
    config field, ``true`` (what config files still carry) is accepted
    and ignored, and ``false`` — a request for a layout the engine no
    longer has — is refused by name, from the config file and from a
    ``serve()`` override alike."""
    from deepspeed_tpu.inference.serving.config import ServingConfig
    assert gone not in ServingConfig.model_fields
    eng = _build_engine(serving={**PAGED, gone: True})
    assert eng.serve().kernel_modes["decode"] == "pallas_paged_decode"
    with pytest.raises(ValueError, match=rf"serving\.{gone}=False.*removed"):
        eng.serve(**{gone: False})
    with pytest.raises(ValueError, match=rf"serving\.{gone}=False.*removed"):
        _build_engine(serving={**PAGED, gone: False}).serve()


def test_pool_size_validation():
    """A pool without one allocatable page beside the trash page fails
    loudly."""
    with pytest.raises(ValueError, match="num_pages"):
        _build_engine(serving={**PAGED, "num_pages": 1}).serve()


def test_page_pool_and_prefix_index_unit():
    """Host bookkeeping invariants: trash page pinned, refcounted
    alloc/free, chain-hash lookup/register, leaf-first LRU eviction,
    and the compact page-string round trip."""
    pool = PagePool(6)                       # pages 1..5 allocatable
    assert pool.allocatable == 5 and pool.free_count == 5
    got = pool.alloc(3)
    assert got is not None and 0 not in got
    assert pool.alloc(3) is None             # never a partial grab
    pool.incref(got[0])
    for p in got:
        pool.decref(p)
    assert pool.free_count == 4              # got[0] still referenced
    pool.decref(got[0])
    assert pool.free_count == 5

    idx = PrefixIndex()
    toks = np.arange(32, dtype=np.int32)
    row = pool.alloc(2)
    assert idx.register(toks, 16, row, pool, 2) == 2
    for p in row:
        pool.decref(p)          # the registering slot retires — only the
    hit = idx.lookup(toks, 16, pool, 2)     # index's references remain
    assert hit == row
    for p in hit:
        pool.decref(p)
    # divergence INSIDE block 2: only block 1 matches
    toks2 = toks.copy()
    toks2[20] = 96
    hit2 = idx.lookup(toks2, 16, pool, 2)
    assert hit2 == row[:1]
    for p in hit2:
        pool.decref(p)
    # eviction is leaf-first: the chain's tail goes before its parent
    assert idx.evict(pool, 1) == 1
    assert len(idx) == 1 and pool.refcount(row[1]) == 0
    idx.clear(pool)
    assert pool.free_count == 5

    assert compact_page_str([4, 5, 6, 9, 2]) == "4-6,9,2"
    assert expand_page_str("4-6,9,2") == [4, 5, 6, 9, 2]
    assert compact_page_str([]) == "" and expand_page_str("") == []


# --------------------------------------------------------------------- #
# SlotPages: which pages back which slot (host bookkeeping, no device)
# --------------------------------------------------------------------- #
def _slot_pages(num_pages=0, share=True, num_slots=2):
    """An 8-page virtual lane of 16-position pages, prefill chunks of
    64 — the geometry of the chunk-alignment comment in ``reserve``."""
    stats = {"prefix_lookups": 0, "prefix_hits": 0,
             "prefix_tokens_reused": 0, "page_evictions": 0}
    sp = SlotPages(None, Transformer(tiny_cfg()).slot_contract(), num_slots,
                   cache_len=128, page_size=16, num_pages=num_pages,
                   chunk=64, share_prefixes=share, stats=stats)
    assert (sp.pages_per_slot, sp.cache_len) == (8, 128)
    return sp, stats


def _fill(seed, n=120):
    return np.random.default_rng(seed).integers(1, 97, (n,)).astype(np.int32)


def test_slot_pages_trims_the_matched_prefix_to_a_chunk_boundary():
    """Page 16, chunk 64, P=120: 7 indexed pages match, but a prefill
    from 112 would pad through 176, past the 8-page lane.  The match is
    trimmed to 4 pages — the start lands on a chunk boundary, the padded
    end on the lane's — and the 3 trimmed pages give their reference
    back."""
    sp, stats = _slot_pages()
    fill = _fill(1)
    row0, start0 = sp.reserve(0, fill, max_new=8)
    assert (len(row0), start0) == (8, 0)
    sp.share(0, fill)
    assert len(sp._prefix) == 7                  # 120 // 16 full pages
    row1, start1 = sp.reserve(1, fill, max_new=8)
    assert start1 == 64 and row1[:4] == row0[:4]
    assert not set(row1[4:]) & set(row0)
    assert len(row1) == 8                        # 64 + one chunk of 64
    ref = sp._pool.refcount
    assert [ref(p) for p in row0] == [3] * 4 + [2] * 3 + [1]
    assert (sp.row(1)[0] == row1).all() and (sp.table()[0] == row0).all()
    assert stats == {"prefix_lookups": 2, "prefix_hits": 1,
                     "prefix_tokens_reused": 64, "page_evictions": 0}


@pytest.mark.parametrize("share", [False, True])
def test_slot_pages_reserve_that_cannot_be_backed_allocates_nothing(share):
    """A pool of 9 pages with a slot holding 5 (still prefilling: nothing
    indexed yet): a request for 5 more is refused — no page allocated,
    every refcount as it was, no row, no lookup counted."""
    sp, stats = _slot_pages(num_pages=10, share=share)
    assert sp.cannot_hold(128) is None and "10 pages" in sp.cannot_hold(145)
    sp.reserve(0, _fill(1, 60), max_new=10)      # 70 positions: 5 pages
    refs, free, counted = sp._pool._ref.copy(), sp._pool.free_count, \
        dict(stats)
    assert sp.reserve(1, _fill(2, 60), max_new=10) is None
    assert (sp._pool._ref == refs).all() and sp._pool.free_count == free == 4
    assert 1 not in sp._rows and (sp.row(1) == 0).all()
    assert stats == counted
    # one chunk of 64 with its decode tail inside it: 4 pages
    assert sp.reserve(1, _fill(2, 40), max_new=8) is not None


def test_slot_pages_evicts_unreferenced_prefix_pages_and_retries():
    """Under pressure the index's pages go before the request waits: a
    retired request's 7 indexed pages are all the pool has left, and the
    next, unrelated request takes them."""
    sp, stats = _slot_pages(num_pages=9)
    fill = _fill(1)
    sp.reserve(0, fill, max_new=8)
    sp.share(0, fill)
    sp.release(0)
    assert sp.in_use == 7 == len(sp._prefix)     # the index's alone
    row, start = sp.reserve(1, _fill(2), max_new=8)
    assert (len(row), start) == (8, 0)
    assert stats["page_evictions"] == 7 and len(sp._prefix) == 0
    assert sp.in_use == 8 and sp.utilization == 1.0


def test_slot_pages_release_keeps_the_other_holders_pages():
    """Two slots behind one prefix: the first one's retirement drops one
    reference of each shared page — the second keeps reading them."""
    sp, _ = _slot_pages()
    fill = _fill(1)
    row0, _ = sp.reserve(0, fill, max_new=8)
    sp.share(0, fill)
    row1, _ = sp.reserve(1, fill, max_new=8)
    sp.release(0)
    ref = sp._pool.refcount
    assert [ref(p) for p in row0] == [2] * 4 + [1] * 3 + [0]
    assert (sp.table()[0] == 0).all() and (sp.row(1)[0] == row1).all()
    assert sp.slot_pages_str(0) is None
    assert expand_page_str(sp.slot_pages_str(1)) == row1
    sp.release(1)
    assert sp.in_use == 7 == len(sp._prefix)
    sp.reset()
    assert sp.in_use == 0 and len(sp._prefix) == 0


def test_gather_fallback_bitwise_and_fallback_counter(monkeypatch):
    """The pre-kernel gather path (the registry's ``reference_fallback``,
    reached here through the tests' ``DSTPU_DISABLE_FLASH=1`` switch):
    greedy outputs stay BITWISE-identical to the kernel path (both match
    solo generate()), the engine's kernel_modes attribution flips to
    reference_fallback, and every gather-path decode dispatch is counted
    in stats["paged_attention_fallback"] (the kernel path counts
    zero)."""
    eng_on = _build_engine()
    eng_off = _build_engine()
    rng = np.random.default_rng(31)
    prompts, news = _mixed_workload(rng, n=5)
    outs = {}
    for tag, eng in (("on", eng_on), ("off", eng_off)):
        if tag == "off":
            monkeypatch.setenv("DSTPU_DISABLE_FLASH", "1")
        srv = eng.serve()
        want = ("pallas_paged_decode" if tag == "on"
                else "reference_fallback")
        assert srv.kernel_modes["decode"] == want
        rids = [srv.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        res = srv.drain()
        _assert_bitwise(eng, res, rids, prompts, news)
        fb = srv.stats["paged_attention_fallback"]
        if tag == "on":
            assert fb == 0, fb
        else:
            assert fb == srv.stats["decode_calls"] > 0, fb
        outs[tag] = [res[r] for r in rids]
        srv.close()
    for a, b in zip(outs["on"], outs["off"]):
        np.testing.assert_array_equal(a, b)
