"""Continuous-batching serving engine tests, the chunk ROWS a prefill
dispatch takes (``inference/serving/``, ``docs/serving.md`` "Prefill
dispatches").  A file of its own beside ``test_serving.py`` — moved out of
it, test for test — because under ``--dist loadfile`` a file is one worker's
and the seven ``test_serving*`` files are the end of the run."""

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


# --------------------------------------------------------------------- #
# Prefill dispatches take chunk rows (docs/serving.md "Prefill
# dispatches"): at a chunk of 128 a dispatch holds 512 // 128 = 4 rows —
# the pending prompt's next chunks, then the next admissions' — and the
# iteration's grant is whole dispatches.
# --------------------------------------------------------------------- #
ROWS = {"enabled": True, "num_slots": 4, "max_cache_len": 640,
        "prefill_chunk": 128, "page_size": 64, "decode_block": 2}


@pytest.fixture(scope="module")
def rows_engine():
    model = Transformer(tiny_cfg(max_seq_len=1024))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 97, (2, 12)),
                      jnp.int32)
    params = model.init(jax.random.key(0), {"input_ids": ids})
    eng = deepspeed_tpu.init_inference(
        model, config={"dtype": "float32", "prefill_chunk_size": 128,
                       "serving": ROWS})
    eng.set_params(params)
    return eng


def _record_dispatches(srv, monkeypatch):
    """Every prefill dispatch's live rows as ``[(rid, chunk index)]``, the
    abstract shapes the chunk program was handed, and what each admit
    dispatch got past its seven arguments (the row of the logits)."""
    log, shapes, admit_rows = [], [], []
    run, guarded = srv._run_prefill_dispatch, srv.engine._run_guarded

    def dispatch(rows):
        log.append([(p.req.rid, ci) for p, ci in rows])
        return run(rows)

    def run_guarded(fn, args):
        if fn is srv._chunk_fn:
            shapes.append(tuple(a.shape for a in args[2:]))
        if fn is srv._admit_fn:
            admit_rows.append([int(a) for a in args[7:]])
        return guarded(fn, args)
    srv._run_prefill_dispatch = dispatch
    # the engine outlives the server (module fixture): undone after the test
    monkeypatch.setattr(srv.engine, "_run_guarded", run_guarded)
    return log, shapes, admit_rows


def _long_prompts(rng, lens):
    return [rng.integers(1, 97, (n,)).astype(np.int32) for n in lens]


def _assert_solo(eng, outs, rids, prompts, new):
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(
            outs[rid],
            np.asarray(eng.generate(p[None], max_new_tokens=new))[0],
            err_msg=f"request {rid} (P={len(p)})")


def test_rows_fill_in_order_and_every_finished_row_admits(rows_engine,
                                                          monkeypatch):
    """Three prompts of 3, 2 and 4 chunks in one iteration of an idle
    4-slot server (the grant: 4 budgets = 16 rows): rows fill in order
    across admissions, the last dispatch leaves dead rows once the queue
    is empty, every prompt whose last chunk rode a dispatch is admitted
    from its own row — tokens bitwise the solo runs'."""
    eng = rows_engine
    srv = eng.serve()
    try:
        assert (srv.chunk, srv.chunk_rows) == (128, 4)
        log, shapes, admit_rows = _record_dispatches(srv, monkeypatch)
        prompts = _long_prompts(np.random.default_rng(7), (300, 130, 500))
        rids = [srv.submit(p, max_new_tokens=5) for p in prompts]
        srv.step()
        a, b, c = rids
        assert log == [[(a, 0), (a, 1), (a, 2), (b, 0)],
                       [(b, 1), (c, 0), (c, 1), (c, 2)],
                       [(c, 3)]]
        # one program: 4 rows of 128, a start and a last position a row
        assert set(shapes) == {((4, srv.table_width), (4, 128), (4,), (4,))}
        assert srv.stats["prefill_dispatches"] == 3
        assert srv.stats["prefill_rows"] == 9
        assert srv.stats["prefill_tokens"] == 9 * 128
        assert srv.stats["admitted"] == 3 and srv._pending is None
        # a's last chunk rode row 2, b's row 0 of the second, c's row 0
        assert admit_rows == [[2], [0], [0]]
        _assert_solo(eng, srv.drain(), rids, prompts, 5)
    finally:
        srv.close()


@pytest.mark.parametrize("budget,live,want_rows", [
    (512, 0, 16),    # nobody waits: 4 budgets, 4 whole dispatches
    (512, 2, 8),
    (512, 3, 4),     # floor(5.33) = 5 rows -> one whole dispatch
    (512, 4, 4),     # the budget itself IS a dispatch
    (768, 3, 8),     # 8 rows, two dispatches; ceil(768 / 128) = 6 is less
    (640, 3, 5),     # 6 rows round down to 4: never under ceil(640/128) = 5
    (256, 4, 2),     # under a dispatch: granted as it is
    (0, 1, 0),       # 0 stays unbounded
])
def test_the_grant_is_whole_dispatches(rows_engine, budget, live,
                                       want_rows):
    """``_prefill_limit`` at 4 slots, chunk 128, 4 rows a dispatch: the
    live-lane budget in whole chunks, rounded down to whole dispatches
    where it is more than one, never under ``ceil(budget / chunk)``."""
    srv = rows_engine.serve(prefill_token_budget=budget)
    try:
        with srv._lock:
            srv._mirror_active[:live] = True
            limit, seen, widened = srv._prefill_limit()
            srv._mirror_active[:] = False
        assert (limit, seen) == (want_rows * 128, live)
        assert widened == (want_rows > -(-budget // 128) > 0)
    finally:
        srv.close()


def test_spent_counts_live_rows_and_the_pending_prompt_carries_over(
        rows_engine, monkeypatch):
    """One budget (4 rows) an iteration: a 3-chunk prompt shares its
    dispatch with the next admission's first chunk, whose other three
    ride the next iteration's beside a third prompt's first."""
    eng = rows_engine
    srv = eng.serve()
    try:
        log, _, admit_rows = _record_dispatches(srv, monkeypatch)
        prompts = _long_prompts(np.random.default_rng(11), (300, 500, 200))
        rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
        a, b, c = rids
        with srv._lock:
            srv._mirror_active[:] = True        # every lane waits: 1 budget
            assert srv._prefill_limit()[0] == 512
            srv._mirror_active[:] = False
            srv._ensure_workspace()
            tokens0 = srv.stats["prefill_tokens"]
            srv._admit_under_budget(512)
            assert srv.stats["prefill_tokens"] - tokens0 == 512
            assert srv._pending.req.rid == b and srv._pending.ci == 1
            srv._admit_under_budget(512)
            assert srv._pending.req.rid == c and srv._pending.ci == 1
        assert log == [[(a, 0), (a, 1), (a, 2), (b, 0)],
                       [(b, 1), (b, 2), (b, 3), (c, 0)]]
        assert admit_rows == [[2], [2]]
        _assert_solo(eng, srv.drain(), rids, prompts, 4)
    finally:
        srv.close()


def test_pool_pressure_mid_dispatch_leaves_rows_dead(rows_engine,
                                                     monkeypatch):
    """A pool that backs one request: the second admission stalls while
    the first one's rows are placed — the dispatch goes out with dead
    rows, exactly one stall is counted, and the stalled request runs once
    pages come back."""
    eng = rows_engine
    # 130 + 4 positions = 3 pages a request; 4 pages + trash back one
    srv = eng.serve(num_pages=5)
    try:
        log, _, _ = _record_dispatches(srv, monkeypatch)
        prompts = _long_prompts(np.random.default_rng(13), (130, 131))
        rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
        srv.step()
        assert log == [[(rids[0], 0), (rids[0], 1)]]
        assert srv.stats["admission_stalls"] == 1
        assert srv.stats["prefill_rows"] == 2
        _assert_solo(eng, srv.drain(), rids, prompts, 4)
        assert log[1] == [(rids[1], 0), (rids[1], 1)]
    finally:
        srv.close()


def test_a_failed_dispatch_loses_every_admission_that_rode_it(rows_engine):
    """The donated pool dies with the dispatch: both prompts in its rows
    end ABORTED (and the lane that was decoding), the queued request
    survives and is served on the fresh pool."""
    from deepspeed_tpu.inference.serving.slo import RequestStatus
    eng = rows_engine
    srv = eng.serve(num_slots=3)
    try:
        rng = np.random.default_rng(17)
        prompts = _long_prompts(rng, (140, 200, 150, 135))
        rids = [srv.submit(p, max_new_tokens=3) for p in prompts]
        guarded = eng._run_guarded

        def boom(fn, args):
            if fn is srv._chunk_fn:
                eng._run_guarded = guarded
                raise RuntimeError("injected chunk failure")
            return guarded(fn, args)
        eng._run_guarded = boom
        with pytest.raises(RuntimeError, match="injected chunk failure"):
            srv.step()
        lost = [srv.result(r) for r in rids[:2]]
        assert [r.status for r in lost] == [RequestStatus.ABORTED] * 2
        assert all("prefill dispatch failed" in r.detail for r in lost)
        assert srv._pending is None and len(srv._free) == 3
        assert srv._pages.in_use == 0
        outs = srv.drain()
        _assert_solo(eng, outs, rids[2:], prompts[2:], 3)
    finally:
        eng._run_guarded = guarded
        srv.close()


def test_preempt_with_several_admissions_in_flight(rows_engine, tmp_path):
    """One iteration admits two prompts and leaves a third pending; the
    snapshot taken right there restores on a fresh server and every
    request's stitched output is its solo run's."""
    eng = rows_engine
    srv = eng.serve(prefill_token_budget=128)       # 4 rows an idle step
    prompts = _long_prompts(np.random.default_rng(19), (129, 200, 500))
    rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
    with srv._lock:
        srv._mirror_active[:] = True                # one budget: 4 rows...
        srv._ensure_workspace()
        srv._admit_under_budget(5 * 128)            # ...and one more
        srv._mirror_active[:] = False
    assert srv.stats["admitted"] == 2
    assert srv._pending.req.rid == rids[2] and srv._pending.ci == 1
    tag, snapped, fin = srv.preempt(str(tmp_path), drain_budget_s=0.0)
    assert sorted(snapped) == sorted(set(rids) - set(fin))
    srv2 = eng.serve()
    try:
        srv2.restore(str(tmp_path))
        outs = {**fin, **srv2.drain()}
        _assert_solo(eng, outs, rids, prompts, 6)
    finally:
        srv2.close()


def test_cancel_of_the_pending_prompt_spares_those_admitted_beside_it(
        rows_engine):
    eng = rows_engine
    srv = eng.serve()
    try:
        prompts = _long_prompts(np.random.default_rng(23), (140, 500))
        rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
        with srv._lock:
            srv._ensure_workspace()
            srv._admit_under_budget(4 * 128)
        assert srv._pending.req.rid == rids[1] and srv._pending.ci == 2
        assert srv.cancel(rids[1])
        assert srv._pending is None
        outs = srv.drain()
        assert outs[rids[1]] is None
        _assert_solo(eng, outs, rids[:1], prompts[:1], 4)
        assert len(srv._free) == srv.num_slots
    finally:
        srv.close()


@pytest.mark.parametrize("case,want", [
    ("dense_chunk_128", 4), ("dense_chunk_8", 64), ("dense_chunk_256", 2),
    ("chunk_512", 1), ("speculation", 1), ("experts", 4),
    ("state_kinds", 1), ("own_chunk_path", 1), ("chunk_cap", 4),
    ("chunk_fault", 4), ("row_scatter_form", 1),
])
def test_who_takes_rows_is_observed(case, want):
    """``chunk_rows``: what the chunk kernel's bound holds of the chunk
    the user set, dropless experts or none — and ONE row for a contract
    with per-slot state or a chunk geometry of its own (``own_chunk_path``,
    SAID: a contract that merely declares a cap or a fault function, the
    defaults' own values, gets the rows a silent one gets), under
    speculation, where the chunk is the bound already, and where the write
    is not page runs."""
    import dataclasses
    from deepspeed_tpu.inference.serving.slots import chunk_rows
    contract = Transformer(tiny_cfg()).slot_contract()
    chunk, page, spec = 128, 64, False
    if case.startswith(("dense_chunk_", "chunk_512")):
        chunk = int(case.rsplit("_", 1)[1])
        page = min(chunk, 64)
    elif case == "speculation":
        spec = True
    elif case == "experts":
        contract = Transformer(tiny_cfg(
            moe_num_experts=4, moe_top_k=2, moe_capacity_factor=None,
            scan_layers=False)).slot_contract()
        assert contract.routes_experts
    elif case == "row_scatter_form":
        chunk, page = 96, 64
    else:
        contract = dataclasses.replace(contract, **{
            "state_kinds": {"state_kinds": ("conv",)},
            "own_chunk_path": {"own_chunk_path": True},
            "chunk_cap": {"chunk_cap": 512},
            "chunk_fault": {"chunk_fault": lambda chunk: None}}[case])
    assert chunk_rows(contract, chunk, page, spec) == want


def test_one_row_servers_keep_the_scalar_start_program(rows_engine,
                                                       monkeypatch):
    """``prefill_chunk`` 512 is the kernel's bound already: one row a
    dispatch, handed to the program with today's abstract signature —
    a ``[1, table_width]`` table row, ``[1, 512]`` ids, a SCALAR start —
    and no row index to the admit program."""
    eng = rows_engine
    srv = eng.serve(prefill_chunk=512, max_cache_len=1024, num_slots=2)
    try:
        assert srv.chunk_rows == 1
        _, shapes, admit_rows = _record_dispatches(srv, monkeypatch)
        prompt = _long_prompts(np.random.default_rng(29), (600,))[0]
        rid = srv.submit(prompt, max_new_tokens=3)
        out = srv.drain()[rid]
        assert shapes == [((1, srv.table_width), (1, 512), (), (1,))] * 2
        assert admit_rows == [[]]
        assert srv.stats["prefill_dispatches"] == 2 \
            == srv.stats["prefill_rows"]
        assert out.shape == (603,)
    finally:
        srv.close()


# --------------------------------------------------------------------- #
# An expert model takes rows too: an expert layer flattens a dispatch's
# rows to tokens, so 4 rows of 128 are ONE call of it — 512 tokens, which
# take the sorted form (``moe.experts_grouped``), gated experts and since PR
# 60 un-gated ones (its two-matrix body) alike — and the dispatch has one
# load vector, whoever's rows it carried.
# --------------------------------------------------------------------- #
TOP_K, EXPERT_LAYERS = 2, 1              # moe_every 2 of 2 layers
EXPERT_FORMS = {"sorted": dict(gated_mlp=True, activation="silu"),
                "gmm": {}}                # un-gated experts (two matrices)


@pytest.mark.parametrize("form", list(EXPERT_FORMS))
def test_expert_rows_of_two_prompts_and_a_dead_row(form, tmp_path):
    """Prompts of 2 and 1 chunks in one iteration of an idle server (three
    live rows and a dead one), then one of 4 chunks, traced.  ONE test a
    form — one engine built, in one worker, whatever ``--dist`` says:

    * greedy tokens of every request are its solo ``generate()`` run's
      (one row of 128 a call there: ``moe.experts_gmm`` for either kind),
      and the 7 chunks took ``ceil(7 / 4)`` dispatches;
    * every live token — the prompts' 800, and each generated token but a
      request's last — chose ``top_k`` experts an expert layer; the dead
      row's 128 tokens and the tails' 96 chose none;
    * the dispatch's one load vector rides with its FIRST row's admission
      and is summed once: the admit waits carry ``moe_calls`` = expert
      layers for requests 0 and 2 and nothing for request 1, and the spans'
      assignments add up to the statistics'."""
    import json
    from deepspeed_tpu.monitor import trace as span_trace
    model = Transformer(tiny_cfg(
        max_seq_len=1024, moe_num_experts=4, moe_top_k=TOP_K,
        moe_capacity_factor=None, scan_layers=False, **EXPERT_FORMS[form]))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 97, (2, 12)),
                      jnp.int32)
    eng = deepspeed_tpu.init_inference(
        model, config={"dtype": "float32", "prefill_chunk_size": 128,
                       "serving": ROWS})
    eng.set_params(model.init(jax.random.key(0), {"input_ids": ids}))
    srv, patch = eng.serve(tracing=True), pytest.MonkeyPatch()
    try:
        assert (srv.chunk, srv.chunk_rows) == (128, 4)
        log, _, _ = _record_dispatches(srv, patch)
        prompts = _long_prompts(np.random.default_rng(31), (200, 100, 500))
        rids = [srv.submit(p, max_new_tokens=5) for p in prompts[:2]]
        srv.step()
        rids.append(srv.submit(prompts[2], max_new_tokens=5))
        a, b, c = rids
        outs = srv.drain()
        path = srv.dump_trace(str(tmp_path / "trace.json"))
        with srv._lock:
            stats, tokens = dict(srv.stats), srv.moe_expert_tokens.copy()
    finally:
        patch.undo()
        srv.close()
        span_trace.disable()
    assert log == [[(a, 0), (a, 1), (b, 0)],
                   [(c, 0), (c, 1), (c, 2), (c, 3)]]
    assert stats["prefill_dispatches"] == 2 and stats["prefill_rows"] == 7
    _assert_solo(eng, outs, rids, prompts, 5)

    prompt_tokens = sum(len(p) for p in prompts)
    live = prompt_tokens + 3 * (5 - 1)
    assert stats["moe_assignments"] == live * TOP_K * EXPERT_LAYERS \
        == tokens.sum()
    assert tokens.shape == (EXPERT_LAYERS, 4)

    with open(path) as f:
        events = json.load(f)["traceEvents"]
    loads = [e["args"] for e in events
             if e["name"] in ("dstpu.sched.wait_device", "dstpu.sched.commit")
             and "moe_calls" in e["args"]]
    admits = [x for x in loads if x.get("event") == "admit"]
    assert [x["moe_calls"] for x in admits] == [EXPERT_LAYERS] * 2
    assert sum(x["moe_assignments"] for x in admits) \
        == prompt_tokens * TOP_K * EXPERT_LAYERS
    assert sum(x["moe_assignments"] for x in loads) \
        == stats["moe_assignments"]
