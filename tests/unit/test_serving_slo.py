"""Serving SLO / robustness tests (``inference/serving/``,
``docs/serving.md`` "Robustness & SLOs").

Covers the typed terminal statuses (deadline shedding before admission
and in-slot, client cancellation), bounded-queue backpressure
(reject/block), the dispatch circuit breaker (trip, reject-with-reason,
half-open recovery), the drain() wall-clock timeout diagnostics, and the
graceful-preemption drain → crash-atomic snapshot → bitwise resume path
— including the acceptance proof that compile-cache counters show ZERO
new decode executables across an overload + drain + resume cycle.  (The
other one, a subprocess driver killed at EVERY serving fault-injection
seam, is ``test_serving_kill.py``: a file of its own, so ``--dist
loadfile`` hands it to another worker.)"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.serving.slo import (CircuitOpen, DrainTimeout,
                                                 QueueFull, RequestStatus)
from deepspeed_tpu.models.transformer import Transformer, TransformerConfig
from deepspeed_tpu.runtime.fault import inject
from deepspeed_tpu.runtime.fault.manifest import verify_manifest


@pytest.fixture(autouse=True)
def _disarm_injection():
    inject.reset_injection()
    yield
    inject.reset_injection()


def tiny_cfg(**over):
    base = dict(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
                max_seq_len=64, use_flash_attention=False, dtype="float32")
    base.update(over)
    return TransformerConfig(**base)


SERVING = {"enabled": True, "num_slots": 3, "max_cache_len": 64,
           "prefill_chunk": 8, "prefill_token_budget": 16,
           "decode_block": 2}


@pytest.fixture
def served_engine():
    model = Transformer(tiny_cfg())
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 97, (2, 12)),
                      jnp.int32)
    params = model.init(jax.random.key(0), {"input_ids": ids})
    eng = deepspeed_tpu.init_inference(
        model, config={"dtype": "float32", "prefill_chunk_size": 8,
                       "serving": SERVING})
    eng.set_params(params)
    return eng


def _prompts(rng, n, lo=9, hi=21):
    return [rng.integers(1, 97, (int(p),)).astype(np.int32)
            for p in rng.integers(lo, hi, (n,))]


# What a waiting request waits FOR: a free slot (the pool at its auto
# size, a page for every position of every slot) or free pages (a pool
# smaller than two of the test's requests need, with slots to spare) —
# the robustness flows below run under both.
SLOT_BOUND = pytest.param({}, id="slot-bound")


def _page_bound(num_pages):
    return pytest.param({"num_slots": 3, "page_size": 16,
                         "num_pages": num_pages}, id="page-bound")


def _pages_all_returned(srv):
    """No slot holds a page and every table row points at the trash
    page: what is still referenced is the prefix index's alone."""
    sp = srv._pages
    return (not sp._rows and (sp.table() == 0).all()
            and sp.in_use == len(sp._prefix))


# --------------------------------------------------------------------- #
# Deadlines: shed before admission, retire in-slot
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("pool", [SLOT_BOUND, _page_bound(5)])
def test_deadline_shed_before_admission(served_engine, pool):
    """A deadline that expires while the request waits — already expired
    at submit, or run out behind a running request, waiting for its slot
    or for its pages — sheds it from the queue with terminal status
    SHED_DEADLINE: it never occupies a slot and never holds a page,
    while the deadline-less request completes bitwise."""
    eng = served_engine
    rng = np.random.default_rng(41)
    p1, p2, p3 = _prompts(rng, 3)
    srv = eng.serve(**{"num_slots": 1, **pool})
    r_ok = srv.submit(p1, max_new_tokens=36, client_id="ok")   # 3-4 pages
    r_shed = srv.submit(p2, max_new_tokens=5, deadline_s=0.0)
    r_late = srv.submit(p3, max_new_tokens=20, deadline_s=60.0)  # 2-3
    outs = {}
    while srv.active_slots == 0:
        outs.update(srv.step())
    outs.update(srv.step())
    assert srv.status(r_late) == RequestStatus.QUEUED
    assert (srv.stats["admission_stalls"] > 0) == ("num_pages" in pool)
    srv._requests[r_late].deadline = time.monotonic() - 1.0  # force expiry
    outs.update(srv.drain())
    assert sorted(outs) == sorted([r_ok, r_shed, r_late])
    for rid in (r_shed, r_late):
        assert outs[rid] is None
        res = srv.result(rid)
        assert res.status == RequestStatus.SHED_DEADLINE
        assert "never occupied a slot" in res.detail
    assert srv.stats["admitted"] == 1, "shed requests must not admit"
    assert srv.stats["shed"] == 2
    assert _pages_all_returned(srv)
    ok = srv.result(r_ok)
    assert ok.status == RequestStatus.COMPLETED
    assert ok.client_id == "ok" and ok.ttft_s is not None
    np.testing.assert_array_equal(
        outs[r_ok], np.asarray(eng.generate(p1[None], max_new_tokens=36))[0])


def test_deadline_retires_in_slot_and_slot_is_reusable(served_engine):
    """An in-slot deadline expiry retires the request at the next
    scheduling point (host-mirror only — no device round trip) and the
    freed lane serves the next request bitwise-correctly."""
    eng = served_engine
    rng = np.random.default_rng(43)
    p1, p2 = _prompts(rng, 2)
    srv = eng.serve(num_slots=1)
    r1 = srv.submit(p1, max_new_tokens=30, deadline_s=60.0)
    r2 = srv.submit(p2, max_new_tokens=4)
    while srv.active_slots == 0:
        srv.step()
    srv._requests[r1].deadline = time.monotonic() - 1.0   # force expiry
    outs = srv.drain()
    assert outs[r1] is None
    assert srv.result(r1).status == RequestStatus.SHED_DEADLINE
    assert "in slot" in srv.result(r1).detail
    np.testing.assert_array_equal(
        outs[r2], np.asarray(eng.generate(p2[None], max_new_tokens=4))[0])


@pytest.mark.parametrize("pool", [SLOT_BOUND, _page_bound(5)])
def test_cancel_queued_and_running(served_engine, pool):
    eng = served_engine
    rng = np.random.default_rng(45)
    p1, p2, p3 = _prompts(rng, 3)
    srv = eng.serve(**{"num_slots": 1, **pool})
    r1 = srv.submit(p1, max_new_tokens=30)          # 3-4 pages of 16
    r2 = srv.submit(p2, max_new_tokens=20)          # 2-3
    # queued cancellation is immediate
    assert srv.cancel(r2) is True
    assert srv.result(r2).status == RequestStatus.CANCELLED
    assert srv.cancel(r2) is False, "terminal requests cannot re-cancel"
    # an id this server never issued is a CLIENT error, not a no-op
    with pytest.raises(KeyError, match="unknown request id"):
        srv.cancel(10**9)
    # in-slot cancellation retires at this scheduling point
    while srv.active_slots == 0:
        srv.step()
    assert srv.cancel(r1) is True
    assert srv.active_slots == 0
    assert _pages_all_returned(srv), "a cancelled slot's pages return"
    r3 = srv.submit(p3, max_new_tokens=4)
    outs = srv.drain()
    assert outs.get(r1, None) is None and outs.get(r2, "x") in (None, "x")
    np.testing.assert_array_equal(
        outs[r3], np.asarray(eng.generate(p3[None], max_new_tokens=4))[0])
    assert srv.stats["cancelled"] == 2
    assert _pages_all_returned(srv)


# --------------------------------------------------------------------- #
# Backpressure: bounded queue, reject / block
# --------------------------------------------------------------------- #
def test_backpressure_reject_and_block(served_engine):
    eng = served_engine
    rng = np.random.default_rng(47)
    prompts = _prompts(rng, 5)
    srv = eng.serve(num_slots=1, max_queue_depth=2, queue_policy="reject")
    srv.submit(prompts[0], max_new_tokens=3)
    srv.submit(prompts[1], max_new_tokens=3)
    with pytest.raises(QueueFull, match="max_queue_depth=2"):
        srv.submit(prompts[2], max_new_tokens=3)
    srv.drain()

    srv2 = eng.serve(num_slots=1, max_queue_depth=2, queue_policy="block")
    rids = [srv2.submit(p, max_new_tokens=3) for p in prompts]
    outs = srv2.drain()
    outs.update({r: srv2.result(r).output for r in rids
                 if r not in outs})          # finished during blocking
    for r, p in zip(rids, prompts):
        np.testing.assert_array_equal(
            outs[r], np.asarray(eng.generate(p[None], max_new_tokens=3))[0])

    with pytest.raises(ValueError, match="queue_policy"):
        eng.serve(queue_policy="drop")


# --------------------------------------------------------------------- #
# Circuit breaker
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("pool", [SLOT_BOUND, _page_bound(4)])
def test_circuit_breaker_trips_rejects_and_recovers(served_engine, pool):
    """N consecutive failed dispatches trip the breaker: failures are
    absorbed (every request that rode the lost dispatch ABORTED,
    scheduler stays consistent, every page mapping dropped with the pool
    buffer), submit() rejects with the reason, and after the cooldown a
    half-open probe closes it — the queued requests then complete
    bitwise."""
    eng = served_engine
    rng = np.random.default_rng(49)
    prompts = _prompts(rng, 6)
    # page-bound: 3 allocatable pages, 1-2 a request — one or two at a time
    srv = eng.serve(**{"num_slots": 2, **pool}, breaker_threshold=2,
                    breaker_cooldown_s=0.05)
    rids = [srv.submit(p, max_new_tokens=4) for p in prompts]

    real_run = eng._run_guarded
    sick = [True]
    rode = set()                 # whose chunks rode a failed dispatch

    def failing_run(fn, args):
        if sick[0]:
            raise RuntimeError("injected sick-device dispatch failure")
        return real_run(fn, args)

    dispatch = srv._run_prefill_dispatch

    def recording(rows):
        if sick[0]:
            rode.update(p.req.rid for p, _ in rows)
        return dispatch(rows)

    srv._run_prefill_dispatch = recording
    eng._run_guarded = failing_run
    try:
        srv.step()                       # failure 1 — absorbed
        assert not srv._breaker.open
        srv.step()                       # failure 2 — breaker trips
        assert srv._breaker.open
        assert srv._pages.in_use == 0 and _pages_all_returned(srv)
        with pytest.raises(CircuitOpen, match="consecutive dispatch"):
            srv.submit(prompts[0], max_new_tokens=2)
        # open breaker: no dispatches are attempted at all
        calls = srv.stats["prefill_tokens"]
        srv.step()
        assert srv.stats["prefill_tokens"] == calls
    finally:
        eng._run_guarded = real_run
    sick[0] = False
    time.sleep(0.06)                     # past the cooldown -> half-open
    outs = srv.drain()
    assert not srv._breaker.open
    aborted = [r for r in rids
               if srv.result(r).status == RequestStatus.ABORTED]
    done = [r for r in rids
            if srv.result(r).status == RequestStatus.COMPLETED]
    assert set(aborted) == rode and 2 <= len(aborted) <= 4 \
        and len(done) == len(rids) - len(aborted), \
        [srv.result(r).status for r in rids]
    for r in done:
        p = prompts[rids.index(r)]
        np.testing.assert_array_equal(
            outs[r], np.asarray(eng.generate(p[None], max_new_tokens=4))[0])
    assert srv._breaker.trips == 1
    assert (srv.stats["admission_stalls"] > 0) == ("num_pages" in pool)
    # after recovery a fresh submit works again
    r_new = srv.submit(prompts[0], max_new_tokens=3)
    np.testing.assert_array_equal(
        srv.drain()[r_new],
        np.asarray(eng.generate(prompts[0][None], max_new_tokens=3))[0])


def test_circuit_breaker_half_open_admits_submissions(served_engine):
    """A breaker that opened with an EMPTY queue must not lock the
    server out of submit() forever: once the cooldown elapses
    (half-open), submissions are admitted again and the next dispatch is
    the probe."""
    eng = served_engine
    rng = np.random.default_rng(59)
    (p1,) = _prompts(rng, 1)
    srv = eng.serve(num_slots=1, breaker_threshold=2,
                    breaker_cooldown_s=0.05)
    srv._breaker.record_failure(RuntimeError("boom 1"))
    srv._breaker.record_failure(RuntimeError("boom 2"))
    assert srv._breaker.open
    with pytest.raises(CircuitOpen):
        srv.submit(p1, max_new_tokens=3)
    time.sleep(0.06)                      # cooldown elapsed -> half-open
    r = srv.submit(p1, max_new_tokens=3)  # admitted: the probe's work
    out = srv.drain()[r]
    assert not srv._breaker.open          # probe dispatch succeeded
    np.testing.assert_array_equal(
        out, np.asarray(eng.generate(p1[None], max_new_tokens=3))[0])


def test_restore_rejects_requests_that_do_not_fit(served_engine, tmp_path):
    """A snapshot from a larger-lane server restored onto a smaller one:
    requests that cannot fit the new lanes are ABORTED with a clear
    reason (never streamed past the lane's end); fitting ones resume."""
    eng = served_engine
    rng = np.random.default_rng(61)
    big = eng.serve(max_cache_len=128, num_slots=2)
    r_big = big.submit(rng.integers(1, 97, (50,)).astype(np.int32),
                       max_new_tokens=40)
    r_ok = big.submit(rng.integers(1, 97, (10,)).astype(np.int32),
                      max_new_tokens=4)
    big.preempt(str(tmp_path), drain_budget_s=0.0)

    small = eng.serve(max_cache_len=64, num_slots=2)
    restored = small.restore(str(tmp_path))
    assert restored == [r_ok]
    res = small.result(r_big)
    assert res.status == RequestStatus.ABORTED
    assert "cache positions" in res.detail
    outs = small.drain()
    assert outs[r_ok] is not None and r_big in outs


# --------------------------------------------------------------------- #
# drain() timeout diagnostics
# --------------------------------------------------------------------- #
def test_drain_timeout_reports_per_slot_diagnostics(served_engine):
    eng = served_engine
    rng = np.random.default_rng(51)
    (p1,) = _prompts(rng, 1)
    srv = eng.serve(num_slots=2)
    r1 = srv.submit(p1, max_new_tokens=30)
    while srv.active_slots == 0:
        srv.step()
    srv._dispatch_decode = lambda: False          # wedge the scheduler
    with pytest.raises(DrainTimeout) as ei:
        srv.drain(timeout_s=0.2)
    msg = str(ei.value)
    assert "slot" in msg and f"request {r1}" in msg \
        and "last dispatch" in msg, msg


# --------------------------------------------------------------------- #
# Serving fault-injection seams
# --------------------------------------------------------------------- #
def test_serving_seams_registered_and_fire(served_engine):
    for point in ("serving.pre_admit", "serving.pre_decode_dispatch",
                  "serving.mid_drain", "serving.sigterm_at_iter"):
        assert point in inject.injection_points()
    # a raise at the decode seam propagates (breaker off = seed behavior)
    # and the scheduler recovers consistently afterwards
    eng = served_engine
    rng = np.random.default_rng(53)
    p1, p2 = _prompts(rng, 2)
    srv = eng.serve(num_slots=1)
    srv.submit(p1, max_new_tokens=4)
    inject.configure_injection({"point": "serving.pre_decode_dispatch",
                                "action": "raise"})
    with pytest.raises(IOError, match="injected transient fault"):
        srv.drain()
    inject.reset_injection()
    assert srv.active_slots == 0 and not srv._events
    r2 = srv.submit(p2, max_new_tokens=4)
    np.testing.assert_array_equal(
        srv.drain()[r2],
        np.asarray(eng.generate(p2[None], max_new_tokens=4))[0])


# --------------------------------------------------------------------- #
# Graceful preemption: drain -> snapshot -> bitwise resume (in-process)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("pool", [SLOT_BOUND, _page_bound(4)])
def test_preempt_snapshot_resume_bitwise(served_engine, tmp_path, pool):
    """Mid-flight preemption: undrained requests (including ones with
    PARTIAL token progress) snapshot crash-atomically; a fresh server
    restores them — same rids, prefix continuation — and every request's
    stitched output is bitwise its solo generate() run."""
    from deepspeed_tpu.inference.serving.snapshot import read_snapshot_tag
    eng = served_engine
    rng = np.random.default_rng(55)
    prompts = _prompts(rng, 5)
    news = [int(n) for n in rng.integers(6, 13, (5,))]
    # page-bound: 3 allocatable pages, 2 a request — one runs at a time
    pool = {"num_slots": 2, **pool}
    srv = eng.serve(**pool)
    rids = [srv.submit(p, max_new_tokens=n, client_id=i)
            for i, (p, n) in enumerate(zip(prompts, news))]
    early = {}
    for i in range(40):                   # some requests mid-decode
        early.update(srv.step())
        if i >= 5 and any(r is not None and 0 < len(r.tokens) < r.max_new
                          for r in srv._slots):
            break
    tag, snapped, finished = srv.preempt(str(tmp_path), drain_budget_s=0.0)
    finished = {**early, **finished}
    assert snapped, "expected undrained work at preemption"
    assert verify_manifest(str(tmp_path / tag)) == []
    state = read_snapshot_tag(str(tmp_path), tag)
    assert any(r["tokens"] for r in state["requests"]), \
        "expected a mid-decode request with partial tokens"
    assert {r["rid"] for r in state["requests"]} == set(snapped)
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit(prompts[0], max_new_tokens=2)
    # a stream subscribed AFTER the preemption (an HTTP handler that lost
    # the race under load) still ends with the typed event and its reason
    late, end = srv.token_events(snapped[0]), None
    while end is None:
        ev = late.get(timeout=5)
        end = ev if ev["event"] == "end" else None
    assert end["status"] == RequestStatus.PREEMPTED \
        and "resume" in end["detail"] and tag in end["detail"], end

    srv2 = eng.serve(**pool)
    restored = srv2.restore(str(tmp_path))
    assert sorted(restored) == sorted(snapped)
    assert srv2.stats["resumed"] == len(restored)
    outs = dict(finished)
    outs.update(srv2.drain())
    for rid, p, n in zip(rids, prompts, news):
        want = np.asarray(eng.generate(p[None], max_new_tokens=n))[0]
        np.testing.assert_array_equal(
            outs[rid], want,
            err_msg=f"resumed request {rid} diverges from solo run")
        assert srv2.result(rid).client_id == rids.index(rid) \
            if rid in restored else True
    assert (srv2.stats["admission_stalls"] > 0) == ("num_pages" in pool)
    # a new submission on the resumed server gets a fresh, unused rid
    assert srv2.submit(prompts[0], max_new_tokens=2) not in rids
    srv2.drain()
    assert _pages_all_returned(srv2)


def test_snapshot_corruption_walks_back(tmp_path):
    from deepspeed_tpu.inference.serving.snapshot import (
        load_newest_snapshot, save_snapshot)
    req = {"rid": 0, "client_id": None, "prompt": [1, 2, 3], "tokens": [],
           "max_new": 4, "eos": -1, "deadline_remaining_s": None,
           "submitted_it": 0}
    save_snapshot(str(tmp_path), "serving_1",
                  {"seq": 1, "next_rid": 1, "rng": [0, 0],
                   "requests": [req]})
    save_snapshot(str(tmp_path), "serving_2",
                  {"seq": 2, "next_rid": 2, "rng": [0, 0],
                   "requests": [dict(req, rid=1)]})
    tag, state = load_newest_snapshot(str(tmp_path))
    assert tag == "serving_2" and state["requests"][0]["rid"] == 1
    # size-preserving corruption: manifest checksums catch it, walk back
    payload = tmp_path / "serving_2" / "serving_state.json"
    raw = bytearray(payload.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    payload.write_bytes(bytes(raw))
    tag, state = load_newest_snapshot(str(tmp_path))
    assert tag == "serving_1" and state["requests"][0]["rid"] == 0
    # stale staging orphans are never candidates
    (tmp_path / "serving_9.tmp").mkdir()
    tag, _ = load_newest_snapshot(str(tmp_path))
    assert tag == "serving_1"


# --------------------------------------------------------------------- #
# The one-decode-executable invariant across overload + drain + resume
# --------------------------------------------------------------------- #
def test_overload_drain_resume_zero_new_decode_executables(tmp_path):
    """Acceptance: an overload burst (submits > slots, a deadline shed,
    a cancellation) + graceful drain + restarted-server resume mints
    ZERO new decode executables — each server compiles exactly ONE
    decode-step signature for its whole lifetime (overload, drain and
    resume all ride traced slot arguments), and the serving programs
    never touch the executable store (reloaded serving executables
    corrupt the slot workspace — ServingEngine.__init__)."""
    from deepspeed_tpu.runtime import compile_cache as cc

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        model = Transformer(tiny_cfg())
        ids = jnp.asarray(np.random.default_rng(0).integers(0, 97, (1, 12)),
                          jnp.int32)
        params = model.init(jax.random.key(0), {"input_ids": ids})
        config = {"dtype": "float32", "prefill_chunk_size": 8,
                  "serving": SERVING,
                  "compile_cache": {"enabled": True,
                                    "cache_dir": str(tmp_path / "cache"),
                                    "min_compile_time_secs": 0.0}}
        snap = str(tmp_path / "snap")
        rng = np.random.default_rng(57)
        prompts = _prompts(rng, 7)
        news = [int(n) for n in rng.integers(4, 9, (7,))]

        def fresh_server():
            eng = deepspeed_tpu.init_inference(model, config=config)
            eng.set_params(params)
            srv = eng.serve()
            return eng, srv, srv.warmup()

        # --- overload burst on a cold server, then graceful drain ---
        eng1, srv1, report1 = fresh_server()
        rids = [srv1.submit(p, max_new_tokens=n, client_id=i)
                for i, (p, n) in enumerate(zip(prompts[:5], news[:5]))]
        r_shed = srv1.submit(prompts[5], max_new_tokens=4, deadline_s=0.0)
        r_cancel = srv1.submit(prompts[6], max_new_tokens=4)
        srv1.cancel(r_cancel)
        early = {}
        for _ in range(4):
            early.update(srv1.step())
        s1 = cc.stats().snapshot()
        tag, snapped, finished = srv1.preempt(snap, drain_budget_s=0.0)
        finished = {**early, **finished}
        assert srv1.result(r_shed).status == RequestStatus.SHED_DEADLINE
        assert srv1.result(r_cancel).status == RequestStatus.CANCELLED

        # --- restarted server: resume and finish ---
        eng2, srv2, report2 = fresh_server()
        s2 = cc.stats().snapshot()
        # the restart compiled its own serving programs — no store
        # traffic in either direction (reloaded serving executables are
        # the corruption hazard the opt-out exists for)
        assert any(k.startswith("serving_decode") for k in report2)
        assert s2["executable_saves"] == s1["executable_saves"]
        assert s2["executable_hits"] == s1["executable_hits"]
        restored = srv2.restore(snap)
        assert sorted(restored) == sorted(snapped)
        outs = dict(finished)
        outs.update(srv2.drain())
        s3 = cc.stats().snapshot()
        assert s3["executable_saves"] == s1["executable_saves"], \
            "the overload+drain+resume cycle persisted a new executable"
        # the cycle minted no decode executables beyond ONE per server:
        # overload, shed, cancel, drain and resume all ride traced slot
        # arguments
        for srv, eng in ((srv1, eng1), (srv2, eng2)):
            n_decode = sum(1 for sig in eng._aot
                           if sig and sig[0] == id(srv._decode_fn))
            assert n_decode == 1, n_decode
        for rid, p, n in zip(rids, prompts[:5], news[:5]):
            want = np.asarray(
                eng2.generate(p[None], max_new_tokens=n))[0]
            np.testing.assert_array_equal(outs[rid], want)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
        cc._configured_dir = prev_dir
