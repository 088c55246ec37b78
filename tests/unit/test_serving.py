"""Continuous-batching serving engine tests (``inference/serving/``).

The scheduler-correctness acceptance contract: with fewer slots than
requests, every request's output is BITWISE-identical to its solo
``generate()`` run (greedy), EOS retirement frees slots mid-decode
(asserted via the slot-occupancy trace), and exactly one decode-step
executable is compiled for the whole run — plus compile-cache counters
proving a restarted server RELOADS the decode program instead of
recompiling it."""

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


SERVING = {"enabled": True, "num_slots": 3, "max_cache_len": 64,
           "prefill_chunk": 8, "prefill_token_budget": 16,
           "decode_block": 2}


@pytest.fixture
def served_engine():
    model = Transformer(tiny_cfg())
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 97, (2, 12)),
                      jnp.int32)
    params = model.init(jax.random.key(0), {"input_ids": ids})
    # prefill_chunk_size=8: solo generate() reference runs the SAME
    # split-prefill chunk program the serving admission path replays
    eng = deepspeed_tpu.init_inference(
        model, config={"dtype": "float32", "prefill_chunk_size": 8,
                       "serving": SERVING})
    eng.set_params(params)
    return eng


def _mixed_workload(rng, n=7):
    lens = rng.integers(9, 21, (n,))          # > chunk: solo also splits
    news = rng.integers(3, 13, (n,))
    prompts = [rng.integers(1, 97, (int(p),)).astype(np.int32)
               for p in lens]
    return prompts, [int(x) for x in news]


def test_serving_matches_solo_generate(served_engine):
    """The acceptance contract: num_slots(3) < num_requests(7); greedy
    outputs bitwise-equal to solo generate(); EOS frees slots mid-decode;
    ONE decode-step executable for the whole run."""
    eng = served_engine
    rng = np.random.default_rng(3)
    prompts, news = _mixed_workload(rng)

    # per-request eos that actually fires mid-stream for some requests:
    # probe the greedy continuation and pick the token emitted ~halfway
    eos_ids = []
    for i, (p, n) in enumerate(zip(prompts, news)):
        if i % 2 == 0:
            probe = np.asarray(eng.generate(p[None], max_new_tokens=n))[0]
            eos_ids.append(int(probe[len(p) + n // 2]))
        else:
            eos_ids.append(-1)

    srv = eng.serve()
    rids = [srv.submit(p, max_new_tokens=n, eos_token_id=e)
            for p, n, e in zip(prompts, news, eos_ids)]
    outs = srv.drain()
    assert sorted(outs) == sorted(rids)

    for rid, p, n, e in zip(rids, prompts, news, eos_ids):
        want = np.asarray(eng.generate(p[None], max_new_tokens=n,
                                       eos_token_id=e))[0]
        np.testing.assert_array_equal(
            outs[rid], want,
            err_msg=f"request {rid} (P={len(p)}, new={n}, eos={e}) "
                    f"diverges from its solo generate() run")

    # EOS retirement mid-flight: the occupancy trace must show slots
    # FREEING while later requests still got admitted afterwards (churn:
    # occupancy dips and recovers)
    occ = [o for _, o in srv.occupancy_trace]
    assert any(occ[i] < occ[i - 1] for i in range(1, len(occ))), occ
    assert any(occ[i] > occ[i - 1] for i in range(1, len(occ))), occ
    assert srv.stats["completed"] == len(rids)
    assert srv.stats["admitted"] == len(rids)

    # exactly ONE decode-step executable for the whole run: slot
    # occupancy/EOS/admission all ride traced arguments
    n_decode_sigs = sum(1 for sig in eng._aot
                        if sig and sig[0] == id(srv._decode_fn))
    assert n_decode_sigs == 1, n_decode_sigs


def test_serving_slot_lane_reuse_no_stale_rows(served_engine):
    """A slot lane reused across requests must not leak the previous
    occupant's KV rows: run a LONG request through a slot, then a SHORT
    one (strictly inside the old live region) with single-slot serving —
    its output must equal the solo run on a fresh cache."""
    eng = served_engine
    rng = np.random.default_rng(11)
    long_p = rng.integers(1, 97, (20,)).astype(np.int32)
    short_p = rng.integers(1, 97, (9,)).astype(np.int32)
    srv = eng.serve(num_slots=1)
    r1 = srv.submit(long_p, max_new_tokens=12)
    r2 = srv.submit(short_p, max_new_tokens=4)
    outs = srv.drain()
    want1 = np.asarray(eng.generate(long_p[None], max_new_tokens=12))[0]
    want2 = np.asarray(eng.generate(short_p[None], max_new_tokens=4))[0]
    np.testing.assert_array_equal(outs[r1], want1)
    np.testing.assert_array_equal(outs[r2], want2)


def test_serving_decode_block_invariance(served_engine):
    """Tokens are independent of the decode block size (the block only
    changes how many steps run per host round trip)."""
    eng = served_engine
    rng = np.random.default_rng(5)
    prompts, news = _mixed_workload(rng, n=5)
    ref = None
    for block in (1, 3):
        srv = eng.serve(decode_block=block)
        rids = [srv.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        outs = srv.drain()
        got = [outs[r] for r in rids]
        if ref is None:
            ref = got
        else:
            for a, b in zip(ref, got):
                np.testing.assert_array_equal(a, b)


def test_serving_submit_while_running(served_engine):
    """Requests submitted mid-flight join freed slots (in-flight batching,
    not batch boundaries)."""
    eng = served_engine
    rng = np.random.default_rng(7)
    p1 = rng.integers(1, 97, (10,)).astype(np.int32)
    p2 = rng.integers(1, 97, (13,)).astype(np.int32)
    srv = eng.serve()
    r1 = srv.submit(p1, max_new_tokens=8)
    outs = {}
    outs.update(srv.step())
    outs.update(srv.step())
    r2 = srv.submit(p2, max_new_tokens=5)      # joins while r1 decodes
    while srv.queue_depth or srv.active_slots:
        outs.update(srv.step())
    np.testing.assert_array_equal(
        outs[r1], np.asarray(eng.generate(p1[None], max_new_tokens=8))[0])
    np.testing.assert_array_equal(
        outs[r2], np.asarray(eng.generate(p2[None], max_new_tokens=5))[0])


def _fill_lanes_then_queue(eng, live, slots=4, **over):
    """A ``slots``-slot server with ``live`` lanes decoding (long outputs:
    they stay live) and a queue deeper than any limit can drain: prompts
    of 40 tokens = 5 chunks of 8 apiece."""
    rng = np.random.default_rng(19)
    srv = eng.serve(num_slots=slots, **over)
    for _ in range(live):
        srv.submit(rng.integers(1, 97, (9,)).astype(np.int32),
                   max_new_tokens=40)
    while srv.queue_depth or srv.active_slots < live:
        srv.step()
    for _ in range(2 * slots):
        srv.submit(rng.integers(1, 97, (40,)).astype(np.int32),
                   max_new_tokens=2)
    return srv


@pytest.mark.parametrize("budget,live,want_chunks", [
    (16, 0, 8),     # nobody waits: num_slots budgets (16 x 4 / 8)
    (16, 1, 8),     # floor(16 x 4 / (8 x 1))
    (16, 2, 4),     # half the lanes wait: twice the budget
    (16, 3, 2),     # floor(2.67) = ceil(16 / 8): never fewer than that
    (12, 3, 2),     # a budget that is no whole chunk: ceil(12 / 8) = 2
    (0, 2, 10),     # 0 stays unbounded: both free slots fill, 5 chunks each
])
def test_prefill_budget_follows_live_lanes(served_engine, budget, live,
                                           want_chunks):
    """One ``step()`` of a 4-slot server with ``live`` lanes live and a
    deep queue prefills ``floor(budget x slots / (chunk x live))``
    chunks — never fewer than the configured budget alone buys, up to
    ``slots`` budgets when no lane is live — and ``prefill_token_budget
    = 0`` is still unbounded.  ``live`` is read BEFORE the iteration's
    own admissions."""
    srv = _fill_lanes_then_queue(served_engine, live,
                                 prefill_token_budget=budget)
    assert srv.active_slots == live
    tokens0 = srv.stats["prefill_tokens"]
    widened0 = srv.stats["prefill_budget_widened"]
    srv.step()
    assert (srv.stats["prefill_tokens"] - tokens0) // srv.chunk \
        == want_chunks
    base = -(-budget // srv.chunk)
    assert srv.stats["prefill_budget_widened"] - widened0 \
        == (budget > 0 and want_chunks > base)
    srv.close()


def test_prefill_budget_changes_the_schedule_not_the_tokens(served_engine):
    """The same request set under the live-lane budget (16) and under
    no budget (0) gives bit-identical greedy outputs — each equal to its
    solo ``generate()`` — in no more iterations the wider the limit."""
    eng = served_engine
    rng = np.random.default_rng(23)
    prompts, news = _mixed_workload(rng, n=9)
    outs, iters = [], []
    for budget in (16, 0):
        srv = eng.serve(num_slots=4, prefill_token_budget=budget)
        rids = [srv.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        got = srv.drain()
        outs.append([got[r] for r in rids])
        iters.append(srv.stats["iterations"])
        srv.close()
    for a, b, p, n in zip(*outs, prompts, news):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(
            a, np.asarray(eng.generate(p[None], max_new_tokens=n))[0])
    assert iters[1] <= iters[0]


def test_serving_admission_policies_and_validation(served_engine):
    eng = served_engine
    rng = np.random.default_rng(9)
    srv = eng.serve(admission="shortest_first", num_slots=1,
                    prefill_token_budget=0)
    long_p = rng.integers(1, 97, (20,)).astype(np.int32)
    short_p = rng.integers(1, 97, (9,)).astype(np.int32)
    r_long = srv.submit(long_p, max_new_tokens=3)
    r_short = srv.submit(short_p, max_new_tokens=3)
    first_done = None
    while first_done is None:
        done = srv.step()
        if done:
            first_done = sorted(done)
    # shortest_first: the short prompt (submitted second) admits first
    assert first_done[0] == r_short
    srv.drain()

    with pytest.raises(ValueError, match="cache positions"):
        srv.submit(np.ones((60,), np.int32), max_new_tokens=32)
    with pytest.raises(ValueError, match="max_new_tokens"):
        srv.submit(short_p, max_new_tokens=0)
    with pytest.raises(ValueError, match="empty"):
        srv.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="admission"):
        eng.serve(admission="priority")


def test_serving_max_new_one_and_first_token_eos(served_engine):
    """Requests that finish AT admission (max_new=1, or eos on the first
    token) release their slot without ever entering decode."""
    eng = served_engine
    rng = np.random.default_rng(13)
    p = rng.integers(1, 97, (9,)).astype(np.int32)
    want1 = np.asarray(eng.generate(p[None], max_new_tokens=1))[0]
    first_tok = int(want1[-1])
    srv = eng.serve()
    r1 = srv.submit(p, max_new_tokens=1)
    r2 = srv.submit(p, max_new_tokens=6, eos_token_id=first_tok)
    outs = srv.drain()
    np.testing.assert_array_equal(outs[r1], want1)
    want2 = np.asarray(eng.generate(p[None], max_new_tokens=6,
                                    eos_token_id=first_tok))[0]
    np.testing.assert_array_equal(outs[r2], want2)
    assert srv.stats["decode_tokens"] == 0       # nothing ever decoded


def test_serving_sampled_generation_runs(served_engine):
    eng = served_engine
    rng = np.random.default_rng(15)
    prompts, news = _mixed_workload(rng, n=4)
    srv = eng.serve(do_sample=True, temperature=0.8, top_k=10, top_p=0.9)
    rids = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    outs = srv.drain()
    for rid, p, n in zip(rids, prompts, news):
        assert outs[rid].shape == (len(p) + n,)
        assert (outs[rid] >= 0).all() and (outs[rid] < 97).all()


def test_serving_row_step_efficiency(served_engine):
    """The perf mechanism, deterministically (no wall clocks): on a
    mixed-completion workload the serving engine spends fewer decode
    row-steps (iterations x slots) than lockstep whole-batch generate()
    spends (batch x the batch's max max_new) — the waste continuous
    batching exists to recover."""
    eng = served_engine
    rng = np.random.default_rng(17)
    prompts = [rng.integers(1, 97, (int(p),)).astype(np.int32)
               for p in rng.integers(9, 16, (8,))]
    news = [2, 30, 2, 30, 2, 30, 2, 30]
    srv = eng.serve(num_slots=2, max_cache_len=64, decode_block=2)
    rids = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    srv.drain()
    serving_row_steps = srv.stats["decode_calls"] * srv.block * 2
    # lockstep: 4 sequential batches of 2, each decoding to ITS max (30)
    lockstep_row_steps = 4 * 2 * 30
    assert serving_row_steps < lockstep_row_steps, \
        (serving_row_steps, lockstep_row_steps)


def test_serving_monitor_events(served_engine):
    """Per-iteration Serving/* monitor events (queue depth, occupancy,
    decode tokens/s, prefill/decode ratio) + Compile/ events from warmup."""
    eng = served_engine

    class FakeMonitor:
        enabled = True

        def __init__(self):
            self.events = []

        def write_events(self, evs):
            self.events.extend(evs)

    mon = FakeMonitor()
    srv = eng.serve(monitor=mon)
    srv.warmup()
    rng = np.random.default_rng(19)
    prompts, news = _mixed_workload(rng, n=4)
    for p, n in zip(prompts, news):
        srv.submit(p, max_new_tokens=n)
    srv.drain()
    names = {n for n, _, _ in mon.events}
    for want in ("Serving/queue_depth", "Serving/slot_occupancy",
                 "Serving/decode_tok_s", "Serving/prefill_decode_ratio",
                 "Serving/completed"):
        assert want in names, names
    assert any(n.startswith("Compile/serving_decode") for n in names), names
    occ = [v for n, v, _ in mon.events if n == "Serving/slot_occupancy"]
    assert occ and max(occ) <= 1.0 and min(occ) >= 0.0


def test_serving_programs_bypass_persistent_cache_across_restarts(tmp_path):
    """The serving slot programs must NOT round-trip either persistent
    cache layer: cross-process reloaded serving executables corrupt the
    donated slot workspace (wrong tokens / cross-lane mixing / segfaults
    — bisected with the kill-harness driver, see
    ServingEngine.__init__).  A restarted server recompiles its three
    serving programs — zero store saves/hits — and serves outputs
    bitwise-identical to the first server's."""
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
                                    "cache_dir": str(tmp_path),
                                    "min_compile_time_secs": 0.0}}

        def run_server():
            eng = deepspeed_tpu.init_inference(model, config=config)
            eng.set_params(params)
            srv = eng.serve()
            report = srv.warmup()
            rng = np.random.default_rng(3)
            p = rng.integers(1, 97, (11,)).astype(np.int32)
            rid = srv.submit(p, max_new_tokens=5)
            out = srv.drain()[rid]
            return report, out

        s0 = cc.stats().snapshot()
        report1, out1 = run_server()
        s1 = cc.stats().snapshot()
        # the decode program really compiled — and NOTHING serving was
        # persisted to the executable store
        assert any(k.startswith("serving_decode") for k in report1)
        assert s1["executable_saves"] == s0["executable_saves"]

        report2, out2 = run_server()
        s2 = cc.stats().snapshot()
        # restarted server: compiles again (a fresh report, no store
        # traffic), outputs bitwise-identical
        assert any(k.startswith("serving_decode") for k in report2)
        assert s2["executable_saves"] == s1["executable_saves"]
        assert s2["executable_hits"] == s1["executable_hits"]
        np.testing.assert_array_equal(out1, out2)
        # within one server lifetime nothing recompiles: warmup again is
        # a no-op (0.0 = already compiled in this process)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
        cc._configured_dir = prev_dir


def test_serving_decode_failure_recovers(served_engine):
    """A failed decode dispatch (donated cache/state dead) aborts the
    in-flight requests but must leave the scheduler CONSISTENT: every
    slot returns to the free list, stale events are dropped, and queued
    requests complete correctly on a fresh workspace afterwards
    (regression: the slots leaked and drain() spun forever; a stale
    admit event replayed against the fresh state emitted -1 garbage)."""
    eng = served_engine
    rng = np.random.default_rng(23)
    prompts, news = _mixed_workload(rng, n=6)
    srv = eng.serve(num_slots=2)
    for p, n in zip(prompts[:4], news[:4]):
        srv.submit(p, max_new_tokens=n)
    srv.step()
    srv.step()                                   # slots busy, events live

    real_run = eng._run_guarded
    blown = []

    def blow_decode(fn, args):
        if fn is srv._decode_fn and not blown:
            blown.append(True)
            for leaf in jax.tree.leaves((args[1], args[2])):
                if hasattr(leaf, "delete"):
                    leaf.delete()            # simulate post-donation death
            raise RuntimeError("injected decode failure")
        return real_run(fn, args)

    eng._run_guarded = blow_decode
    with pytest.raises(RuntimeError, match="injected decode failure"):
        srv.drain()
    eng._run_guarded = real_run
    # consistent after the failure: all slots free, nothing in flight
    assert len(srv._free) == 2 and not srv._events
    assert srv.active_slots == 0
    assert srv.stats.get("aborted", 0) >= 1

    # queued + fresh requests complete bitwise-correct on a new workspace
    tail = [srv.submit(p, max_new_tokens=n)
            for p, n in zip(prompts[4:], news[4:])]
    outs = srv.drain()
    for rid, p, n in zip(tail, prompts[4:], news[4:]):
        want = np.asarray(eng.generate(p[None], max_new_tokens=n))[0]
        np.testing.assert_array_equal(outs[rid], want)


def test_serving_close_releases_and_recovers(served_engine):
    """close() retires the server: workspaces released, undrained request
    ids reported (idempotently), submit() afterwards raises — a fresh
    serve() on the same engine reproduces the outputs bitwise."""
    eng = served_engine
    rng = np.random.default_rng(21)
    p = rng.integers(1, 97, (10,)).astype(np.int32)
    srv = eng.serve()
    r1 = srv.submit(p, max_new_tokens=4)
    out1 = srv.drain()[r1]
    q = srv.submit(p, max_new_tokens=4)        # left undrained on purpose
    undrained = srv.close()
    assert srv._cache is None
    assert undrained == [q]
    assert srv.result(q).status == "ABORTED"
    # idempotent: a second close() is a no-op reporting the same ids
    assert srv.close() == [q]
    with pytest.raises(RuntimeError, match="closed ServingEngine"):
        srv.submit(p, max_new_tokens=4)
    # a fresh server on the same engine serves identically
    srv2 = eng.serve()
    r2 = srv2.submit(p, max_new_tokens=4)
    out2 = srv2.drain()[r2]
    np.testing.assert_array_equal(out1, out2)
