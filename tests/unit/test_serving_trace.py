"""Serving observability tests (``docs/observability.md``): span
tracing, the flight recorder, histogram metrics and the debug
endpoints.

The acceptance contract: with ``serving.tracing`` OFF, serving outputs
and executable counts are identical to pre-observability behavior; with
it ON, greedy outputs stay bitwise-identical, ``dump_trace()`` emits
valid Chrome trace-event JSON holding one complete span tree per
request in a mixed 7-request/3-slot run, and every ``RequestResult``
carries a queue/prefill/host/decode latency breakdown that sums to the
measured wall total.  A breaker-open and a ``DrainTimeout`` each
produce a flight-recorder dump whose tail reconstructs the failing
dispatch sequence.  ``/metrics`` exposes TTFT / TBT / queue-wait /
dispatch-duration / lock-wait histograms that survive a text-format
round trip (with hostile label values), and TTFT/TBT stamps ride an
injectable clock and are never re-stamped by a late-attached
``TokenStream`` replay.

Deliberately the SMALLEST serving model in the suite (1 layer, hidden
32): every assertion here is about HOST bookkeeping, so the device
program only needs to exist — tier-1 runs under a hard wall-clock cap
and every serve() compiles a fresh program trio."""

import http.client
import json
import os
import re
import signal
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.serving.slo import DrainTimeout
from deepspeed_tpu.monitor import trace as span_trace
from deepspeed_tpu.models.transformer import Transformer, TransformerConfig

SERVING = {"enabled": True, "num_slots": 3, "max_cache_len": 64,
           "prefill_chunk": 8, "prefill_token_budget": 16,
           "decode_block": 2}


@pytest.fixture(scope="module")
def shared_engine():
    model = Transformer(TransformerConfig(
        vocab_size=61, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=64, use_flash_attention=False, dtype="float32"))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 61, (2, 12)),
                      jnp.int32)
    params = model.init(jax.random.key(0), {"input_ids": ids})
    eng = deepspeed_tpu.init_inference(
        model, config={"dtype": "float32", "prefill_chunk_size": 8,
                       "serving": SERVING})
    eng.set_params(params)
    return eng


def _workload(rng, n=7):
    prompts = [rng.integers(1, 61, (int(p),)).astype(np.int32)
               for p in rng.integers(9, 21, (n,))]
    news = [int(x) for x in rng.integers(3, 9, (n,))]
    return prompts, news


# --------------------------------------------------------------------- #
# Tracing on/off: bitwise outputs, zero new executables, span trees,
# latency breakdown
# --------------------------------------------------------------------- #
def test_tracing_off_on_bitwise_zero_new_execs_spans_breakdown(
        shared_engine, tmp_path):
    """The acceptance proof, one engine, two servers: the SAME mixed
    7-request/3-slot workload with tracing off and on — outputs
    bitwise-equal (the off-run's equality to solo generate() is
    test_serving.py's own proof), the same executable count minted by
    both servers (observability adds zero programs), dump_trace() holds
    one complete span tree per request, and the RequestResult breakdown
    sums exactly to latency_s."""
    eng = shared_engine
    rng = np.random.default_rng(7)
    prompts, news = _workload(rng)

    span_trace.disable()
    srv_off = eng.serve()
    n_aot_0 = len(eng._aot)
    rids = [srv_off.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, news)]
    outs_off = srv_off.drain()
    execs_off = len(eng._aot) - n_aot_0
    assert srv_off.histograms() is None
    assert srv_off.flightrec_snapshot() is None
    # tracing off: only queue_s (two monotonic stamps) is known
    res_off = srv_off.result(rids[0])
    assert res_off.queue_s is not None and res_off.latency_s is None
    assert 0 <= res_off.queue_s <= res_off.ttft_s
    # every seam's span() ran (as a profiler annotation) and the ring
    # stayed off: the process tracer holds nothing
    assert span_trace.tracer() is None
    with pytest.raises(RuntimeError, match="serving.tracing is off"):
        srv_off.dump_trace(str(tmp_path / "no.json"))
    with pytest.raises(RuntimeError, match="flight_recorder is off"):
        srv_off.dump_flightrec()
    srv_off.close()

    srv = eng.serve(tracing=True, flight_recorder=True,
                    flight_recorder_dir=str(tmp_path))
    n_aot_1 = len(eng._aot)
    rids_on = [srv.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, news)]
    outs_on = srv.drain()
    execs_on = len(eng._aot) - n_aot_1
    # zero-new-executables, extended over the observability layer:
    # every server compiles its own decode/admit/chunk trio (fresh fn
    # identities per serve()), and the tracing server minted EXACTLY
    # the same count — observability adds no program
    assert execs_on == execs_off, (execs_off, execs_on)
    n_decode = sum(1 for sig in eng._aot
                   if sig and sig[0] == id(srv._decode_fn))
    assert n_decode == 1, n_decode
    for r_off, r_on in zip(rids, rids_on):
        np.testing.assert_array_equal(
            outs_off[r_off], outs_on[r_on],
            err_msg="tracing changed serving outputs")

    # the engine's tracer IS the process's one tracer
    assert span_trace.tracer() is srv._tracer

    # ---- latency breakdown sums exactly to the measured wall total
    for rid in rids_on:
        res = srv.result(rid)
        parts = (res.queue_s, res.prefill_s, res.host_s, res.decode_s)
        assert all(p is not None and p >= 0 for p in parts), res
        assert res.latency_s > 0
        assert abs(sum(parts) - res.latency_s) < 1e-9, (parts,
                                                        res.latency_s)
        assert res.ttft_s is not None

    # ---- Chrome trace export: valid JSON, one span tree per request
    path = srv.dump_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        trace = json.load(f)
    evs = trace["traceEvents"]
    assert trace["otherData"]["dropped"] == 0
    tracks = {e["args"]["name"] for e in evs if e.get("ph") == "M"}
    # one track per slot plus the scheduler/queue/handler threads
    assert {"scheduler", "queue", "handler"} <= tracks, tracks
    assert {f"slot {s}" for s in range(srv.num_slots)} <= tracks, tracks
    for e in evs:
        assert e["ph"] in ("X", "M", "i"), e
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float)) \
                and isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    by_rid = {}
    for e in evs:
        a = e.get("args", {})
        if e.get("ph") == "X" and "rid" in a:
            by_rid.setdefault(a["rid"], set()).add(e["name"])
    for rid in rids_on:
        assert {"request", "queue", "prefill", "first_token_lag",
                "decode"} <= by_rid[rid], (rid, by_rid.get(rid))
    # the first_token_lag phase span IS RequestResult.host_s
    lag = {e["args"]["rid"]: e["dur"] for e in evs
           if e["name"] == "first_token_lag"}
    for rid in rids_on:
        assert abs(lag[rid] * 1e-6 - srv.result(rid).host_s) < 1e-5
    # ---- the scheduler's seams, all through the one span helper
    names = {e["name"] for e in evs}
    assert {"dstpu.sched.step", "dstpu.sched.shed", "dstpu.sched.admit",
            "dstpu.sched.dispatch.prefill_chunk",
            "dstpu.sched.dispatch.admit", "dstpu.sched.dispatch.decode",
            "dstpu.sched.wait_device", "dstpu.sched.commit",
            "dstpu.sched.emit", "dstpu.engine.lock_wait"} <= names, names
    assert not {"step", "commit"} & names     # no hand-rolled twin left
    steps = [e for e in evs if e["name"] == "dstpu.sched.step"]
    assert [e["args"]["it"] for e in steps] == list(range(len(steps)))
    assert all({"live_slots", "queue_depth"} <= set(e["args"])
               for e in steps)
    admits = [e for e in evs if e["name"] == "dstpu.sched.admit"]
    assert sum(e["args"]["admitted"] for e in admits) == len(rids_on)
    assert sum(e["args"]["prefill_tokens"] for e in admits) \
        == srv.stats["prefill_tokens"]
    # kv_positions is EXACT (no eos here): the step that makes a
    # request's token i attends prompt + i positions
    decodes = [e for e in evs if e["name"] == "dstpu.sched.dispatch.decode"]
    want = sum(len(p) + i for p, n in zip(prompts, news)
               for i in range(1, n))
    assert sum(e["args"]["kv_positions"] for e in decodes) == want
    # commit markers carry tokens-committed counts at the mirror drain
    commits = [e for e in evs if e["name"] == "dstpu.sched.commit"]
    assert commits and all("tokens" in e["args"] for e in commits)
    assert sum(e["args"]["tokens"] for e in commits) \
        == srv.stats["decode_tokens"]

    # ---- histograms observed the run
    h = srv.histograms()
    assert h.ttft.count == len(rids_on)
    assert h.queue_wait.count == len(rids_on)
    assert h.tbt.count == srv.stats["decode_tokens"]
    assert set(h.dispatch._children) >= {"decode", "admit",
                                         "prefill_chunk"}

    # ---- flight recorder saw the whole story
    snap = srv.flightrec_snapshot()
    kinds = {e["ev"] for e in snap["events"]}
    assert {"submit", "admit_start", "dispatch_begin", "dispatch_end",
            "commit", "terminal"} <= kinds, kinds
    srv.close()
    # the ring outlives its engine: readers run after close
    assert span_trace.tracer() is srv._tracer
    assert span_trace.tracer().span_snapshot()[1] > 0
    span_trace.disable()


def test_admit_span_carries_the_limit_and_widened_is_counted(
        shared_engine, tmp_path):
    """``dstpu.sched.admit`` says what it was held to: ``budget_tokens``
    (the iteration's limit: the configured 16 scaled by slots / live
    lanes, whole chunks) and ``live_slots`` (read before the iteration's
    admissions).  ``stats["prefill_budget_widened"]`` counts exactly the
    iterations whose limit exceeded what the budget alone buys — none
    while every lane is live."""
    eng = shared_engine
    rng = np.random.default_rng(47)
    prompts, news = _workload(rng)
    srv = eng.serve(tracing=True)
    for p, n in zip(prompts, news):
        srv.submit(p, max_new_tokens=n)
    srv.drain()
    with open(srv.dump_trace(str(tmp_path / "trace.json"))) as f:
        evs = json.load(f)["traceEvents"]
    admits = [e["args"] for e in evs if e["name"] == "dstpu.sched.admit"]
    assert len(admits) == srv.stats["iterations"]
    S, C, B = srv.num_slots, srv.chunk, 16
    for a in admits:
        assert a["budget_tokens"] \
            == max(B, B * S // (C * max(a["live_slots"], 1)) * C), a
        assert a["prefill_tokens"] <= a["budget_tokens"], a
    # the first iteration meets no live lane and spends S budgets; with
    # 7 requests over 3 slots some later one meets all three live
    assert admits[0]["live_slots"] == 0 \
        and admits[0]["budget_tokens"] == B * S
    full = [a for a in admits if a["live_slots"] == S]
    assert full and all(a["budget_tokens"] == B for a in full)
    assert srv.stats["prefill_budget_widened"] \
        == sum(a["budget_tokens"] > B for a in admits) > 0
    srv.close()

    # one slot is never less than full (slots / max(live, 1) = 1), and
    # an unbounded budget (0, which the span shows as 0) has nothing to
    # widen: the counter stays 0
    for budget in (B, 0):
        srv = eng.serve(tracing=True, num_slots=1,
                        prefill_token_budget=budget)
        for p in prompts[:2]:
            srv.submit(p, max_new_tokens=3)
        srv.drain()
        assert srv.stats["prefill_budget_widened"] == 0
        with open(srv.dump_trace(str(tmp_path / "one.json"))) as f:
            evs = json.load(f)["traceEvents"]
        mine = [e["args"] for e in evs if e["name"] == "dstpu.sched.admit"
                ][-srv.stats["iterations"]:]
        assert {a["budget_tokens"] for a in mine} == {budget}
        assert {a["live_slots"] for a in mine} == {0, 1}
        srv.close()
    span_trace.disable()


# --------------------------------------------------------------------- #
# The one span helper
# --------------------------------------------------------------------- #
def test_span_nests_mirrors_only_when_on_and_is_thread_safe():
    span_trace.disable()
    with span_trace.span("dstpu.test.outer", n=1) as sp:
        pass
    assert span_trace.tracer() is None and sp.dur_s >= 0
    tr = span_trace.enable()
    assert span_trace.tracer() is tr

    def work():
        for i in range(500):
            with span_trace.span("dstpu.test.outer", i=i, skipped=None):
                with span_trace.span("dstpu.test.inner") as inner:
                    inner.set(found=i)

    threads = [threading.Thread(target=work, name=f"w{k}") for k in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans, added = tr.span_snapshot()
    assert added == len(spans) == 2000 and tr.dropped == 0
    for track in ("w0", "w1"):
        mine = [s for s in spans if s[4] == track]
        outer = [s for s in mine if s[0] == "dstpu.test.outer"]
        inner = [s for s in mine if s[0] == "dstpu.test.inner"]
        assert len(outer) == len(inner) == 500
        # each inner span closes first and lies within its outer span
        for o, i in zip(outer, inner):
            assert o[2] <= i[2] <= i[3] <= o[3]
            assert i[5] == {"found": o[5]["i"]} and "skipped" not in o[5]
    # a hand-off stamp moves the ring span's start back
    t_handoff = span_trace.now()
    time.sleep(0.01)
    with span_trace.span("dstpu.test.handoff", start=t_handoff) as sp:
        pass
    assert sp.dur_s >= 0.01 and tr.span_snapshot()[0][-1][2] == t_handoff
    span_trace.disable()
    with span_trace.span("dstpu.test.outer"):
        pass
    assert tr.span_snapshot()[1] == 2001     # nothing after disable()


def test_nvtx_names_route_through_span():
    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.utils.nvtx import (instrument_w_nvtx, range_pop,
                                          range_push)
    tr = span_trace.enable()

    @instrument_w_nvtx
    def hot(x):
        return x + 1

    assert hot(1) == 2
    range_push("region")
    get_accelerator().range_push("inner")
    get_accelerator().range_pop()
    range_pop()
    range_pop()                              # empty stack: a no-op
    names = [s[0] for s in tr.span_snapshot()[0]]
    assert names[0].endswith("hot") and names[1:] == ["inner", "region"]
    span_trace.disable()


def test_profiler_sees_scheduler_steps_with_their_children(
        shared_engine, tmp_path):
    """On the profiler's clock: a server stepped under
    ``jax.profiler.start_trace`` leaves ``dstpu.sched.step`` events that
    contain their ``wait_device`` children on the same thread, with the
    decode dispatch's ``kv_positions`` as an event stat — tracing off,
    so the annotations alone carry it."""
    from jax.profiler import ProfileData
    import glob
    eng = shared_engine
    rng = np.random.default_rng(43)
    prompts, _ = _workload(rng, n=2)
    span_trace.disable()
    srv = eng.serve()
    srv.submit(prompts[0], max_new_tokens=3)
    srv.drain()                              # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        srv.submit(prompts[1], max_new_tokens=6)
        srv.drain()
    finally:
        jax.profiler.stop_trace()
    srv.close()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    by_line = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("dstpu.")]
            if evs:
                by_line.append(evs)
    assert len(by_line) == 1                 # one scheduler thread
    evs = by_line[0]
    steps = [e for e in evs if e[0] == "dstpu.sched.step"]
    waits = [e for e in evs if e[0] == "dstpu.sched.wait_device"]
    assert steps and waits
    assert [e[3]["it"] for e in steps] == sorted(e[3]["it"] for e in steps)
    for w in waits:
        assert sum(s[1] <= w[1] and w[2] <= s[2] for s in steps) == 1, w
    assert {w[3]["event"] for w in waits} == {"admit", "decode"}
    decodes = [e for e in evs if e[0] == "dstpu.sched.dispatch.decode"]
    P = len(prompts[1])
    assert sum(e[3]["kv_positions"] for e in decodes) \
        == sum(P + i for i in range(1, 6))
    assert all(e[3]["program"] == "decode" for e in decodes)
    assert span_trace.tracer() is None


# --------------------------------------------------------------------- #
# Flight-recorder auto-dumps: breaker-open and DrainTimeout
# --------------------------------------------------------------------- #
def test_flightrec_dump_on_breaker_open(shared_engine, tmp_path):
    """Two consecutive dispatch failures trip the breaker; the dump
    lands on disk and its tail reconstructs the failing dispatch
    sequence (dispatch_begin -> dispatch_error -> breaker_open)."""
    eng = shared_engine
    rng = np.random.default_rng(23)
    # two failed dispatches need two steps' worth of admissions: a lost
    # dispatch loses every admission that rode it (both slots' here)
    prompts, _ = _workload(rng, n=4)
    srv = eng.serve(num_slots=2, breaker_threshold=2,
                    breaker_cooldown_s=30.0, flight_recorder=True,
                    flight_recorder_dir=str(tmp_path / "fr"))
    for p in prompts:
        srv.submit(p, max_new_tokens=4)

    real_run = eng._run_guarded

    def failing_run(fn, args):
        raise RuntimeError("injected sick-device dispatch failure")

    eng._run_guarded = failing_run
    try:
        srv.step()                       # failure 1 — absorbed
        assert srv._flightrec.last_dump_path is None
        srv.step()                       # failure 2 — breaker OPENS
    finally:
        eng._run_guarded = real_run
    assert srv._breaker.open
    dump_path = srv._flightrec.last_dump_path
    assert dump_path is not None and os.path.exists(dump_path)
    with open(dump_path) as f:
        dump = json.load(f)
    assert dump["reason"] == "breaker_open"
    tail = [e["ev"] for e in dump["events"]]
    # the last events tell the failure story in order
    i_begin = max(i for i, e in enumerate(tail) if e == "dispatch_begin")
    i_err = max(i for i, e in enumerate(tail) if e == "dispatch_error")
    i_open = tail.index("breaker_open")
    assert i_begin < i_err < i_open == len(tail) - 1, tail[-8:]
    errs = [e for e in dump["events"] if e["ev"] == "dispatch_error"]
    assert all("sick-device" in e["error"] for e in errs)
    assert all("seq" in e and "t_mono" in e and "t_wall" in e
               for e in dump["events"])
    srv.close()


def test_flightrec_dump_on_drain_timeout(shared_engine, tmp_path):
    eng = shared_engine
    rng = np.random.default_rng(29)
    prompts, _ = _workload(rng, n=1)
    srv = eng.serve(num_slots=2, flight_recorder=True,
                    flight_recorder_dir=str(tmp_path / "fr2"))
    r1 = srv.submit(prompts[0], max_new_tokens=30)
    while srv.active_slots == 0:
        srv.step()
    srv._dispatch_decode = lambda: False          # wedge the scheduler
    with pytest.raises(DrainTimeout):
        srv.drain(timeout_s=0.2)
    dump_path = srv._flightrec.last_dump_path
    assert dump_path is not None and os.path.exists(dump_path)
    with open(dump_path) as f:
        dump = json.load(f)
    assert dump["reason"] == "drain_timeout"
    kinds = [e["ev"] for e in dump["events"]]
    assert kinds[-1] == "drain_timeout"
    # the ring holds the request's real dispatch history before the
    # wedge — the sequence a point-in-time diagnostic cannot show
    assert "dispatch_end" in kinds and "admit_start" in kinds
    assert f"request {r1}" in dump["events"][-1]["diag"]
    srv.close()


# --------------------------------------------------------------------- #
# Injected clock: TTFT/TBT determinism + replay never re-stamps
# --------------------------------------------------------------------- #
def test_ttft_tbt_injected_clock_and_replay_no_restamp(shared_engine):
    """The tracer's clock is injectable: all TTFT/TBT observations are
    exact multiples of the fake tick, proving the histograms ride the
    injected clock; a late-attached TokenStream replay (which re-reads
    the token record) leaves every histogram bit-identical — replayed
    events never re-stamp timestamps."""
    eng = shared_engine
    rng = np.random.default_rng(31)
    prompts, _ = _workload(rng, n=2)
    news = [4, 5]
    srv = eng.serve(tracing=True)
    tick = [0.0]

    def fake_clock():
        tick[0] += 0.125
        return tick[0]

    srv._tracer._clock = fake_clock
    rids = [srv.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, news)]
    srv.drain()
    h = srv.histograms()
    assert h.ttft.count == 2
    assert h.tbt.count == sum(news) - len(news)
    for hist in (h.ttft, h.tbt, h.queue_wait):
        snap = hist.snapshot()
        scaled = snap["sum"] / 0.125
        assert abs(scaled - round(scaled)) < 1e-6, \
            "histogram stamps did not come from the injected clock"
    before = {k: getattr(h, k).snapshot()
              for k in ("ttft", "tbt", "queue_wait")}

    # late attach: full replay of every token + the end event
    for rid, n in zip(rids, news):
        toks, end = srv.token_events(rid).tokens(timeout=5)
        assert len(toks) == n and end["status"] == "COMPLETED"
    after = {k: getattr(h, k).snapshot()
             for k in ("ttft", "tbt", "queue_wait")}
    assert after == before, "TokenStream replay re-stamped timestamps"
    srv.close()


# --------------------------------------------------------------------- #
# /metrics round trip (HELP/TYPE everywhere, escaping, histograms) +
# the gated-off debug endpoints on the same frontend
# --------------------------------------------------------------------- #
_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (-?[0-9.eE+-]+|\+Inf|NaN)$')
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape(v):
    out, i = [], 0
    while i < len(v):
        if v[i] == "\\" and i + 1 < len(v):
            out.append({"n": "\n", '"': '"', "\\": "\\"}.get(
                v[i + 1], v[i + 1]))
            i += 2
        else:
            out.append(v[i])
            i += 1
    return "".join(out)


def parse_prometheus(text):
    """Minimal Prometheus text-format parser: returns (types, helps,
    samples) with samples = [(name, labels_dict, value)].  Raises on
    any line that is neither a comment nor a well-formed sample."""
    types, helps, samples = {}, {}, []
    for line in text.rstrip("\n").split("\n"):
        if line.startswith("# HELP "):
            name = line.split(" ", 3)[2]
            helps[name] = line.split(" ", 3)[3]
        elif line.startswith("# TYPE "):
            _, _, name, typ = line.split(" ", 3)
            types[name] = typ.strip()
        elif line.startswith("#"):
            continue
        else:
            m = _SAMPLE_RE.match(line)
            assert m, f"malformed exposition line: {line!r}"
            labels = {k: _unescape(v)
                      for k, v in _LABEL_RE.findall(m.group(2) or "")}
            samples.append((m.group(1), labels, float(m.group(3))))
    return types, helps, samples


def _family(name, types):
    if name in types:
        return name
    for suf in ("_bucket", "_sum", "_count"):
        if name.endswith(suf) and name[:-len(suf)] in types:
            return name[:-len(suf)]
    return None


def _get(port, path, method="GET"):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request(method, path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, body


NASTY_CLIENT = 'we"ird\\ten\nant-{x="1"}'


def test_metrics_round_trip_histograms_escaping_and_gating(
        shared_engine):
    eng = shared_engine
    srv = eng.serve(tracing=True, fairness_tokens_per_s=10000.0)
    from deepspeed_tpu.inference.serving.frontend import \
        ServingHTTPFrontend
    rng = np.random.default_rng(37)
    prompts, _ = _workload(rng, n=2)
    with ServingHTTPFrontend(srv) as fe:
        for k, p in enumerate(prompts):
            conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                              timeout=180)
            conn.request("POST", "/v1/generate", json.dumps(
                {"input_ids": [int(t) for t in p], "max_new_tokens": 4,
                 "client_id": NASTY_CLIENT if k == 0 else "plain"}))
            assert conn.getresponse().status == 200
            conn.close()
        # one streamed request: a dstpu.frontend.write span per event
        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=180)
        conn.request("POST", "/v1/generate", json.dumps(
            {"input_ids": [int(t) for t in prompts[0]],
             "max_new_tokens": 3, "stream": True}))
        streamed = [json.loads(ln) for ln in
                    conn.getresponse().read().decode().splitlines()]
        conn.close()
        stream_rid = streamed[-1]["rid"]
        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=60)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        assert resp.status == 200
        body = resp.read().decode()
        conn.close()
        # this server has tracing but NO flight recorder and NO profile
        # endpoint: the debug routes answer 404-with-reason
        status, b = _get(fe.port, "/debug/flightrec")
        assert status == 404 and b"flight recorder disabled" in b
        status, b = _get(fe.port, "/debug/profile?secs=1", "POST")
        assert status == 404 and b"profiling endpoint disabled" in b
    srv.close()

    # ---- the front end's way in, span by span (the ring, after close)
    ring = span_trace.tracer().span_snapshot()[0]
    submits = [s for s in ring if s[0] == "dstpu.frontend.submit"]
    assert sorted(s[5]["rid"] for s in submits) == [0, 1, 2]
    assert all(0 <= s[5]["lock_wait_s"] <= s[3] - s[2] for s in submits)
    for name in ("dstpu.frontend.parse", "dstpu.frontend.subscribe"):
        assert sum(s[0] == name for s in ring) == 3, name
    subs = {s[5]["rid"] for s in ring if s[0] == "dstpu.frontend.subscribe"}
    assert subs == {0, 1, 2}
    writes = [s for s in ring if s[0] == "dstpu.frontend.write"]
    assert len(writes) == len(streamed) \
        and {s[5]["rid"] for s in writes} == {stream_rid}
    # front-end spans sit on their own threads' tracks, never the
    # scheduler's
    assert all(s[4] != "scheduler" for s in submits + writes)
    assert any(s[0] == "dstpu.sched.idle" and s[4] == "scheduler"
               for s in ring)
    span_trace.disable()

    types, helps, samples = parse_prometheus(body)
    # exposition correctness: every sample belongs to a family with
    # BOTH # TYPE and # HELP
    for name, labels, value in samples:
        fam = _family(name, types)
        assert fam is not None, f"sample {name} has no # TYPE"
        assert fam in helps, f"sample {name} has no # HELP"
    # hostile label value round-trips exactly
    fairness = [(la, v) for n, la, v in samples
                if n == "dstpu_serving_fairness_window_tokens"]
    assert any(la.get("client") == NASTY_CLIENT for la, _ in fairness), \
        fairness
    # a stat that is a NAME is a gauge of 1 with the name in a label
    assert [(la, v) for n, la, v in samples
            if n == "dstpu_serving_chunk_write"] \
        == [({"value": srv.stats["chunk_write"]}, 1.0)]
    # the five histogram families, each parsing as a real histogram
    for fam in ("dstpu_serving_ttft_seconds",
                "dstpu_serving_tbt_seconds",
                "dstpu_serving_queue_wait_seconds",
                "dstpu_serving_dispatch_seconds",
                "dstpu_serving_lock_acquire_wait_seconds"):
        assert types.get(fam) == "histogram", (fam, types.get(fam))
        rows = [(la, v) for n, la, v in samples
                if n == f"{fam}_bucket"]
        assert rows, fam
        # cumulative counts are monotone in le, per label subset
        keysets = {tuple(sorted((k, v) for k, v in la.items()
                                if k != "le")) for la, _ in rows}
        for ks in keysets:
            sub = [(la["le"], v) for la, v in rows
                   if tuple(sorted((k, v2) for k, v2 in la.items()
                            if k != "le")) == ks]
            fin = sorted([(float(le), v) for le, v in sub
                          if le != "+Inf"])
            counts = [v for _, v in fin]
            assert counts == sorted(counts), (fam, ks, fin)
            inf = [v for le, v in sub if le == "+Inf"]
            cnt = [v for n, la, v in samples
                   if n == f"{fam}_count"
                   and tuple(sorted((k, v2) for k, v2 in la.items())) == ks]
            assert inf == cnt, (fam, ks, inf, cnt)
    # TTFT histogram actually measured the run
    ttft_count = [v for n, la, v in samples
                  if n == "dstpu_serving_ttft_seconds_count"]
    assert ttft_count == [float(len(prompts) + 1)], ttft_count  # + stream


# --------------------------------------------------------------------- #
# Debug endpoints live: /debug/flightrec, /debug/profile, SIGUSR2
# --------------------------------------------------------------------- #
def test_debug_flightrec_profile_and_sigusr2(shared_engine, tmp_path):
    from deepspeed_tpu.inference.serving.frontend import \
        ServingHTTPFrontend
    eng = shared_engine
    rng = np.random.default_rng(41)
    prompts, _ = _workload(rng, n=1)
    srv = eng.serve(flight_recorder=True,
                    flight_recorder_dir=str(tmp_path / "fr3"),
                    profile_endpoint=True)
    fe = ServingHTTPFrontend(srv).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=180)
        conn.request("POST", "/v1/generate", json.dumps(
            {"input_ids": [int(t) for t in prompts[0]],
             "max_new_tokens": 3}))
        assert conn.getresponse().status == 200
        conn.close()
        status, body = _get(fe.port, "/debug/flightrec")
        assert status == 200
        snap = json.loads(body)
        assert snap["recorded"] >= len(snap["events"]) > 0
        assert {"submit", "terminal"} <= {e["ev"] for e in snap["events"]}

        status, body = _get(fe.port, "/debug/profile?secs=0", "POST")
        assert status == 200, body
        prof = json.loads(body)
        assert os.path.isdir(prof["trace_dir"])
        status, body = _get(fe.port, "/debug/profile?secs=abc", "POST")
        assert status == 400

        # SIGUSR2 -> ring dump, engine lock never taken
        if threading.current_thread() is threading.main_thread():
            fe.install_flightrec_signal_handler()
            os.kill(os.getpid(), signal.SIGUSR2)
            for _ in range(100):
                if srv._flightrec.last_dump_path:
                    break
                time.sleep(0.05)
            assert srv._flightrec.last_dump_path \
                and os.path.exists(srv._flightrec.last_dump_path)
            with open(srv._flightrec.last_dump_path) as f:
                assert json.load(f)["reason"] == "sigusr2"
    finally:
        fe.shutdown()                    # restores signal handlers
        srv.close()
