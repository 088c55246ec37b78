"""The readers of the program's spans: each new per-layer metric on hand-made
events or a hand-filled ring with known answers, the profiler route on a
trace recorded here on the CPU, and every reader on a program that has no
such span (a parent commit): nothing to read, no error."""

import os
import types

import pytest

from benchmark import spans, spec, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
D0, OPS, MODS = "/device:TPU:0", trace.OPS_LINE, trace.MODULES_LINE


def _kernel(name):
    return (f"%{name} = (bf16[32,32,64]{{2,1,0}}) custom-call(s32[32]{{0}} "
            f'%p), custom_call_target="tpu_custom_call"')


FUSION = "%fusion.1 = bf16[2,2048]{1,0} fusion(bf16[2]{0} %x)"


@pytest.fixture(scope="module")
def bench():
    return spec.Benchmark(ROOT)


def _span(name, start, dur, thread=(0, 0), **stats):
    return {"name": name, "start_s": start, "dur_s": dur, "thread": thread,
            "stats": stats}


def _run(**kw):
    base = dict(trace=None, observed={}, slice_t0=None, slice_s=None)
    return types.SimpleNamespace(**{**base, **kw})


# ---- nesting by thread, own time, the scheduler's host share ----------- #
def _iteration_spans():
    # two iterations on one thread; the second holds two device waits.
    # another thread's wait lies inside the first by time and must not count
    return [
        _span("dstpu.sched.step", 0.0, 1.0, it=0),
        _span("dstpu.sched.admit", 0.1, 0.2),
        _span("dstpu.sched.wait_device", 0.4, 0.5, event="decode"),
        _span("dstpu.sched.wait_device", 0.45, 0.1, thread=(0, 1)),
        _span("dstpu.sched.step", 2.0, 2.0, it=1),
        _span("dstpu.sched.wait_device", 2.1, 0.5, event="admit"),
        _span("dstpu.sched.wait_device", 3.0, 0.9, event="decode"),
    ]


def test_children_nest_by_thread_and_the_host_share_is_what_is_left():
    evs = _iteration_spans()
    assert [e["stats"].get("event") for e in spans.inside(
        evs[4], evs, "dstpu.sched.wait_device")] == ["admit", "decode"]
    assert spans.step_host_seconds(evs) == pytest.approx([0.5, 0.6])
    own = spans.self_seconds(evs)
    assert own["dstpu.sched.step"] == pytest.approx(3.0 - 0.2 - 0.5 - 1.4)
    assert own["dstpu.sched.wait_device"] == pytest.approx(2.0)


@pytest.mark.parametrize("metric", ["sched.host_ms_per_iter.chat",
                                    "sched.host_ms_per_iter.batch"])
def test_host_ms_per_iter_is_the_median_iteration(bench, monkeypatch, metric):
    monkeypatch.setattr(spans, "host_spans", lambda *a: _iteration_spans())
    read = bench.reader(metric).read
    assert read(_run(trace=object())) == pytest.approx(550.0)
    assert read(_run()) is None                      # no traced slice
    monkeypatch.setattr(spans, "host_spans", lambda *a: [])
    assert read(_run(trace=object())) is None        # a parent: no spans


def test_idle_cover_share():
    tr = trace.Trace([(D0, OPS, FUSION, 0.0, 1.0), (D0, OPS, FUSION, 3.0, 1.0),
                      (D0, OPS, FUSION, 6.0, 4.0)])
    # idle 1-3 and 4-6; spans cover 1-2.5 (twice over) and 5-7
    cover = [_span("dstpu.sched.step", 0.5, 2.0), _span("dstpu.x", 1.0, 1.0),
             _span("dstpu.sched.idle", 5.0, 2.0, thread=(0, 1))]
    assert spans.idle_cover_share(tr, cover) == pytest.approx(2.5 / 4.0)
    assert spans.idle_cover_share(tr, []) == 0.0
    assert spans.idle_cover_share(None, cover) is None


# ---- the ring ---------------------------------------------------------- #
@pytest.fixture
def ring():
    from deepspeed_tpu.monitor import trace as program_trace
    tr = program_trace.enable()
    # requests 1..3 are the window's; 0 is a warm-up request
    for rid, (queue, prefill, lag, submit, lock) in enumerate(
            [(9.0, 9.0, 9.0, 9.0, 9.0), (0.010, 0.030, 0.100, 0.050, 0.040),
             (0.020, 0.040, 0.090, 0.070, 0.060),
             (0.300, 0.050, 0.110, 0.060, 0.055)]):
        t = 100.0 * rid
        tr.add("dstpu.frontend.submit", "frontend", t, t + submit,
               track="exec_0", rid=rid, lock_wait_s=lock)
        tr.add("queue", "phase", t, t + queue, track=rid, rid=rid)
        tr.add("prefill", "phase", t + queue, t + queue + prefill, track=rid,
               rid=rid)
        tr.add("first_token_lag", "phase", t + 1, t + 1 + lag, track=rid,
               rid=rid)
        tr.add("queue", "dispatch", t, t + 5.0, track="scheduler", rid=rid)
    tr.add("submit", "request", 1.0, None, track="handler", rid=1)
    yield tr
    program_trace.disable()


@pytest.mark.parametrize("metric,want", [
    ("frontend.submit_wait_p50_ms", 60.0),
    ("frontend.lock_wait_p50_ms", 55.0),
    ("sched.queue_wait_p50_ms", 20.0),
    ("sched.prefill_p50_ms", 40.0),
    ("sched.first_token_lag_p50_ms", 100.0)])
def test_first_token_parts_are_medians_over_the_windows_requests(
        bench, ring, metric, want):
    run = _run(observed={"records": [{"rid": 1}, {"rid": 2}, {"rid": 3},
                                     {"rid": None}]})
    assert bench.reader(metric).read(run) == pytest.approx(want)
    # requests the ring does not know: nothing to read
    assert bench.reader(metric).read(
        _run(observed={"records": [{"rid": 77}]})) is None


def test_ring_readers_find_nothing_without_a_process_tracer(bench):
    from deepspeed_tpu.monitor import trace as program_trace
    program_trace.disable()
    assert spans.ring_spans() == []
    run = _run(observed={"records": [{"rid": 1}]})
    assert bench.reader("sched.queue_wait_p50_ms").read(run) is None


# ---- kernels by name ---------------------------------------------------- #
def _train_trace():
    # two train steps on device 0; per step: forward + recomputed forward
    # (0.1 each), dq 0.3, dkv 0.2, under three spellings of the names
    evs = []
    for s in (0.0, 10.0):
        evs.append((D0, MODS, "jit_train_step(5)", s, 5.0))
        for name, at, dur in [("attn.flash_fwd.3", 0.0, 0.1),
                              ("jvp_attn.flash_fwd_.9", 1.0, 0.1),
                              ("attn.flash_dq.1", 2.0, 0.3),
                              ("transpose_jvp_attn.flash_dkv__.1", 3.0, 0.2),
                              ("attn.paged_decode.4", 4.0, 0.7)]:
            evs.append((D0, OPS, _kernel(name), s + at, dur))
    evs.append((D0, OPS, _kernel("attn.flash_fwd.3"), 7.0, 1.0))  # no step
    # the slice ends half way through a third step: half its kernels
    evs.append((D0, MODS, "jit_train_step(5)", 20.0, 2.5))
    for name, at, dur in [("attn.flash_fwd.3", 20.0, 0.1),
                          ("attn.flash_dq.1", 21.0, 0.15),
                          ("attn.flash_dkv.1", 22.0, 0.1)]:
        evs.append((D0, OPS, _kernel(name), at, dur))
    return trace.Trace(evs)


def test_flash_kernels_per_train_step_by_name(bench):
    run = _run(trace=_train_trace())
    assert bench.reader("kernel.flash_fwd_ms_per_step").read(run) \
        == pytest.approx(200.0)
    assert bench.reader("kernel.flash_bwd_ms_per_step").read(run) \
        == pytest.approx(500.0)
    assert spans.kernel_seconds(run.trace, "attn.flash_fwd",
                                module="train_step")[1] == 5


def test_paged_decode_share_and_the_parents_unnamed_kernels(bench):
    read = bench.reader("kernel.paged_decode_share_pct.batch").read
    tr = trace.Trace([
        (D0, OPS, _kernel("attn.paged_decode.54"), 0.0, 6.0),
        (D0, OPS, _kernel("attn.paged_chunk_prefill.2"), 6.0, 2.0),
        (D0, OPS, FUSION, 8.0, 2.0)])
    assert read(_run(trace=tr)) == pytest.approx(60.0)
    # the names short_name gives breakdown.device_ops: two entries
    assert {trace.short_name(e[2]) for e in tr.device_ops()} == {
        "attn.paged_decode pallas", "attn.paged_chunk_prefill pallas",
        "fusion fusion"}
    # the accepted reader's match still takes the named decode kernel
    assert trace.is_pallas(_kernel("attn.paged_decode.54"), "attn")
    # a parent names both kernels %attn.N: nothing to read, no error
    old = trace.Trace([(D0, MODS, "jit_train_step(5)", 0.0, 9.0),
                       (D0, OPS, _kernel("attn.7"), 0.0, 6.0)])
    assert read(_run(trace=old)) is None
    assert read(_run()) is None
    for metric in ("kernel.flash_fwd_ms_per_step",
                   "kernel.flash_bwd_ms_per_step"):
        assert bench.reader(metric).read(_run(trace=old)) is None
        assert bench.reader(metric).read(_run()) is None


# ---- compile phases ----------------------------------------------------- #
def test_setup_counters_sum_phases_up_to_the_slice(bench, monkeypatch):
    from deepspeed_tpu.runtime import compile_cache
    T, L, B = spans.COMPILE_TRACE, spans.COMPILE_LOWER, spans.COMPILE_BACKEND
    events = [(10.0, T, 1.0),           # an inner jit's trace: 9-10 ...
              (12.0, T, 3.0),           # ... inside the outer's 8-12: 4 - 1
              (13.0, L, 0.5), (20.0, B, 7.0),
              (30.0, T, 2.0), (31.0, L, 0.25), (35.0, B, 3.0),
              (90.0, T, 50.0), (95.0, B, 40.0)]   # the reference, afterwards
    stats = types.SimpleNamespace(compile_events=events)
    monkeypatch.setattr(compile_cache, "stats", lambda: stats)
    run = _run(slice_t0=60.0)
    assert bench.reader("setup.trace_lower_s").read(run) \
        == pytest.approx(4.0 + 2.0 + 0.5 + 0.25)
    assert bench.reader("setup.backend_compile_s").read(run) \
        == pytest.approx(10.0)
    # a program that keeps no such events (a parent)
    monkeypatch.setattr(compile_cache, "stats",
                        lambda: types.SimpleNamespace())
    assert bench.reader("setup.trace_lower_s").read(run) is None
    assert bench.reader("setup.backend_compile_s").read(run) is None


def test_the_programs_compile_counters_feed_the_reader():
    import time
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.runtime import compile_cache
    compile_cache._register_jax_listener()
    before = compile_cache.stats().snapshot()

    @jax.jit
    def fresh(x):
        time.sleep(0.01)                # a trace long enough to be kept
        return jnp.tanh(x) * 3.0 + 0.125

    t0 = time.monotonic()
    fresh(jnp.ones((3, 5))).block_until_ready()
    after = compile_cache.stats().snapshot()
    for key in ("trace_seconds", "lower_seconds", "backend_compile_seconds"):
        assert after[key] > before[key], key
    kept = [e for e in after["compile_events"] if e[0] >= t0]
    assert any(e[1] == spans.COMPILE_TRACE and e[2] >= 0.01 for e in kept)
    assert all(e[2] >= compile_cache.COMPILE_EVENT_MIN_SECS for e in kept)
    # the reader sums the kept events: never more than the program's sums
    phases = spans.compile_phase_seconds()
    assert 0.01 <= phases["trace"] <= after["trace_seconds"] + 1e-9
    assert phases["backend"] <= after["backend_compile_seconds"] + 1e-9
    assert spans.compile_phase_seconds(until=t0 - 3600.0) \
        == {"trace": 0.0, "lower": 0.0, "backend": 0.0}


# ---- the profiler route, on a trace recorded here ---------------------- #
def test_host_spans_from_a_recorded_trace(tmp_path):
    import time
    import jax
    from deepspeed_tpu.monitor.trace import span
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with span("dstpu.sched.step", it=4, live_slots=2) as outer:
            with span("dstpu.sched.wait_device", event="decode"):
                time.sleep(0.02)
            outer.set(admitted=1)
        with span("not.ours"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = spans.host_spans(str(tmp_path))
    assert [e["name"] for e in got] == ["dstpu.sched.step",
                                        "dstpu.sched.wait_device"]
    assert got[0]["stats"] == {"it": 4, "live_slots": 2, "admitted": 1}
    assert got[1]["stats"] == {"event": "decode"}
    assert spans.inside(got[0], got) == [got[1]]
    host = spans.step_host_seconds(got)
    assert len(host) == 1 and 0 <= host[0] < got[0]["dur_s"] - 0.019
    assert spans.host_spans(str(tmp_path / "nothing_here")) == []


NEW_METRICS = [
    "frontend.submit_wait_p50_ms", "frontend.lock_wait_p50_ms",
    "sched.queue_wait_p50_ms", "sched.prefill_p50_ms",
    "sched.first_token_lag_p50_ms", "sched.host_ms_per_iter.chat",
    "sched.host_ms_per_iter.batch", "kernel.paged_decode_share_pct.batch",
    "kernel.flash_fwd_ms_per_step", "kernel.flash_bwd_ms_per_step",
    "setup.trace_lower_s", "setup.backend_compile_s"]


def test_the_new_metrics_are_entries_with_readers(bench):
    assert spec.validate(bench) == [] and spec.check_files(bench) == []
    names = [m["name"] for m in bench.doc["per_layer"]]
    # the twelve this test knew, found by name, side by side in their
    # order; later PRs append
    first = names.index(NEW_METRICS[0])
    assert names[first:first + len(NEW_METRICS)] == NEW_METRICS
    chat = {m["name"] for m in bench.cell("opt13b-serve-chat")["per_layer"]}
    assert {"frontend.lock_wait_p50_ms", "sched.first_token_lag_p50_ms",
            "sched.host_ms_per_iter.chat", "setup.trace_lower_s"} <= chat
    assert "sched.host_ms_per_iter.batch" not in chat
    for cell in ("opt13b-sft-1chip", "opt67b-zero3-4chip"):
        got = {m["name"] for m in bench.cell(cell)["per_layer"]}
        assert {"kernel.flash_fwd_ms_per_step", "kernel.flash_bwd_ms_per_step",
                "setup.backend_compile_s"} <= got
