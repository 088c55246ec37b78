"""Set-up timed from inside the program (``docs/observability.md``
"Start-up"): the entry points' ``dstpu.setup.*`` spans are kept in
``monitor.trace.setup_spans()`` with the ring OFF, every compile that passes
``compile_cache.aot_compile_with_store`` is ONE ``dstpu.setup.compile`` span
named by its program, and nothing of category ``setup`` is recorded once
every program has run once.

Two tests build an engine through the public entry points (the smallest
models of the suite: the assertions are about host bookkeeping); the rest
drive the helper and the compile seam directly."""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.monitor import trace
from deepspeed_tpu.models.transformer import Transformer, TransformerConfig
from deepspeed_tpu.runtime import compile_cache as cc

P = "dstpu.setup."


@pytest.fixture
def ring_off():
    """The ring as this test wants it, and as the test before left it
    afterwards (``serving.tracing`` leaves its tracer installed)."""
    before = trace.tracer()
    trace.disable()
    yield
    trace._TRACER = before


def _since(t):
    return [s for s in trace.setup_spans() if s[1] >= t]


def _named(spans, name, **args):
    return [s for s in spans if s[0] == P + name
            and all(s[4].get(k) == v for k, v in args.items())]


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def _tree_bytes(tree):
    return sum(l.nbytes for l in jax.tree.leaves(tree))


def _model(layers=1):
    return Transformer(TransformerConfig(
        vocab_size=61, hidden_size=32, num_layers=layers, num_heads=2,
        max_seq_len=64, use_flash_attention=False, dtype="float32",
        remat=False, scan_layers=False))


def _phases(span):
    a = span[4]
    return a["trace_s"] + a["lower_s"] + a["backend_s"]


def _phases_fit(span):
    """JAX's three phases lie inside the span — to a millisecond, and to
    the little they overlap: a function first traced while another is being
    lowered is counted in both phases (a few ms in a second)."""
    took = span[2] - span[1]
    return 0 < _phases(span) <= took + 1e-3 + 0.02 * took


# --------------------------------------------------------------------- #
# the two flows
# --------------------------------------------------------------------- #
def test_serving_flow_leaves_the_tabled_spans(ring_off):
    """(a) ``init_inference -> set_params -> serve() -> warmup()`` leaves
    exactly the tabled names, children inside parents, sizes equal to the
    trees'; (c) a compile's three phases fit its span; (d) a second
    warm-up compiles nothing and the admit program's first-use compile is
    marked ``after_warmup``; (e) after that, 50 scheduler iterations
    record no set-up span."""
    model = _model()
    params = model.init(jax.random.key(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    t = time.monotonic()
    eng = deepspeed_tpu.init_inference(model, config={
        "dtype": "float32", "prefill_chunk_size": None,
        "serving": {"enabled": True, "num_slots": 2, "max_cache_len": 64,
                    "page_size": 16, "prefill_chunk": 32, "decode_block": 2}})
    eng.set_params(params)
    srv = eng.serve()
    report = srv.warmup()
    ready = _since(t)
    # the lazy imports' spans depend on what the process ran before this
    # test (an xdist worker's earlier tests): present or absent, each names
    # its module and lies inside the engine's span
    probes = _named(ready, "lazy_import")
    ready = [s for s in ready if s not in probes]
    assert [s[0][len(P):] for s in ready] == [
        "engine", "weights", "serve", "compile", "compile", "warmup"]
    engine, weights, serve, chunk, block, warmup = ready
    assert all(set(p[4]) == {"module"} and _inside(p, engine)
               for p in probes)
    assert engine[4] == {"entry": "init_inference", "chips": 8}
    assert weights[4] == {"bytes": _tree_bytes(eng.params),
                          "leaves": len(jax.tree.leaves(eng.params)),
                          "sharded": 0}
    assert serve[4] == {"num_slots": 2, "num_pages": srv.num_pages}
    assert warmup[4] == {"programs": 2}
    # set_params and serve() are the caller's own calls: beside the engine
    # span, not inside it; the compiles are the warm-up's
    assert engine[2] <= weights[1] and weights[2] <= serve[1] <= warmup[1]
    assert _inside(chunk, warmup) and _inside(block, warmup)
    assert (chunk[4]["program"], block[4]["program"]) == \
        ("prefill_chunk", "decode")
    for c in (chunk, block):
        a = c[4]
        assert a["tag"].startswith("infer:serving_") and a["opt_out"] == 1 \
            and a["store_hit"] == 0 and a["after_warmup"] == 0
        assert _phases_fit(c)
    # one timing: what warmup() returns is each compile span's duration
    assert sorted(report.values()) == sorted(
        c[2] - c[1] for c in (chunk, block))

    # (d) the same signatures again: a warm-up span, no compile
    assert set(srv.warmup().values()) == {0.0}
    again = _since(t)[len(ready) + len(probes):]
    assert [s[0] for s in again] == [P + "warmup"]

    # first requests: the pools are allocated and the admit program
    # compiles on its first use, after the warm-up closed
    rng = np.random.default_rng(0)
    submit = lambda: srv.submit(rng.integers(1, 61, (9,)).astype(np.int32),
                                max_new_tokens=40)
    submit()
    while not _named(_since(t), "compile", program="admit"):
        srv.step()
    first = _since(t)[len(ready) + len(probes) + 1:]
    assert [s[0][len(P):] for s in first] == ["pools", "compile"]
    pools, admit = first
    with srv._lock:
        assert pools[4] == {"bytes": _tree_bytes(srv._cache)}
    assert admit[4]["after_warmup"] == 1 and admit[4]["opt_out"] == 1

    # (e) every program has run once: the scheduler loop records nothing
    srv.drain()
    settled = trace.setup_spans()
    submit(), submit(), submit()
    its = 0
    while its < 50:
        srv.step()
        its += 1
        if not srv.work_pending():
            submit()
    assert trace.setup_spans() == settled
    srv.close()

    line = trace.ready_line("serving")
    assert line.startswith("ready[serving]: import ")
    for word in ("lazy_import ", "engine ", "weights ", "serve ", "warmup ",
                 "pools ", "prefill_chunk ", "decode ", "admit ", "(miss)"):
        assert word in line, (word, line)


def test_training_flow_leaves_the_tabled_spans_ring_on(ring_off):
    """(b) ``initialize -> warmup -> train_batch`` with ``dstpu.train.compile``
    around the compile span; (e) five more ``train_batch`` calls record no
    set-up span; (f) with the ring ON every set-up span is in the ring
    too, once."""
    model = _model()
    ids = np.zeros((1, jax.device_count(), 16), np.int32)
    params = model.init(jax.random.key(0), {"input_ids": ids[0]})
    ring = trace.enable()
    t = time.monotonic()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config={
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1}})
    report = engine.warmup(batch={"input_ids": ids})
    engine.train_batch(batch={"input_ids": ids})
    spans = _since(t)
    assert [s[0][len(P):] for s in spans] == [
        "weights", "optimizer_state", "lazy_import", "engine", "compile",
        "warmup"]
    weights, opt, orbax, built, compiled, warmup = spans
    assert built[4] == {"entry": "initialize", "chips": 8}
    assert _inside(weights, built) and _inside(opt, built) \
        and weights[2] <= opt[1]
    assert orbax[4] == {"module": "orbax.checkpoint"} \
        and _inside(orbax, built)
    assert weights[4]["bytes"] == _tree_bytes(engine._params)
    assert weights[4]["leaves"] == len(jax.tree.leaves(engine._params))
    assert opt[4] == {"bytes": _tree_bytes(engine._opt_state)}
    assert compiled[4]["program"] == compiled[4]["tag"] == "train_step" \
        and compiled[4]["after_warmup"] == 0 and compiled[4]["opt_out"] == 0
    assert _phases_fit(compiled)
    assert report == {"train_step": compiled[2] - compiled[1]}
    assert _inside(compiled, warmup) and warmup[4] == {"programs": 1}
    # (f) the ring holds each of them once, category "setup", and PR 34's
    # record of the remat rung around the compile
    rows, _ = ring.span_snapshot()
    in_ring = [(n, t0, t1) for n, cat, t0, t1, _, _ in rows if cat == "setup"]
    assert sorted(in_ring) == sorted((s[0], s[1], s[2]) for s in spans)
    around = [r for r in rows if r[0] == "dstpu.train.compile"]
    assert len(around) == 1 and _inside(
        compiled, (None, around[0][2], around[0][3])) \
        and _inside((None, around[0][2], around[0][3]), warmup)
    # (e) the hot path
    trace.disable()
    settled = trace.setup_spans()
    for _ in range(5):
        engine.train_batch(batch={"input_ids": ids})
    assert trace.setup_spans() == settled
    assert "train_step " in trace.ready_line("training")


# --------------------------------------------------------------------- #
# the compile seam and the helper, directly
# --------------------------------------------------------------------- #
def _snap():
    s = cc.stats()
    return s.trace_seconds, s.lower_seconds, s.backend_compile_seconds


def test_a_compile_spans_phases_are_the_counters_deltas(ring_off, tmp_path):
    """(c) over a stretch that holds nothing but compile spans their three
    phases sum to ``CacheStats``' deltas; a store hit reads all three 0."""
    t = time.monotonic()
    x = jnp.arange(8.0)
    before = _snap()
    exe, secs, hit = cc.aot_compile_with_store(
        None, "infer:gen", (), jax.jit(lambda v: jnp.tanh(v) * 3), (x,),
        program="generate")
    pc = cc.ProgramCache(cc.CompileCacheConfig(
        enabled=True, cache_dir=str(tmp_path), min_compile_time_secs=0.0))
    fn, key = jax.jit(lambda v: jnp.cos(v) + 1), (cc.abstract_signature((x,)),)
    _, miss_s, miss = cc.aot_compile_with_store(pc, "rollout", key, fn, (x,))
    after = _snap()
    _, hit_s, was_hit = cc.aot_compile_with_store(pc, "rollout", key, fn, (x,))
    assert (hit, miss, was_hit) == (False, False, True) and hit_s == 0.0
    one, two, three = _named(_since(t), "compile")
    assert (one[4]["program"], one[4]["tag"]) == ("generate", "infer:gen")
    assert two[4]["program"] == two[4]["tag"] == "rollout"   # default: the tag
    assert (secs, miss_s) == (one[2] - one[1], two[2] - two[1])
    assert cc.stats().compile_seconds["rollout"] == miss_s
    for i, phase in enumerate(("trace_s", "lower_s", "backend_s")):
        assert one[4][phase] + two[4][phase] == \
            pytest.approx(after[i] - before[i], abs=1e-9)
    for s in (one, two):
        assert _phases_fit(s)
        assert s[4]["store_hit"] == 0 and s[4]["after_warmup"] == 1
    assert three[4]["store_hit"] == 1 and _phases(three) == 0.0
    assert [s[4]["opt_out"] for s in (one, two, three)] == [0, 0, 0]


def test_a_failed_compile_is_a_span_and_a_fallback(ring_off):
    class Broken:
        def lower(self, *args):
            raise RuntimeError("no such program")
    t, fallbacks = time.monotonic(), cc.stats().aot_fallbacks
    assert cc.aot_compile_with_store(None, "train_step", (), Broken(), ()) \
        == (None, 0.0, False)
    assert cc.stats().aot_fallbacks == fallbacks + 1
    (failed,) = _named(_since(t), "compile", program="train_step")
    assert _phases(failed) == 0.0


def test_opt_out_and_the_open_warmup_are_seen_by_the_compile(ring_off):
    t = time.monotonic()
    fn, x = jax.jit(lambda v: v - 2), jnp.arange(4.0)
    with trace.span(P + "warmup", cat="setup"):
        assert trace.setup_open(P + "warmup")
        with cc.suspended_persistent_cache():
            cc.aot_compile_with_store(None, "infer:x", (), fn, (x,))
        seen = []
        other = threading.Thread(
            target=lambda: seen.append(trace.setup_open(P + "warmup")))
        other.start()
        other.join(timeout=10)
        assert seen == [False]          # another thread's compile is its own
    assert not trace.setup_open(P + "warmup")
    (c,) = _named(_since(t), "compile")
    assert (c[4]["opt_out"], c[4]["after_warmup"]) == (1, 0)


def test_setup_spans_are_kept_ring_off_and_bounded(ring_off):
    """(g) the list is bounded; other categories never reach it."""
    assert trace.tracer() is None
    with trace.span("dstpu.sched.step"):
        pass
    before, mark = trace.setup_spans(), time.monotonic()
    for i in range(trace.SETUP_SPANS_KEPT + 7):
        with trace.span(P + "pools", cat="setup", bytes=i):
            pass
    kept = trace.setup_spans()
    assert len(kept) == trace.SETUP_SPANS_KEPT
    assert all(s[0] == P + "pools" and s[1] >= mark for s in kept)
    assert kept[-1][4] == {"bytes": trace.SETUP_SPANS_KEPT + 6}
    assert kept[0][4] == {"bytes": 7}
    kept.clear()                        # a copy
    assert len(trace.setup_spans()) == trace.SETUP_SPANS_KEPT
    assert kept == [] and trace.setup_spans()[-1][3] == \
        threading.current_thread().name
    with trace._SETUP_LOCK:             # what this process had recorded
        trace._SETUP.clear()
        trace._SETUP.extend(before)


def test_ready_line_begins_with_the_import_past_the_bound(ring_off):
    """A process that has built many engines (an xdist worker late in the
    suite) has pushed the package's import span off the bounded list: the
    ``ready`` line still begins with it."""
    before = trace.setup_spans()
    try:
        for i in range(trace.SETUP_SPANS_KEPT):
            with trace.span(P + "pools", cat="setup", bytes=i):
                pass
        assert not _named(trace.setup_spans(), "import")
        assert trace.ready_line("serving").startswith(
            "ready[serving]: import ")
    finally:
        with trace._SETUP_LOCK:
            trace._SETUP.clear()
            trace._SETUP.extend(before)


def test_a_ring_turned_on_inside_a_setup_span_still_gets_it(ring_off):
    """``ServingEngine.__init__`` turns the ring on under
    ``dstpu.setup.serve``: the span lands in the ring it finds at exit."""
    with trace.span(P + "serve", cat="setup", num_slots=2) as sp:
        ring = trace.enable()
    rows, added = ring.span_snapshot()
    assert added == 1 and rows[0][:2] == (P + "serve", "setup")
    assert rows[0][2:4] == (sp.t0, sp.t1) and rows[0][5] == {"num_slots": 2}
    assert trace.setup_spans()[-1][:3] == (P + "serve", sp.t0, sp.t1)
    with trace.span("dstpu.sched.step"):
        pass
    assert ring.span_snapshot()[1] == 2 and \
        trace.setup_spans()[-1][0] == P + "serve"


def test_the_import_span_is_recorded_once_when_the_package_loads():
    """In a fresh interpreter (this one's list has long since turned over):
    ``dstpu.setup.import`` runs from the package's first line to its last,
    and importing records nothing else."""
    code = ("import time; t0 = time.monotonic(); import deepspeed_tpu as d; "
            "t1 = time.monotonic(); "
            "from deepspeed_tpu.monitor import trace; "
            "(s,) = trace.setup_spans(); "
            "assert s[0] == 'dstpu.setup.import' and s[4] == {}, s; "
            "assert t0 <= s[1] == d._T_IMPORT < s[2] <= t1, (t0, s, t1); "
            "assert trace.tracer() is None; "
            "print(trace.ready_line('x'))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [os.path.dirname(os.path.dirname(deepspeed_tpu.__file__))]
        + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    out = subprocess.run([sys.executable, "-c", code], env=env, timeout=120,
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-2000:]
    line = out.stdout.strip().splitlines()[-1]
    assert line.startswith("ready[x]: import ") and \
        line.endswith("; compiled: nothing")


def test_ready_line_sums_own_time_by_phase_and_seconds_by_program(
        ring_off, monkeypatch):
    m = "MainThread"
    hit = dict(store_hit=0, persistent_requests=2, persistent_hits=2)
    miss = dict(store_hit=0, persistent_requests=1, persistent_hits=0)
    monkeypatch.setattr(trace, "setup_spans", lambda: [
        (P + "import", 0.0, 4.0, m, {}),
        (P + "engine", 1.0, 2.0, m, {}),          # an older engine: left out
        (P + "engine", 10.0, 13.0, m, {}),
        (P + "weights", 11.0, 12.5, m, {}),
        (P + "warmup", 20.0, 50.0, m, {}),
        (P + "compile", 21.0, 30.0, m, dict(miss, program="prefill_chunk")),
        (P + "compile", 30.0, 49.0, m, dict(hit, program="decode")),
        (P + "compile", 60.0, 61.0, m, dict(miss, program="decode")),
        (P + "compile", 62.0, 62.5, m,
         dict(store_hit=1, persistent_requests=0, persistent_hits=0,
              program="train_step"))])
    assert trace.ready_line("serving") == (
        "ready[serving]: import 4.0s, engine 1.5s, weights 1.5s, warmup 2.0s; "
        "compiled: prefill_chunk 9.0s (miss), decode 20.0s (cache/miss), "
        "train_step 0.5s (store)")
