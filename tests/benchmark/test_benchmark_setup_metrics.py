"""The nine readers that split ``setup_s`` (layer "entry points"): each on a
hand-made list of the program's set-up spans with known answers — own time,
the cut at the slice's opening, nothing to read on a program without the
spans (a parent commit) — and ``BENCHMARK.json`` with their entries."""

import os
import types

import pytest

from benchmark import harness, setup_spans, spans, spec
from deepspeed_tpu.monitor import trace as program_trace
from deepspeed_tpu.runtime import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
P, M = "dstpu.setup.", "MainThread"
T0 = 1000.0                              # the made-up process's start
TRACE, LOWER, BACKEND = ("/jax/core/compile/jaxpr_trace_duration",
                         "/jax/core/compile/jaxpr_to_mlir_module_duration",
                         "/jax/core/compile/backend_compile_duration")
SERVING = ["opt13b-serve-chat", "opt13b-serve-longprompt-batch",
           "olmoe-serve-gen-batch", "dots3-serve-longdoc-batch",
           "lfm2-serve-widegen-batch", "evabyte-serve-bytedoc-batch",
           "glm5-serve-reasongen-batch", "longcat-serve-agentgen-batch",
           "trinity-serve-mixedlen-batch"]
TRAINING = ["opt13b-sft-1chip", "opt67b-zero3-4chip"]
METRICS = {  # name -> cells, in BENCHMARK.json's order
    "setup.outside_program_s": "all", "setup.import_s": "all",
    "setup.engine_build_s": "all", "setup.weights_s": "all",
    "setup.compile_chunk_s": SERVING, "setup.compile_block_s": SERVING,
    "setup.compile_admit_s": SERVING,
    "setup.compile_train_step_s": TRAINING,
    "setup.compile_after_warmup_s": "all"}


def _c(t0, t1, program, track=M):
    return (P + "compile", t0, t1, track, {"program": program})


# a server's start-up, seconds after T0: 2 s before the import, the engine
# 8-9 (half of it a lazy import), the weights' draw 9-15 (no span: the caller's), set_params 15-16,
# serve() 16-17.5, warmup 18-60 holding two compiles and 2 s of its own, the
# pools and the admit program in the ramp; the slice opens at T0 + 90.  A
# compile on another thread lies inside the warm-up by time and is not its
# child; one span closes after the slice opened and one compile is of an
# older process phase (before T0)
SERVER = [
    _c(T0 - 50, T0 - 40, "decode"),
    (P + "import", T0 + 2, T0 + 7, M, {}),
    (P + "engine", T0 + 8, T0 + 9, M, {"entry": "init_inference"}),
    (P + "lazy_import", T0 + 8.25, T0 + 8.75, M, {"module": "torch"}),
    (P + "weights", T0 + 15, T0 + 16, M, {"bytes": 10}),
    (P + "serve", T0 + 16, T0 + 17.5, M, {"num_slots": 2}),
    (P + "warmup", T0 + 18, T0 + 60, M, {"programs": 2}),
    _c(T0 + 18.5, T0 + 35.5, "prefill_chunk"),
    _c(T0 + 36, T0 + 59, "spec_block"),
    _c(T0 + 40, T0 + 41, "draft_admit", track="other"),
    (P + "pools", T0 + 61, T0 + 61.25, M, {"bytes": 7}),
    _c(T0 + 62, T0 + 63.5, "admit"),
    _c(T0 + 89, T0 + 91, "prefill_chunk"),      # still open at the slice
]
# a trainer's: the engine 20-30 holds weights 21-24 and the optimizer's
# state 24-26; warmup 40-50 holds the step's compile, both rungs tried
TRAINER = [
    (P + "import", T0 + 1, T0 + 4, M, {}),
    (P + "engine", T0 + 20, T0 + 30, M, {"entry": "initialize"}),
    (P + "weights", T0 + 21, T0 + 24, M, {}),
    (P + "optimizer_state", T0 + 24, T0 + 26, M, {}),
    (P + "warmup", T0 + 40, T0 + 50, M, {"programs": 1}),
    _c(T0 + 41, T0 + 44, "train_step"),
    _c(T0 + 44.5, T0 + 49.5, "train_step"),
]
EVENTS = [(T0 + 30.0, BACKEND, 9.0),     # inside the warm-up
          (T0 + 60.0, TRACE, 5.0),       # AT its close: not after it
          (T0 + 62.5, TRACE, 0.25), (T0 + 63.0, LOWER, 0.5),
          (T0 + 63.4, BACKEND, 0.75),    # the admit program, in the ramp
          (T0 + 92.0, TRACE, 0.125),     # inside the slice
          (T0 + 93.5, BACKEND, 64.0)]    # after it closed


@pytest.fixture(scope="module")
def bench():
    return spec.Benchmark(ROOT)


@pytest.fixture
def program(monkeypatch):
    """The program's side, made up: ``program(spans, events)``."""
    def install(rows, events=()):
        stats = compile_cache.CacheStats()
        stats.compile_events.extend(events)
        monkeypatch.setattr(program_trace, "setup_spans", lambda: list(rows))
        monkeypatch.setattr(compile_cache, "_STATS", stats)
        monkeypatch.setattr(harness, "T_PROCESS_START", T0)
    return install


def _run(slice_t0=T0 + 90.0, slice_s=3.0):
    return types.SimpleNamespace(slice_t0=slice_t0, slice_s=slice_s,
                                 trace=None, observed={})


def _read(bench, run=None):
    run = run or _run()
    return {name: bench.reader(name).read(run) for name in METRICS}


def test_each_reader_on_a_servers_start_up(bench, program):
    program(SERVER, EVENTS)
    assert _read(bench) == {
        # 60 s to ready, less import 5, engine 1, weights 1, serve 1.5 and
        # the warm-up's 42
        "setup.outside_program_s": pytest.approx(60 - 50.5),
        "setup.import_s": pytest.approx(5.0),
        # own: engine (1 - 0.5) + serve 1.5 + warmup (42 - 17 - 23), and the
        # named parts: the lazy import 0.5 + pools 0.25
        "setup.engine_build_s": pytest.approx(0.5 + 1.5 + 2 + 0.5 + 0.25),
        "setup.weights_s": pytest.approx(1.0),
        # the older compile counts too: the reader cuts at the slice only
        "setup.compile_chunk_s": pytest.approx(17.0),
        "setup.compile_block_s": pytest.approx(10.0 + 23.0),
        "setup.compile_admit_s": pytest.approx(1.5),
        "setup.compile_train_step_s": None,
        "setup.compile_after_warmup_s": pytest.approx(1.5 + 0.125)}


def test_each_reader_on_a_trainers_start_up(bench, program):
    program(TRAINER, [(T0 + 48.0, BACKEND, 4.0), (T0 + 55.0, TRACE, 0.5)])
    assert _read(bench, _run(slice_t0=T0 + 70.0, slice_s=None)) == {
        "setup.outside_program_s": pytest.approx(50 - 3 - 10 - 10),
        "setup.import_s": pytest.approx(3.0),
        "setup.engine_build_s": pytest.approx(5.0 + 2.0),
        "setup.weights_s": pytest.approx(3.0 + 2.0),
        "setup.compile_chunk_s": None, "setup.compile_block_s": None,
        "setup.compile_admit_s": None,
        "setup.compile_train_step_s": pytest.approx(3.0 + 5.0),
        "setup.compile_after_warmup_s": pytest.approx(0.5)}


def test_the_cut_at_the_slices_opening(bench, program):
    """Only what CLOSED before the slice opened is set-up: an early slice
    leaves the warm-up, and everything read from its close, unread."""
    program(SERVER, EVENTS)
    early = _read(bench, _run(slice_t0=T0 + 36.0))
    assert early["setup.compile_chunk_s"] == pytest.approx(17.0)
    assert early["setup.compile_block_s"] == pytest.approx(10.0)
    assert early["setup.compile_admit_s"] is None
    assert early["setup.outside_program_s"] is None
    assert early["setup.compile_after_warmup_s"] is None
    assert early["setup.engine_build_s"] == pytest.approx(1 + 1.5)
    # a second warm-up (another server of the process): ready is the LAST
    program(SERVER + [(P + "warmup", T0 + 70, T0 + 80, M, {})], EVENTS)
    late = _read(bench)
    assert late["setup.outside_program_s"] == pytest.approx(
        80 - 50.5 - 0.25 - 1.5 - 10)
    assert late["setup.compile_after_warmup_s"] == pytest.approx(0.125)


def test_own_time_nests_by_track():
    assert setup_spans.BUILD == ("engine", "serve", "warmup", "pools",
                                 "lazy_import")
    rows = [{"name": n, "start_s": a, "dur_s": b - a, "thread": k}
            for n, a, b, k in [
                ("warmup", 0, 10, M), ("compile", 1, 4, M),
                ("compile", 4, 9, M), ("compile", 2, 3, "other"),
                ("serve", 10, 12, M), ("pools", 10.5, 11, M),
                ("engine", 20, 21, M)]]
    assert spans.self_seconds(rows) == pytest.approx(
        {"warmup": 2.0, "compile": 9.0, "serve": 1.5, "pools": 0.5,
         "engine": 1.0})
    assert setup_spans.summed(rows, "compile", "pools") == pytest.approx(9.5)
    assert setup_spans.summed(rows, "weights") is None
    assert setup_spans.summed(None, "weights") is None


def test_nothing_to_read_on_a_program_without_the_spans(bench, program,
                                                        monkeypatch):
    """This PR's parent: compile events and no ``setup_spans``; and a
    program that kept none yet.  Every reader returns None, none raises."""
    program([], EVENTS)
    assert set(_read(bench).values()) == {None}
    monkeypatch.delattr(program_trace, "setup_spans")
    assert setup_spans.closed_before() is None
    assert set(_read(bench).values()) == {None}


def test_the_real_programs_list_is_what_the_helper_reads():
    with program_trace.span(P + "pools", cat="setup", bytes=3) as sp:
        pass
    last = setup_spans.closed_before()[-1]
    assert last == {"name": "pools", "start_s": sp.t0, "dur_s": sp.dur_s,
                    "thread": last["thread"], "stats": {"bytes": 3}}
    assert all(r["start_s"] + r["dur_s"] <= sp.t0
               for r in setup_spans.closed_before(sp.t0) or ())


@pytest.mark.parametrize("name", list(METRICS))
def test_the_entry_and_its_reader(bench, name):
    (m,) = [m for m in bench.doc["per_layer"] if m["name"] == name]
    cells = [w["name"] for w in bench.doc["workloads"]]
    assert m == {"name": name, "unit": "s", "better": "lower",
                 "source": "program_span", "layer": "entry points",
                 "moves": "setup_s",
                 "workloads": cells if METRICS[name] == "all"
                 else METRICS[name]}
    assert callable(bench.reader(name).read)
    assert bench.reader(name).__doc__


def test_the_nine_are_appended_and_the_file_is_clean(bench):
    names = [m["name"] for m in bench.doc["per_layer"]]
    first = names.index("setup.outside_program_s")
    assert names[first:first + 9] == list(METRICS)
    assert names.index("setup.backend_compile_s") < first
    assert spec.validate(bench) == [] and spec.check_files(bench) == []
    for cell in SERVING + TRAINING:
        got = {m["name"] for m in bench.cell(cell)["per_layer"]}
        want = {n for n, cells in METRICS.items()
                if cells == "all" or cell in cells}
        assert want <= got and not (set(METRICS) - want) & got
