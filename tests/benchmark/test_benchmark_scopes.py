"""The readers of device time by the program's own parts
(``benchmark/scopes.py``): each new per-layer metric on hand-made events
and hand-made tables with known answers (the tables are what the profiler
stores in the trace, ``profiler.trace_scopes``: keyed by program id), the
table printed by hand, and every reader on a program without the join (a
parent commit) or a run without a trace: nothing to read, no error."""

import os
import types

import pytest

from benchmark import scopes, spec, trace
from deepspeed_tpu.profiling.flops_profiler import profiler

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
D0, OPS, MODS = "/device:TPU:0", trace.OPS_LINE, trace.MODULES_LINE
NEW = {
    "scope.mlp_ms_per_step": ("programs", "train_tokens_per_s_per_chip"),
    "scope.attn_proj_ms_per_step": ("programs",
                                    "train_tokens_per_s_per_chip"),
    "scope.head_loss_ms_per_step": ("programs",
                                    "train_tokens_per_s_per_chip"),
    "scope.optimizer_ms_per_step": ("programs",
                                    "train_tokens_per_s_per_chip"),
    "scope.unattributed_pct.train": ("programs",
                                     "train_tokens_per_s_per_chip"),
    "scope.unattributed_pct.batch": ("programs", "batch_tokens_per_s"),
    "conv.short_share_pct": ("kernels", "batch_tokens_per_s"),
    "moe.route_scope_share_pct": ("experts", "batch_tokens_per_s"),
    "attn.mla_decompress_share_pct": ("kernels", "batch_tokens_per_s"),
}
F = "jit(train_step)/jvp(Transformer)/Transformer.hidden_states/"
T = "jit(train_step)/transpose(jvp(Transformer))/Transformer.hidden_states/"
S = "jit(decode_block)/while/body/closed_call/Lfm2Model.decode/"


@pytest.fixture(scope="module")
def bench():
    return spec.Benchmark(ROOT)


def _op(name, start_ms, dur_ms, opcode="fusion"):
    return (D0, OPS, f"%{name} = bf16[2,2048]{{1,0}} {opcode}(bf16[2]{{0}} "
            f"%x)", start_ms * 1e-3, dur_ms * 1e-3)


def _mod(name, start_ms, dur_ms, program=123):
    return (D0, MODS, f"{name}({program})", start_ms * 1e-3, dur_ms * 1e-3)


def _run(events):
    return types.SimpleNamespace(trace=trace.Trace(events) if events else None,
                                 observed={}, slice_t0=None, slice_s=None)


@pytest.fixture
def stored(tmp_path, monkeypatch):
    """What the trace stores, for a test to fill: ``{program id: {instruction
    name: op_name}}``; ``.bench_trace`` in a tmp dir."""
    class Stored(dict):
        asked = []
    tables = Stored()
    monkeypatch.setattr(profiler, "trace_scopes",
                        lambda path: tables.asked.append(path) or tables)
    monkeypatch.setattr(scopes.spans, "trace_dir",
                        lambda: str(tmp_path / ".bench_trace"))
    return tables


def _train_events():
    # two whole steps of 100 ms and the 50 ms tail of a third: 2.5 steps
    events = [_mod("jit_train_step", 0, 100), _mod("jit_train_step", 100, 100),
              _mod("jit_train_step", 200, 50)]
    for t0 in (0, 100):
        events += [
            _op("fusion.1", t0 + 0, 20),               # mlp fwd
            _op("fusion.2", t0 + 20, 30),              # mlp bwd
            _op("fusion.3", t0 + 50, 10),              # attn.proj
            _op("fusion.4", t0 + 60, 5),               # head
            _op("fusion.5", t0 + 65, 5),               # loss
            _op("fusion.6", t0 + 70, 5),               # embed
            _op("fusion.7", t0 + 75, 15),              # optim
            _op("fusion.8", t0 + 90, 10),              # no scope
        ]
    events += [_op("fusion.1", 200, 20), _op("fusion.2", 220, 30)]
    return events


TRAIN_SCOPES = {
    "fusion.1": F + "layers_0/mlp/up_proj/dot_general",
    "fusion.2": T + "layers_0/mlp/down_proj/dot_general",
    "fusion.3": F + "layers_0/attn/o_proj/dot_general",
    "fusion.4": F + "final_norm/mul",
    "fusion.5": "jit(train_step)/jvp(Transformer)/loss/reduce_max",
    "fusion.6": T + "embed_tokens/jit(_take)/scatter-add",
    "fusion.7": "jit(train_step)/optim.update/mul",
}


@pytest.mark.parametrize("metric, want", [
    ("scope.mlp_ms_per_step", (2 * 50 + 50) / 2.5),
    ("scope.attn_proj_ms_per_step", 2 * 10 / 2.5),
    ("scope.head_loss_ms_per_step", 2 * 15 / 2.5),
    ("scope.optimizer_ms_per_step", 2 * 15 / 2.5),
    ("scope.unattributed_pct.train", 100.0 * 20 / 250),
])
def test_the_training_readers(bench, stored, metric, want):
    # 456: another program's table in the same trace, not this module's
    stored.update({123: TRAIN_SCOPES, 456: {"fusion.1": "jit(x)/loss/mul"}})
    run = _run(_train_events())
    assert bench.reader(metric).read(run) == pytest.approx(want)
    assert bench.reader("scope.mlp_ms_per_step").read(run) \
        == pytest.approx(150 / 2.5)
    assert stored.asked == [scopes.spans.trace_dir()]       # once a run
    # the batch readers find no slot program in a training cell's trace
    assert bench.reader("scope.unattributed_pct.batch").read(run) is None


def _serve_events():
    events = [_mod("jit_decode_block", 0, 60, 11),
              _mod("jit_chunk_step", 60, 40, 22), _mod("jit_admit", 100, 1, 33)]
    events += [
        _op("while.1", 0, 60, "while"),                # holds the steps
        _op("fusion.1", 0, 12),                        # conv.short (in_proj)
        _op("fusion.2", 12, 8),                        # conv.short (scope)
        _op("fusion.3", 20, 10),                       # moe.route
        _op("fusion.4", 30, 24),                       # mlp
        # in the chunk step the same NAMES mean other things
        _op("fusion.1", 60, 25),                       # attn.mla_decompress
        _op("fusion.2", 85, 5),                        # moe.route
        _op("fusion.9", 90, 10),                       # no scope
        _op("fusion.5", 100, 1),                       # the admit's: not read
    ]
    return events


DECODE_SCOPES = {
    "fusion.1": S + "layers_0/conv/in_proj/dot_general",
    "fusion.2": S + "layers_0/conv/conv.short/mul",
    "fusion.3": S + "layers_2/moe_mlp/moe_mlp._scored/moe.route/top_k",
    "fusion.4": S + "layers_0/feed_forward/down_proj/dot_general",
}
CHUNK_SCOPES = {
    "fusion.1": "jit(chunk_step)/Dots3Model.decode/layers_0/attn.chunk/"
                "attn._chunk_full/attn._attend/attn.mla_decompress/"
                "lr,rhd->hld/dot_general",
    "fusion.2": "jit(chunk_step)/Dots3Model.decode/layers_1/moe_mlp/"
                "moe_mlp._scored/moe.route/top_k",
}


@pytest.mark.parametrize("metric, want", [
    ("conv.short_share_pct", 100.0 * 20 / 101),
    ("moe.route_scope_share_pct", 100.0 * 15 / 101),
    ("attn.mla_decompress_share_pct", 100.0 * 25 / 101),
    # the while's own 6 ms and the chunk's unnamed 10
    ("scope.unattributed_pct.batch", 100.0 * 16 / 101),
])
def test_the_serving_readers_keep_two_programs_names_apart(
        bench, stored, metric, want):
    stored.update({11: DECODE_SCOPES, 22: CHUNK_SCOPES})
    assert bench.reader(metric).read(_run(_serve_events())) \
        == pytest.approx(want)


def test_two_signatures_of_one_module_keep_their_own_tables(bench, stored):
    """Two programs named ``jit_chunk_step`` whose instruction names mean
    different things: each execution is read against its own program's
    table (the id in its event's name), so nothing is ambiguous."""
    stored.update({22: CHUNK_SCOPES,
                   23: {"fusion.1": CHUNK_SCOPES["fusion.2"],
                        "fusion.2": CHUNK_SCOPES["fusion.1"]}})
    events = [_mod("jit_chunk_step", 0, 40, 22),
              _mod("jit_chunk_step", 40, 60, 23),
              _op("fusion.1", 0, 30), _op("fusion.2", 30, 10),
              _op("fusion.1", 40, 40), _op("fusion.2", 80, 20)]
    read = lambda metric: bench.reader(metric).read(_run(events))
    assert read("attn.mla_decompress_share_pct") == pytest.approx(50.0)
    assert read("moe.route_scope_share_pct") == pytest.approx(50.0)
    assert read("scope.unattributed_pct.batch") == 0.0


@pytest.mark.parametrize("metric", sorted(NEW))
def test_nothing_to_read_is_none_not_an_error(bench, stored, metric,
                                              monkeypatch):
    read = bench.reader(metric).read
    stored.update({123: TRAIN_SCOPES, 11: DECODE_SCOPES, 22: CHUNK_SCOPES})
    assert read(_run(None)) is None               # a run without a trace
    # a trace that holds no execution of the metric's programs
    other = [_mod("jit_admit", 0, 10, 33), _op("fusion.1", 0, 10)]
    assert read(_run(other)) is None
    # a parent commit: no join in the program
    events = _train_events() + [
        (D0, e[1], e[2], e[3] + 1.0, e[4]) for e in _serve_events()]
    assert read(_run(events)) is not None
    monkeypatch.delattr(profiler, "trace_scopes")
    assert read(_run(events)) is None


def test_the_table_prints_by_hand_from_the_trace_alone(
        stored, tmp_path, monkeypatch, capsys):
    stored[123] = TRAIN_SCOPES
    run = _run(_train_events())
    assert scopes.by_part(run, scopes.TRAIN)["total_s"] == pytest.approx(0.250)
    # by hand, in another process: events and tables from the profiler's
    # file, nothing else beside it
    monkeypatch.setattr(scopes.trace, "read_events",
                        lambda path: _train_events())
    scopes.summarize(str(tmp_path / ".bench_trace"))
    out = capsys.readouterr().out
    assert "2.50 train steps" in out and "3 executions" in out
    row = next(l for l in out.splitlines() if l.startswith("mlp"))
    assert row.split()[1:5] == ["0.0600", "0.0900", "0.0000", "0.1500"]
    assert "jit_train_step:fusion.8" in out
    assert stored.asked[-1] == str(tmp_path / ".bench_trace")
    assert not os.path.exists(tmp_path / ".bench_trace")   # writes nothing


def test_the_nine_entries_are_appended_with_readers(bench):
    assert spec.validate(bench) == []
    assert spec.check_files(bench) == []
    # found by name, in the order they were appended; later PRs append
    tail = [m for m in bench.doc["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in tail] == list(NEW)
    first = bench.doc["per_layer"].index(tail[0])
    assert bench.doc["per_layer"][first:first + len(NEW)] == tail
    cells = {w["name"] for w in bench.doc["workloads"]}
    for m in tail:
        assert (m["layer"], m["moves"]) == NEW[m["name"]]
        assert m["better"] == "lower" and m["source"] == "device_trace"
        assert set(m["workloads"]) <= cells
        assert m["unit"] == ("ms" if m["name"].endswith("_per_step") else "%")
        assert callable(bench.reader(m["name"]).read)
    by_name = {m["name"]: m["workloads"] for m in tail}
    assert by_name["scope.optimizer_ms_per_step"] == [
        "opt13b-sft-1chip", "opt67b-zero3-4chip"]
    assert "opt13b-serve-chat" not in by_name["scope.unattributed_pct.batch"]
    assert by_name["conv.short_share_pct"] == ["lfm2-serve-widegen-batch"]
