"""The OLMoE configuration, its cell, its family's float8 control, and the
expert-layer readers — on hand-made events with known answers, and on a
program that has no such span or kernel (a parent commit, a dense cell):
nothing to read, no error."""

import os
import types

import numpy as np
import pytest

from benchmark import opsbytes_moe, spans, spec, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONFIG = "olmoe-serve-gen-batch", "olmoe-1b-7b-l8"
D0, OPS = "/device:TPU:0", trace.OPS_LINE
TOY = dict(hidden_size=128, intermediate_size=64, num_hidden_layers=4,
           num_attention_heads=4, num_key_value_heads=4, num_experts=8,
           num_experts_per_tok=2, norm_topk_prob=False, vocab_size=1024,
           max_position_embeddings=64)


@pytest.fixture(scope="module")
def bench():
    return spec.Benchmark(ROOT)


# ---- the configuration and the cell ------------------------------------ #
def test_configuration_keeps_every_published_number_but_the_depth(bench):
    assert spec.validate(bench) == [] and spec.check_files(bench) == []
    entry = bench._entry("configs", CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    cfg = bench.cell(CELL)["config"]
    src = cfg["source_config"]
    changed = {k for k, v in src.items() if cfg.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"}
    assert (src["num_hidden_layers"], cfg["num_hidden_layers"]) == (16, 8)
    # the sizes the cut and the cache arithmetic rest on
    z = bench.family(cfg["family"]).sizes_of(cfg)
    assert (z["h"], z["heads"], z["d"], z["f"], z["experts"], z["top_k"]) \
        == (2048, 16, 128, 1024, 64, 8) and not z["norm_topk"]
    assert cfg["kv_bytes_per_token"] == 2 * z["layers"] * z["h"] * 2
    per_layer = 4 * z["h"] ** 2 + 4 * z["h"] + z["h"] * z["experts"] \
        + z["experts"] * 3 * z["h"] * z["f"]
    both = 2 * z["vocab"] * z["h"] + z["h"]
    assert cfg["parameters"] == 8 * per_layer + both == 3_562_604_544
    assert cfg["parameters_published_depth"] == 16 * per_layer + both \
        == 6_919_161_856


def test_cell_is_the_issues(bench):
    cell = bench.cell(CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    mix = cell["traffic"]
    assert mix["kind"] == "closed_loop_engine" and mix["callers"] == 96
    assert mix["prompt_len"] == {"dist": "uniform", "min": 128, "max": 512}
    assert mix["output_len"] == {"dist": "uniform", "min": 192, "max": 576}
    assert (mix["cycle"], mix["base_seed"], mix["ramp_s"]) == (64, 27, 16.0)
    serving = cell["system"]["serving"]
    assert serving["paged"] and serving["num_slots"] == 64
    # the longest request fits a slot's pages
    assert serving["max_cache_len"] >= 512 + 576
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"batch_tokens_per_s", "setup_s"}
    assert {"sched.occupancy_pct", "step.decode_block_ms.batch",
            "step.prefill_chunk_ms", "device.idle_pct.batch",
            "kernel.paged_decode_share_pct.batch"} \
        <= {m["name"] for m in cell["per_layer"]}
    limits = cell["system"]["correct"]
    assert limits["sample_requests"] == 12 and 0 < limits["mean_logit_gap"]


def test_traffic_is_the_generators(bench):
    from benchmark import trafficgen
    mix = bench.cell(CELL)["traffic"]
    stream = trafficgen.closed_loop_requests(mix, 50304, 3000002701)
    sizes = [(len(p), n) for _, p, n in (next(stream) for _ in range(128))]
    assert all(128 <= p <= 512 and 192 <= n <= 576 for p, n in sizes)
    assert sizes[:64] == sizes[64:]           # one fixed cycle of sizes


# ---- the family's control ---------------------------------------------- #
def _toy_family(bench):
    """A family instance of its own (``bench.family`` loads the file anew)
    whose weights have, at hidden 128 and expert width 64, the per-feature
    magnitudes of the real configuration: sqrt(hidden) x std = 0.9, as 2048
    at 0.02, and sqrt(width) x the down-projections' std = 6.4, as 1024 at
    0.2 — at the real stds a toy's layers add nothing to its embeddings
    and every precision picks the same tokens."""
    fam = bench.family("olmoe")
    fam._W, fam._DOWN = 0.08, 0.8
    return fam, fam.sizes_of(TOY)


def test_float8_control_fails_the_logit_gap_comparison(bench):
    """As ``test_benchmark_control.py`` holds ``opt``: the reference in the
    program's place, decoding greedily in float8, must fail the comparison
    that bfloat16 passes.  The limit is this toy's."""
    fam, z = _toy_family(bench)
    mean = {}
    for precision in ("bfloat16", "float8"):
        gaps = []
        for i in range(2):
            prompt = np.random.default_rng([4, i]).integers(0, 1024, 24)
            toks = fam.greedy(z, 4, prompt, 24, 64, precision)
            assert (toks[:24] == prompt).all() and len(toks) == 48
            gaps.append(fam.chosen_gaps(z, 4, toks, 24, 24, 64))
        mean[precision] = float(np.mean(gaps))
    limit = 2e-2
    assert mean["bfloat16"] < limit / 2, mean
    assert mean["float8"] > 2 * limit, mean


def test_chooser_control_reads_the_same_positions(bench):
    """``chosen_gaps(chooser=...)``: float32 choosing in the served tokens'
    place picks the reference's own argmax everywhere (gap 0); float8
    does not."""
    fam, z = _toy_family(bench)
    toks = np.random.default_rng(9).integers(0, 1024, 40)
    gap = lambda chooser: fam.chosen_gaps(z, 4, toks, 16, 24, 64, chooser)
    assert not gap("float32").any()
    assert gap("float8").mean() > 1e-5
    nll = np.asarray(fam.nll_at(z, 4, toks[None], np.arange(8)[None]))
    assert nll.shape == (1, 8) and (nll > 0).all()


def test_experts_only_control_lies_between_bfloat16_and_float8(bench):
    """``"float8_experts"`` rounds the operands of the experts' three
    matmuls alone (the router, attention, the residual and the head stay
    bfloat16): its logits lie further from float32's than bfloat16's and
    nearer than whole-model float8's."""
    fam, z = _toy_family(bench)
    toks = np.random.default_rng(11).integers(0, 1024, (2, 48))
    ref = np.asarray(fam.logits(z, 4, toks))
    err = {p: float(np.sqrt(np.mean(
        (np.asarray(fam.logits(z, 4, toks, p)) - ref) ** 2)))
        for p in ("bfloat16", "float8_experts", "float8")}
    assert 1.3 * err["bfloat16"] < err["float8_experts"] \
        < err["float8"] / 1.5, err
    assert fam._split("float8_experts") == ("bfloat16", "float8")
    assert fam._split("float8") == ("float8", "float8")
    gap = fam.chosen_gaps(z, 4, toks[0], 16, 24, 64, "float8_experts")
    assert gap.shape == (24,) and (gap >= 0).all()


# ---- operations and bytes ----------------------------------------------- #
def test_needed_bytes_and_operations():
    # one call that touches all 64 experts of OLMoE-1B-7B: 805 MB
    assert opsbytes_moe.experts_bytes(64, 2048, 1024) == 805_306_368
    # 64 lanes x 8 experts: three [2048 x 1024] matrices a pair, 2 a MAC
    assert opsbytes_moe.experts_flops(512, 2048, 1024) \
        == 512 * 3 * 2 * 2048 * 1024


# ---- the readers --------------------------------------------------------- #
def _kernel(name):
    return (f"%{name} = (bf16[64,2048]{{1,0}}) custom-call(s32[64]{{0}} "
            f'%p), custom_call_target="tpu_custom_call"')


def _span(name, **stats):
    return {"name": name, "start_s": 0.0, "dur_s": 0.1, "thread": (0, 0),
            "stats": stats}


def _run(bench, tr):
    cell = bench.cell(CELL)
    return types.SimpleNamespace(
        trace=tr, observed={}, slice_t0=None, slice_s=None, cell=cell,
        family=bench.family("olmoe"), peaks=bench.peaks("tpu v5e"))


def _moe_trace():
    # two expert calls of 2 ms, two routing calls of 0.1 ms, 5.8 ms of
    # everything else: a 10 ms slice
    return trace.Trace([
        (D0, OPS, _kernel("moe.experts_gmm.7"), 0.000, 0.002),
        (D0, OPS, _kernel("moe.route.3"), 0.002, 0.0001),
        (D0, OPS, _kernel("moe.experts_gmm.9"), 0.003, 0.002),
        (D0, OPS, _kernel("moe.route.4"), 0.005, 0.0001),
        (D0, OPS, "%fusion.1 = bf16[2,2048]{1,0} fusion(bf16[2]{0} %x)",
         0.0052, 0.0048)])


def _load_spans():
    # 16 calls in the spans: 64 experts touched in each, 512 assignments
    return [_span("dstpu.sched.commit", tokens=512, moe_assignments=4096,
                  moe_experts_touched=512, moe_max_expert_tokens=120,
                  moe_calls=8),
            _span("dstpu.sched.wait_device", event="admit",
                  moe_assignments=4096, moe_experts_touched=512,
                  moe_max_expert_tokens=136, moe_calls=8),
            _span("dstpu.sched.commit", tokens=3)]


def test_readers_on_known_events(bench, monkeypatch):
    monkeypatch.setattr(spans, "host_spans", lambda *a: _load_spans())
    run = _run(bench, _moe_trace())
    read = lambda m: bench.reader(m).read(run)
    assert read("kernel.moe_experts_share_pct") == pytest.approx(40.0)
    assert read("moe.route_share_pct") == pytest.approx(2.0)
    # busiest expert 256 tokens over 16 calls = 16 a call; mean 512 / 64 = 8
    assert read("moe.load_max_over_mean") == pytest.approx(2.0)
    # a call must read 64 experts = 805.3 MB = 0.9833 ms at 819 GB/s (its
    # 512 pairs' 12.9 GFLOP are 0.065 ms); a call took 2 ms
    assert read("kernel.moe_experts_roofline") == pytest.approx(
        100 * 805_306_368 / 819e9 / 0.002)
    assert opsbytes_moe.span_load() == {
        "moe_assignments": 8192, "moe_experts_touched": 1024,
        "moe_max_expert_tokens": 256, "moe_calls": 16}


def test_readers_find_nothing_on_a_program_without_experts(
        bench, monkeypatch):
    dense = trace.Trace([
        (D0, OPS, _kernel("attn.paged_decode.54"), 0.0, 6.0),
        (D0, OPS, _kernel("attn.7"), 6.0, 2.0)])
    for host in ([], [_span("dstpu.sched.commit", tokens=3)]):
        monkeypatch.setattr(spans, "host_spans", lambda *a, h=host: h)
        assert opsbytes_moe.span_load() is None
        for metric in opsbytes_moe.READERS:
            for tr in (dense, None):
                assert bench.reader(metric).read(_run(bench, tr)) is None
    # kernels in the trace, but spans that carry no load: no roofline
    monkeypatch.setattr(spans, "host_spans", lambda *a: [])
    run = _run(bench, _moe_trace())
    assert bench.reader("kernel.moe_experts_roofline").read(run) is None
    assert bench.reader("moe.load_max_over_mean").read(run) is None
    assert bench.reader("moe.route_share_pct").read(run) is not None


MOE_METRICS = [
    ("kernel.moe_experts_roofline", "%", "higher", "device_trace", "kernels"),
    ("kernel.moe_experts_share_pct", "%", "lower", "device_trace",
     "kernels"),
    ("moe.route_share_pct", "%", "lower", "device_trace", "experts"),
    ("moe.load_max_over_mean", "ratio", "lower", "program_counter",
     "experts")]


def test_the_four_expert_layer_metrics_are_the_last_entries(bench):
    """The last entries when PR 27 appended them: today the four are found
    by name, side by side in their order right after the last entry the
    benchmark had then, and this cell heads each one's list (later cells
    append)."""
    names = [m["name"] for m in bench.doc["per_layer"]]
    first = names.index(MOE_METRICS[0][0])
    last = bench.doc["per_layer"][first:first + 4]
    assert names[first - 1] == "setup.backend_compile_s"
    assert [(m["name"], m["unit"], m["better"], m["source"], m["layer"])
            for m in last] == MOE_METRICS
    for m in last:
        assert m["moves"] == "batch_tokens_per_s" \
            and m["workloads"][0] == CELL \
            and m["workloads"].count(CELL) == 1
        assert callable(bench.reader(m["name"]).read)
    assert [m["name"] for m in last] == list(opsbytes_moe.READERS)
    got = {m["name"] for m in bench.cell(CELL)["per_layer"]}
    assert set(opsbytes_moe.READERS) <= got
    for other in ("opt13b-serve-chat", "opt13b-serve-longprompt-batch"):
        assert not set(opsbytes_moe.READERS) \
            & {m["name"] for m in bench.cell(other)["per_layer"]}


def test_what_the_two_outgrown_pins_still_hold(bench):
    """What two position pins held of the file before they were rewritten
    to what they mean (PR 44):
    ``test_benchmark_spec.py::test_two_configurations_and_one_four_chip_cell``
    pinned the list of configurations and
    ``test_benchmark_spans.py::test_the_new_metrics_are_entries_with_readers``
    the number of per-layer entries.  Everything else they assert,
    asserted here on the file as it stands: the order of what they knew,
    never a count."""
    assert [c["name"] for c in bench.doc["configs"]][:2] \
        == ["opt-1.3b", "opt-6.7b-l8"]
    assert [w["name"] for w in bench.doc["workloads"] if w["chips"] == 4] \
        == ["opt67b-zero3-4chip"]
    names = [m["name"] for m in bench.doc["per_layer"]]
    assert names[27:27 + 4] == [m[0] for m in MOE_METRICS]
    assert names[15] == "frontend.submit_wait_p50_ms"
    chat = {m["name"] for m in bench.cell("opt13b-serve-chat")["per_layer"]}
    assert {"frontend.lock_wait_p50_ms", "sched.first_token_lag_p50_ms",
            "sched.host_ms_per_iter.chat", "setup.trace_lower_s"} <= chat
    assert "sched.host_ms_per_iter.batch" not in chat
    for cell in ("opt13b-sft-1chip", "opt67b-zero3-4chip"):
        got = {m["name"] for m in bench.cell(cell)["per_layer"]}
        assert {"kernel.flash_fwd_ms_per_step",
                "kernel.flash_bwd_ms_per_step",
                "setup.backend_compile_s"} <= got
