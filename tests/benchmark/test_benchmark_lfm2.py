"""The LFM2 configuration, its cell, its family's controls, and the readers
of what it adds — on hand-made events with known answers, and on a program
that has no such span (a parent commit, another model's cell): nothing to
read, no error.  Nothing here pins HOW MANY configurations, cells or
per-layer entries ``BENCHMARK.json`` has, or which come last: entries are
found by name, and a list is held to the ORDER of the cells it had."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import opsbytes_lfm2 as ob, opsbytes_moe, spans, spec, trace
from benchmark import trafficgen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONFIG = "lfm2-serve-widegen-batch", "lfm2-24b-a2b-l10"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
D0, OPS = "/device:TPU:0", trace.OPS_LINE
REDUCED = ["num_hidden_layers", "layer_types"]
NEW_METRICS = {
    "moe.rows_per_touched_expert": ("ratio", "program_counter", "experts"),
    "cache.state_share_pct": ("%", "program_counter", "cache manager")}
# the lists this cell was appended to, each with the cells it had before,
# in the order it had them (PR 31's ``cells[-1] == CELL`` pins asserted
# that order through the last entry; see PERF.md Open question c2)
BATCH = ["opt13b-serve-longprompt-batch", "olmoe-serve-gen-batch",
         "dots3-serve-longdoc-batch"]
EVERY = ["opt13b-serve-chat", "opt13b-sft-1chip", "opt67b-zero3-4chip"] \
    + BATCH
SHARED = {
    "batch_tokens_per_s": BATCH, "sched.occupancy_pct": BATCH,
    "step.decode_block_ms.batch": BATCH, "step.prefill_chunk_ms": BATCH,
    "device.idle_pct.batch": BATCH, "sched.host_ms_per_iter.batch": BATCH,
    "setup.trace_lower_s": EVERY, "setup.backend_compile_s": EVERY,
    "kernel.paged_decode_share_pct.batch": BATCH[:2],
    "kernel.moe_experts_roofline": BATCH[1:2],
    "kernel.moe_experts_share_pct": BATCH[1:2],
    "moe.load_max_over_mean": BATCH[1:2]}
TOY = dict(
    model_type="lfm2_moe", conv_L_cache=3, conv_bias=False, hidden_size=64,
    intermediate_size=160,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"],
    max_position_embeddings=512, moe_intermediate_size=48, norm_eps=1e-5,
    norm_topk_prob=True, num_attention_heads=4, num_dense_layers=2,
    num_experts=8, num_experts_per_tok=4, num_hidden_layers=6,
    num_key_value_heads=2,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=1, use_expert_bias=True, vocab_size=256)


@pytest.fixture(scope="module")
def bench():
    return spec.Benchmark(ROOT)


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


# ---- the configuration against its source, key by key -------------------- #
@pytest.mark.parametrize("key", sorted(_config()["source_config"]))
def test_configuration_keeps_the_published_value(key):
    cfg = _config()
    if key in REDUCED:
        assert cfg[key] != cfg["source_config"][key]
    else:
        assert cfg[key] == cfg["source_config"][key]


def test_source_config_is_the_catalogs_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "LFM2-24B-A2B")
    cfg = _config()
    assert cfg["source_config"] == row["config"]
    assert cfg["source"] == row["source_url"]


def test_the_cut_is_the_issues(bench):
    cfg, entry = _config(), bench._entry("configs", CONFIG)
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert not any(spec.WIDTH_RE.search(k) for k in REDUCED)
    assert cfg["num_hidden_layers"] == 10
    assert cfg["layer_types"] == cfg["source_config"]["layer_types"][:10] \
        == ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 2
    # the leading dense layers and two whole periods of the pattern
    assert cfg["source_config"]["layer_types"][2:38] \
        == ["full_attention", "conv", "conv", "conv"] * 9
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["conv_L_cache"],
            cfg["vocab_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["num_dense_layers"]) \
        == (2048, 11776, 1536, 64, 4, 3, 65536, 32, 8, 2)
    for said in ("w_in_thirds", "conv_state", "gate_sum_eps", "router",
                 "rope", "qk_norm", "tie_word_embeddings", "final_norm",
                 "precision", "weights"):
        assert said in cfg["assumed"]
    for said in ("four pipeline stages", "STAGE ONE", "47.7 GB",
                 "only stage with dense layers", "12.4 GB"):
        assert said in cfg["deployment"]


def test_parameters_are_counted_from_the_shapes(bench):
    import jax
    import jax.numpy as jnp
    cfg, fam = _config(), bench.family("lfm2")
    module = fam.program_model(cfg)
    tree = jax.eval_shape(module.init, jax.random.key(0),
                          {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    sizes = [int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree)]
    assert sum(sizes) == cfg["parameters"] == 5_267_090_176
    assert abs(cfg["parameters"] / 5.267e9 - 1) < 1e-3       # the issue's
    parts = cfg["parameters_by_part"]
    assert parts == fam.parameters_by_part(fam.sizes_of(cfg))
    assert sum(parts.values()) == cfg["parameters"]
    assert parts["routed_layers"] == 8 * (64 * 3 * 2048 * 1536
                                          + 2048 * 64 + 64)
    assert cfg["parameters_published_depth"] == sum(
        fam.parameters_by_part(fam.sizes_of(cfg["source_config"])).values())
    # the pools at the cell's sizes: K/V layers for the ATTENTION layers
    # only, one state row a slot behind the trash row
    pools = jax.eval_shape(lambda: module.init_paged_cache(
        5377, 64, state_rows=257))
    assert {k: v.shape for k, v in pools.items()} == {
        "k": (2, 5377, 64, 512), "v": (2, 5377, 64, 512),
        "conv": (8, 257, 4096)}
    assert cfg["kv_bytes_per_token"] == 2 * 2 * 512 * 2 == 4096
    assert cfg["state_bytes_per_slot"] == 8 * 4096 * 2 == 65536


def test_benchmark_file_is_valid_with_the_new_entries(bench):
    assert spec.validate(bench) == []
    assert spec.check_files(bench) == []


def test_cell_is_the_issues(bench):
    cell = bench.cell(CELL)
    assert (cell["config_name"], cell["traffic_name"], cell["chips"]) \
        == (CONFIG, "widegen-closed384", 1)
    assert len(cell["why"]) <= 200
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"batch_tokens_per_s", "setup_s"}
    serving = cell["system"]["serving"]
    assert (serving["num_slots"], serving["max_cache_len"],
            serving["page_size"], serving["decode_block"]) \
        == (256, 1344, 64, 8)
    assert serving["prefill_chunk"] in (128, 256, 512)
    correct = cell["system"]["correct"]
    assert correct["mean_logit_gap"] > 0 and correct["sample_requests"] >= 6
    got = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) | (set(SHARED) - {"batch_tokens_per_s"}) <= got


def test_traffic_is_the_issues(bench):
    mix = bench.cell(CELL)["traffic"]
    assert {k: v for k, v in mix.items() if k != "describes"} == {
        "kind": "closed_loop_engine", "callers": 384,
        "prompt_len": {"dist": "uniform", "min": 128, "max": 512},
        "output_len": {"dist": "uniform", "min": 256, "max": 768},
        "cycle": 128, "base_seed": 33, "ramp_s": 20.0,
        "trace_slice_s": 3.0}
    serving = bench.cell(CELL)["system"]["serving"]
    sizes = trafficgen.sizes(mix, 128)
    for p, o in sizes:
        assert p + o <= serving["max_cache_len"]
        assert -(-p // serving["prefill_chunk"]) * serving["prefill_chunk"] \
            <= serving["max_cache_len"]
    assert 300 < np.mean([p for p, _ in sizes]) < 340
    assert 480 < np.mean([o for _, o in sizes]) < 545
    a, b = (next(trafficgen.closed_loop_requests(mix, 65536, s))
            for s in (3_000_000_033, 33))
    assert len(a[1]) == len(b[1]) and 32768 < a[1].max() < 65536
    assert (a[1][:64] != b[1][:64]).any()


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_is_an_entry_with_a_reader(bench, name):
    entry = bench._entry("per_layer", name)
    unit, source, layer = NEW_METRICS[name]
    assert (entry["unit"], entry["source"], entry["layer"]) \
        == (unit, source, layer)
    assert entry["moves"] == "batch_tokens_per_s" \
        and entry["workloads"] == [CELL]
    assert callable(bench.reader(name).read)
    for other in ("opt13b-serve-chat", "olmoe-serve-gen-batch"):
        assert name not in {m["name"]
                            for m in bench.cell(other)["per_layer"]}


@pytest.mark.parametrize("name", sorted(SHARED))
def test_shared_metric_keeps_its_cells_in_order_then_this_cell(bench, name):
    """Everything PR 31's eight ``cells[-1] == CELL`` pins and four
    ``workloads == [olmoe]`` pins hold, but the position of the last
    entry: each list holds each of its earlier cells once, in their old
    order, then this cell."""
    section = "end_to_end" if name == "batch_tokens_per_s" else "per_layer"
    cells = bench._entry(section, name)["workloads"]
    assert len(cells) == len(set(cells))
    assert cells[:len(SHARED[name])] == SHARED[name]
    assert cells.index(CELL) == len(SHARED[name])


MOE_METRICS = [
    ("kernel.moe_experts_roofline", "%", "higher", "device_trace", "kernels"),
    ("kernel.moe_experts_share_pct", "%", "lower", "device_trace",
     "kernels"),
    ("moe.route_share_pct", "%", "lower", "device_trace", "experts"),
    ("moe.load_max_over_mean", "ratio", "lower", "program_counter",
     "experts")]


@pytest.mark.parametrize("want", MOE_METRICS, ids=[m[0] for m in MOE_METRICS])
def test_the_four_expert_layer_metrics_found_by_name(bench, want):
    """What ``test_benchmark_dots3.py``'s test of this name holds of each
    entry, less ``workloads == [olmoe]``: the OLMoE cell comes first, no
    metric is doubled under a new name, and this cell reads the three
    whose readers find something in its program — ``moe.route_share_pct``
    reads the Pallas kernel ``moe.route``, and this family's router is
    XLA under that scope, as dots3's is."""
    m = bench._entry("per_layer", want[0])
    assert (m["name"], m["unit"], m["better"], m["source"], m["layer"]) \
        == want
    assert m["moves"] == "batch_tokens_per_s" \
        and m["workloads"][0] == "olmoe-serve-gen-batch"
    assert callable(bench.reader(m["name"]).read)
    assert want[0] in opsbytes_moe.READERS
    names = [x["name"] for x in bench.doc["per_layer"]]
    assert names.count(want[0]) == 1
    assert (CELL in m["workloads"]) == (want[0] != "moe.route_share_pct")


# ---- the controls read above a sound run at toy size ---------------------- #
def _toy_family(bench):
    """A family instance of its own whose weights have, at hidden 64, the
    per-feature magnitudes of the real configuration."""
    fam = bench.family("lfm2")
    fam._W, fam._OUT, fam._DOWN, fam._EMBED, fam._OWN = \
        0.12, 0.2, 0.3, 0.15, 0.5
    return fam, fam.sizes_of(TOY)


PRECISIONS = ("float32", "bfloat16", "float8", "float8_experts",
              "stale_conv_state", "top3")


@pytest.fixture(scope="module")
def control_logits(bench):
    fam, z = _toy_family(bench)
    toks = np.random.default_rng(11).integers(0, 256, 64)
    return {p: np.asarray(fam.logits(z, 4, toks, p, stale_from=24))
            for p in PRECISIONS}


@pytest.mark.parametrize("control", PRECISIONS[2:])
def test_control_reads_above_a_sound_run(control_logits, control):
    """Each control — the whole model in float8, the experts' matmuls
    alone, the generated positions' conv state lost, three experts a token
    in four's place — is bfloat16 but for ONE thing, and that thing is
    visible: its logits leave the bfloat16 computation's by more than 2% of
    a logit's size (bfloat16 against itself reads 0), and it lies no
    nearer float32 than bfloat16 does."""
    rms = lambda a, b: float(np.sqrt(np.mean((a - b) ** 2)))
    lg = control_logits
    scale = float(np.abs(lg["float32"]).mean())
    assert scale > 0.3
    assert rms(lg[control], lg["bfloat16"]) > 0.02 * scale
    assert rms(lg[control], lg["float32"]) \
        > 0.9 * rms(lg["bfloat16"], lg["float32"])


def test_the_stale_state_control_spares_the_prompt(control_logits):
    """``stale_conv_state`` takes the state away at the GENERATED
    positions only: before ``stale_from`` it is the bfloat16 computation
    to the bit, from there on it is not."""
    lg = control_logits
    assert (lg["stale_conv_state"][:24] == lg["bfloat16"][:24]).all()
    assert np.abs(lg["stale_conv_state"][24:]
                  - lg["bfloat16"][24:]).max() > 0.1


def test_chooser_control_reads_the_generated_positions(bench):
    fam, z = _toy_family(bench)
    toks = np.random.default_rng(12).integers(0, 256, 40)
    served = fam.chosen_gaps(z, 4, toks, 30, 10, 64)
    control = fam.chosen_gaps(z, 4, toks, 30, 10, 64, "stale_conv_state")
    assert served.shape == control.shape == (10,)
    assert (served >= 0).all() and control.mean() > 0
    both = fam.gaps_under(z, 4, toks, 30, 10, 64, [None, "top3"])
    assert (both[None] == served).all()
    with pytest.raises(ValueError):
        fam.chosen_gaps(z, 4, np.zeros(900, np.int32), 20, 800, 1024)


@pytest.mark.parametrize("key,value", [
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}),
    ("conv_bias", True), ("tie_word_embeddings", False),
    ("use_expert_bias", False), ("layer_types", ["conv"] * 5)])
def test_sizes_of_refuses_what_the_reference_lacks(bench, key, value):
    with pytest.raises(ValueError):
        bench.family("lfm2").sizes_of({**TOY, key: value})


# ---- the readers ----------------------------------------------------------- #
def _kernel(name):
    return (f"%{name} = (bf16[256,2048]{{1,0}}) custom-call(s32[64]{{0}} "
            f'%p), custom_call_target="tpu_custom_call"')


def _span(name, **stats):
    return {"name": name, "start_s": 0.0, "dur_s": 0.1, "thread": (0, 0),
            "stats": stats}


def _run(bench, tr, cell=CELL, family="lfm2"):
    return types.SimpleNamespace(
        trace=tr, observed={}, slice_t0=None, slice_s=None,
        cell=bench.cell(cell), family=bench.family(family),
        peaks=bench.peaks("tpu v5e"))


def _lfm2_trace():
    # a 100 ms slice: one decode step's eight dense-form expert calls of
    # 2 ms, one chunk's eight grouped calls of 2.5 ms, two paged-decode
    # calls, two routing kernels as OLMoE's program has them, the rest XLA
    ev, t = [], 0.0
    for name, dur, n in (("moe.experts_gmm", 0.002, 8),
                         ("moe.experts_grouped", 0.0025, 8),
                         ("attn.paged_decode", 0.001, 2),
                         ("moe.route", 0.0005, 2)):
        for i in range(n):
            ev.append((D0, OPS, _kernel(f"{name}.{i}"), t, dur))
            t += dur
    ev.append((D0, OPS, '%fusion.7 = bf16[256,2048]{1,0} fusion(bf16[2]{0} '
               '%x), metadata={op_name="jit(decode_block)/conv.short/mul"}',
               t, 0.004))
    ev.append((D0, OPS, "%fusion.1 = bf16[2,2048]{1,0} fusion(bf16[2]{0} %x)",
               t + 0.004, 0.1 - t - 0.004))
    return trace.Trace(ev)


def _lfm2_spans():
    return [
        _span(ob.DECODE, live_slots=250, state_rows=2000,
              state_bytes=250 * 65536, kv_bytes_mapped=3400 * 262144),
        _span(ob.DECODE, live_slots=256, state_rows=2048,
              state_bytes=256 * 65536, kv_bytes_mapped=3500 * 262144),
        _span("dstpu.sched.dispatch.prefill_chunk", state_rows=1,
              kv_pages=16),
        # a block of one step: 256 lanes x 4 choices in each of 8 layers
        _span("dstpu.sched.commit", tokens=256, moe_assignments=8192,
              moe_experts_touched=512, moe_max_expert_tokens=240,
              moe_calls=8),
        # a chunk of 320 real rows
        _span("dstpu.sched.wait_device", event="admit",
              moe_assignments=10240, moe_experts_touched=512,
              moe_max_expert_tokens=300, moe_calls=8),
        _span("dstpu.sched.commit", tokens=3)]


def test_new_readers_on_known_events(bench, monkeypatch):
    monkeypatch.setattr(spans, "host_spans", lambda *a: _lfm2_spans())
    run = _run(bench, _lfm2_trace())
    read = lambda m: bench.reader(m).read(run)
    assert read("moe.rows_per_touched_expert") == pytest.approx(18432 / 1024)
    share = (250 * 65536 / (250 * 65536 + 3400 * 262144)
             + 256 * 65536 / (256 * 65536 + 3500 * 262144)) / 2
    assert ob.state_share() == pytest.approx(share)
    assert read("cache.state_share_pct") == pytest.approx(100 * share)
    assert 1.7 < read("cache.state_share_pct") < 1.9


@pytest.mark.parametrize("name", opsbytes_moe.READERS)
def test_the_accepted_expert_readers_read_this_family(bench, monkeypatch,
                                                      name):
    """The four accepted expert-layer readers take ``h``, ``f`` and
    ``experts`` from this family's ``sizes_of`` under those names, and
    match both expert kernels (``moe.experts*``) by name."""
    monkeypatch.setattr(spans, "host_spans", lambda *a: _lfm2_spans())
    run = _run(bench, _lfm2_trace())
    z = run.family.sizes_of(run.cell["config"])
    assert (z["h"], z["f"], z["experts"]) == (2048, 1536, 64)
    value = bench.reader(name).read(run)
    want = {
        # a call reads 64 touched experts = 1.208 GB = 1.475 ms of the
        # 2.25 ms the sixteen calls average; the chosen pairs' operations
        # (1,152 a call) are a twentieth of that
        "kernel.moe_experts_roofline":
            100 * 64 * 3 * 2048 * 1536 * 2 / 819e9 / 0.00225,
        "kernel.moe_experts_share_pct": 36.0,
        "moe.route_share_pct": 1.0,
        "moe.load_max_over_mean": 540 * 64 / 18432}[name]
    assert value == pytest.approx(want)
    assert value < 100


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_reader_finds_nothing_on_a_program_without_it(bench, monkeypatch,
                                                      name):
    """A parent commit, a dense model: no such span arg — the reader
    returns None and does not raise."""
    other = trace.Trace([
        (D0, OPS, _kernel("attn.paged_decode.54"), 0.0, 6.0),
        (D0, OPS, _kernel("attn.paged_chunk_prefill.7"), 6.0, 2.0)])
    hosts = ([], [_span("dstpu.sched.commit", tokens=3),
                  _span(ob.DECODE, live_slots=3, kv_pages=40,
                        kv_pages_table=290)])
    for host in hosts:
        monkeypatch.setattr(spans, "host_spans", lambda *a, h=host: h)
        for tr in (other, None):
            assert bench.reader(name).read(_run(bench, tr)) is None
