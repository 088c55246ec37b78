"""The LongCat-Flash-Chat configuration, its cell, what its family adds (the
controls that differ from bfloat16 by one thing, the balanced selection
bias), and the readers of what the cell adds — on hand-made spans and joins
with known answers, and on a program that has no such span or scope (a
parent commit, another model's cell): nothing to read, no error.  Nothing
here pins HOW MANY configurations, cells or per-layer entries
``BENCHMARK.json`` has, or which come last: entries are found by name, and
a list is held to the ORDER of the cells it had."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import opsbytes_longcat as ob, scopes, spec, trafficgen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONFIG = "longcat-serve-agentgen-batch", "longcat-flash-chat-l4-e16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_layers": (4, 28), "n_routed_experts": (16, 512),
           "vocab_size": (16384, 131072)}
NEW_METRICS = {
    "scmoe.expert_branch_share_pct": ("%", "lower", "device_trace",
                                      "programs"),
    "attn.mla_dense_share_pct": ("%", "lower", "device_trace", "kernels"),
    "kernel.mla_dense_decode_roofline": ("%", "higher", "device_trace",
                                         "kernels"),
    "kernel.mla_causal_prefill_roofline": ("%", "higher", "device_trace",
                                           "kernels"),
    "kernel.moe_held_grouped_roofline": ("%", "higher", "device_trace",
                                         "kernels"),
    "moe.zero_pick_share_pct": ("%", "higher", "program_counter", "experts"),
    "moe.held_rows_per_touched_expert": ("ratio", "higher",
                                         "program_counter", "experts")}
# the lists this cell was appended to, each with the cells it had before,
# in the order it had them
BATCH = ["opt13b-serve-longprompt-batch", "olmoe-serve-gen-batch",
         "dots3-serve-longdoc-batch", "lfm2-serve-widegen-batch",
         "evabyte-serve-bytedoc-batch"]
SPEC_TOO = BATCH + ["glm5-serve-reasongen-batch"]
EVERY = ["opt13b-serve-chat", "opt13b-sft-1chip", "opt67b-zero3-4chip"] \
    + SPEC_TOO
SHARED = {
    "batch_tokens_per_s": SPEC_TOO, "sched.occupancy_pct": SPEC_TOO,
    "device.idle_pct.batch": SPEC_TOO,
    "sched.host_ms_per_iter.batch": SPEC_TOO,
    "setup.trace_lower_s": EVERY, "setup.backend_compile_s": EVERY,
    "step.prefill_chunk_ms": SPEC_TOO, "step.decode_block_ms.batch": BATCH,
    "scope.unattributed_pct.batch": SPEC_TOO,
    "moe.route_scope_share_pct": ["dots3-serve-longdoc-batch",
                                  "lfm2-serve-widegen-batch",
                                  "glm5-serve-reasongen-batch"],
    "kernel.moe_experts_share_pct": ["olmoe-serve-gen-batch",
                                     "lfm2-serve-widegen-batch"]}
# metrics whose readers would find something in this cell's programs but
# whose lists the benchmark's own tests hold to other cells, or whose count
# is another family's (PERF.md section 7 c2): the cell is on none of them
NOT_LISTED = [
    "attn.latent_share_pct", "attn.mla_decompress_share_pct",
    "kernel.mla_chunk_prefill_roofline", "kernel.moe_grouped_share_pct",
    "kernel.moe_grouped_roofline", "kernel.moe_gmm_share_pct",
    "moe.held_load_max_over_mean", "moe.rows_per_touched_expert",
    # reads ``sizes_of()["f"]`` as the expert width: here the dense FFN's
    "kernel.moe_experts_roofline",
    # reads the self-drafting block's spans: this cell has none
    "kernel.mla_lane_decode_roofline"]
TOY = dict(
    attention_bias=False, vocab_size=64, hidden_size=32, ffn_hidden_size=48,
    expert_ffn_hidden_size=16, num_layers=2, num_attention_heads=2,
    kv_lora_rank=16, q_lora_rank=16, qk_rope_head_dim=8, v_head_dim=8,
    qk_nope_head_dim=8, mla_scale_q_lora=True, mla_scale_kv_lora=True,
    routed_scaling_factor=6, n_routed_experts=4,
    n_routed_experts_published=8, held_experts=[0, 4],
    max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10000000,
    attention_method="MLA", zero_expert_num=4, zero_expert_type="identity",
    moe_topk=3)


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
        assert (cfg[key], cfg["source_config"][key]) == REDUCED[key]
        assert cfg[key + "_published"] == REDUCED[key][1]
    else:
        assert cfg[key] == cfg["source_config"][key]


def test_source_config_is_the_catalogs_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not in this environment")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LongCat-Flash-Chat")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    assert cfg["source_config"] == row["config"]


def test_the_cut_is_the_issues(bench):
    cfg = _config()
    entry = bench._entry("configs", CONFIG)
    assert entry["reduced"] == cfg["reduced"] == list(REDUCED)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert cfg["family"] == "longcat" and cfg["precision"] == "bfloat16"
    assert cfg["held_experts"] == [0, 16]
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"],
            cfg["qk_nope_head_dim"], cfg["v_head_dim"],
            cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"],
            cfg["moe_topk"], cfg["routed_scaling_factor"],
            cfg["zero_expert_num"], cfg["zero_expert_type"]) \
        == (6144, 64, 1536, 512, 64, 128, 128, 12288, 2048, 12, 6, 256,
            "identity")
    for reading in ("precision", "double_layer", "latent_attention",
                    "latent_scales", "rope_layout", "router", "zero_experts",
                    "norms", "weights"):
        assert len(cfg["assumed"][reading]) > 40
    for word in ("32 v5e chips", "FIRST stage", "LAST stage",
                 "seven pipeline stages of four", "5.17 B parameters",
                 "What the cut distorts", "ROADMAP M4"):
        assert word in cfg["deployment"]
    parts = cfg["parameters_by_part"]
    assert parts["double_layer_outside_its_experts"] \
        + parts["held_experts_16_each_of_4"] \
        == parts["double_layer_16_held_each_of_4"] == 1242824704
    assert 4 * parts["double_layer_16_held_each_of_4"] + parts["embedding"] \
        + parts["head"] == parts["matrices"]
    assert parts["matrices"] + parts["norm_gains_and_biases"] \
        == cfg["parameters"]


def test_benchmark_file_is_valid_with_the_new_entries(bench):
    assert spec.validate(bench) == []
    assert spec.check_files(bench) == []


def test_cell_is_the_issues(bench):
    entry = bench._entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "agentgen-closed192", 1)
    assert len(entry["why"]) <= 200 and "zero experts" in entry["why"]
    cell = bench.cell(CELL)
    serving = cell["system"]["serving"]
    assert (serving["num_slots"], serving["page_size"],
            serving["max_cache_len"]) == (128, 64, 2112)
    assert serving["max_cache_len"] // serving["page_size"] == 33
    assert "speculative" not in serving
    # the pools the sizing reckons: 8 pool layers of 640 stored features
    pool = (128 * 33 + 1) * 8 * 64 * 640 * 2
    assert round(pool / 1e9, 2) == 2.77
    correct = cell["system"]["correct"]
    assert 0 < correct["mean_logit_gap"] < 1 and correct["sample_requests"]
    assert {"sweep", "calibration", "two_sets_of_six"} \
        <= set(cell["system"]["defined_by"])


def test_traffic_is_the_issues(bench):
    mix = bench.cell(CELL)["traffic"]
    assert mix["kind"] == "closed_loop_engine" and mix["callers"] == 192
    assert mix["prompt_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    serving = bench.cell(CELL)["system"]["serving"]
    chunk = serving["prefill_chunk"]
    fam = bench.family("longcat")
    for p, o in trafficgen.sizes(mix, mix["cycle"]):
        assert p + o <= serving["max_cache_len"] and o <= fam.GAP_ROWS
        assert -(-p // chunk) * chunk <= serving["max_cache_len"]
    a, b = (next(trafficgen.closed_loop_requests(mix, 16384, s))
            for s in (3_000_000_047, 47))
    assert len(a[1]) == len(b[1]) and a[1].max() < 16384
    assert (a[1][:64] != b[1][:64]).any()


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_is_an_entry_with_a_reader(bench, name):
    entry = bench._entry("per_layer", name)
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == NEW_METRICS[name]
    assert entry["moves"] == "batch_tokens_per_s" \
        and CELL in entry["workloads"]
    assert callable(bench.reader(name).read)
    for other in ("opt13b-serve-chat", "glm5-serve-reasongen-batch"):
        assert name not in {m["name"]
                            for m in bench.cell(other)["per_layer"]}


@pytest.mark.parametrize("name", sorted(SHARED))
def test_shared_metric_keeps_its_cells_in_order_then_this_cell(bench, name):
    section = "end_to_end" if name == "batch_tokens_per_s" else "per_layer"
    cells = bench._entry(section, name)["workloads"]
    had = [c for c in cells if c in SHARED[name]]
    assert had == SHARED[name]
    assert cells.count(CELL) == 1 and cells.index(CELL) > max(
        cells.index(c) for c in had)


@pytest.mark.parametrize("name", NOT_LISTED)
def test_a_metric_held_to_other_cells_does_not_list_the_cell(bench, name):
    assert CELL not in bench._entry("per_layer", name)["workloads"]


# ---- the family ----------------------------------------------------------- #
def test_sizes_of_counts_two_pool_layers_a_double_layer(bench):
    fam = bench.family("longcat")
    z = fam.sizes_of(_config())
    assert z["layers"] == 4 and len(z["kinds"]) == 8
    assert set(z["kinds"]) == {"full_attention"} and z["held"] == (0, 16)
    assert (z["experts"], z["zero"], z["top_k"], z["scaling"]) \
        == (512, 256, 12, 6.0)
    assert dict(z["full"])["index_topk"] == 0 and z["rescale"]
    assert (z["f"], z["ef"]) == (12288, 2048)
    for key, value in (("zero_expert_type", "constant"),
                       ("rope_scaling", {"factor": 2}),
                       ("mla_scale_kv_lora", False),
                       ("attention_method", "MHA"),
                       ("n_routed_experts", 8)):
        with pytest.raises(ValueError):
            fam.sizes_of(dict(_config(), **{key: value}))
    assert set(fam.CONTROLS) == {
        "float8_experts", "float8_latent", "zero_dropped",
        "shortcut_misplaced", "no_latent_scale", "held_dropped"}


@pytest.fixture(scope="module")
def toy(bench):
    """The toy's sizes and tokens; the family's scales raised to a toy's
    (tests/unit/test_longcat.py)."""
    fam = bench.family("longcat")
    fam._W, fam._OUT, fam._ATTN, fam._DOWN, fam._EMBED = \
        0.15, 0.15, 0.2, 0.6, 1.0
    tokens = np.random.default_rng(2).integers(0, 64, 64).astype(np.int32)
    return fam, fam.sizes_of(TOY), tokens


def test_the_balanced_bias_evens_the_routers_outputs(toy):
    """Every router output, real or zero, is chosen equally often on the
    stream the bias was balanced on: a third of the choices on zero experts
    whatever the seed draws."""
    import jax
    import jax.numpy as jnp
    fam, z, _ = toy
    key = fam.seed_key(11)
    biases = fam.balanced_biases(z, key)
    assert biases.shape == (2, 12) and biases.dtype == jnp.bfloat16
    assert fam.balanced_biases(z, key) is biases         # kept
    g = fam.global_weights(z, key)
    ids = fam.balance_ids(z, key)
    assert ids.shape == (fam.BALANCE_SEQUENCES, fam.BALANCE_LENGTH)
    x = fam._embed_jit(g, ids.reshape(-1), precision="float32")
    a1 = fam._attend(x, fam.attn_weights(z, key, 0, 0), len(ids),
                     sizes=fam._static(z), precision="float32")
    ln_g = fam.ffn_weights(z, key, 0, 0)["ln_g"]
    w = fam.router_weights(z, key, 0)
    scores = fam._scores(fam._rms_norm(a1, ln_g, z["eps"]), w, "float32")

    def zero_share(bias):
        _, top = jax.lax.top_k(scores + bias.astype(jnp.float32), z["top_k"])
        load = np.bincount(np.asarray(top).reshape(-1), minlength=12)
        return load, load[8:].sum() / load.sum()

    load, share = zero_share(biases[0])         # 8 real + 4 zero outputs
    assert load.max() / load.mean() < 1.25 and abs(share - 1 / 3) < 0.03
    drawn, _ = zero_share(w["select_bias"])
    assert drawn.max() / drawn.mean() > load.max() / load.mean()


def test_chooser_control_reads_the_generated_positions(toy):
    fam, z, tokens = toy
    gaps = fam.gaps_under(z, 3, tokens, 40, 24, 64,
                          [None, "float32", "held_dropped"])
    assert all(g.shape == (24,) and (g >= 0).all() for g in gaps.values())
    assert gaps["float32"].max() == 0.0     # the reference picks its own
    assert gaps[None].max() > 0.0           # random tokens are not its picks
    with pytest.raises(ValueError):
        fam.gaps_under(z, 3, tokens, 40, fam.GAP_ROWS + 1, 64, [None])


# ---- the readers, on spans and joins with known counters ----------------- #
def _spans(monkeypatch, stats):
    from benchmark import opsbytes_dots3
    events = [{"name": name, "start_s": float(i), "dur_s": 0.1,
               "thread": (0, 0), "stats": s}
              for i, (name, s) in enumerate(stats)]
    monkeypatch.setattr(opsbytes_dots3.spans, "host_spans",
                        lambda path=None: events)


def _joined(monkeypatch, by_op_name):
    monkeypatch.setattr(scopes, "by_part",
                        lambda run, modules: {"by_op_name": by_op_name})


def test_pick_readers_on_known_spans(bench, monkeypatch):
    _spans(monkeypatch, [
        ("dstpu.sched.commit", dict(moe_zero_picks=400, moe_assignments=30,
                                    moe_assignments_elsewhere=770,
                                    moe_experts_touched=12)),
        ("dstpu.sched.wait_device", dict(moe_zero_picks=100,
                                         moe_assignments=10,
                                         moe_assignments_elsewhere=190,
                                         moe_experts_touched=8)),
        ("dstpu.sched.dispatch.decode", dict(causal_pairs=5))])
    run = types.SimpleNamespace(trace=object())
    assert bench.reader("moe.zero_pick_share_pct").read(run) \
        == pytest.approx(100 * 500 / 1500)
    assert bench.reader("moe.held_rows_per_touched_expert").read(run) \
        == pytest.approx(40 / 20)


def test_scope_share_readers_on_a_known_join(bench, monkeypatch):
    _joined(monkeypatch, {
        "jit(decode_block)/layers_0/scmoe.experts/moe_mlp/moe.route/dot": 0.02,
        "jit(decode_block)/layers_0/scmoe.experts/moe_mlp/ExpertsMLP_0": 0.10,
        "jit(decode_block)/layers_0/scmoe.dense_ffn/mlp_0/mul": 0.30,
        "jit(decode_block)/layers_1/attn_0/attn.mla_dense_decode/x": 0.05,
        "jit(chunk_step)/layers_1/attn_1/attn.mla_dense_chunk/y": 0.15,
        "jit(chunk_step)/layers_1/attn_1/q_b": 0.40})
    run = types.SimpleNamespace(trace=types.SimpleNamespace(window_s=2.0))
    assert bench.reader("scmoe.expert_branch_share_pct").read(run) \
        == pytest.approx(100 * 0.12 / 2.0)
    assert bench.reader("attn.mla_dense_share_pct").read(run) \
        == pytest.approx(100 * 0.20 / 2.0)


@pytest.mark.parametrize("name,frame,program,span,seconds,want", [
    # a decode dispatch: 8 pool layers x 8 steps x 128 lanes of ~1,000 live
    # rows, read once each — memory-bound
    ("kernel.mla_dense_decode_roofline", "attn.mla_dense_decode",
     "decode_block", "dstpu.sched.dispatch.decode", 0.030,
     100 * (8 * 8 * 128 * 1000 * 576 * 2 / 819e9) / 0.030),
    # a chunk dispatch: 8 pool layers x the causal pairs of 512 queries at
    # positions 512 .. 1023 — compute-bound
    ("kernel.mla_causal_prefill_roofline", "attn.mla_dense_chunk",
     "chunk_step", "dstpu.sched.dispatch.prefill_chunk", 0.004,
     100 * (2 * 64 * (192 + 128) * 8 * (512 * 512 + 512 * 513 // 2)
            / 197e12) / 0.004)])
def test_dense_attention_rooflines_on_known_spans(
        bench, monkeypatch, name, frame, program, span, seconds, want):
    """Both sides per DISPATCH: the spans' counters over their number, the
    scope's device seconds over the program's executions."""
    work = {"attn.mla_dense_decode": dict(
                causal_pairs=8 * 8 * 128 * 1000,
                latent_rows_read=8 * 8 * 128 * 1000),
            "attn.mla_dense_chunk": dict(
                causal_pairs=8 * (512 * 512 + 512 * 513 // 2),
                latent_rows_read=8 * 1024)}[frame]
    _spans(monkeypatch, [(span, work), (span, work), (span, work)])
    _joined(monkeypatch, {f"jit({program})/layers_0/attn_0/{frame}/k":
                          seconds * 5})
    run = types.SimpleNamespace(
        cell=bench.cell(CELL), family=bench.family("longcat"),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace=types.SimpleNamespace(
            window_s=1.0,
            module_durations=lambda sub: [0.1] * 5 if sub == program else []))
    got = bench.reader(name).read(run)
    assert got == pytest.approx(want) and 0 < got < 100


def test_held_grouped_roofline_on_known_spans(bench, monkeypatch):
    """Per call: 16 held experts touched, their three matrices read once —
    memory-bound at a few rows an expert."""
    load = dict(moe_zero_picks=2048, moe_assignments=4 * 128,
                moe_experts_touched=4 * 16, moe_calls=4)
    _spans(monkeypatch, [("dstpu.sched.wait_device", load)] * 2)
    run = types.SimpleNamespace(
        cell=bench.cell(CELL), family=bench.family("longcat"),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace=types.SimpleNamespace(
            op_seconds=lambda match, plane=None, module=None:
            (0.002 * 8, 8)))
    got = bench.reader("kernel.moe_held_grouped_roofline").read(run)
    assert got == pytest.approx(
        100 * (16 * 3 * 6144 * 2048 * 2 / 819e9) / 0.002)
    assert 0 < got < 100


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_reader_finds_nothing_on_a_program_without_it(bench, monkeypatch,
                                                      name):
    """A parent commit, or another model's cell: no ``moe_zero_picks`` or
    ``causal_pairs`` on any span, no ``scmoe.*`` or ``attn.mla_dense_*``
    scope in the join (or no join at all) — None, and no error."""
    _spans(monkeypatch, [
        ("dstpu.sched.dispatch.decode", dict(dsa_keys_scored=7)),
        ("dstpu.sched.commit", dict(moe_assignments=5,
                                    moe_experts_touched=2)),
        ("dstpu.sched.wait_device", dict(moe_assignments=5, moe_calls=2,
                                         moe_experts_touched=2))])
    read = bench.reader(name).read
    assert read(types.SimpleNamespace(trace=None, observed={})) is None
    empty = types.SimpleNamespace(
        window_s=1.0, device_planes=[], events=[],
        module_durations=lambda name: [], device_ops=lambda: [],
        op_seconds=lambda match, plane=None, module=None: (0.0, 0))
    run = types.SimpleNamespace(
        trace=empty, observed={}, cell=bench.cell(CELL),
        family=bench.family("longcat"),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    for join in (None, {"by_op_name": {"jit(x)/layers_0/attn/q_b": 0.5}}):
        monkeypatch.setattr(scopes, "by_part",
                            lambda run, modules, join=join: join)
        assert read(run) is None
