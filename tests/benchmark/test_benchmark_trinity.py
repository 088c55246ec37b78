"""The Trinity-Mini configuration, its cell and traffic, what its family adds
(the controls that differ from bfloat16 by one thing, the balanced selection
bias, the vocabulary tables drawn in blocks), the operations and bytes of
the window chunk kernel against hand counts, and the readers of what the
cell adds — on hand-made spans and joins with known answers, and on a
program that has no such span or scope (a parent commit, another model's
cell): nothing to read, no error.  Nothing here pins HOW MANY
configurations, cells or per-layer entries ``BENCHMARK.json`` has, or which
come last: entries are found by name, and a list is held to the ORDER of the
cells it had."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import opsbytes_trinity as ob, scopes, spec, trafficgen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONFIG = "trinity-serve-mixedlen-batch", "trinity-mini-l5"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
KINDS = ["sliding_attention"] * 4 + ["full_attention"]
REDUCED = {"num_hidden_layers": 5, "num_dense_layers": 1,
           "layer_types": KINDS}
NEW_METRICS = {
    "attn.window_share_pct": ("%", "lower", "device_trace", "kernels"),
    "attn.full_share_pct": ("%", "lower", "device_trace", "kernels"),
    "kernel.gqa_window_chunk_roofline": ("%", "higher", "device_trace",
                                         "kernels"),
    "window.keys_over_full_keys": ("ratio", "lower", "program_counter",
                                   "cache manager"),
    "cache.ring_share_pct": ("%", "lower", "program_counter",
                             "cache manager"),
    "head.logits_share_pct": ("%", "lower", "device_trace", "programs")}
# the lists this cell was appended to, each with the cells it had before,
# in the order it had them
BATCH = ["opt13b-serve-longprompt-batch", "olmoe-serve-gen-batch",
         "dots3-serve-longdoc-batch", "lfm2-serve-widegen-batch",
         "evabyte-serve-bytedoc-batch"]
ALL_BATCH = BATCH + ["glm5-serve-reasongen-batch",
                     "longcat-serve-agentgen-batch"]
EVERY = ["opt13b-serve-chat", "opt13b-sft-1chip", "opt67b-zero3-4chip"] \
    + ALL_BATCH
SHARED = {
    "batch_tokens_per_s": ALL_BATCH, "sched.occupancy_pct": ALL_BATCH,
    "device.idle_pct.batch": ALL_BATCH,
    "sched.host_ms_per_iter.batch": ALL_BATCH,
    "setup.trace_lower_s": EVERY, "setup.backend_compile_s": EVERY,
    "step.prefill_chunk_ms": ALL_BATCH,
    "step.decode_block_ms.batch": BATCH + ["longcat-serve-agentgen-batch"],
    "scope.unattributed_pct.batch": ALL_BATCH,
    "kernel.paged_decode_share_pct.batch": [
        "opt13b-serve-longprompt-batch", "olmoe-serve-gen-batch",
        "lfm2-serve-widegen-batch"],
    "moe.route_scope_share_pct": [
        "dots3-serve-longdoc-batch", "lfm2-serve-widegen-batch",
        "glm5-serve-reasongen-batch", "longcat-serve-agentgen-batch"],
    "moe.load_max_over_mean": ["olmoe-serve-gen-batch",
                               "lfm2-serve-widegen-batch"],
    "kernel.moe_experts_share_pct": [
        "olmoe-serve-gen-batch", "lfm2-serve-widegen-batch",
        "longcat-serve-agentgen-batch"],
    "kernel.moe_experts_roofline": ["olmoe-serve-gen-batch",
                                    "lfm2-serve-widegen-batch"]}
# metrics whose readers would find something in this cell's programs but
# whose lists the benchmark's own tests hold to other cells
# (``test_benchmark_dots3.py``, ``test_benchmark_lfm2.py``: files this PR
# may not edit), or whose count reads a span this model does not write: the
# cell is on none of them
NOT_LISTED = ["kernel.moe_gmm_share_pct", "kernel.moe_grouped_share_pct",
              "moe.rows_per_touched_expert", "kernel.moe_grouped_roofline",
              "cache.state_share_pct", "cache.summary_share_pct"]
TOY = dict(
    vocab_size=64, hidden_size=32, num_hidden_layers=3,
    layer_types=["sliding_attention", "sliding_attention", "full_attention"],
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    intermediate_size=48, moe_intermediate_size=16, num_experts=8,
    num_experts_per_tok=2, num_shared_experts=1, num_dense_layers=1,
    route_norm=True, route_scale=2.826, score_func="sigmoid", n_group=1,
    topk_group=1, sliding_window=16, rope_theta=10000, rope_scaling=None,
    max_position_embeddings=256, mup_enabled=True, rms_norm_eps=1e-5,
    hidden_act="silu", tie_word_embeddings=False)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


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
        assert cfg[key] == REDUCED[key] != cfg["source_config"][key]
    else:
        assert cfg[key] == cfg["source_config"][key]


def test_source_config_is_the_catalogs_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not in this environment")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    assert cfg["source_config"] == row["config"]


def test_the_cut_is_the_issues(bench):
    cfg = _config()
    entry = bench._entry("configs", CONFIG)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
        == sorted(REDUCED)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert cfg["family"] == "trinity" and cfg["precision"] == "bfloat16"
    # every width, every expert and the whole vocabulary as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["num_shared_experts"], cfg["sliding_window"],
            cfg["route_scale"], cfg["vocab_size"]) \
        == (2048, 32, 4, 128, 6144, 1024, 128, 8, 1, 2048, 2.826, 200192)
    # the published 3 : 1 in the period held
    assert cfg["layer_types"][1:] == cfg["source_config"]["layer_types"][4:8]
    for reading in ("source_of_what_follows", "output_gate", "qk_norm",
                    "rope", "norms", "embedding_multiplier", "router",
                    "window", "weights"):
        assert len(cfg["assumed"][reading]) > 40
    assert "1e-20" in cfg["assumed"]["router"] \
        and "sqrt(hidden_size)" in cfg["assumed"]["embedding_multiplier"]
    for word in ("FIRST stage", "LAST stage", "every one of the 128 experts",
                 "whole 200,192-row vocabulary", "8.48 GB",
                 "What the cut distorts", "ROADMAP M5"):
        assert word in cfg["deployment"]
    parts = cfg["parameters_by_part"]
    assert parts["attention_each_of_5_q_k_v_o_gate"] == 27262976
    assert parts["expert_layer_each_of_4"] == 27262976 + 805306368 \
        + 6291456 + 262144 == 839122944
    assert parts["dense_layer"] + 4 * parts["expert_layer_each_of_4"] \
        + parts["embedding"] + parts["head"] == parts["matrices"] \
        == cfg["parameters"] == 4241489920
    assert round(2 * cfg["parameters"] / 1e9, 2) == 8.48


def test_parameters_by_part_are_recounted_from_the_shapes(bench):
    fam = bench.family("trinity")
    parts = fam.parameters_by_part(fam.sizes_of(_config()))
    assert parts["attention_each"] == 2 * 2048 * 4096 + 2 * 2048 * 512 \
        + 2048 * 4096
    assert parts["dense_ffn_each"] == 3 * 2048 * 6144
    assert parts["one_expert"] == 3 * 2048 * 1024
    assert parts["embedding"] == parts["head"] == 200192 * 2048
    assert parts["matrices"] == _config()["parameters"]
    assert parts["norm_gains_and_biases"] \
        == _config()["parameters_by_part"]["norm_gains_and_biases"] \
        == 5 * (4 * 2048 + 2 * 128) + 2048 + 4 * 128


def test_benchmark_file_is_valid_with_the_new_entries(bench):
    assert spec.validate(bench) == []
    assert spec.check_files(bench) == []


def test_cell_is_the_issues(bench):
    entry = bench._entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "mixedlen-closed192", 1)
    assert len(entry["why"]) <= 200 and "ring" in entry["why"]
    cell = bench.cell(CELL)
    serving = cell["system"]["serving"]
    assert (serving["num_slots"], serving["page_size"],
            serving["max_cache_len"]) == (128, 64, 16384 + 1024 + 64)
    assert "speculative" not in serving and serving["paged"]
    # the lane pool holds ~8k rows a slot of the ONE full layer, 2.15 GB;
    # the rings 4 layers x 128 slots x 2,048 rows, 2.15 GB
    lane = serving["num_pages"] * 64 * 2 * 512 * 2
    ring = 4 * (128 * 32 + 1) * 64 * 2 * 512 * 2
    assert round(lane / 1e9, 2) == round(ring / 1e9, 2) == 2.15
    assert (serving["num_pages"] - 1) * 64 // 128 == 8192
    correct = cell["system"]["correct"]
    assert 0 < correct["mean_logit_gap"] < 1 and correct["sample_requests"]
    assert {"sweep", "calibration", "two_sets_of_six"} \
        <= set(cell["system"]["defined_by"])


def test_traffic_is_the_issues(bench):
    cell = bench.cell(CELL)
    mix, serving = cell["traffic"], cell["system"]["serving"]
    assert mix["kind"] == "closed_loop_engine"
    assert (mix["callers"], mix["cycle"], mix["trace_slice_s"]) \
        == (192, 192, 4.0)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 3072,
                                 "sigma": 1.0, "min": 256, "max": 16384}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert mix["ramp_s"] >= 30
    sizes = trafficgen.sizes(mix, mix["cycle"])
    prompts = np.asarray([p for p, _ in sizes])
    assert prompts.min() >= 256 and prompts.max() <= 16384
    assert 0.28 < (prompts < 2048).mean() < 0.40     # a third under the window
    assert 0.07 < (prompts > 10000).mean() < 0.15    # a tenth over 10k
    chunk = serving["prefill_chunk"]
    fam = bench.family("trinity")
    for p, o in sizes:
        assert p + o <= serving["max_cache_len"] and o <= fam.GAP_ROWS
        assert -(-p // chunk) * chunk <= serving["max_cache_len"]
    # what a calibration serves — the first ``sample_requests`` sizes — has a
    # prompt under the window and one over 8k: ring wrap-around and the full
    # layer's long table are both compared
    first = [p for p, _ in sizes[:cell["system"]["correct"][
        "sample_requests"]]]
    assert min(first) < 2048 and max(first) > 8192
    a, b = (next(trafficgen.closed_loop_requests(mix, 200192, s))
            for s in (3_000_000_050, 50))
    assert len(a[1]) == len(b[1]) and 65536 < a[1].max() < 200192
    assert (a[1][:64] != b[1][:64]).any()


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_is_an_entry_with_a_reader(bench, name):
    entry = bench._entry("per_layer", name)
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == NEW_METRICS[name]
    assert entry["moves"] == "batch_tokens_per_s" \
        and CELL in entry["workloads"]
    assert callable(bench.reader(name).read)
    for other in ("opt13b-serve-chat", "dots3-serve-longdoc-batch"):
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
def test_sizes_of_reads_the_files_keys(bench):
    fam = bench.family("trinity")
    z = fam.sizes_of(_config())
    assert (z["layers"], z["dense_layers"], z["kinds"]) \
        == (5, 1, tuple(KINDS))
    assert (z["heads"], z["kv_heads"], z["d"], z["window"]) \
        == (32, 4, 128, 2048)
    assert (z["experts"], z["top_k"], z["shared"], z["scaling"],
            z["held"]) == (128, 8, 1, 2.826, (0, 128))
    # the expert width under both names the benchmark's readers use
    assert (z["f"], z["ef"], z["dense_f"]) == (1024, 1024, 6144)
    assert z["mup"] and z["route_norm"] and z["vocab"] == 200192
    for key, value in (("rope_scaling", {"factor": 2}),
                       ("score_func", "softmax"), ("n_group", 8),
                       ("tie_word_embeddings", True),
                       ("num_hidden_layers", 6)):
        with pytest.raises(ValueError):
            fam.sizes_of(dict(_config(), **{key: value}))
    assert set(fam.CONTROLS) == {
        "float8_experts", "rope_on_full", "gate_dropped", "stale_ring_row",
        "window_off_by_one", "bias_dropped"}


@pytest.fixture(scope="module")
def toy(bench):
    """The toy's sizes and tokens; the family's scale raised to a toy's
    (tests/unit/test_trinity.py) and its balance run on a toy's sample."""
    fam = bench.family("trinity")
    fam._W = 0.15
    fam.BALANCE_SEQUENCES, fam.BALANCE_LENGTH = 2, 512
    tokens = np.random.default_rng(2).integers(0, 64, 64).astype(np.int32)
    return fam, fam.sizes_of(TOY), tokens


def test_the_program_is_the_reference_at_a_toy_size(toy):
    """``program_model`` / ``program_params`` hand the program the tensors
    the reference draws: the uncached forward is ``logits``."""
    import jax
    import jax.numpy as jnp
    fam, z, tokens = toy
    module = fam.program_model(TOY, dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          fam.program_params(module, TOY, 3))
    got = module.apply(params, {"input_ids": jnp.asarray(tokens[None])})[0]
    want = fam.logits(z, 3, tokens)
    assert float(jnp.abs(want).mean()) > 0.1
    assert float(jnp.abs(got - want).max()) < 2e-4
    nll = fam.nll_at(z, 3, tokens[None], np.asarray([[5, 30, 62]]))
    gold = jax.nn.log_softmax(want)[np.asarray([5, 30, 62]),
                                    tokens[[6, 31, 63]]]
    np.testing.assert_allclose(np.asarray(nll[0]), -np.asarray(gold),
                               atol=1e-5)
    out = fam.greedy(z, 3, tokens[:20], 3, 64, "float32")
    assert out.shape == (23,) and (out[:20] == tokens[:20]).all()
    assert out[20] == int(np.argmax(np.asarray(fam.logits(
        z, 3, tokens[:20])[-1])))


def test_a_table_is_drawn_in_blocks_of_rows(toy):
    import jax
    fam, _, _ = toy
    key = fam.seed_key(5)
    table = fam._table(key, 0, 64, 32, 1.0)
    assert table.shape == (64, 32) and str(table.dtype) == "bfloat16"
    # 16 blocks of 4 rows, each from its own key: rows differ, the draw is
    # the seed's, and a size that is no whole blocks is drawn at once
    assert len({tuple(np.asarray(r, np.float32)) for r in table}) == 64
    assert (np.asarray(fam._table(key, 0, 64, 32, 1.0), np.float32)
            == np.asarray(table, np.float32)).all()
    assert fam._table(key, 0, 50, 32, 1.0).shape == (50, 32)
    assert abs(float(np.asarray(table, np.float32).std()) - 1.0) < 0.1
    del jax


def test_the_balanced_bias_evens_the_routers_outputs(toy):
    import jax
    import jax.numpy as jnp
    fam, z, _ = toy
    key = fam.seed_key(11)
    biases = fam.balanced_biases(z, key)
    assert biases.shape == (2, 8) and biases.dtype == jnp.bfloat16
    assert fam.balanced_biases(z, key) is biases         # kept
    ids = fam.balance_ids(z, key)
    assert ids.shape == (fam.BALANCE_SEQUENCES, fam.BALANCE_LENGTH)
    kw = dict(sizes=fam._static(z), precision="float32")
    x = fam._embedded(z, key, ids.reshape(-1), "float32")
    x = fam._layer(z, key, 0, x, "float32", sequences=len(ids))
    w = fam.layer_weights(z, key, 1)
    x = fam._attend(x, w, len(ids), sliding=True, **kw)
    scores = fam._scores(fam._rms_norm(x, w["ln_pre_ffn"], z["eps"]), w,
                         "float32")

    def load(bias):
        _, top = jax.lax.top_k(scores + bias.astype(jnp.float32), z["top_k"])
        return np.bincount(np.asarray(top).reshape(-1), minlength=8)

    even, drawn = load(biases[0]), load(w["select_bias"])
    assert even.max() / even.mean() < 1.15
    assert drawn.max() / drawn.mean() > even.max() / even.mean()


def test_chooser_control_reads_the_generated_positions(toy):
    fam, z, tokens = toy
    gaps = fam.gaps_under(z, 3, tokens, 40, 24, 64,
                          [None, "float32", "rope_on_full"])
    assert all(g.shape == (24,) and (g >= 0).all() for g in gaps.values())
    assert gaps["float32"].max() == 0.0     # the reference picks its own
    assert gaps[None].max() > 0.0           # random tokens are not its picks
    assert np.asarray(fam.chosen_gaps(z, 3, tokens, 40, 24, 64)).tolist() \
        == gaps[None].tolist()
    with pytest.raises(ValueError):
        fam.gaps_under(z, 3, tokens, 40, fam.GAP_ROWS + 1, 64, [None])


# ---- operations and bytes against hand counts ---------------------------- #
def test_window_chunk_operations_and_bytes_by_hand():
    # 512 queries under a full window of 2,048 keys, 32 heads of 128: a
    # score and a value product a head a pair
    pairs = 512 * 2048
    assert ob.attention_flops(pairs, 32, 128) == 2 * 32 * (128 + 128) * pairs
    # 2,047 ring rows + the chunk's 512, K and V of 4 heads x 128 bf16 =
    # 2,048 B a row; the 512 queries in and out at 8,192 B each
    assert ob.window_chunk_bytes(2047, 512, 32, 4, 128) \
        == (2047 + 512) * 2048 + 512 * 2 * 8192
    # compute binds: 34 GFLOP at 197 TFLOP/s is 174 us, 13.6 MB is 17 us
    assert ob.attention_flops(pairs, 32, 128) / 197e12 \
        > 5 * ob.window_chunk_bytes(2047, 512, 32, 4, 128) / 819e9


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


def _run(bench, **trace):
    return types.SimpleNamespace(
        cell=bench.cell(CELL), family=bench.family("trinity"), peaks=PEAKS,
        trace=types.SimpleNamespace(window_s=2.0, **trace))


def test_counter_readers_on_known_spans(bench, monkeypatch):
    _spans(monkeypatch, [
        ("dstpu.sched.dispatch.prefill_chunk",
         dict(window_keys=4 * 600, full_keys=1000)),
        ("dstpu.sched.dispatch.decode",
         dict(window_keys=4 * 400, full_keys=1000, ring_bytes_held=300,
              kv_bytes_mapped=100)),
        ("dstpu.sched.dispatch.decode",
         dict(window_keys=4 * 200, full_keys=1000, ring_bytes_held=100,
              kv_bytes_mapped=100)),
        ("dstpu.sched.commit", dict(moe_assignments=5))])
    run = _run(bench)
    # a layer of each kind: (600 + 400 + 200) / 3000
    assert bench.reader("window.keys_over_full_keys").read(run) \
        == pytest.approx(0.4)
    assert bench.reader("cache.ring_share_pct").read(run) \
        == pytest.approx(100 * (0.75 + 0.5) / 2)


def test_scope_share_readers_on_a_known_join(bench, monkeypatch):
    _joined(monkeypatch, {
        "jit(decode_block)/layers_1/self_attn/attn.window/cache.write/s": 0.02,
        "jit(decode_block)/layers_1/self_attn/attn.window/attn.paged_decode":
            0.10,
        "jit(chunk_step)/layers_2/self_attn/attn.window/"
        "attn.gqa_window_chunk": 0.08,
        "jit(decode_block)/layers_4/self_attn/attn.full/attn.paged_decode":
            0.06,
        "jit(decode_block)/layers_4/self_attn/q_proj/dot_general": 0.40,
        "jit(decode_block)/head.logits/lm_head/dot_general": 0.05,
        "jit(decode_block)/head.sample/argmax": 0.01})
    run = _run(bench)
    assert bench.reader("attn.window_share_pct").read(run) \
        == pytest.approx(100 * 0.20 / 2.0)
    assert bench.reader("attn.full_share_pct").read(run) \
        == pytest.approx(100 * 0.06 / 2.0)
    assert bench.reader("head.logits_share_pct").read(run) \
        == pytest.approx(100 * 0.05 / 2.0)


def test_window_chunk_roofline_on_known_spans(bench, monkeypatch):
    """Both sides per CALL: a chunk span covers one call a sliding layer —
    here three chunks of 512 rows at positions past the window, four layers,
    twelve kernel events of 300 us."""
    work = dict(window_keys=4 * 512 * 2048, window_ring_rows=4 * 2047,
                window_chunk_rows=4 * 512)
    _spans(monkeypatch, [("dstpu.sched.dispatch.prefill_chunk", work)] * 3)
    run = _run(bench, op_seconds=lambda match, plane=None, module=None:
               (0.0003 * 12, 12))
    got = bench.reader("kernel.gqa_window_chunk_roofline").read(run)
    assert got == pytest.approx(
        100 * (2 * 32 * 256 * 512 * 2048 / 197e12) / 0.0003)
    assert 0 < got < 100


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_reader_finds_nothing_on_a_program_without_it(bench, monkeypatch,
                                                      name):
    """A parent commit, or another model's cell: no ``window_keys`` with
    ``full_keys`` or ``ring_bytes_held`` on any span, no ``attn.window`` /
    ``attn.full`` / ``head.logits`` scope in the join (or no join at all), no
    kernel of the name — None, and no error."""
    _spans(monkeypatch, [
        ("dstpu.sched.dispatch.decode", dict(dsa_keys_scored=7,
                                             kv_bytes_mapped=5)),
        ("dstpu.sched.dispatch.prefill_chunk", dict(eva_local_pairs=3)),
        ("dstpu.sched.commit", dict(moe_assignments=5,
                                    moe_experts_touched=2))])
    read = bench.reader(name).read
    assert read(types.SimpleNamespace(trace=None, observed={})) is None
    empty = types.SimpleNamespace(
        window_s=1.0, device_planes=[], events=[],
        module_durations=lambda name: [], device_ops=lambda: [],
        op_seconds=lambda match, plane=None, module=None: (0.0, 0))
    run = types.SimpleNamespace(
        trace=empty, observed={}, cell=bench.cell(CELL),
        family=bench.family("trinity"), peaks=PEAKS)
    for join in (None, {"by_op_name": {"jit(x)/layers_0/attn/q_b": 0.5}}):
        monkeypatch.setattr(scopes, "by_part",
                            lambda run, modules, join=join: join)
        assert read(run) is None
