"""The granite-4.0-h-small configuration, its cell and traffic, what its
family adds (the controls that differ from bfloat16 by one thing, the router
made blind to the stream's common component, the held share of a 72-wide
router), the operations and bytes of the two state-space kernels against
hand counts, and the readers of what the cell adds — on hand-made spans and
joins with known answers, and on a program that has no such span or scope (a
parent commit, another model's cell): nothing to read, no error.  Nothing
here pins HOW MANY configurations, cells or per-layer entries
``BENCHMARK.json`` has, or which come last: entries are found by name, and a
list is held to the ORDER of the cells it had."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import opsbytes_granite as ob, scopes, spec, trafficgen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONFIG = "granite-serve-chatgen-batch", "granite-4.0-h-small-l10-e18"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
REDUCED = {"num_hidden_layers": 10, "layer_types": PERIOD,
           "num_local_experts": 18, "vocab_size": 25088}
NEW_METRICS = {
    "attn.ssd_share_pct": ("%", "lower", "device_trace", "kernels"),
    "ssd.scan_share_pct": ("%", "lower", "device_trace", "kernels"),
    "kernel.ssd_chunk_roofline": ("%", "higher", "device_trace", "kernels"),
    "kernel.ssd_decode_roofline": ("%", "higher", "device_trace", "kernels")}
# the lists this cell was appended to, each with the cells it had before,
# in the order it had them
BATCH = ["opt13b-serve-longprompt-batch", "olmoe-serve-gen-batch",
         "dots3-serve-longdoc-batch", "lfm2-serve-widegen-batch",
         "evabyte-serve-bytedoc-batch"]
ALL_BATCH = BATCH + ["glm5-serve-reasongen-batch",
                     "longcat-serve-agentgen-batch",
                     "trinity-serve-mixedlen-batch",
                     "solar-serve-longctx-batch"]
EVERY = ["opt13b-serve-chat", "opt13b-sft-1chip", "opt67b-zero3-4chip"] \
    + ALL_BATCH
SHARED = {
    "batch_tokens_per_s": ALL_BATCH, "sched.occupancy_pct": ALL_BATCH,
    "device.idle_pct.batch": ALL_BATCH,
    "sched.host_ms_per_iter.batch": ALL_BATCH,
    "sched.prefill_rows_per_dispatch": ["opt13b-serve-longprompt-batch",
                                        "solar-serve-longctx-batch"],
    "step.prefill_chunk_ms": ALL_BATCH,
    "step.decode_block_ms.batch": BATCH + ["longcat-serve-agentgen-batch",
                                           "trinity-serve-mixedlen-batch",
                                           "solar-serve-longctx-batch"],
    "scope.unattributed_pct.batch": ALL_BATCH,
    "kernel.paged_decode_share_pct.batch": [
        "opt13b-serve-longprompt-batch", "olmoe-serve-gen-batch",
        "lfm2-serve-widegen-batch", "trinity-serve-mixedlen-batch",
        "solar-serve-longctx-batch"],
    "moe.route_scope_share_pct": [
        "dots3-serve-longdoc-batch", "lfm2-serve-widegen-batch",
        "glm5-serve-reasongen-batch", "longcat-serve-agentgen-batch",
        "trinity-serve-mixedlen-batch", "solar-serve-longctx-batch"],
    "attn.full_share_pct": ["trinity-serve-mixedlen-batch",
                            "solar-serve-longctx-batch"],
    "head.logits_share_pct": ["trinity-serve-mixedlen-batch",
                              "solar-serve-longctx-batch"],
    "setup.trace_lower_s": EVERY, "setup.backend_compile_s": EVERY,
    "setup.outside_program_s": EVERY, "setup.import_s": EVERY,
    "setup.engine_build_s": EVERY, "setup.weights_s": EVERY,
    "setup.compile_after_warmup_s": EVERY}
# metrics whose readers find something in this cell's programs but whose
# lists the benchmark's own tests hold to ONE other cell
# (``test_benchmark_dots3.py``, ``test_benchmark_lfm2.py``,
# ``test_benchmark_evabyte.py``: files this PR may not edit) or to the
# serving cells of PR 52 (``test_benchmark_setup_metrics.py``), or whose
# count reads a span this model does not write (``moe_zero_picks``: the two
# ``held`` readers of LongCat's): the cell is on none of them
NOT_LISTED = ["kernel.moe_held_grouped_roofline", "kernel.moe_gmm_share_pct",
              "kernel.moe_grouped_share_pct",
              "step.decode_share_pct", "moe.held_load_max_over_mean",
              "cache.state_share_pct", "conv.short_share_pct",
              "moe.held_rows_per_touched_expert", "setup.compile_chunk_s",
              "setup.compile_block_s", "setup.compile_admit_s"]
TOY = dict(
    model_type="granitemoehybrid", hidden_size=128, num_hidden_layers=4,
    layer_types=["mamba", "attention", "mamba", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, attention_bias=False,
    attention_multiplier=0.03125, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=16, hidden_act="silu",
    normalization_function="rmsnorm", position_embedding_type="nope",
    mamba_n_heads=4, mamba_d_head=64, mamba_d_state=32, mamba_expand=2,
    mamba_n_groups=1, mamba_d_conv=4, mamba_conv_bias=True,
    mamba_proj_bias=False, mamba_chunk_size=256, intermediate_size=32,
    shared_intermediate_size=64, num_local_experts=4,
    num_local_experts_published=16, held_experts=[4, 4],
    num_experts_per_tok=3, vocab_size=128, rms_norm_eps=1e-5,
    rope_scaling=None, rope_theta=10000, tie_word_embeddings=True,
    max_position_embeddings=512)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STATE = 128 * 64 * 128 * 4                  # a layer's state a slot: 4 MiB


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
                   if r["name"] == "granite-4.0-h-small")
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
    assert cfg["family"] == "granite_hybrid" \
        and cfg["precision"] == "bfloat16"
    # every width as published: no width is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["shared_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["num_local_experts_published"]) \
        == (4096, 32, 8, 768, 1536, 10, 72)
    assert (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_expand"], cfg["mamba_d_conv"], cfg["mamba_n_groups"],
            cfg["mamba_conv_bias"], cfg["mamba_proj_bias"]) \
        == (128, 64, 128, 2, 4, 1, True, False)
    assert (cfg["attention_multiplier"], cfg["embedding_multiplier"],
            cfg["residual_multiplier"], cfg["logits_scaling"],
            cfg["position_embedding_type"], cfg["tie_word_embeddings"]) \
        == (1 / 128, 12, 0.22, 16, "nope", True)
    # one whole period of the published nine to one, every layer an expert
    # layer, a quarter of the experts and of the vocabulary
    assert cfg["source_config"]["layer_types"][:10] == PERIOD \
        and cfg["source_config"]["layer_types"] == PERIOD * 4
    assert cfg["held_experts"] == [0, 18] and 4 * 18 == 72
    assert 4 * cfg["vocab_size"] == cfg["vocab_size_published"] == 100352
    for reading in ("source_of_what_follows", "precision", "mamba_in_proj",
                    "mamba_conv", "mamba_discretisation", "mamba_recurrence",
                    "mamba_output", "gqa_mixer", "norms_and_multipliers",
                    "router", "experts", "weights"):
        assert len(cfg["assumed"][reading]) > 40
    for word in ("4 pipeline stages", "4 v5e chips", "18 a chip",
                 "25,088 rows", "16 chips", "What the cut distorts",
                 "about 24", "10 layers, not 40", "ROADMAP M5"):
        assert word in cfg["deployment"]
    parts = cfg["parameters_by_part"]
    assert parts["gqa_mixer_layer_5_q_k_v_o"] == 41943040
    assert parts["expert_layer_ffn_each_of_10"] == 18 * 9437184 \
        + 3 * 4096 * 1536 + 4096 * 72 == 189038592
    assert parts["all"] == cfg["parameters"] == 2955758208
    assert round(2 * cfg["parameters"] / 1e9, 2) == 5.91


def test_parameters_by_part_are_recounted_from_the_shapes(bench):
    fam = bench.family("granite_hybrid")
    parts = fam.parameters_by_part(fam.sizes_of(_config()))
    h, w, cw = 4096, 8192, 8192 + 256
    assert parts["gqa_mixer_each"] == 2 * h * h + 2 * h * 1024
    assert parts["mamba_mixer_each"] == h * (w + cw + 128) + w * h \
        + 5 * cw + 3 * 128 + w == 102286976
    assert parts["one_expert"] == 3 * h * 768
    assert parts["embedding_tied_head"] == 25088 * h        # counted once
    assert parts["all"] == _config()["parameters"]
    assert parts["norm_gains"] \
        == _config()["parameters_by_part"]["norm_gains"] == 10 * 2 * h + h


def test_benchmark_file_is_valid_and_every_new_file_is_found_by_name(bench):
    assert spec.validate(bench) == []
    assert spec.check_files(bench) == []
    cell = bench.cell(CELL)
    assert cell["config"]["name"] == CONFIG and cell["chips"] == 1
    assert bench.driver(cell["traffic"]["kind"]).run
    fam = bench.family(cell["config"]["family"])
    for name in ("sizes_of", "program_model", "program_params", "logits",
                 "chosen_gaps", "gaps_under", "greedy", "ssm_states",
                 "parameters_by_part", "router_means"):
        assert callable(getattr(fam, name))
    got = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) | (set(SHARED) - {"batch_tokens_per_s"}) <= got
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"batch_tokens_per_s", "setup_s"}


def test_cell_is_the_issues(bench):
    entry = bench._entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "chatgen-closed264", 1)
    assert len(entry["why"]) <= 200 and "36 MiB" in entry["why"]
    cell = bench.cell(CELL)
    serving = cell["system"]["serving"]
    assert (serving["num_slots"], serving["page_size"]) == (176, 64)
    assert serving["max_cache_len"] >= 2048 + 768 + 64
    assert "speculative" not in serving and serving["paged"]
    # the state pool: 177 rows x 9 layers x (4 MiB + 50,688 B) = 6.76 GB —
    # more than the weights' 5.91 and seven times the ONE attention layer's
    # K and V pools
    state = 177 * 9 * (STATE + 3 * 8448 * 2)
    lane = serving["num_pages"] * 64 * 2 * 1024 * 2
    assert round(state / 1e9, 2) == 6.76 > 5.92
    assert 0.8e9 < lane < 1.1e9 and state > 6 * lane
    correct = cell["system"]["correct"]
    assert 0 < correct["mean_logit_gap"] < 1 and correct["sample_requests"]
    assert {"sweep", "calibration", "two_sets_of_six"} \
        <= set(cell["system"]["defined_by"])
    assert "state" in cell["system"]["sizing"]


def test_traffic_is_the_issues(bench):
    cell = bench.cell(CELL)
    mix, serving = cell["traffic"], cell["system"]["serving"]
    assert mix["kind"] == "closed_loop_engine"
    assert (mix["callers"], mix["cycle"], mix["base_seed"]) == (264, 264, 56)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.8, "min": 64, "max": 2048}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 768}
    assert mix["ramp_s"] >= 30 and mix["trace_slice_s"] > 0
    sizes = trafficgen.sizes(mix, mix["cycle"])
    prompts = np.asarray([p for p, _ in sizes])
    assert prompts.min() == 64 and prompts.max() == 2048
    assert 450 < prompts.mean() < 600                 # ~530
    assert 330 < np.median(prompts) < 440
    # about half of a request's tokens are decoded
    assert 0.45 < prompts.sum() / sum(p + o for p, o in sizes) < 0.56
    chunk = serving["prefill_chunk"]
    fam = bench.family("granite_hybrid")
    for p, o in sizes:
        assert p + o <= serving["max_cache_len"] and o <= fam.GAP_ROWS
        assert -(-p // chunk) * chunk <= serving["max_cache_len"]
    assert fam.TAIL_CHUNK == chunk
    a, b = (next(trafficgen.closed_loop_requests(mix, 25088, s))
            for s in (3_000_000_056, 56))
    assert len(a[1]) == len(b[1]) and 16384 < a[1].max() < 25088
    assert (a[1][:64] != b[1][:64]).any()


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_is_an_entry_with_a_reader(bench, name):
    entry = bench._entry("per_layer", name)
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == NEW_METRICS[name]
    assert entry["moves"] == "batch_tokens_per_s" \
        and CELL in entry["workloads"]
    assert callable(bench.reader(name).read)
    for other in ("opt13b-serve-chat", "solar-serve-longctx-batch"):
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
    fam = bench.family("granite_hybrid")
    z = fam.sizes_of(_config())
    assert (z["layers"], z["kinds"]) == (
        10, ("state_space",) * 5 + ("full_attention",)
        + ("state_space",) * 4)
    assert (z["heads"], z["kv_heads"], z["d"], z["scale"]) \
        == (32, 8, 128, 1 / 128)
    assert (z["ssm_heads"], z["ssm_d"], z["ssm_n"], z["taps"]) \
        == (128, 64, 128, 4)
    assert (z["experts"], z["held"], z["top_k"], z["sf"]) \
        == (72, (0, 18), 10, 1536)
    # the expert width under both names the benchmark's readers use
    assert (z["f"], z["ef"], z["h"], z["vocab"]) == (768, 768, 4096, 25088)
    assert (z["embed_x"], z["residual_x"], z["logits_over"]) \
        == (12.0, 0.22, 16.0)
    for key, value in (("rope_scaling", {"factor": 2}),
                       ("position_embedding_type", "rope"),
                       ("mamba_n_groups", 8), ("mamba_proj_bias", True),
                       ("mamba_conv_bias", False),
                       ("tie_word_embeddings", False),
                       ("mamba_d_head", 32), ("num_local_experts", 72)):
        with pytest.raises(ValueError):
            fam.sizes_of(dict(_config(), **{key: value}))
    assert set(fam.CONTROLS) == {
        "bfloat16_state", "state_not_cleared", "tail_advances_state",
        "dt_bias_dropped", "skip_dropped", "norm_before_gate",
        "conv_bias_dropped", "residual_multiplier_dropped",
        "attention_scale_sqrt", "float8_experts"}


@pytest.fixture(scope="module")
def toy(bench):
    """The toy's sizes and tokens; the family's scales raised to a toy's
    (tests/unit/test_granite_hybrid.py), its router's means read on a toy's
    sample, and the serving controls' tail and stale rows cut to a toy's
    lengths."""
    fam = bench.family("granite_hybrid")
    fam._W, fam._QK, fam._EMBED, fam._ROUTER = 0.09, 0.3, 0.35, 0.15
    fam._SSM_OUT, fam._ATT_OUT, fam._SHARED_DOWN, fam._DOWN = \
        6.0, 3.0, 3.0, 6.0
    fam.BALANCE_SEQUENCES, fam.BALANCE_LENGTH = 2, 128
    fam.TAIL_CHUNK, fam.STALE_ROWS = 16, 32
    tokens = np.random.default_rng(2).integers(0, 128, 192).astype(np.int32)
    return fam, fam.sizes_of(TOY), tokens


def test_every_control_separates_from_bfloat16_at_the_toy_size(toy):
    """Each control is bfloat16 but for ONE thing, and that thing moves the
    logits after the prompt: by more than a quarter of what bfloat16 itself
    lies from float32 (``bfloat16_state``, the faintest: the state's
    rounding adds up over 192 positions) and for most by several times
    it."""
    fam, z, tokens = toy
    prompt = 70
    ref = np.asarray(fam.logits(z, 3, tokens))
    sound = np.asarray(fam.logits(z, 3, tokens, "bfloat16",
                                  prompt_len=prompt))
    noise = np.abs(sound - ref)[prompt:].mean()
    assert 0 < noise < 0.2 * np.abs(ref).mean()
    moved = {}
    for control in fam.CONTROLS:
        lg = np.asarray(fam.logits(z, 3, tokens, control, prompt_len=prompt))
        assert lg.shape == ref.shape and np.isfinite(lg).all()
        moved[control] = np.abs(lg - sound)[prompt:].mean()
        # before the prompt's end the tail control is sound
        if control == "tail_advances_state":
            assert (lg[:prompt] == sound[:prompt]).all()
    assert all(m > 0.25 * noise for m in moved.values()), (noise, moved)
    assert sum(m > 2 * noise for m in moved.values()) >= 6, (noise, moved)


def test_the_router_is_blind_to_the_streams_common_component(toy):
    """No selection bias exists: the router's columns are made orthogonal
    to the mean of its normed input, so what every token shares moves no
    logit and the choices spread as the tokens differ."""
    import jax
    import jax.numpy as jnp
    fam, z, _ = toy
    key = fam.seed_key(11)
    means = fam.router_means(z, key)
    assert means.shape == (4, 128) and means.dtype == jnp.float32
    assert np.allclose(np.asarray((means * means).sum(-1)), 1.0, atol=1e-5)
    assert fam.router_means(z, key) is means              # kept
    ids = fam.balance_ids(z, key)
    assert ids.shape == (fam.BALANCE_SEQUENCES, fam.BALANCE_LENGTH)
    x = fam._embedded(z, key, ids.reshape(-1), "float32")
    plain = fam.layer_weights(z, key, 0)
    centred = fam.layer_weights(z, key, 0, centre=means[0])
    x, _ = fam._mix(x, plain, len(ids), None, softmax=False,
                    sizes=fam._static(z), precision="float32")
    normed = fam._rms_norm(x, plain["ln_post"], z["eps"])
    shared = jnp.mean(normed, axis=0)

    def offset(w):
        """What the common component adds to every token's logits, beside
        the spread of the logits themselves."""
        logits = fam._router_logits(normed, w, "float32")
        return float(jnp.abs(shared @ w["router"].astype(jnp.float32)).max()
                     / logits.std())

    assert offset(centred) < 0.1 * offset(plain)
    for name in plain:
        if name != "router":
            assert (np.asarray(plain[name], np.float32)
                    == np.asarray(centred[name], np.float32)).all()


def test_chooser_control_reads_the_generated_positions(toy):
    fam, z, tokens = toy
    tokens = tokens[:64]
    gaps = fam.gaps_under(z, 3, tokens, 40, 24, 64,
                          [None, "float32", "skip_dropped",
                           "norm_before_gate"])
    assert all(g.shape == (24,) and (g >= 0).all() for g in gaps.values())
    assert gaps["float32"].max() == 0.0     # the reference picks its own
    assert gaps[None].max() > 0.0           # random tokens are not its picks
    assert gaps["skip_dropped"].mean() > 0 < gaps["norm_before_gate"].mean()
    assert np.asarray(fam.chosen_gaps(z, 3, tokens, 40, 24, 64)).tolist() \
        == gaps[None].tolist()
    with pytest.raises(ValueError):
        fam.gaps_under(z, 3, tokens, 40, fam.GAP_ROWS + 1, 64, [None])


def test_the_shares_routed_parts_and_the_shared_mlp_once_are_the_layer(toy):
    """The guide's share test at a small size: the four chips' held shares
    of a 16-wide router, each the routed part alone, plus the shared MLP
    ONCE add up to the uncut layer — and a share with its shared MLP is
    what the program's expert layer computes."""
    import jax
    fam, z, _ = toy
    key = fam.seed_key(5)
    w = fam.layer_weights(z, key, 1)
    h = jax.random.normal(jax.random.key(1), (48, z["h"]))
    whole = fam.expert_layer(z, key, 1, h, w, "float32", held=(0, 16))
    routed = [fam.expert_layer(z, key, 1, h, w, "float32", held=(first, 4),
                               shared=False) for first in (0, 4, 8, 12)]
    none = fam.expert_layer(z, key, 1, h, w, "float32", held=(0, 0))
    assert float(np.abs(np.asarray(sum(routed))).mean()) > 0.05
    scale = float(np.abs(np.asarray(whole)).max())
    assert float(np.abs(np.asarray(sum(routed) + none - whole)).max()) \
        < 1e-5 * scale
    mine = fam.expert_layer(z, key, 1, h, w, "float32")     # (4, 4) + shared
    assert float(np.abs(np.asarray(routed[1] + none - mine)).max()) \
        < 1e-6 * scale


# ---- operations and bytes against hand counts ---------------------------- #
def test_state_kernel_operations_and_bytes_by_hand():
    # a 512-row chunk of one layer, 128 heads of 64 x 128: the decay-and-add
    # and the read-out a position and head, 2 a multiply-add each
    assert ob.scan_flops(512, 128, 64, 128) == 4 * 64 * 128 * 128 * 512
    # the state read and written once: 2 x 4 MiB
    assert ob.state_bytes(1, 128, 64, 128) == 2 * 4 * 2 ** 20
    # x and y at 2 B a channel, the step size at 4 B a head, B and C at 2 B
    assert ob.scan_bytes(512, 1, 128, 64, 128) \
        == 512 * (128 * (2 * 64 * 2 + 4) + 2 * 128 * 2) + 2 * 4 * 2 ** 20
    # the bytes bind (25.7 MB: 31 us; 2.1 GFLOP: 11 us), a third of them
    # the state, and the reader takes the larger
    assert ob.scan_bytes(512, 1, 128, 64, 128) / 819e9 \
        > ob.scan_flops(512, 128, 64, 128) / 197e12


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
        cell=bench.cell(CELL), family=bench.family("granite_hybrid"),
        peaks=PEAKS, trace=types.SimpleNamespace(window_s=2.0, **trace))


def test_scope_share_reader_on_a_known_join(bench, monkeypatch):
    _joined(monkeypatch, {
        "jit(chunk_step)/layers_1/mamba/attn.ssd/in_proj/dot_general": 0.10,
        "jit(chunk_step)/layers_1/mamba/attn.ssd/conv.short/mul": 0.02,
        "jit(chunk_step)/layers_2/mamba/attn.ssd/ssd.scan/ssd.chunk_scan":
            0.08,
        "jit(decode_block)/layers_3/mamba/attn.ssd/ssd.scan/"
        "ssd.decode_step": 0.06,
        "jit(decode_block)/layers_5/self_attn/attn.full/attn.paged_decode":
            0.30,
        "jit(decode_block)/layers_1/moe_mlp/dot_general": 0.40})
    assert bench.reader("attn.ssd_share_pct").read(_run(bench)) \
        == pytest.approx(100 * 0.26 / 2.0)


def test_kernel_share_and_rooflines_on_known_spans(bench, monkeypatch):
    """Both sides per CALL.  Three chunks of 384 real rows, nine Mamba
    layers: 27 ``ssd.chunk_scan`` events of 0.1 ms; two decode blocks of the
    cell's ``decode_block`` steps with 150 and 170 live lanes: ``2 x block x
    9`` ``ssd.decode_step`` events of 2 ms."""
    block = bench.cell(CELL)["system"]["serving"]["decode_block"]
    steps = 2 * block * 9
    _spans(monkeypatch, [
        ("dstpu.sched.dispatch.prefill_chunk",
         dict(ssd_scan_rows=9 * 384, ssd_state_rows=9))] * 3 + [
        ("dstpu.sched.dispatch.decode",
         dict(ssd_scan_rows=9 * block * n, ssd_state_rows=9 * block * n))
        for n in (150, 170)])
    asked = []

    def op_seconds(match, plane=None, module=None):
        hits = [n for n in ("ssd.chunk_scan", "ssd.decode_step")
                if match(f"%{n}.3 = f32[8] custom-call(), "
                         f"custom_call_target=\"tpu_custom_call\"")]
        asked.append(hits)
        return {("ssd.chunk_scan",): (0.0001 * 27, 27),
                ("ssd.decode_step",): (0.002 * steps, steps),
                ("ssd.chunk_scan", "ssd.decode_step"):
                    (0.0001 * 27 + 0.002 * steps, 27 + steps)}[tuple(hits)]

    run = _run(bench, op_seconds=op_seconds)
    assert bench.reader("ssd.scan_share_pct").read(run) \
        == pytest.approx(100 * (0.0027 + 0.002 * steps) / 2.0)
    chunk = bench.reader("kernel.ssd_chunk_roofline").read(run)
    assert chunk == pytest.approx(
        100 * (ob.scan_bytes(384, 1, 128, 64, 128) / 819e9) / 0.0001)
    decode = bench.reader("kernel.ssd_decode_roofline").read(run)
    # a call moves the mean live lanes' rows: 160 x 8 MiB
    assert decode == pytest.approx(
        100 * (160 * 2 * 4 * 2 ** 20 / 819e9) / 0.002)
    assert 0 < chunk < 100 and 0 < decode < 100
    assert ["ssd.chunk_scan"] in asked and ["ssd.decode_step"] in asked


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_reader_finds_nothing_on_a_program_without_it(bench, monkeypatch,
                                                      name):
    """A parent commit, or another model's cell: no ``ssd_scan_rows`` on any
    span, no ``attn.ssd`` scope in the join (or no join at all), no kernel of
    the name — None, and no error."""
    _spans(monkeypatch, [
        ("dstpu.sched.dispatch.decode", dict(full_keys=7, state_rows=3,
                                             kv_bytes_mapped=5,
                                             kda_scan_rows=4)),
        ("dstpu.sched.dispatch.prefill_chunk", dict(window_keys=3)),
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
        family=bench.family("granite_hybrid"), peaks=PEAKS)
    for join in (None, {"by_op_name": {"jit(x)/layers_0/attn/q_b": 0.5}}):
        monkeypatch.setattr(scopes, "by_part",
                            lambda run, modules, join=join: join)
        assert read(run) is None
