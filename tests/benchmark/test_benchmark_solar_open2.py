"""The Solar-Open2-250B configuration, its cell and traffic, what its family
adds (the controls that differ from bfloat16 by one thing, the balanced
selection bias, the held share of a 320-wide router), the operations and
bytes of the two state kernels against hand counts, and the readers of what
the cell adds — on hand-made spans and joins with known answers, and on a
program that has no such span or scope (a parent commit, another model's
cell): nothing to read, no error.  Nothing here pins HOW MANY configurations,
cells or per-layer entries ``BENCHMARK.json`` has, or which come last:
entries are found by name, and a list is held to the ORDER of the cells it
had."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import opsbytes_solar as ob, scopes, spec, trafficgen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONFIG = "solar-serve-longctx-batch", "solar-open2-250b-l4-e40"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 4, "gqa_layers": [0], "n_routed_experts": 40,
           "vocab_size": 24576}
NEW_METRICS = {
    "attn.kda_share_pct": ("%", "lower", "device_trace", "kernels"),
    "kda.scan_share_pct": ("%", "lower", "device_trace", "kernels"),
    "kernel.kda_chunk_roofline": ("%", "higher", "device_trace", "kernels"),
    "kernel.kda_decode_roofline": ("%", "higher", "device_trace", "kernels")}
# the lists this cell was appended to, each with the cells it had before,
# in the order it had them
BATCH = ["opt13b-serve-longprompt-batch", "olmoe-serve-gen-batch",
         "dots3-serve-longdoc-batch", "lfm2-serve-widegen-batch",
         "evabyte-serve-bytedoc-batch"]
ALL_BATCH = BATCH + ["glm5-serve-reasongen-batch",
                     "longcat-serve-agentgen-batch",
                     "trinity-serve-mixedlen-batch"]
EVERY = ["opt13b-serve-chat", "opt13b-sft-1chip", "opt67b-zero3-4chip"] \
    + ALL_BATCH
SHARED = {
    "batch_tokens_per_s": ALL_BATCH, "sched.occupancy_pct": ALL_BATCH,
    "device.idle_pct.batch": ALL_BATCH,
    "sched.host_ms_per_iter.batch": ALL_BATCH,
    "sched.prefill_rows_per_dispatch": ["opt13b-serve-longprompt-batch"],
    "step.prefill_chunk_ms": ALL_BATCH,
    "step.decode_block_ms.batch": BATCH + ["longcat-serve-agentgen-batch",
                                           "trinity-serve-mixedlen-batch"],
    "scope.unattributed_pct.batch": ALL_BATCH,
    "kernel.paged_decode_share_pct.batch": [
        "opt13b-serve-longprompt-batch", "olmoe-serve-gen-batch",
        "lfm2-serve-widegen-batch", "trinity-serve-mixedlen-batch"],
    "moe.route_scope_share_pct": [
        "dots3-serve-longdoc-batch", "lfm2-serve-widegen-batch",
        "glm5-serve-reasongen-batch", "longcat-serve-agentgen-batch",
        "trinity-serve-mixedlen-batch"],
    "attn.full_share_pct": ["trinity-serve-mixedlen-batch"],
    "head.logits_share_pct": ["trinity-serve-mixedlen-batch"],
    "setup.trace_lower_s": EVERY, "setup.backend_compile_s": EVERY,
    "setup.outside_program_s": EVERY, "setup.import_s": EVERY,
    "setup.engine_build_s": EVERY, "setup.weights_s": EVERY,
    "setup.compile_after_warmup_s": EVERY}
# metrics whose readers find something in this cell's programs (the traced
# run of PR 54 read each) but whose lists the benchmark's own tests hold to
# ONE other cell (``test_benchmark_dots3.py``, ``test_benchmark_lfm2.py``,
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
    model_type="solar_open2",
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                            num_heads=4, num_kv_heads=None),
    hidden_size=64, num_hidden_layers=4, num_attention_heads=4, head_dim=16,
    num_key_value_heads=2, vocab_size=128, intermediate_size=160,
    moe_intermediate_size=32, rms_norm_eps=1e-5, rope_theta=10000,
    partial_rotary_factor=1, tie_word_embeddings=False,
    max_position_embeddings=512, first_k_dense_replace=0, use_rope=False,
    gqa_interval=3, gqa_layers=[0, 4, 8], use_gqa_gate=True,
    kda_use_full_proj=False, kda_allow_neg_eigval=True,
    n_routed_experts=4, n_routed_experts_published=16, held_experts=[4, 4],
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=1,
    num_experts_per_tok=2)
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
                   if r["name"] == "Solar-Open2-250B")
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
    assert cfg["family"] == "solar_open2" and cfg["precision"] == "bfloat16"
    # every width as published: no width is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["n_shared_experts"], cfg["n_routed_experts_published"]) \
        == (4096, 64, 8, 128, 1280, 8, 1, 320)
    assert cfg["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    # one whole period of the published 3 : 1, every layer an expert layer,
    # an eighth of the experts and of the vocabulary
    assert cfg["source_config"]["gqa_layers"][:2] == [0, 4]
    assert cfg["held_experts"] == [0, 40] and 8 * 40 == 320
    assert 8 * cfg["vocab_size"] == cfg["vocab_size_published"] == 196608
    for reading in ("source_of_what_follows", "kda_projections",
                    "kda_normalisation", "kda_decay", "kda_low_ranks",
                    "kda_beta", "kda_recurrence", "kda_output", "gqa_mixer",
                    "norms", "router", "weights"):
        assert len(cfg["assumed"][reading]) > 40
    for word in ("12 pipeline stages", "8 v5e chips", "40 a chip",
                 "24,576 rows", "What the cut distorts", "about 51",
                 "4 layers, not 48", "ROADMAP M5"):
        assert word in cfg["deployment"]
    parts = cfg["parameters_by_part"]
    assert parts["gqa_mixer_layer_0_q_k_v_gate_o"] == 109051904
    assert parts["expert_layer_ffn_each_of_4"] == 40 * 15728640 + 15728640 \
        + 4096 * 320 == 646184960
    assert parts["all"] == cfg["parameters"] == 3308353344
    assert round(2 * cfg["parameters"] / 1e9, 2) == 6.62


def test_parameters_by_part_are_recounted_from_the_shapes(bench):
    fam = bench.family("solar_open2")
    parts = fam.parameters_by_part(fam.sizes_of(_config()))
    h, w = 4096, 64 * 128
    assert parts["gqa_mixer_each"] == 3 * h * w + 2 * h * 1024
    assert parts["kda_mixer_each"] == 4 * h * w + 2 * (h * 128 + 128 * w) \
        + h * 64 + 4 * 3 * w + 64 + w + 128
    assert parts["one_expert"] == 3 * h * 1280
    assert parts["embedding"] == parts["head"] == 24576 * h
    assert parts["all"] == _config()["parameters"]
    assert parts["norm_gains_and_biases"] \
        == _config()["parameters_by_part"]["norm_gains_and_biases"] \
        == 4 * (2 * h + 320) + h


def test_benchmark_file_is_valid_and_every_new_file_is_found_by_name(bench):
    assert spec.validate(bench) == []
    assert spec.check_files(bench) == []
    cell = bench.cell(CELL)
    assert cell["config"]["name"] == CONFIG and cell["chips"] == 1
    assert bench.driver(cell["traffic"]["kind"]).run
    fam = bench.family(cell["config"]["family"])
    for name in ("sizes_of", "program_model", "program_params", "logits",
                 "chosen_gaps", "gaps_under", "greedy", "nll_at",
                 "kda_states", "parameters_by_part"):
        assert callable(getattr(fam, name))
    got = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) | (set(SHARED) - {"batch_tokens_per_s"}) <= got
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"batch_tokens_per_s", "setup_s"}


def test_cell_is_the_issues(bench):
    entry = bench._entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "longctx-closed96", 1)
    assert len(entry["why"]) <= 200 and "33.8k" in entry["why"]
    cell = bench.cell(CELL)
    serving = cell["system"]["serving"]
    assert (serving["num_slots"], serving["page_size"],
            serving["max_cache_len"]) == (64, 64, 32768 + 1024 + 64)
    assert "speculative" not in serving and serving["paged"]
    # the lane pool holds 16k rows a slot of the ONE softmax layer, 4.3 GB;
    # the state 65 rows x 3 layers x (4 MiB + 147,456 B), 0.85 GB
    lane = serving["num_pages"] * 64 * 2 * 1024 * 2
    state = 65 * 3 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    assert round(lane / 1e9, 1) == 4.3 and round(state / 1e9, 2) == 0.85
    assert (serving["num_pages"] - 1) * 64 // 64 == 16384
    correct = cell["system"]["correct"]
    assert 0 < correct["mean_logit_gap"] < 1 and correct["sample_requests"]
    assert {"sweep", "calibration", "two_sets_of_six"} \
        <= set(cell["system"]["defined_by"])


def test_traffic_is_the_issues(bench):
    cell = bench.cell(CELL)
    mix, serving = cell["traffic"], cell["system"]["serving"]
    assert mix["kind"] == "closed_loop_engine"
    assert (mix["callers"], mix["cycle"], mix["base_seed"]) == (96, 96, 54)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                 "sigma": 0.8, "min": 1024, "max": 32768}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert mix["ramp_s"] >= 30 and mix["trace_slice_s"] > 0
    sizes = trafficgen.sizes(mix, mix["cycle"])
    prompts = np.asarray([p for p, _ in sizes])
    assert prompts.min() >= 1024 and prompts.max() == 32768
    assert 9000 < prompts.mean() < 13000             # ~11k
    assert 0.01 < (prompts == 32768).mean() < 0.08   # one in ~25 at the cap
    # ~94% of a request's tokens are prefill
    assert 0.92 < prompts.sum() / sum(p + o for p, o in sizes) < 0.96
    chunk = serving["prefill_chunk"]
    fam = bench.family("solar_open2")
    for p, o in sizes:
        assert p + o <= serving["max_cache_len"] and o <= fam.GAP_ROWS
        assert -(-p // chunk) * chunk <= serving["max_cache_len"]
    a, b = (next(trafficgen.closed_loop_requests(mix, 24576, s))
            for s in (3_000_000_054, 54))
    assert len(a[1]) == len(b[1]) and 16384 < a[1].max() < 24576
    assert (a[1][:64] != b[1][:64]).any()


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_is_an_entry_with_a_reader(bench, name):
    entry = bench._entry("per_layer", name)
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == NEW_METRICS[name]
    assert entry["moves"] == "batch_tokens_per_s" \
        and entry["workloads"] == [CELL]
    assert callable(bench.reader(name).read)
    for other in ("opt13b-serve-chat", "lfm2-serve-widegen-batch"):
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
    fam = bench.family("solar_open2")
    z = fam.sizes_of(_config())
    assert (z["layers"], z["gqa"], z["kinds"]) == (
        4, (0,), ("full_attention",) + ("linear_attention",) * 3)
    assert (z["heads"], z["kv_heads"], z["d"]) == (64, 8, 128)
    assert (z["kda_heads"], z["kda_d"], z["taps"], z["rank"]) \
        == (64, 128, 4, 128)
    assert (z["experts"], z["held"], z["top_k"], z["shared"], z["scaling"]) \
        == (320, (0, 40), 8, 1, 1.0)
    # the expert width under both names the benchmark's readers use
    assert (z["f"], z["ef"], z["h"], z["vocab"]) == (1280, 1280, 4096, 24576)
    assert z["neg"] and z["gate"] and z["norm_topk"]
    linear = dict(_config()["linear_attn_config"], num_kv_heads=8)
    for key, value in (("rope_scaling", {"factor": 2}), ("use_rope", True),
                       ("kda_use_full_proj", True), ("n_group", 8),
                       ("linear_attn_config", linear),
                       ("tie_word_embeddings", True),
                       ("first_k_dense_replace", 1),
                       ("n_routed_experts", 320)):
        with pytest.raises(ValueError):
            fam.sizes_of(dict(_config(), **{key: value}))
    assert set(fam.CONTROLS) == {
        "bfloat16_state", "scalar_decay", "beta_unscaled",
        "tail_advances_state", "state_not_cleared", "gate_dropped",
        "qk_unnormalised", "float8_experts", "bias_dropped"}


@pytest.fixture(scope="module")
def toy(bench):
    """The toy's sizes and tokens; the family's scales raised to a toy's
    (tests/unit/test_solar_open2.py), its balance run on a toy's sample, and
    the serving controls' tail and stale rows cut to a toy's lengths."""
    fam = bench.family("solar_open2")
    fam._W, fam._QK, fam._OUT, fam._DOWN, fam._EMBED = \
        0.12, 0.15, 0.2, 0.3, 0.5
    fam._BIAS = 0.3
    fam.BALANCE_SEQUENCES, fam.BALANCE_LENGTH = 2, 256
    fam.TAIL_CHUNK, fam.STALE_ROWS = 16, 32
    fam.MIXER_BLOCK = 64        # the toy's 192 positions cross two seams
    tokens = np.random.default_rng(2).integers(0, 128, 192).astype(np.int32)
    return fam, fam.sizes_of(TOY), tokens


def test_the_linear_mixers_blocks_hand_the_state_on(toy, monkeypatch):
    """The reference makes the rows around its recurrence a block of
    positions at a time: in blocks of 64 it is the sequence in one — the
    last layer's state after 150 positions holds every layer's stream."""
    fam, z, tokens = toy
    states = np.asarray(fam.kda_states(z, 3, tokens[:150]))
    monkeypatch.setattr(fam, "MIXER_BLOCK", 2048)
    fam._mixer_jit.clear_cache()
    assert np.abs(np.asarray(fam.kda_states(z, 3, tokens[:150]))
                  - states).max() < 1e-5
    assert states.shape == (3, 4, 16, 16) and np.abs(states).max() > 0.1
    fam._mixer_jit.clear_cache()


def test_every_control_separates_from_bfloat16_at_the_toy_size(toy):
    """Each control is bfloat16 but for ONE thing, and that thing moves the
    logits after the prompt: by more than a quarter of what bfloat16 itself
    lies from float32 (``bfloat16_state``, the faintest: the state's rounding
    adds up over 192 positions) and for most by several times it;
    ``qk_unnormalised``'s state grows without bound and reads as not
    finite."""
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
        assert lg.shape == ref.shape
        moved[control] = np.abs(lg - sound)[prompt:].mean()
        # before the prompt's end the two serving-path controls are sound
        if control == "tail_advances_state":
            assert (lg[:prompt] == sound[:prompt]).all()
    assert not np.isfinite(moved.pop("qk_unnormalised"))
    assert all(m > 0.25 * noise for m in moved.values()), (noise, moved)
    assert sum(m > 2 * noise for m in moved.values()) >= 5, (noise, moved)


def test_the_balanced_bias_evens_the_routers_outputs(toy):
    import jax
    import jax.numpy as jnp
    fam, z, _ = toy
    key = fam.seed_key(11)
    biases = fam.balanced_biases(z, key)
    assert biases.shape == (4, 16) and biases.dtype == jnp.bfloat16
    assert fam.balanced_biases(z, key) is biases         # kept
    ids = fam.balance_ids(z, key)
    assert ids.shape == (fam.BALANCE_SEQUENCES, fam.BALANCE_LENGTH)
    x = fam._embedded(z, key, ids.reshape(-1), "float32")
    w = fam.layer_weights(z, key, 0)
    x, _ = fam._mix(x, w, len(ids), None, softmax=True,
                    sizes=fam._static(z), precision="float32")
    scores = fam._scores(fam._rms_norm(x, w["ln_post"], z["eps"]), w,
                         "float32")

    def load(bias):
        _, top = jax.lax.top_k(scores + bias.astype(jnp.float32), z["top_k"])
        return np.bincount(np.asarray(top).reshape(-1), minlength=16)

    even, drawn = load(biases[0]), load(w["select_bias"])
    assert even.max() / even.mean() < 1.15
    assert drawn.max() / drawn.mean() > even.max() / even.mean()


def test_chooser_control_reads_the_generated_positions(toy):
    fam, z, tokens = toy
    tokens = tokens[:64]
    gaps = fam.gaps_under(z, 3, tokens, 40, 24, 64,
                          [None, "float32", "gate_dropped",
                           "qk_unnormalised"])
    assert all(g.shape == (24,) and (g >= 0).all() for g in gaps.values())
    assert gaps["float32"].max() == 0.0     # the reference picks its own
    assert gaps[None].max() > 0.0           # random tokens are not its picks
    # 64 positions are too few for its state to overflow: finite and wrong
    assert gaps["qk_unnormalised"].mean() > 0 < gaps["gate_dropped"].mean()
    assert np.asarray(fam.chosen_gaps(z, 3, tokens, 40, 24, 64)).tolist() \
        == gaps[None].tolist()
    with pytest.raises(ValueError):
        fam.gaps_under(z, 3, tokens, 40, fam.GAP_ROWS + 1, 64, [None])


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_layer(
        toy):
    """The guide's share test at a small size: the four chips' held shares
    of a 16-wide router, each the routed part alone, plus the shared expert
    ONCE add up to the uncut layer — and a share with its shared expert is
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
    assert float(np.abs(np.asarray(sum(routed) + none - whole)).max()) < 1e-5
    mine = fam.expert_layer(z, key, 1, h, w, "float32")     # (4, 4) + shared
    assert float(np.abs(np.asarray(routed[1] + none - mine)).max()) < 1e-6


# ---- operations and bytes against hand counts ---------------------------- #
def test_state_kernel_operations_and_bytes_by_hand():
    # a 2,048-row chunk of one layer, 64 heads of 128 x 128: three d x d
    # products a position and head, 2 a multiply-add
    assert ob.scan_flops(2048, 64, 128) == 6 * 128 * 128 * 64 * 2048
    # the state read and written once: 2 x 4 MiB
    assert ob.state_bytes(1, 64, 128) == 2 * 4 * 2 ** 20
    # q, k, v, o at 2 B, g at 4 B a channel and beta a head: 1,540 B a head
    assert ob.scan_bytes(2048, 1, 64, 128) \
        == 2048 * 64 * (4 * 256 + 512 + 4) + 2 * 4 * 2 ** 20
    # the rows' bytes bind (210 MB: 257 us; 12.9 GFLOP: 65 us) — a third
    # of them the float32 log-decay —, and the reader takes the larger
    assert ob.scan_bytes(2048, 1, 64, 128) / 819e9 \
        > ob.scan_flops(2048, 64, 128) / 197e12


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
        cell=bench.cell(CELL), family=bench.family("solar_open2"),
        peaks=PEAKS, trace=types.SimpleNamespace(window_s=2.0, **trace))


def test_scope_share_reader_on_a_known_join(bench, monkeypatch):
    _joined(monkeypatch, {
        "jit(chunk_step)/layers_1/linear_attn/attn.kda/q_proj/dot_general":
            0.10,
        "jit(chunk_step)/layers_1/linear_attn/attn.kda/conv.short/mul": 0.02,
        "jit(chunk_step)/layers_2/linear_attn/attn.kda/kda.scan/"
        "kda.chunk_scan": 0.08,
        "jit(decode_block)/layers_3/linear_attn/attn.kda/kda.scan/"
        "kda.decode_step": 0.06,
        "jit(decode_block)/layers_0/self_attn/attn.full/attn.paged_decode":
            0.30,
        "jit(decode_block)/layers_1/moe_mlp/dot_general": 0.40})
    assert bench.reader("attn.kda_share_pct").read(_run(bench)) \
        == pytest.approx(100 * 0.26 / 2.0)


def test_kernel_share_and_rooflines_on_known_spans(bench, monkeypatch):
    """Both sides per CALL.  Three whole chunks of 2,048 rows, three linear
    layers: nine ``kda.chunk_scan`` events of 1 ms; two decode blocks of 8
    steps with 50 and 60 live lanes: 48 ``kda.decode_step`` events of 0.7
    ms."""
    _spans(monkeypatch, [
        ("dstpu.sched.dispatch.prefill_chunk",
         dict(kda_scan_rows=3 * 2048, kda_state_rows=3))] * 3 + [
        ("dstpu.sched.dispatch.decode",
         dict(kda_scan_rows=3 * 8 * n, kda_state_rows=3 * 8 * n))
        for n in (50, 60)])
    asked = []

    def op_seconds(match, plane=None, module=None):
        hits = [n for n in ("kda.chunk_scan", "kda.decode_step")
                if match(f"%{n}.3 = f32[8] custom-call(), "
                         f"custom_call_target=\"tpu_custom_call\"")]
        asked.append(hits)
        return {("kda.chunk_scan",): (0.001 * 9, 9),
                ("kda.decode_step",): (0.0007 * 48, 48),
                ("kda.chunk_scan", "kda.decode_step"):
                    (0.001 * 9 + 0.0007 * 48, 57)}[tuple(hits)]

    run = _run(bench, op_seconds=op_seconds)
    assert bench.reader("kda.scan_share_pct").read(run) \
        == pytest.approx(100 * (0.009 + 0.0336) / 2.0)
    chunk = bench.reader("kernel.kda_chunk_roofline").read(run)
    assert chunk == pytest.approx(
        100 * (ob.scan_bytes(2048, 1, 64, 128) / 819e9) / 0.001)
    decode = bench.reader("kernel.kda_decode_roofline").read(run)
    # a call moves the mean live lanes' rows: 55 x 8 MiB
    assert decode == pytest.approx(
        100 * (55 * 2 * 4 * 2 ** 20 / 819e9) / 0.0007)
    assert 0 < chunk < 100 and 0 < decode < 100
    assert ["kda.chunk_scan"] in asked and ["kda.decode_step"] in asked


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_reader_finds_nothing_on_a_program_without_it(bench, monkeypatch,
                                                      name):
    """A parent commit, or another model's cell: no ``kda_scan_rows`` on any
    span, no ``attn.kda`` scope in the join (or no join at all), no kernel of
    the name — None, and no error."""
    _spans(monkeypatch, [
        ("dstpu.sched.dispatch.decode", dict(full_keys=7, state_rows=3,
                                             kv_bytes_mapped=5)),
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
        family=bench.family("solar_open2"), peaks=PEAKS)
    for join in (None, {"by_op_name": {"jit(x)/layers_0/attn/q_b": 0.5}}):
        monkeypatch.setattr(scopes, "by_part",
                            lambda run, modules, join=join: join)
        assert read(run) is None
