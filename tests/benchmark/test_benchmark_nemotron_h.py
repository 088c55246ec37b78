"""The nemotron-3-nano-30b-a3b configuration, its cell and traffic, what its
family adds (the controls that differ from bfloat16 by one thing, the
balanced selection bias, the held half of a 128-wide router and the share
test), the operations and bytes of the UN-GATED experts against hand counts,
and the readers of what the cell adds — on hand-made spans with known
answers, and on a program that has no such span or kernel (a parent commit,
another model's cell): nothing to read, no error.  Nothing here pins HOW
MANY configurations, cells or per-layer entries ``BENCHMARK.json`` has, or
which come last: entries are found by name, and a list is held to the ORDER
of the cells it had."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import opsbytes_nemotron as ob, spec, trafficgen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONFIG = "nemotron3-serve-thinkgen-batch", \
    "nemotron-3-nano-30b-a3b-l14-e64"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PATTERN = "MEMEM*EMEMEM*E"
REDUCED = {"num_hidden_layers": 14, "hybrid_override_pattern": PATTERN,
           "n_routed_experts": 64, "vocab_size": 65536}
NEW_METRICS = {
    "kernel.moe_ungated_gmm_roofline": ("%", "higher", "device_trace",
                                        "kernels"),
    "kernel.moe_ungated_grouped_roofline": ("%", "higher", "device_trace",
                                            "kernels"),
    "moe.ungated_experts_share_pct": ("%", "lower", "device_trace",
                                      "kernels")}
# the lists this cell was appended to, each with the cell it follows
AFTER_GRANITE = [
    "batch_tokens_per_s", "sched.occupancy_pct", "device.idle_pct.batch",
    "sched.host_ms_per_iter.batch", "sched.prefill_rows_per_dispatch",
    "step.prefill_chunk_ms", "step.decode_block_ms.batch",
    "scope.unattributed_pct.batch", "kernel.paged_decode_share_pct.batch",
    "moe.route_scope_share_pct", "attn.full_share_pct",
    "head.logits_share_pct", "setup.trace_lower_s",
    "setup.backend_compile_s", "setup.outside_program_s", "setup.import_s",
    "setup.engine_build_s", "setup.weights_s",
    "setup.compile_after_warmup_s", "attn.ssd_share_pct",
    "ssd.scan_share_pct", "kernel.ssd_chunk_roofline",
    "kernel.ssd_decode_roofline"]
# the rooflines that count THREE matrices an expert (on a two-matrix expert
# an honest 80% would read 120%), and the lists the benchmark's own tests
# hold to other cells
NOT_LISTED = ["kernel.moe_experts_roofline", "kernel.moe_grouped_roofline",
              "kernel.moe_held_grouped_roofline", "kernel.moe_gmm_share_pct",
              "kernel.moe_grouped_share_pct", "step.decode_share_pct",
              "moe.held_load_max_over_mean", "cache.state_share_pct",
              "conv.short_share_pct", "moe.held_rows_per_touched_expert",
              "setup.compile_chunk_s", "setup.compile_block_s",
              "setup.compile_admit_s"]
TOY = dict(
    model_type="nemotron_h", hidden_size=128, num_hidden_layers=6,
    hybrid_override_pattern="ME*MEM", num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, attention_bias=False,
    mamba_num_heads=16, mamba_head_dim=16, ssm_state_size=16, n_groups=2,
    conv_kernel=4, use_conv_bias=True, mamba_proj_bias=False, chunk_size=128,
    expand=2, n_routed_experts=4, n_routed_experts_published=8,
    held_experts=[4, 4], num_experts_per_tok=3, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, n_shared_experts=1,
    mlp_hidden_act="relu2", mamba_hidden_act="silu", norm_topk_prob=True,
    routed_scaling_factor=2.5, n_group=1, topk_group=1,
    layer_norm_epsilon=1e-5, vocab_size=128, tie_word_embeddings=False,
    max_position_embeddings=512, rope_theta=10000, mlp_bias=False,
    use_bias=False)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
EXPERT = 2 * 2688 * 1856 * 2                # an expert's two matrices, bytes


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
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
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
    assert cfg["family"] == "nemotron_h" and cfg["precision"] == "bfloat16"
    # every width as published: no width is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) \
        == (2688, 32, 2, 128)
    assert (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"],
            cfg["use_conv_bias"], cfg["mamba_proj_bias"]) \
        == (64, 64, 128, 8, 4, True, False)
    assert (cfg["n_routed_experts_published"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"], cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["mlp_hidden_act"], cfg["n_group"], cfg["topk_group"],
            cfg["tie_word_embeddings"]) \
        == (128, 6, 2.5, 1856, 3712, "relu2", 1, 1, False)
    # the published pattern's first 14 blocks: 6 : 6 : 2 of 23 : 23 : 6
    published = cfg["source_config"]["hybrid_override_pattern"]
    assert published[:14] == PATTERN == cfg["hybrid_override_pattern"]
    assert [published.count(c) for c in "ME*"] == [23, 23, 6]
    assert [PATTERN.count(c) for c in "ME*"] == [6, 6, 2]
    assert cfg["held_experts"] == [0, 64] and 2 * 64 == 128
    assert 2 * cfg["vocab_size"] == cfg["vocab_size_published"] == 131072
    assert cfg["num_hidden_layers_published"] == 52
    for reading in ("source_of_what_follows", "a1_no_dt_clamp",
                    "a2_group_wise_gate_norm", "a3_no_positional_encoding",
                    "a4_gates_from_scores", "precision", "mamba_in_proj",
                    "mamba_recurrence", "experts", "stored_width",
                    "weights"):
        assert len(cfg["assumed"][reading]) > 40
    for word in ("FOUR pipeline stages", "2 v5e chips", "64 a chip",
                 "8 chips", "65,536 rows", "What the cut distorts",
                 "14 blocks, not 52", "ROADMAP M5"):
        assert word in cfg["deployment"]
    parts = cfg["parameters_by_part"]
    assert parts["one_expert_2x2688x1856"] == 2 * 2688 * 1856
    assert parts["expert_block_each_of_6"] == 64 * 2 * 2688 * 1856 \
        + 2 * 2688 * 3712 + 2688 * 128 + 128 == 658882688
    assert parts["all"] == cfg["parameters"] == 4584903936
    assert round(2 * cfg["parameters"] / 1e9, 2) == 9.17


def test_parameters_are_recounted_from_the_programs_shapes(bench):
    """``parameters_by_part`` from the sizes, and the program's own shapes
    at the published expert width: the tree's count less the experts' zero
    padding (1856 stored as 1920) is ``parameters``."""
    import jax
    import jax.numpy as jnp
    fam = bench.family("nemotron_h")
    cfg = _config()
    parts = fam.parameters_by_part(fam.sizes_of(cfg))
    h, w, cw = 2688, 4096, 4096 + 2 * 8 * 128
    assert parts["gqa_mixer_each"] == 2 * h * 4096 + 2 * h * 256
    assert parts["mamba_mixer_each"] == h * (w + cw + 64) + w * h \
        + 5 * cw + 3 * 64 + w == 38742208
    assert parts["one_expert"] == 2 * h * 1856
    assert parts["embedding"] == parts["head"] == 65536 * h
    assert parts["norm_gains"] == 14 * h + h
    assert parts["all"] == cfg["parameters"]
    module = fam.program_model(cfg)
    tree = jax.eval_shape(module.init, jax.random.key(0),
                          {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    stored = sum(x.size for x in jax.tree.leaves(tree))
    padding = 6 * 64 * 2 * h * (module.config.stored_expert_width - 1856)
    assert module.config.stored_expert_width == 1920
    assert stored - padding == cfg["parameters"]
    # at the published depth, experts and vocabulary: the card's 31.6 B
    whole = fam.sizes_of(dict(
        cfg, num_hidden_layers=52, n_routed_experts=128, vocab_size=131072,
        held_experts=[0, 128], hybrid_override_pattern=cfg["source_config"][
            "hybrid_override_pattern"]))
    assert round(fam.parameters_by_part(whole)["all"] / 1e9, 2) == 31.58


def test_benchmark_file_is_valid_and_every_new_file_is_found_by_name(bench):
    assert spec.validate(bench) == []
    assert spec.check_files(bench) == []
    cell = bench.cell(CELL)
    assert cell["config"]["name"] == CONFIG and cell["chips"] == 1
    assert bench.driver(cell["traffic"]["kind"]).run
    fam = bench.family(cell["config"]["family"])
    for name in ("sizes_of", "program_model", "program_params", "logits",
                 "nll_at", "chosen_gaps", "gaps_under", "greedy",
                 "ssm_states", "parameters_by_part", "balanced_biases"):
        assert callable(getattr(fam, name))
    got = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) | (set(AFTER_GRANITE)
                               - {"batch_tokens_per_s"}) <= got
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"batch_tokens_per_s", "setup_s"}


def test_cell_is_the_issues(bench):
    entry = bench._entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "thinkgen-closed288", 1)
    assert len(entry["why"]) <= 200 and "two-matrix experts" in entry["why"]
    cell = bench.cell(CELL)
    serving = cell["system"]["serving"]
    assert (serving["num_slots"], serving["page_size"],
            serving["max_cache_len"]) == (192, 64, 5632)
    assert "speculative" not in serving and serving["paged"]
    # the state pool: 193 rows x 6 Mamba blocks x (2 MiB + 36,864 B) = 2.47
    # GB; the K/V pools of 2 blocks x 2 KV heads x 128: 1 KiB a token each
    state = 193 * 6 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    lane = serving["num_pages"] * 64 * 2 * 256 * 2 * 2
    assert round(state / 1e9, 2) == 2.47
    assert 0.8e9 < lane < 1.3e9
    correct = cell["system"]["correct"]
    assert 0 < correct["mean_logit_gap"] < 1 and correct["sample_requests"]
    assert {"sweep", "calibration", "two_sets_of_six", "traced", "parent"} \
        <= set(cell["system"]["defined_by"])
    assert "state" in cell["system"]["sizing"]


def test_traffic_is_the_issues(bench):
    cell = bench.cell(CELL)
    mix, serving = cell["traffic"], cell["system"]["serving"]
    assert mix["kind"] == "closed_loop_engine"
    assert (mix["callers"], mix["cycle"], mix["base_seed"]) == (288, 288, 60)
    assert mix["callers"] * 2 == serving["num_slots"] * 3
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.8, "min": 128, "max": 4096}
    assert mix["output_len"] == {"dist": "uniform", "min": 512, "max": 1536}
    assert mix["ramp_s"] == 60 and mix["trace_slice_s"] == 4
    sizes = trafficgen.sizes(mix, mix["cycle"])
    prompts = np.asarray([p for p, _ in sizes])
    assert prompts.min() == 128 and prompts.max() == 4096
    assert 600 < prompts.mean() < 850 and 440 < np.median(prompts) < 600
    # more than half of a request's tokens are decoded
    assert 0.52 < sum(o for _, o in sizes) \
        / sum(p + o for p, o in sizes) < 0.65
    chunk = serving["prefill_chunk"]
    fam = bench.family("nemotron_h")
    for p, o in sizes:
        assert p + o <= serving["max_cache_len"] and o <= fam.GAP_ROWS
        assert -(-p // chunk) * chunk <= serving["max_cache_len"]
    assert fam.TAIL_CHUNK == chunk
    a, b = (next(trafficgen.closed_loop_requests(mix, 65536, s))
            for s in (3_000_000_060, 60))
    assert len(a[1]) == len(b[1]) and 32768 < a[1].max() < 65536
    assert (a[1][:64] != b[1][:64]).any()


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_is_an_entry_with_a_reader(bench, name):
    entry = bench._entry("per_layer", name)
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == NEW_METRICS[name]
    assert entry["moves"] == "batch_tokens_per_s" \
        and entry["workloads"] == [CELL]
    assert callable(bench.reader(name).read)


@pytest.mark.parametrize("name", AFTER_GRANITE)
def test_shared_metric_lists_this_cell_after_the_cells_it_had(bench, name):
    section = "end_to_end" if name == "batch_tokens_per_s" else "per_layer"
    cells = bench._entry(section, name)["workloads"]
    assert cells.count(CELL) == 1
    assert cells.index(CELL) > cells.index("granite-serve-chatgen-batch")


@pytest.mark.parametrize("name", NOT_LISTED)
def test_a_metric_held_to_other_cells_does_not_list_the_cell(bench, name):
    assert CELL not in bench._entry("per_layer", name)["workloads"]


# ---- the family ----------------------------------------------------------- #
def test_sizes_of_reads_the_files_keys(bench):
    fam = bench.family("nemotron_h")
    z = fam.sizes_of(_config())
    assert (z["layers"], z["pattern"]) == (14, PATTERN)
    assert [z["kinds"].count(k) for k in (
        "state_space", "experts", "full_attention")] == [6, 6, 2]
    assert (z["heads"], z["kv_heads"], z["d"]) == (32, 2, 128)
    assert (z["ssm_heads"], z["ssm_d"], z["ssm_n"], z["ssm_groups"],
            z["taps"]) == (64, 64, 128, 8, 4)
    assert (z["experts"], z["held"], z["top_k"], z["sf"], z["scaling"]) \
        == (128, (0, 64), 6, 3712, 2.5)
    # the expert width under both names the benchmark's readers use
    assert (z["f"], z["ef"], z["h"], z["vocab"]) == (1856, 1856, 2688, 65536)
    for key, value in (("hybrid_override_pattern", "MEMEM-EMEMEM*E"),
                       ("n_group", 2), ("mamba_proj_bias", True),
                       ("use_conv_bias", False),
                       ("tie_word_embeddings", True),
                       ("mlp_hidden_act", "silu"), ("n_groups", 3),
                       ("n_routed_experts", 128)):
        with pytest.raises(ValueError):
            fam.sizes_of(dict(_config(), **{key: value}))
    assert {"bfloat16_state", "state_not_cleared", "tail_advances_state",
            "relu_not_squared", "gate_from_biased_scores", "scaling_dropped",
            "shared_dropped", "one_group_bc", "norm_whole_width",
            "rope_on_attention", "float8_experts"} == set(fam.CONTROLS)


@pytest.fixture(scope="module")
def toy(bench):
    """The toy's sizes and tokens; the family's scales raised to a toy's
    (tests/unit/test_nemotron_h.py), its biases balanced on a toy's sample,
    and the serving controls' tail and stale rows cut to a toy's lengths."""
    fam = bench.family("nemotron_h")
    fam._W, fam._QK = 0.09, 0.15
    fam.BALANCE_SEQUENCES, fam.BALANCE_LENGTH = 2, 128
    fam.TAIL_CHUNK, fam.STALE_ROWS = 16, 32
    tokens = np.random.default_rng(2).integers(0, 128, 192).astype(np.int32)
    return fam, fam.sizes_of(TOY), tokens


def test_every_control_separates_from_bfloat16_at_the_toy_size(toy):
    """Each control is bfloat16 but for ONE thing, and that thing moves the
    logits after the prompt."""
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
        if control == "tail_advances_state":
            assert (lg[:prompt] == sound[:prompt]).all()
    assert all(m > 0.25 * noise for m in moved.values()), (noise, moved)
    assert sum(m > 2 * noise for m in moved.values()) >= 7, (noise, moved)


def test_the_selection_bias_is_balanced_over_the_whole_router(toy):
    """A block's bias evens the loads of all 8 outputs on the balancing
    sample, held or not: about half of the choices fall elsewhere."""
    import jax
    import jax.numpy as jnp
    fam, z, _ = toy
    key = fam.seed_key(11)
    biases = fam.balanced_biases(z, key)
    assert biases.shape == (2, 8) and biases.dtype == jnp.bfloat16
    assert fam.balanced_biases(z, key) is biases          # kept
    ids = fam.balance_ids(z, key)
    x = fam._embedded(z, key, ids.reshape(-1), "float32")
    x = fam._block(z, key, 0, x, "float32", sequences=len(ids))
    w = fam.block_weights(z, key, 1, bias=biases[0])
    scores = fam._scores(fam._rms_norm(x, w["ln"], z["eps"]), w, "float32")

    def spread(bias):
        _, top = jax.lax.top_k(scores + bias.astype(jnp.float32), z["top_k"])
        load = np.bincount(np.asarray(top).reshape(-1), minlength=8)
        return load.max() / load.mean(), load[4:].sum() / load.sum()

    drawn = fam.block_weights(z, key, 1)["select_bias"]
    even, held = spread(biases[0])
    assert even < spread(drawn)[0] and even < 1.25
    assert 0.4 < held < 0.6


def test_chooser_control_reads_the_generated_positions(toy):
    fam, z, tokens = toy
    tokens = tokens[:64]
    gaps = fam.gaps_under(z, 3, tokens, 40, 24, 64,
                          [None, "float32", "shared_dropped",
                           "one_group_bc"])
    assert all(g.shape == (24,) and (g >= 0).all() for g in gaps.values())
    assert gaps["float32"].max() == 0.0     # the reference picks its own
    assert gaps[None].max() > 0.0           # random tokens are not its picks
    assert gaps["shared_dropped"].mean() > 0 < gaps["one_group_bc"].mean()
    assert np.asarray(fam.chosen_gaps(z, 3, tokens, 40, 24, 64)).tolist() \
        == gaps[None].tolist()
    with pytest.raises(ValueError):
        fam.gaps_under(z, 3, tokens, 40, fam.GAP_ROWS + 1, 64, [None])


def test_two_shares_and_the_shared_expert_once_are_the_uncut_block(toy):
    """The guide's share test at a small size: the parts of one ``E``
    block's output that shares ``(0, 4)`` and ``(4, 4)`` of an 8-wide router
    give, each the routed part alone, plus the shared expert ONCE add up to
    the uncut reference's — and a share with the shared expert is what the
    PROGRAM's expert layer computes for it (un-gated experts stored padded,
    the scored router, the dense kernel)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.nemotron_h import (NemotronHModel,
                                                 nemotron_h_config)
    from deepspeed_tpu.moe.layer import MoE
    fam, z, _ = toy
    key = fam.seed_key(5)
    w = fam.block_weights(z, key, 1)
    h = jax.random.normal(jax.random.key(1), (48, z["h"]))
    whole = fam.expert_layer(z, key, 1, h, w, "float32", held=(0, 8))
    routed = [fam.expert_layer(z, key, 1, h, w, "float32", held=(first, 4),
                               shared=False) for first in (0, 4)]
    none = fam.expert_layer(z, key, 1, h, w, "float32", held=(0, 0))
    assert float(np.abs(np.asarray(sum(routed))).mean()) > 0.05
    scale = float(np.abs(np.asarray(whole)).max())
    assert float(np.abs(np.asarray(sum(routed) + none - whole)).max()) \
        < 1e-5 * scale
    mine = fam.expert_layer(z, key, 1, h, w, "float32")     # (4, 4) + shared
    assert float(np.abs(np.asarray(routed[1] + none - mine)).max()) \
        < 1e-6 * scale
    # the program's layer on the same weights, for each share
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    for first, part in zip((0, 4), routed):
        cfg = nemotron_h_config(TOY, held_experts=(first, 4),
                                dtype="float32")
        layer = MoE(**{"hidden_size": 128, "capacity_factor": None,
                       "dtype": jnp.float32,
                       **NemotronHModel.declare(cfg).moe})
        stored = cfg.stored_expert_width
        experts = [fam.expert_weights(z, key, 1, first + e)
                   for e in range(4)]
        pad = lambda t, axis: jnp.pad(f32(t), [
            (0, stored - 48) if i == axis else (0, 0) for i in range(2)])
        params = {
            "gate_kernel": f32(w["router"]),
            "select_bias": f32(w["select_bias"]),
            "shared_up": {"kernel": f32(w["shared_up"])},
            "shared_down": {"kernel": f32(w["shared_down"])},
            "ExpertsMLP_0": {
                "experts_wi": jnp.stack([pad(e["wu"], 1) for e in experts]),
                "experts_wo": jnp.stack([pad(e["wd"], 0) for e in experts])}}
        got = layer.apply({"params": params}, h, train=False)[0]
        assert float(np.abs(np.asarray(got - (part + none))).max()) \
            < 1e-4 * scale


# ---- operations and bytes against hand counts ---------------------------- #
def test_ungated_expert_operations_and_bytes_by_hand():
    # TWO matrices an expert at the published width: 19.96 MB
    assert ob.ungated_bytes(1, 2688, 1856) == EXPERT == 19955712
    # a row through both, 2 a multiply-add
    assert ob.ungated_flops(1, 2688, 1856) == 2 * 2 * 2688 * 1856
    # a decode step at 192 lanes: every one of the 64 held touched, ~9 rows
    # each: the bytes bind (1.28 GB: 1.56 ms; 11.5 GFLOP: 0.06 ms)
    assert ob.ungated_bytes(64, 2688, 1856) / 819e9 \
        > 20 * ob.ungated_flops(576, 2688, 1856) / 197e12
    # two thirds of what the three-matrix count would say
    from benchmark import opsbytes_moe
    assert 3 * ob.ungated_bytes(7, 2688, 1856) \
        == 2 * opsbytes_moe.experts_bytes(7, 2688, 1856)


# ---- the readers, on spans with known counters ---------------------------- #
def _spans(monkeypatch, stats):
    from benchmark import opsbytes_dots3
    events = [{"name": name, "start_s": float(i), "dur_s": 0.1,
               "thread": (0, 0), "stats": s}
              for i, (name, s) in enumerate(stats)]
    monkeypatch.setattr(opsbytes_dots3.spans, "host_spans",
                        lambda path=None: events)


def _run(bench, **trace):
    return types.SimpleNamespace(
        cell=bench.cell(CELL), family=bench.family("nemotron_h"),
        peaks=PEAKS, trace=types.SimpleNamespace(window_s=2.0, **trace))


def _kernels(times):
    """``op_seconds`` of a trace that holds the named kernels' events:
    ``{kernel: (seconds, calls)}``."""
    def op_seconds(match, plane=None, module=None):
        hits = [n for n in times if match(
            f"%{n}.3 = bf16[8] custom-call(), "
            f"custom_call_target=\"tpu_custom_call\"")]
        return (sum(times[n][0] for n in hits),
                sum(times[n][1] for n in hits))
    return op_seconds


@pytest.mark.parametrize("sorted_chunks", [True, False])
def test_rooflines_and_share_on_known_spans(bench, monkeypatch,
                                            sorted_chunks):
    """Both sides per CALL.  Two decode blocks of 8 steps x 6 expert blocks
    with every held expert touched (64) and 9 rows each: 96 ``experts_gmm``
    events of 2 ms; three chunk dispatches of 6 calls touching 60 experts
    with 1,500 held pairs a call — sorted (``experts_grouped``, 3 ms) or,
    under ``GROUPED_MIN_ROWS`` rows, dense like the decode steps'."""
    decode = dict(moe_experts_touched=48 * 64, moe_assignments=48 * 576,
                  moe_calls=48)
    chunk = dict(moe_experts_touched=6 * 60, moe_assignments=6 * 1500,
                 moe_calls=6)
    _spans(monkeypatch, [("dstpu.sched.commit", decode)] * 2
           + [("dstpu.sched.wait_device", chunk)] * 3
           + [("dstpu.sched.wait_device", dict(kind="decode"))])
    times = {"moe.experts_gmm": (0.002 * 96, 96),
             "moe.experts_grouped": (0.003 * 18, 18)} if sorted_chunks \
        else {"moe.experts_gmm": (0.002 * 114, 114)}
    run = _run(bench, op_seconds=_kernels(times))
    share = bench.reader("moe.ungated_experts_share_pct").read(run)
    assert share == pytest.approx(
        100 * sum(s for s, _ in times.values()) / 2.0)
    gmm = bench.reader("kernel.moe_ungated_gmm_roofline").read(run)
    grouped = bench.reader("kernel.moe_ungated_grouped_roofline").read(run)
    if sorted_chunks:
        assert gmm == pytest.approx(100 * (64 * EXPERT / 819e9) / 0.002)
        assert grouped == pytest.approx(100 * (60 * EXPERT / 819e9) / 0.003)
        assert 0 < grouped < 100
    else:
        touched = (96 * 64 + 18 * 60) / 114
        assert gmm == pytest.approx(100 * (touched * EXPERT / 819e9) / 0.002)
        assert grouped is None
    assert 0 < gmm < 100


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_reader_finds_nothing_on_a_program_without_it(bench, monkeypatch,
                                                      name):
    """A parent commit, or another model's cell: no expert load on any span,
    no kernel of the name — None, and no error."""
    _spans(monkeypatch, [
        ("dstpu.sched.dispatch.decode", dict(full_keys=7, state_rows=3)),
        ("dstpu.sched.commit", dict(kind="decode", tokens=5))])
    read = bench.reader(name).read
    assert read(types.SimpleNamespace(trace=None, observed={})) is None
    empty = types.SimpleNamespace(
        window_s=1.0, device_planes=[], events=[],
        module_durations=lambda name: [], device_ops=lambda: [],
        op_seconds=lambda match, plane=None, module=None: (0.0, 0))
    run = types.SimpleNamespace(
        trace=empty, observed={}, cell=bench.cell(CELL),
        family=bench.family("nemotron_h"), peaks=PEAKS)
    assert read(run) is None
    # kernels of the name but no span that carries their load
    run.trace.op_seconds = _kernels({"moe.experts_gmm": (0.1, 5)})
    if "roofline" in name:
        assert read(run) is None
