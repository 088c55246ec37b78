"""The GLM-5 configuration, its cell, what its family adds beside the plain
forward (the module's drafts along a sequence, what a self-drafting server
accepts of them, the ``stale_window_row`` control), and the readers of what
the cell adds — on hand-made spans with known answers, and on a program
that has no such span (a parent commit, another model's cell): nothing to
read, no error.  Nothing here pins HOW MANY configurations, cells or
per-layer entries ``BENCHMARK.json`` has, or which come last: entries are
found by name, and a list is held to the ORDER of the cells it had.
(``test_benchmark_dots3.py::test_new_metric_is_an_entry_with_a_reader`` holds
dots3's metrics to that cell ALONE; this cell's window runs two of the same
kernels and is appended to ``attn.dsa_select_share_pct`` and
``moe.held_load_max_over_mean`` — PERF.md §7 c2.  What those two pins held
of each entry but its list is held here, by name.)"""

import json
import os
import types

import numpy as np
import pytest

from benchmark import opsbytes_glm5 as ob, spec, trafficgen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONFIG = "glm5-serve-reasongen-batch", "glm5-l5-e16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": (5, 78), "first_k_dense_replace": (1, 3),
           "n_routed_experts": (16, 256), "vocab_size": (19360, 154880)}
NEW_METRICS = {
    "spec.accept_rate": ("ratio", "higher", "program_counter", "programs"),
    "step.spec_window_ms": ("ms", "lower", "device_trace", "programs"),
    "mtp.draft_share_pct": ("%", "lower", "device_trace", "programs"),
    "spec.rejected_row_share_pct": ("%", "lower", "program_counter",
                                    "programs"),
    "kernel.mla_lane_decode_roofline": ("%", "higher", "device_trace",
                                        "kernels"),
    "kernel.dsa_lane_index_roofline": ("%", "higher", "device_trace",
                                       "kernels"),
    "spec.attn_core_share_pct": ("%", "lower", "device_trace", "programs"),
    "scope.unattributed_pct.spec": ("%", "lower", "device_trace",
                                    "programs")}
# the lists this cell was appended to, each with the cells it had before,
# in the order it had them
BATCH = ["opt13b-serve-longprompt-batch", "olmoe-serve-gen-batch",
         "dots3-serve-longdoc-batch", "lfm2-serve-widegen-batch",
         "evabyte-serve-bytedoc-batch"]
EVERY = ["opt13b-serve-chat", "opt13b-sft-1chip", "opt67b-zero3-4chip"] \
    + BATCH
SHARED = {
    "batch_tokens_per_s": BATCH, "sched.occupancy_pct": BATCH,
    "device.idle_pct.batch": BATCH, "sched.host_ms_per_iter.batch": BATCH,
    "setup.trace_lower_s": EVERY, "setup.backend_compile_s": EVERY,
    # the chunk program's readers: the traced slice holds chunks
    "step.prefill_chunk_ms": BATCH, "scope.unattributed_pct.batch": BATCH,
    "moe.route_scope_share_pct": ["dots3-serve-longdoc-batch",
                                  "lfm2-serve-widegen-batch"]}
# dots3's own metrics: ``tests/benchmark/test_benchmark_dots3.py`` holds
# each of their lists to that cell alone, and only a ``benchmark`` PR may
# edit a test the benchmark has — the cell is on none of them, although
# its chunk runs the same kernels (PERF.md section 7 c2)
NOT_LISTED = [
    "attn.dsa_select_share_pct", "moe.held_load_max_over_mean",
    "attn.latent_share_pct", "dsa.kept_over_scored",
    "attn.mla_decompress_share_pct", "kernel.dsa_index_roofline",
    "kernel.dsa_topk_roofline", "kernel.mla_chunk_prefill_roofline",
    "kernel.moe_grouped_share_pct", "kernel.moe_grouped_roofline",
    "kernel.moe_gmm_share_pct"]
# the readers that find the decode-block program by its name: the
# self-drafting program is named otherwise, and the cell is on neither list
BY_PROGRAM_NAME = ["step.decode_share_pct", "step.decode_block_ms.batch"]
TOY = dict(
    attention_bias=False, first_k_dense_replace=1, hidden_act="silu",
    hidden_size=32, index_head_dim=16, index_n_heads=2, index_topk=16,
    indexer_rope_interleave=True, intermediate_size=48, kv_lora_rank=16,
    max_position_embeddings=256, moe_intermediate_size=16, moe_layer_freq=1,
    model_type="glm_moe_dsa", n_group=1, n_routed_experts=4,
    n_routed_experts_published=8, held_experts=[0, 4], n_shared_experts=1,
    norm_topk_prob=True, num_attention_heads=2, num_experts_per_tok=2,
    num_hidden_layers=2, num_key_value_heads=2, num_nextn_predict_layers=1,
    q_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
    rms_norm_eps=1e-5, rope_interleave=True,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=2.5, scoring_func="sigmoid",
    tie_word_embeddings=False, topk_group=1, topk_method="noaux_tc",
    v_head_dim=8, vocab_size=64)


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
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    assert cfg["source_config"] == row["config"]


def test_the_cut_is_the_issues(bench):
    cfg = _config()
    entry = bench._entry("configs", CONFIG)
    assert entry["reduced"] == cfg["reduced"] == list(REDUCED)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert cfg["family"] == "glm5" and cfg["precision"] == "bfloat16"
    assert cfg["held_experts"] == [0, 16]
    assert cfg["num_nextn_predict_layers"] == 1
    # every width as published
    assert (cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["index_n_heads"],
            cfg["index_head_dim"], cfg["index_topk"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"]) \
        == (6144, 2048, 512, 64, 192, 64, 256, 32, 128, 2048, 12288, 2048, 8)
    for reading in ("latent_attention", "rope_layout", "indexer", "router",
                    "mtp_form", "mtp_hidden", "mtp_block", "mtp_norm_names",
                    "mtp_acceptance", "weights"):
        assert len(cfg["assumed"][reading]) > 40
    for word in ("16 v5e chips", "FIRST stage", "LAST stage",
                 "TWO sixteenths", "1/78", "4.80 B parameters"):
        assert word in cfg["deployment"]
    parts = cfg["parameters_by_part"]
    assert parts["dense_layer_0"] + 4 * parts[
        "expert_layer_16_held_each_of_4"] + parts[
            "mtp_module_eh_proj_plus_one_expert_layer"] + parts[
                "embedding"] + parts["head"] == parts["matrices"]
    assert parts["matrices"] + parts["norm_gains_and_biases"] \
        == cfg["parameters"]


def test_benchmark_file_is_valid_with_the_new_entries(bench):
    assert spec.validate(bench) == []
    assert spec.check_files(bench) == []


def test_cell_is_the_issues(bench):
    entry = bench._entry("workloads", CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "reasongen-closed96", 1)
    serving = bench.cell(CELL)["system"]["serving"]
    assert (serving["num_slots"], serving["page_size"],
            serving["max_cache_len"]) == (64, 64, 4672)
    assert (serving["speculative"], serving["spec_draft_model"],
            serving["spec_k"]) == (True, "mtp", 1)
    assert serving["max_cache_len"] // serving["page_size"] == 73
    correct = bench.cell(CELL)["system"]["correct"]
    assert 0 < correct["mean_logit_gap"] < 1 and correct["sample_requests"]


def test_traffic_is_the_issues(bench):
    mix = bench.cell(CELL)["traffic"]
    assert mix["kind"] == "closed_loop_engine" and mix["callers"] == 96
    assert mix["prompt_len"] == {"dist": "uniform", "min": 2048,
                                 "max": 3072}
    assert mix["output_len"] == {"dist": "uniform", "min": 1024,
                                 "max": 1536}
    serving = bench.cell(CELL)["system"]["serving"]
    chunk = serving["prefill_chunk"]
    for p, o in trafficgen.sizes(mix, mix["cycle"]):
        assert p + o <= serving["max_cache_len"]
        assert -(-p // chunk) * chunk <= serving["max_cache_len"]
    a, b = (next(trafficgen.closed_loop_requests(mix, 19360, s))
            for s in (3_000_000_040, 40))
    assert len(a[1]) == len(b[1]) and a[1].max() < 19360
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


@pytest.mark.parametrize("name", BY_PROGRAM_NAME + NOT_LISTED)
def test_a_reader_that_names_the_decode_block_does_not_list_the_cell(bench,
                                                                     name):
    assert CELL not in bench._entry("per_layer", name)["workloads"]
    # the self-drafting program's module name holds no "decode_block"
    from deepspeed_tpu.inference.serving import slots
    import inspect
    assert "def spec_block(" in inspect.getsource(slots.make_spec_block_fn)


# ---- what a self-drafting server accepts of a sequence's drafts ---------- #
@pytest.mark.parametrize("guesses,want", [
    # tokens 0..9, prompt of 4: token 4 comes with the admission
    ("right", (3, 2, [])),          # windows at 4, 6, 8 (8: budget's end)
    ("wrong", (5, 0, [5, 6, 7, 8, 9])),
    ("alternate", (4, 1, [5, 8])),
])
def test_accepted_along_walks_the_windows(bench, guesses, want):
    fam = bench.family("glm5")
    tokens = np.arange(10)
    # guesses[t] is the module's guess at token t + 2
    g = {"right": tokens + 2, "wrong": tokens * 0 + 63,
         "alternate": np.where(np.arange(10) % 3 == 1, tokens + 2, 63)}
    assert fam.accepted_along(tokens, g[guesses], 4) == want


def _windows(fam, first_phase, new):
    """Verify windows of a request of ``new`` output tokens whose first
    sampled token has ``first_phase``, by the family's own walk: ids that
    are their positions (a token's phase is its predecessor's + 1), the
    module right but after an id of phase 0."""
    prompt = fam.PHASES + first_phase
    tokens = np.arange(prompt + new)
    guesses = np.where((tokens + 1) % fam.PHASES == 0, -1, tokens + 2)
    return fam.accepted_along(tokens, guesses, prompt)[0]


def test_a_lengths_dispatches_do_not_depend_on_its_first_phase(bench):
    """A request's window count follows the phase of its first token,
    which is the seed's; the mix's draw and ``decode_block`` are chosen so
    that its DISPATCH count does not, and every scheduler step then
    completes the same requests on every seed (the workload file's
    ``defined_by.sited``).  PR 40 needed that to site the window's edges;
    since PR 44 tokens are credited where they are produced and no edge is
    sited, but equal schedules across seeds are still worth having: the
    seeds then differ by their weights' and tokens' work alone."""
    fam = bench.family("glm5")
    mix = bench.cell(CELL)["traffic"]
    block = bench.cell(CELL)["system"]["serving"]["decode_block"]
    assert len({_windows(fam, f, 1280) for f in range(fam.PHASES)}) == 2
    for _, new in trafficgen.sizes(mix, mix["cycle"]):
        assert len({-(-_windows(fam, f, new) // block)
                    for f in range(fam.PHASES)}) == 1, new
    # ... which a draw does not do by itself
    other = dict(mix, base_seed=mix["base_seed"] + 1)
    assert any(len({-(-_windows(fam, f, new) // block)
                    for f in range(fam.PHASES)}) == 2
               for _, new in trafficgen.sizes(other, mix["cycle"]))


def test_sizes_of_counts_the_modules_layer_among_the_pools(bench):
    fam = bench.family("glm5")
    z = fam.sizes_of(_config())
    assert z["layers"] == 5 and z["mtp"] == 1 and len(z["kinds"]) == 6
    assert set(z["kinds"]) == {"full_attention"} and z["held"] == (0, 16)
    assert dict(z["full"])["index_topk"] == 2048
    for key, value in (("rope_interleave", False), ("n_group", 2),
                       ("scoring_func", "softmax")):
        with pytest.raises(ValueError):
            fam.sizes_of(dict(_config(), **{key: value}))
    # successor and rival are of the id's next phase, and one id in
    # PHASES is unreadable: (PHASES - 1) / (PHASES + 1) of the windows of a
    # sequence that follows them are accepted, whatever the seed
    ids = np.arange(19360)
    for nxt in (fam.successor(19360), *fam.rivals(19360)):
        assert ((nxt % fam.PHASES) == (ids + 1) % fam.PHASES).all()
        assert len(set(nxt.tolist())) >= 19359
    assert all((fam.successor(19360) != r).mean() > 0.99
               for r in fam.rivals(19360))
    assert fam.unread(19360).sum() == -(-19360 // fam.PHASES)
    assert (fam.PHASES - 1) / (fam.PHASES + 1) == 0.8
    assert fam.BLIND % fam.PHASES not in (0, 1)


@pytest.fixture(scope="module")
def toy(bench):
    """The toy's tokens and, per precision, its main logits; the family's
    scales raised to a toy's (tests/unit/test_glm5.py)."""
    fam = bench.family("glm5")
    fam._W, fam._OUT, fam._ATTN, fam._DOWN, fam._SHARED, fam._EMBED = \
        0.15, 0.15, 0.2, 0.4, 0.4, 1.0
    fam._SUCC = 5.0
    z = fam.sizes_of(TOY)
    tokens = np.random.default_rng(2).integers(0, 64, 64).astype(np.int32)
    return fam, z, tokens


def test_the_stale_row_control_differs_only_after_a_rejection(toy):
    """``stale_window_row``: bfloat16, but every query attends the rows a
    rejected draft left.  Before the first rejected position nothing is
    stale, so the control IS bfloat16 there; after it, it is not."""
    import jax.numpy as jnp
    fam, z, tokens = toy
    prompt_len = 24
    padded = fam._padded(tokens)
    stale = np.asarray(fam._stale_ids(z, 3, padded, len(tokens), prompt_len))
    guesses = fam.drafts(z, 3, tokens, "bfloat16")
    _, accepted, rejected = fam.accepted_along(tokens, guesses, prompt_len)
    assert rejected and (stale != tokens).sum() == len(rejected)
    assert all(stale[p] == guesses[p - 2] for p in rejected)
    at = jnp.arange(len(tokens))
    sound = np.asarray(fam._forward(z, fam.seed_key(3), padded, at,
                                    "bfloat16"))
    control = np.asarray(fam._forward(z, fam.seed_key(3), padded, at,
                                      "stale_window_row",
                                      stale=jnp.asarray(stale)))
    first = rejected[0]
    assert np.abs(control[:first] - sound[:first]).max() == 0.0
    assert np.abs(control[first + 1:] - sound[first + 1:]).max() > 1e-3


def test_chooser_control_reads_the_generated_positions(toy):
    fam, z, tokens = toy
    gaps = fam.gaps_under(z, 3, tokens, 40, 24, 64,
                          [None, "float32", "held_dropped"])
    assert all(g.shape == (24,) and (g >= 0).all() for g in gaps.values())
    assert gaps["float32"].max() == 0.0     # the reference picks its own
    assert gaps[None].max() > 0.0           # random tokens are not its picks


# ---- the readers, on spans with known counters --------------------------- #
def _spans(monkeypatch, stats):
    from benchmark import opsbytes_dots3
    events = [{"name": name, "start_s": float(i), "dur_s": 0.1,
               "thread": (0, 0), "stats": s}
              for i, (name, s) in enumerate(stats)]
    monkeypatch.setattr(opsbytes_dots3.spans, "host_spans",
                        lambda path=None: events)


def test_new_readers_on_known_spans(bench, monkeypatch):
    _spans(monkeypatch, [
        (ob.SPEC, dict(windows=0, proposed=0, accepted=0, rows_rejected=0)),
        (ob.SPEC, dict(windows=100, proposed=100, accepted=80,
                       rows_rejected=22)),
        (ob.SPEC, dict(windows=60, proposed=60, accepted=48,
                       rows_rejected=12)),
        ("dstpu.sched.dispatch.prefill_chunk", dict(dsa_keys_scored=5))])
    run = types.SimpleNamespace(trace=object())
    assert bench.reader("spec.accept_rate").read(run) \
        == pytest.approx(128 / 160)
    assert bench.reader("spec.rejected_row_share_pct").read(run) \
        == pytest.approx(100 * 34 / 320)
    durations = {"spec_block": [0.08, 0.16, 0.12]}
    run = types.SimpleNamespace(
        trace=types.SimpleNamespace(
            module_durations=lambda name: durations.get(name, [])),
        cell={"system": {"serving": {"decode_block": 8}}})
    assert bench.reader("step.spec_window_ms").read(run) \
        == pytest.approx(15.0)


@pytest.mark.parametrize("name,seconds,want", [
    # 2 spans of a dispatch's windows; a call: 64 lanes x 3,584 live rows,
    # memory-bound
    ("kernel.mla_lane_decode_roofline", 0.0006, 100 * (
        64 * 3584 * 576 * 2 / 819e9) / 0.0006),
    ("kernel.dsa_lane_index_roofline", 0.0001, 100 * (
        64 * 3584 * 128 * 2 / 819e9) / 0.0001)])
def test_window_kernel_rooflines_on_known_spans(bench, monkeypatch, name,
                                                seconds, want):
    """Per call — one layer, one window, every live lane: the lanes' live
    rows once a LANE (``kv_pages`` counts them once a row of the two),
    the pairs over the six pools' layers."""
    cell = bench.cell(CELL)
    lanes, pages, layers = 64, 56, 6
    windows = cell["system"]["serving"]["decode_block"]   # a dispatch
    span = dict(kv_pages=2 * lanes * pages * windows,
                dsa_keys_scored=layers * windows * lanes * 2 * 3584,
                dsa_keys_kept=layers * windows * lanes * 2 * 2048)
    _spans(monkeypatch, [(ob.SPEC, span), (ob.SPEC, span)])
    run = types.SimpleNamespace(
        cell=cell, family=bench.family("glm5"),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace=types.SimpleNamespace(
            op_seconds=lambda match, plane=None, module=None:
            (seconds * 96, 96)))
    work = ob.lane_work(run)
    assert work == {"rows": lanes * pages * 64, "scored": lanes * 2 * 3584,
                    "kept": lanes * 2 * 2048}
    got = bench.reader(name).read(run)
    assert got == pytest.approx(want) and 0 < got < 100


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_reader_finds_nothing_on_a_program_without_it(bench, monkeypatch,
                                                      name):
    """A parent commit, or another model's cell: no ``spec_block`` span, no
    such program in the trace, no ``mtp.*`` scope — None, and no error."""
    _spans(monkeypatch, [
        ("dstpu.sched.dispatch.decode", dict(dsa_keys_scored=7)),
        ("dstpu.sched.dispatch.prefill_chunk", dict(dsa_keys_scored=5))])
    read = bench.reader(name).read
    assert read(types.SimpleNamespace(trace=None, observed={})) is None
    empty = types.SimpleNamespace(
        window_s=1.0, device_planes=[], events=[],
        module_durations=lambda name: [], device_ops=lambda: [],
        op_seconds=lambda match, plane=None, module=None: (0.0, 0))
    run = types.SimpleNamespace(
        trace=empty, observed={},
        cell={"system": {"serving": {"decode_block": 8}}})
    assert read(run) is None
