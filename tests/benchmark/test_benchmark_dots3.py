"""The dots3 configuration, its cell, its family's controls, and the readers
of what it adds — on hand-made events with known answers, and on a program
that has no such span or kernel (a parent commit, another model's cell):
nothing to read, no error.  Nothing here pins HOW MANY configurations or
per-layer entries ``BENCHMARK.json`` has, or which come last: entries are
found by name."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import opsbytes_dots3 as ob, spans, spec, trace, trafficgen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONFIG = "dots3-serve-longdoc-batch", "dots3-note-prev-l5-e32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
D0, OPS = "/device:TPU:0", trace.OPS_LINE
REDUCED = ["num_hidden_layers", "layer_types", "n_routed_experts",
           "vocab_size"]
NEW_METRICS = {
    "attn.latent_share_pct": ("%", "device_trace", "kernels"),
    "attn.dsa_select_share_pct": ("%", "device_trace", "kernels"),
    "dsa.kept_over_scored": ("ratio", "program_counter", "cache manager"),
    "kernel.moe_grouped_share_pct": ("%", "device_trace", "kernels"),
    "kernel.dsa_index_roofline": ("%", "device_trace", "kernels"),
    "kernel.mla_chunk_prefill_roofline": ("%", "device_trace", "kernels"),
    "kernel.mla_window_roofline": ("%", "device_trace", "kernels"),
    "kernel.moe_grouped_roofline": ("%", "device_trace", "kernels"),
    "kernel.dsa_topk_roofline": ("%", "device_trace", "kernels"),
    "kernel.moe_gmm_share_pct": ("%", "device_trace", "kernels"),
    "step.decode_share_pct": ("%", "device_trace", "programs"),
    "moe.held_load_max_over_mean": ("ratio", "program_counter", "experts")}
SHARED_METRICS = [
    "sched.occupancy_pct", "step.decode_block_ms.batch",
    "step.prefill_chunk_ms", "device.idle_pct.batch",
    "sched.host_ms_per_iter.batch", "setup.trace_lower_s",
    "setup.backend_compile_s"]
TOY = dict(
    apply_mla_qkv_lora_rescale=True, attention_bias=False,
    first_k_dense_replace=1, hidden_act="silu", hidden_size=64,
    index_head_dim=16, index_n_heads=8, index_topk=16, intermediate_size=96,
    kv_lora_rank=32, layer_types=["full_attention", "full_attention",
                                  "sliding_attention"],
    max_position_embeddings=512, moe_intermediate_size=32,
    n_routed_experts=8, n_routed_experts_published=16, held_experts=[0, 8],
    n_shared_experts=1, norm_topk_prob=True, num_attention_heads=4,
    num_experts_per_tok=4, num_hidden_layers=3, q_lora_rank=48,
    qk_nope_head_dim=16, qk_rope_head_dim=8, rms_norm_eps=1e-5,
    rope_scaling=None, rope_theta=80000000, routed_scaling_factor=1,
    scoring_func="sigmoid", sliding_window_size=17, swa_kv_lora_rank=40,
    swa_num_attention_heads=2, swa_q_lora_rank=48, swa_qk_nope_head_dim=24,
    swa_qk_rope_head_dim=8, swa_rope_theta=50000, swa_v_head_dim=16,
    tie_word_embeddings=False, topk_method="noaux_tc", v_head_dim=16,
    vocab_size=256)


@pytest.fixture(scope="module")
def bench():
    return spec.Benchmark(ROOT)


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


# ---- (g) the configuration against its source, key by key ---------------- #
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
               if r["name"] == "dots3-note-prev")
    cfg = _config()
    assert cfg["source_config"] == row["config"]
    assert cfg["source"] == row["source_url"]


def test_the_cut_is_the_issues(bench):
    cfg, entry = _config(), bench._entry("configs", CONFIG)
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert not any(spec.WIDTH_RE.search(k) for k in REDUCED)
    assert cfg["num_hidden_layers"] == 5 and cfg["layer_types"] == [
        "full_attention", "full_attention", "sliding_attention",
        "sliding_attention", "sliding_attention"]
    assert cfg["layer_types"] == cfg["source_config"]["layer_types"][:5]
    assert (cfg["n_routed_experts"], cfg["n_routed_experts_published"],
            cfg["held_experts"]) == (32, 256, [0, 32])
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"] == 152064
    # every published width, rank and head size, the router's experts a
    # token, the kept set and the window
    assert (cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["swa_kv_lora_rank"], cfg["index_topk"],
            cfg["sliding_window_size"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"]) \
        == (5120, 1024, 512, 1024, 2048, 513, 8, 1536, 13824)
    for said in ("towers_and_mtp", "apply_mla_qkv_lora_rescale", "indexer",
                 "sliding_window_size", "router", "weights", "rope_layout"):
        assert said in cfg["assumed"]
    for said in ("8 v5e chips", "FIRST stage", "1.4x", "~64 rows"):
        assert said in cfg["deployment"]


def test_parameters_are_counted_from_the_shapes(bench):
    import jax
    import jax.numpy as jnp
    cfg, fam = _config(), bench.family("dots3")
    module = fam.program_model(cfg)
    tree = jax.eval_shape(module.init, jax.random.key(0),
                          {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    sizes = [int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree)]
    assert sum(sizes) == cfg["parameters"] == 4_087_154_176
    parts = cfg["parameters_by_part"]
    assert 2 * parts["full_layer_attention_each_of_2"] \
        + 3 * parts["window_layer_attention_each_of_3"] \
        + parts["dense_ffn_layer_0"] + 4 * parts["expert_layer_each_of_4"] \
        + parts["embedding"] + parts["head"] + parts["norm_gains"] \
        == cfg["parameters"]
    # the pools by row kind at the cell's sizes: rows padded to 128 lanes
    pools = jax.eval_shape(lambda: module.init_paged_cache(
        4113, 64, window_pages=1 + 16 * 9))
    assert {k: v.shape for k, v in pools.items()} == {
        "latent": (2, 4113, 64, 640), "index": (2, 4113, 64, 128),
        "window": (3, 145, 64, 1152)}


def test_benchmark_file_is_valid_with_the_new_entries(bench):
    assert spec.validate(bench) == []
    assert spec.check_files(bench) == []


def test_cell_is_the_issues(bench):
    cell = bench.cell(CELL)
    assert (cell["config_name"], cell["traffic_name"], cell["chips"]) \
        == (CONFIG, "longdoc-closed32", 1)
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"batch_tokens_per_s", "setup_s"}
    serving = cell["system"]["serving"]
    assert (serving["num_slots"], serving["max_cache_len"],
            serving["page_size"], serving["decode_block"]) \
        == (16, 16448, 64, 8)
    assert serving["prefill_chunk"] in (512, 1024, 2048)
    assert cell["system"]["correct"]["mean_logit_gap"] > 0
    got = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) | set(SHARED_METRICS) <= got


def test_traffic_is_the_issues(bench):
    mix = bench.cell(CELL)["traffic"]
    assert mix["kind"] == "closed_loop_engine"
    assert (mix["callers"], mix["cycle"], mix["ramp_s"], mix["base_seed"]) \
        == (32, 64, 16.0, 31)
    assert mix["prompt_len"] == {"dist": "uniform", "min": 8192,
                                 "max": 16384}
    assert mix["output_len"] == {"dist": "uniform", "min": 16, "max": 64}
    sizes = trafficgen.sizes(mix, 64)
    serving = bench.cell(CELL)["system"]["serving"]
    chunk = serving["prefill_chunk"]
    for p, o in sizes:
        assert p + o <= serving["max_cache_len"]
        assert -(-p // chunk) * chunk <= serving["max_cache_len"]
    a, b = (next(trafficgen.closed_loop_requests(mix, 19008, s))
            for s in (3_000_000_031, 31))
    assert len(a[1]) == len(b[1]) and a[1].max() < 19008
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


@pytest.mark.parametrize("name", SHARED_METRICS + ["batch_tokens_per_s"])
def test_shared_metric_lists_the_cell_after_the_cells_it_had(bench, name):
    """Right after the OLMoE cell, which was the list's last when PR 31
    appended this one; later cells append behind it."""
    section = "end_to_end" if name == "batch_tokens_per_s" else "per_layer"
    cells = bench._entry(section, name)["workloads"]
    assert cells.count(CELL) == 1
    assert cells.index(CELL) == cells.index("olmoe-serve-gen-batch") + 1


# ---- the pins that outgrew the file, asserted by name --------------------- #
MOE_METRICS = [
    ("kernel.moe_experts_roofline", "%", "higher", "device_trace", "kernels"),
    ("kernel.moe_experts_share_pct", "%", "lower", "device_trace",
     "kernels"),
    ("moe.route_share_pct", "%", "lower", "device_trace", "experts"),
    ("moe.load_max_over_mean", "ratio", "lower", "program_counter",
     "experts")]


@pytest.mark.parametrize("want", MOE_METRICS, ids=[m[0] for m in MOE_METRICS])
def test_the_four_expert_layer_metrics_found_by_name(bench, want):
    """What ``test_benchmark_olmoe.py``'s test of the four holds of each
    entry, with the entry found by name: the OLMoE cell heads its list
    (later cells append) and this cell, whose experts take the grouped
    form, is on none."""
    from benchmark import opsbytes_moe
    m = bench._entry("per_layer", want[0])
    assert (m["name"], m["unit"], m["better"], m["source"], m["layer"]) \
        == want
    assert m["moves"] == "batch_tokens_per_s" \
        and m["workloads"][0] == "olmoe-serve-gen-batch" \
        and CELL not in m["workloads"]
    assert callable(bench.reader(m["name"]).read)
    assert want[0] in opsbytes_moe.READERS
    assert want[0] not in {x["name"] for x in bench.cell(CELL)["per_layer"]}


def test_what_the_four_outgrown_pins_still_hold(bench):
    """What four position pins held before PR 44 rewrote them to what they
    mean: two pinned the list of configurations and the number of
    per-layer entries, two more that OLMoE's four metrics are the LAST
    entries and that there are 27 + 4.  Everything else they assert, on
    the file as it stands."""
    from benchmark import opsbytes_moe
    names = [m["name"] for m in bench.doc["per_layer"]]
    assert [c["name"] for c in bench.doc["configs"]][:3] \
        == ["opt-1.3b", "opt-6.7b-l8", "olmoe-1b-7b-l8"]
    assert [w["name"] for w in bench.doc["workloads"] if w["chips"] == 4] \
        == ["opt67b-zero3-4chip"]
    assert names[15] == "frontend.submit_wait_p50_ms"
    assert names[27:31] == list(opsbytes_moe.READERS)
    assert len(names) == len(set(names)) and len(names) >= 31
    chat = {m["name"] for m in bench.cell("opt13b-serve-chat")["per_layer"]}
    assert {"frontend.lock_wait_p50_ms", "sched.first_token_lag_p50_ms",
            "sched.host_ms_per_iter.chat", "setup.trace_lower_s"} <= chat
    assert "sched.host_ms_per_iter.batch" not in chat
    for cell in ("opt13b-sft-1chip", "opt67b-zero3-4chip"):
        got = {m["name"] for m in bench.cell(cell)["per_layer"]}
        assert {"kernel.flash_fwd_ms_per_step",
                "kernel.flash_bwd_ms_per_step",
                "setup.backend_compile_s"} <= got


# ---- (f) the controls read above a toy limit ------------------------------ #
def _toy_family(bench):
    """A family instance of its own whose weights have, at hidden 64, the
    per-feature magnitudes of the real configuration."""
    fam = bench.family("dots3")
    fam._W, fam._OUT, fam._ATTN, fam._DOWN, fam._EMBED = \
        0.12, 0.12, 0.2, 2.0, 1.0
    return fam, fam.sizes_of(TOY)


@pytest.fixture(scope="module")
def control_logits(bench):
    fam, z = _toy_family(bench)
    toks = np.random.default_rng(11).integers(0, 256, 64)
    return {p: np.asarray(fam.logits(z, 4, toks, p))
            for p in ("float32", "bfloat16", "float8", "float8_experts",
                      "float8_latent", "recent_topk", "held_dropped")}


@pytest.mark.parametrize("control", ["float8", "float8_experts",
                                     "float8_latent", "recent_topk",
                                     "held_dropped"])
def test_control_reads_above_the_toy_limit(control_logits, control):
    """Each control — the whole model in float8, the experts' matmuls
    alone, the cached rows alone, the kept set replaced by the most recent
    positions, the held experts' part left out — is bfloat16 but for ONE
    thing, and that thing is visible:
    its logits leave the bfloat16 computation's by more than the toy's
    limit (2% of a logit's size; bfloat16 against itself reads 0), and it
    lies no nearer float32 than bfloat16 does."""
    rms = lambda a, b: float(np.sqrt(np.mean((a - b) ** 2)))
    lg = control_logits
    scale = float(np.abs(lg["float32"]).mean())
    assert scale > 0.3
    assert rms(lg[control], lg["bfloat16"]) > 0.02 * scale
    assert rms(lg[control], lg["float32"]) \
        > 0.9 * rms(lg["bfloat16"], lg["float32"])


def test_the_indexer_follows_the_logit_the_heads_share(bench):
    """The distilled draw (``families/dots3.py::_distilled``): a full
    layer's index scores rank a query's keys as the heads' MEAN attention
    logit ranks them, and every query's head weights sum positive — so the
    keys at the indexer's threshold are keys the heads do not attend.  With
    every tensor drawn on its own (the tie taken out) the two rankings
    have nothing in common.  Rank correlation over the last queries of 96
    positions, over the keys a score tells apart: > 0.45 tied (0.57 at this
    toy's 4 heads of 8 followed + 8 unfollowed nope columns; the heads' own
    share averages out over 128 real heads), under 0.2 not."""
    import jax.numpy as jnp
    fam, z = _toy_family(bench)
    fam._EMBED_MEAN = 0.75
    a, key = dict(z["full"]), fam.seed_key(3)
    toks = np.random.default_rng(3).integers(0, 256, 96)
    H, J, D, S = a["heads"], a["index_heads"], a["index_dim"], 96

    def rankings(w):
        f = lambda n: w[n].astype(jnp.float32)
        x = fam._rms_norm(f32(fam.global_weights(z, key)["embed"])[toks],
                          w["ln1_g"], z["eps"])
        up = lambda rank: np.sqrt(z["h"] / rank)
        c_q = fam._rms_norm(x @ f("q_a"), w["q_a_norm"], z["eps"]) \
            * up(a["q_rank"])
        kv = x @ f("kv_a")
        c_kv = fam._rms_norm(kv[:, :a["kv_rank"]], w["kv_a_norm"],
                             z["eps"]) * up(a["kv_rank"])
        k_r = fam._rope(kv[:, a["kv_rank"]:], a["theta"])
        q = (c_q @ f("q_b")).reshape(S, H, -1)
        q_r = fam._rope(q[..., a["nope"]:], a["theta"])
        k_n = (c_kv @ f("kv_b")).reshape(S, H, -1)[..., :a["nope"]]
        logit = jnp.einsum("qhd,shd->qs", q[..., :a["nope"]], k_n) / H \
            + jnp.einsum("qhd,sd->qs", q_r, k_r) / H
        qi = fam._rope((c_q @ f("index_q")).reshape(S, J, D), a["theta"],
                       a["rope"])
        ki = fam._rope(fam._layer_norm(
            x @ f("index_k"), w["index_k_norm_scale"],
            w["index_k_norm_bias"], z["eps"]), a["theta"], a["rope"])
        wi = x @ f("index_w")
        score = jnp.einsum("qjs,qj->qs", jnp.maximum(
            jnp.einsum("qjd,sd->qjs", qi, ki), 0.0), wi)
        return np.asarray(logit), np.asarray(score), np.asarray(wi)

    def rank_correlation(logit, score):
        """Over the keys a query's score tells apart (relu leaves the
        lower half of the common logit tied at 0)."""
        rank = lambda v: np.argsort(np.argsort(v))
        out = []
        for t in range(64, 96):
            on = np.nonzero(score[t, :t + 1] != 0)[0]
            out.append(np.corrcoef(rank(logit[t, on]),
                                   rank(score[t, on]))[0, 1])
        return np.mean(out)

    f32 = lambda t: t.astype(jnp.float32)
    logit, score, wi = rankings(fam.layer_weights(z, key, 0))
    assert (wi.sum(axis=1) > 0).all()
    assert rank_correlation(logit, score) > 0.45
    tied, fam._distilled = fam._distilled, lambda z, a, w, mean: w
    try:
        logit, score, _ = rankings(fam.layer_weights(z, key, 0))
    finally:
        fam._distilled = tied
    assert abs(rank_correlation(logit, score)) < 0.2


def test_controls_split_the_precision_by_part(bench):
    fam, z = _toy_family(bench)
    assert fam._parts("float8") \
        == ("float8", "float8", "float8", True, True)
    assert fam._parts("float8_experts") \
        == ("bfloat16", "float8", "bfloat16", True, True)
    assert fam._parts("float8_latent") \
        == ("bfloat16", "bfloat16", "float8", True, True)
    assert fam._parts("recent_topk") \
        == ("bfloat16", "bfloat16", "bfloat16", False, True)
    assert fam._parts("held_dropped") \
        == ("bfloat16", "bfloat16", "bfloat16", True, False)
    toks = np.random.default_rng(9).integers(0, 256, 60)
    gap = lambda chooser: fam.chosen_gaps(z, 4, toks, 20, 40, 128, chooser)
    assert not gap("float32").any()
    assert gap("float8").shape == (40,) and gap("float8").mean() > 1e-5
    assert (gap("recent_topk") >= 0).all()
    nll = np.asarray(fam.nll_at(z, 4, toks[None], np.arange(8)[None]))
    assert nll.shape == (1, 8) and (nll > 0).all()
    out = fam.greedy(z, 4, toks[:20], 3, 64, "bfloat16")
    assert len(out) == 23 and (out[:20] == toks[:20]).all()
    with pytest.raises(ValueError):
        fam.chosen_gaps(z, 4, np.zeros(200, np.int32), 20, 65, 256)


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn"}), ("scoring_func", "softmax"),
    ("tie_word_embeddings", True), ("n_routed_experts", 4)])
def test_sizes_of_refuses_what_the_reference_lacks(bench, key, value):
    with pytest.raises(ValueError):
        bench.family("dots3").sizes_of({**TOY, key: value})


# ---- operations and bytes -------------------------------------------------- #
def test_needed_operations_and_bytes():
    # a query against one key: 64 heads of 128, 2 a multiply-add
    assert ob.index_flops(1, 64, 128) == 16384
    assert ob.index_bytes(16384, 128) == 16384 * 256
    # a kept pair in a full layer: 128 heads of 192 + 128
    assert ob.attention_flops(1, 128, 192, 128) == 2 * 128 * 320
    assert ob.latent_bytes(2048, 576) == 2048 * 1152
    # all 32 held experts of a layer: 1.51 GB
    assert ob.grouped_bytes(32, 5120, 1536) == 32 * 47_185_920
    assert ob.grouped_flops(2048, 5120, 1536) == 2048 * 47_185_920


# ---- the readers ----------------------------------------------------------- #
def _kernel(name):
    return (f"%{name} = (bf16[64,2048]{{1,0}}) custom-call(s32[64]{{0}} "
            f'%p), custom_call_target="tpu_custom_call"')


def _span(name, **stats):
    return {"name": name, "start_s": 0.0, "dur_s": 0.1, "thread": (0, 0),
            "stats": stats}


def _run(bench, tr, cell=CELL, family="dots3"):
    return types.SimpleNamespace(
        trace=tr, observed={}, slice_t0=None, slice_s=None,
        cell=bench.cell(cell), family=bench.family(family),
        peaks=bench.peaks("tpu v5e"))


def _dots3_trace():
    # a 100 ms slice: two chunks' kernels (2 full layers, 3 window layers,
    # 4 expert layers each) and 41 ms of everything else
    ev, t = [], 0.0
    for name, dur, n in (("attn.dsa_index", 0.003, 4),
                         ("attn.dsa_topk", 0.001, 4),
                         ("attn.mla_chunk_prefill", 0.006, 4),
                         ("attn.mla_window", 0.001, 6),
                         ("moe.experts_grouped", 0.002, 8),
                         ("moe.experts_gmm", 0.001, 1)):
        for i in range(n):
            ev.append((D0, OPS, _kernel(f"{name}.{i}"), t, dur))
            t += dur
    ev.append((D0, OPS, "%fusion.1 = bf16[2,2048]{1,0} fusion(bf16[2]{0} %x)",
               t, 0.1 - t))
    # one decode block of 12 ms, the expert kernel's 1 ms inside it
    ev.append((D0, trace.MODULES_LINE, "jit_decode_block(123)", 0.088, 0.012))
    return trace.Trace(ev)


def _dots3_spans():
    chunk = dict(dsa_keys_scored=2 * 2048 * 9216, dsa_keys_kept=2 * 2048 * 2048,
                 latent_rows_read=2 * 10240, window_pages=27,
                 window_keys=3 * 2048 * 513)
    return [_span(ob.CHUNK, **chunk), _span(ob.CHUNK, **chunk),
            _span(ob.DECODE, dsa_keys_scored=2 * 8 * 12000,
                  dsa_keys_kept=2 * 8 * 2048, latent_rows_read=2 * 8 * 2048,
                  window_pages=27, window_keys=3 * 8 * 513),
            _span(ob.ADMIT_WAIT, event="admit", moe_assignments=4096,
                  moe_assignments_elsewhere=28672, moe_experts_touched=256,
                  moe_max_expert_tokens=90, moe_calls=8),
            _span("dstpu.sched.commit", tokens=8, moe_assignments=64,
                  moe_assignments_elsewhere=448, moe_experts_touched=200,
                  moe_max_expert_tokens=40, moe_calls=32),
            _span("dstpu.sched.commit", tokens=3)]


def test_readers_on_known_events(bench, monkeypatch):
    monkeypatch.setattr(spans, "host_spans", lambda *a: _dots3_spans())
    run = _run(bench, _dots3_trace())
    read = lambda m: bench.reader(m).read(run)
    assert read("attn.latent_share_pct") == pytest.approx(30.0)
    assert read("attn.dsa_select_share_pct") == pytest.approx(16.0)
    assert read("kernel.moe_grouped_share_pct") == pytest.approx(16.0)
    scored = 2 * 2 * 2048 * 9216 + 2 * 8 * 12000
    kept = 2 * 2 * 2048 * 2048 + 2 * 8 * 2048
    assert read("dsa.kept_over_scored") == pytest.approx(kept / scored)
    assert read("kernel.moe_gmm_share_pct") == pytest.approx(1.0)
    assert read("step.decode_share_pct") == pytest.approx(12.0)
    # busiest 90 + 40 of 4,160 held assignments over 32 held experts
    assert read("moe.held_load_max_over_mean") == pytest.approx(
        130 * 32 / 4160)
    # a call scores 2048 x 9216 pairs: 309 GFLOP = 1.57 ms; it took 3 ms
    assert read("kernel.dsa_index_roofline") == pytest.approx(
        100 * 2048 * 9216 * 16384 / 197e12 / 0.003)
    # and the top-k reads their float32 scores once: 75 MB = 92 us of 1 ms
    assert read("kernel.dsa_topk_roofline") == pytest.approx(
        100 * 2048 * 9216 * 4 / 819e9 / 0.001)
    # a call attends 2048 x 2048 kept pairs x 128 heads x 320 x 2
    assert read("kernel.mla_chunk_prefill_roofline") == pytest.approx(
        100 * 2048 * 2048 * 81920 / 197e12 / 0.006)
    assert read("kernel.mla_window_roofline") == pytest.approx(
        100 * 2048 * 513 * 2 * 64 * 384 / 197e12 / 0.001)
    # a call reads 32 held experts = 1.51 GB = 1.84 ms; it took 2 ms
    assert read("kernel.moe_grouped_roofline") == pytest.approx(
        100 * 32 * 47_185_920 / 819e9 / 0.002)
    assert ob.span_sums(ob.CHUNK, ("window_keys",)) == {
        "window_keys": 2 * 3 * 2048 * 513, "spans": 2}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_reader_finds_nothing_on_a_program_without_it(bench, monkeypatch,
                                                      name):
    """A parent commit, another model: no such kernel, no such span arg —
    the reader returns None and does not raise."""
    other = trace.Trace([
        (D0, OPS, _kernel("attn.paged_decode.54"), 0.0, 6.0),
        (D0, OPS, _kernel("moe.experts_gmm.7"), 6.0, 2.0)])
    hosts = ([], [_span("dstpu.sched.commit", tokens=3),
                  _span(ob.CHUNK, kv_pages=40, kv_pages_table=290),
                  _span(ob.ADMIT_WAIT, moe_assignments=9,
                        moe_experts_touched=4, moe_calls=2)])
    for host in hosts:
        monkeypatch.setattr(spans, "host_spans", lambda *a, h=host: h)
        for tr in (other, None):
            assert bench.reader(name).read(_run(bench, tr)) is None
    # the kernels in the trace, but spans that carry nothing: a share can
    # be read, a roofline or a counter cannot
    monkeypatch.setattr(spans, "host_spans", lambda *a: [])
    value = bench.reader(name).read(_run(bench, _dots3_trace()))
    assert (value is not None) == name.endswith("share_pct")
