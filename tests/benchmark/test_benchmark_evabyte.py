"""The EvaByte configuration, its cell, its family's controls, and the
readers of what it adds — on hand-made events with known answers, and on a
program that has no such span (a parent commit, another model's cell):
nothing to read, no error.  Nothing here pins HOW MANY configurations, cells
or per-layer entries ``BENCHMARK.json`` has, or which come last: entries are
found by name, and a list is held to the ORDER of the cells it had.
(``test_benchmark_scopes.py::test_the_nine_entries_are_appended_with_readers``
pinned ``per_layer[-9:]`` and is outgrown by this PR's entries — PERF.md §7
c2; what it held of each of the nine is held here, by name.)"""

import copy
import json
import os
import types

import numpy as np
import pytest

from benchmark import opsbytes_evabyte as ob, scopes, spans, spec, trace
from benchmark import trafficgen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONFIG = "evabyte-serve-bytedoc-batch", "evabyte-6.5b-l8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
D0, OPS = "/device:TPU:0", trace.OPS_LINE
NEW_METRICS = {
    "attn.eva_share_pct": ("%", "lower", "device_trace", "kernels"),
    "eva.summarise_share_pct": ("%", "lower", "device_trace", "kernels"),
    "kernel.eva_decode_roofline": ("%", "higher", "device_trace", "kernels"),
    "kernel.eva_chunk_roofline": ("%", "higher", "device_trace", "kernels"),
    "eva.remote_over_attended": ("ratio", "lower", "program_counter",
                                 "cache manager"),
    "cache.summary_share_pct": ("%", "lower", "program_counter",
                                "cache manager")}
# the lists this cell was appended to, each with the cells it had before,
# in the order it had them
BATCH = ["opt13b-serve-longprompt-batch", "olmoe-serve-gen-batch",
         "dots3-serve-longdoc-batch", "lfm2-serve-widegen-batch"]
EVERY = ["opt13b-serve-chat", "opt13b-sft-1chip", "opt67b-zero3-4chip"] \
    + BATCH
SHARED = {
    "batch_tokens_per_s": BATCH, "sched.occupancy_pct": BATCH,
    "step.decode_block_ms.batch": BATCH, "step.prefill_chunk_ms": BATCH,
    "device.idle_pct.batch": BATCH, "sched.host_ms_per_iter.batch": BATCH,
    "scope.unattributed_pct.batch": BATCH,
    "setup.trace_lower_s": EVERY, "setup.backend_compile_s": EVERY}
# what the outgrown tail pin held of PR 35's nine entries
NINE = {
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
    "attn.mla_decompress_share_pct": ("kernels", "batch_tokens_per_s")}
TOY = dict(
    model_type="evabyte", attention_class="eva", attention_bias=False,
    chunk_size=4, window_size=32, hidden_size=32, intermediate_size=64,
    num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=3,
    num_pred_heads=8, vocab_size=64, max_position_embeddings=512,
    rms_norm_eps=1e-5, rope_theta=100000, rope_scaling=None,
    tie_word_embeddings=False, norm_add_unit_offset=True, fp32_skip_add=True,
    fp32_logits=True, hidden_act="silu")


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
    if key == "num_hidden_layers":
        assert (cfg[key], cfg["source_config"][key]) == (8, 32)
    else:
        assert cfg[key] == cfg["source_config"][key]


def test_source_config_is_the_catalogs_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not in this environment")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    assert cfg["source_config"] == row["config"]


def test_the_cut_is_the_issues(bench):
    cfg = _config()
    entry = bench._entry("configs", CONFIG)
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert cfg["family"] == "evabyte" and cfg["precision"] == "bfloat16"
    # every width as published: nothing but the depth differs
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["intermediate_size"], cfg["vocab_size"], cfg["chunk_size"],
            cfg["window_size"], cfg["num_pred_heads"]) \
        == (4096, 32, 11008, 320, 16, 2048, 8)
    for reading in ("shared_pooling_weights", "mu_after_pooling",
                    "rope_before_pooling", "block_windows",
                    "summaries_visible_from_next_window",
                    "head_0_is_next_byte", "head_as_one_matrix",
                    "bfloat16_weights"):
        assert len(cfg["assumed"][reading]) > 40
    for word in ("four pipeline stages of eight layers", "STAGE ONE",
                 "v5e host", "1,630,932,992"):
        assert word in cfg["deployment"]


def test_parameters_are_counted_from_the_shapes(bench):
    cfg = _config()
    fam = bench.family("evabyte")
    parts = fam.parameters_by_part(fam.sizes_of(cfg))
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 32 * 128 + 2 * 4096
    assert parts["a_layer"] == layer == 202_391_552
    total = sum(v for k, v in parts.items() if k != "a_layer")
    assert total == cfg["parameters"] == 1_630_932_992
    assert parts == cfg["parameters_by_part"]
    assert cfg["parameters_published_depth"] == total + 24 * layer
    # ... and from the program's own tree
    import jax
    import jax.numpy as jnp
    module = fam.program_model(cfg)
    tree = jax.eval_shape(module.init, jax.random.key(0),
                          {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    assert sum(x.size for x in jax.tree.leaves(tree)) == total
    # the cache: a ring row is K and V of 4096 bf16, a summary row a 16th
    assert cfg["kv_ring_bytes_per_slot_layer"] == 2048 * 16384
    assert cfg["summary_bytes_per_position_layer"] == 16384 // 16


def test_benchmark_file_is_valid_with_the_new_entries(bench):
    assert spec.validate(bench) == []
    assert spec.check_files(bench) == []


@pytest.mark.parametrize("over,refused", [(0, False), (1, True)])
def test_the_validator_counts_four_chip_cells_against_a_quarter(bench, over,
                                                                refused):
    """``test_benchmark_spec.py::test_validator_catches[two_four_chip_cells]``
    makes ONE more cell four-chip and expects a refusal: with this PR's
    eighth cell a quarter of the cells is two, so its broken file is a valid
    one (PERF.md §7 c2).  What it meant, at whatever count the file has: a
    quarter of the cells, rounded down, may ask for four chips — and one
    more may not."""
    doc = copy.deepcopy(bench.doc)
    allowed = max(1, len(doc["workloads"]) // 4)
    one_chip = [w for w in doc["workloads"] if w["chips"] == 1]
    have = len(doc["workloads"]) - len(one_chip)
    for w in one_chip[:allowed + over - have]:
        w["chips"] = 4
    broken = copy.copy(bench)
    broken.doc = doc
    assert any("four-chip cells" in e
               for e in spec.validate(broken)) == refused


def test_cell_is_the_issues(bench):
    cell = bench.cell(CELL)
    assert (cell["config_name"], cell["traffic_name"], cell["chips"]) \
        == (CONFIG, "bytedoc-closed36", 1)
    assert len(cell["why"]) <= 200
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"batch_tokens_per_s", "setup_s"}
    serving = cell["system"]["serving"]
    assert (serving["num_slots"], serving["max_cache_len"],
            serving["page_size"], serving["decode_block"]) \
        == (24, 13376, 64, 8)
    assert serving["prefill_chunk"] in (512, 1024, 2048)
    assert serving["prefill_token_budget"] in (2048, 4096, 8192)
    correct = cell["system"]["correct"]
    assert 0 < correct["mean_logit_gap"] < 0.03 \
        and correct["sample_requests"] >= 6
    got = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) | (set(SHARED) - {"batch_tokens_per_s"}) <= got


def test_traffic_is_the_issues(bench):
    """Issue 37's mix, but for ``ramp_s``, half a second later: PR 37 moved
    it when whole requests were credited to the window they completed in
    and one run in four lost a request at the edge (1.05%; the cell's
    ``defined_by.window_edges``).  Since PR 44 a request is credited where
    its tokens are produced and no edge needs siting; the value stays so
    that the traffic is the same."""
    mix = bench.cell(CELL)["traffic"]
    assert 16.0 <= mix["ramp_s"] <= 17.0
    assert {k: v for k, v in mix.items()
            if k not in ("describes", "ramp_s")} == {
        "kind": "closed_loop_engine", "callers": 36,
        "prompt_len": {"dist": "uniform", "min": 4096, "max": 12288},
        "output_len": {"dist": "uniform", "min": 256, "max": 1024},
        "cycle": 72, "base_seed": 37, "trace_slice_s": 3.0}
    serving = bench.cell(CELL)["system"]["serving"]
    sizes = trafficgen.sizes(mix, 72)
    for p, o in sizes:
        assert p + o <= serving["max_cache_len"]
        assert -(-p // serving["prefill_chunk"]) * serving["prefill_chunk"] \
            <= 14336                   # the lane in whole pages of rows
        assert p // 2048 >= 2          # every request reads summaries
    assert 7600 < np.mean([p for p, _ in sizes]) < 8800
    assert 560 < np.mean([o for _, o in sizes]) < 720
    fam = bench.family("evabyte")
    assert max(o for _, o in sizes) <= fam.GAP_ROWS
    a, b = (next(trafficgen.closed_loop_requests(mix, 320, s))
            for s in (3_000_000_037, 37))
    assert len(a[1]) == len(b[1]) and 256 < a[1].max() < 320
    assert (a[1][:64] != b[1][:64]).any()


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_is_an_entry_with_a_reader(bench, name):
    entry = bench._entry("per_layer", name)
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == NEW_METRICS[name]
    assert entry["moves"] == "batch_tokens_per_s" \
        and entry["workloads"] == [CELL]
    assert callable(bench.reader(name).read)
    for other in ("opt13b-serve-chat", "dots3-serve-longdoc-batch"):
        assert name not in {m["name"]
                            for m in bench.cell(other)["per_layer"]}


@pytest.mark.parametrize("name", sorted(SHARED))
def test_shared_metric_keeps_its_cells_in_order_then_this_cell(bench, name):
    section = "end_to_end" if name == "batch_tokens_per_s" else "per_layer"
    cells = bench._entry(section, name)["workloads"]
    assert len(cells) == len(set(cells))
    assert cells[:len(SHARED[name])] == SHARED[name]
    assert cells.index(CELL) == len(SHARED[name])


@pytest.mark.parametrize("name", sorted(NINE))
def test_what_the_outgrown_tail_pin_still_holds(bench, name):
    """PR 35's nine entries, each found by name where the pin read them off
    the tail: their order among themselves, layer, what they move, units,
    source, readers, and the three lists the pin spelt out."""
    names = [m["name"] for m in bench.doc["per_layer"]]
    assert names.count(name) == 1
    at = [names.index(n) for n in NINE]
    assert at == sorted(at) and at[-1] - at[0] == len(NINE) - 1
    m = bench._entry("per_layer", name)
    assert (m["layer"], m["moves"]) == NINE[name]
    assert m["better"] == "lower" and m["source"] == "device_trace"
    assert set(m["workloads"]) <= {w["name"] for w in bench.doc["workloads"]}
    assert m["unit"] == ("ms" if name.endswith("_per_step") else "%")
    assert callable(bench.reader(name).read)
    want = {"scope.optimizer_ms_per_step": ["opt13b-sft-1chip",
                                            "opt67b-zero3-4chip"],
            "conv.short_share_pct": ["lfm2-serve-widegen-batch"]}
    if name in want:
        assert m["workloads"] == want[name]
    if name == "scope.unattributed_pct.batch":
        assert "opt13b-serve-chat" not in m["workloads"]


# ---- the controls read above a sound run at toy size ---------------------- #
PRECISIONS = ("float32", "bfloat16", "float8", "summaries_dropped",
              "stale_ring", "mean_pooled")


@pytest.fixture(scope="module")
def control_logits(bench):
    """The family draws every matrix at a gain over sqrt(fan-in): the toy
    has the real size's statistics with no rescaling."""
    fam = bench.family("evabyte")
    z = fam.sizes_of(TOY)
    toks = np.random.default_rng(11).integers(0, 64, 120)
    return {p: np.asarray(fam.logits(z, 4, toks, p, heads=1))
            for p in PRECISIONS}


@pytest.mark.parametrize("control", PRECISIONS[2:])
def test_control_reads_above_a_sound_run(control_logits, control):
    """Past the first window (positions 32..119) every control moves the
    logits by several times what bfloat16 does — at toy size, where a
    summary stands for 4 positions and a window is 32."""
    ref = control_logits["float32"][32:]
    sound = np.abs(control_logits["bfloat16"][32:] - ref).mean()
    assert 0 < sound < 0.03
    assert np.abs(control_logits[control][32:] - ref).mean() > 3 * sound


@pytest.mark.parametrize("control", PRECISIONS[3:])
def test_a_mechanism_control_spares_the_first_window(control_logits, control):
    """No summary is visible and no window lies before: the three mechanism
    controls ARE the bfloat16 computation there."""
    first = slice(0, 32)
    assert np.array_equal(control_logits[control][first],
                          control_logits["bfloat16"][first])


def test_chooser_control_reads_the_generated_positions(bench):
    fam = bench.family("evabyte")
    z = fam.sizes_of(TOY)
    toks = np.random.default_rng(12).integers(0, 64, 100)
    out = fam.gaps_under(z, 4, toks, 70, 30, 128,
                         [None, "summaries_dropped", "float32"])
    assert all(g.shape == (30,) and (g >= 0).all() for g in out.values())
    assert out["float32"].max() == 0
    assert out["summaries_dropped"].mean() > 0
    with pytest.raises(ValueError, match="at most"):
        fam.gaps_under(z, 4, toks, 10, fam.GAP_ROWS + 1, 128, [None])


@pytest.mark.parametrize("key,value", [
    ("attention_class", "softmax"), ("tie_word_embeddings", True),
    ("fp32_skip_add", False), ("num_key_value_heads", 2)])
def test_sizes_of_refuses_what_the_reference_lacks(bench, key, value):
    with pytest.raises(ValueError):
        bench.family("evabyte").sizes_of({**TOY, key: value})


# ---- operations and bytes ------------------------------------------------- #
def test_opsbytes_count_from_the_shapes():
    assert ob.row_bytes(1, 4096) == 16384
    assert ob.row_bytes(1536, 4096) == 1536 * 16384
    assert ob.attention_flops(1, 32, 128) == 2 * 32 * 256
    assert ob.attention_flops(10, 4, 8) == 10 * 2 * 4 * 16


# ---- the readers on events with known answers ----------------------------- #
def _kernel(name):
    return (f'%{name} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %x), '
            f'custom_call_target="tpu_custom_call"')


def _span(name, **stats):
    return {"name": name, "start_s": 0.0, "dur_s": 0.1, "thread": (0, 0),
            "stats": stats}


def _run(bench, tr, cell=CELL, family="evabyte"):
    return types.SimpleNamespace(
        trace=tr, observed={}, slice_t0=None, slice_s=None,
        cell=bench.cell(cell), family=bench.family(family),
        peaks=bench.peaks("tpu v5e"))


def _eva_trace():
    # a 100 ms slice: one decode block = 8 steps x 8 layers of
    # attn.eva_decode at 0.5 ms, two chunks = 16 attn.eva_chunk at 1 ms
    ev, t = [], 0.0
    for name, dur, n in (("attn.eva_decode", 0.0005, 64),
                         ("attn.eva_chunk", 0.001, 16)):
        for i in range(n):
            ev.append((D0, OPS, _kernel(f"{name}.{i}"), t, dur))
            t += dur
    ev.append((D0, OPS, "%fusion.1 = bf16[2,2048]{1,0} fusion(bf16[2]{0} %x)",
               t, 0.1 - t))
    return trace.Trace(ev)


L = 8
_DECODE = dict(eva_ring_rows=L * 8 * 24 * 1000, eva_summary_rows=L * 8 * 24 * 500,
               eva_local_pairs=L * 8 * 24 * 1000,
               eva_remote_pairs=L * 8 * 24 * 500, eva_summaries_written=L * 12,
               ring_bytes_held=24 * L * 32 * 2 ** 20,
               summary_bytes_mapped=24 * L * 9 * 2 ** 20)
_CHUNK = dict(eva_ring_rows=L * 1536, eva_summary_rows=L * 384,
              eva_local_pairs=L * 512 * 1280, eva_remote_pairs=L * 512 * 384,
              eva_summaries_written=L * 32)


def _eva_spans():
    return [_span(ob.DECODE, live_slots=24, **_DECODE),
            _span(ob.CHUNK, **_CHUNK), _span(ob.CHUNK, **_CHUNK),
            _span("dstpu.sched.commit", tokens=3)]


def test_new_readers_on_known_events(bench, monkeypatch):
    monkeypatch.setattr(spans, "host_spans", lambda *a: _eva_spans())
    joined = {"parts": {("attn.eva", "fwd"): 0.048, ("mlp", "fwd"): 0.03,
                        ("eva.summarise", "fwd"): 0.002},
              "unattributed_s": 0.001}
    monkeypatch.setattr(scopes, "by_part", lambda run, modules: joined)
    run = _run(bench, _eva_trace())
    read = lambda m: bench.reader(m).read(run)
    assert read("attn.eva_share_pct") == pytest.approx(48.0)
    assert read("eva.summarise_share_pct") == pytest.approx(2.0)
    # a call reads 24 lanes x 1500 rows x 16,384 B = 590 MB = 0.72 ms
    # of the 0.5 ms... no: the made-up kernel is faster than the chip
    per_call = 24 * 1500 * 16384 / 819e9
    assert read("kernel.eva_decode_roofline") \
        == pytest.approx(100 * per_call / 0.0005)
    flops = 2 * 32 * 256 * 512 * (1280 + 384)
    assert read("kernel.eva_chunk_roofline") \
        == pytest.approx(100 * flops / 197e12 / 0.001)
    assert read("kernel.eva_chunk_roofline") < 100
    remote = L * (8 * 24 * 500 + 2 * 512 * 384)
    pairs = remote + L * (8 * 24 * 1000 + 2 * 512 * 1280)
    assert read("eva.remote_over_attended") == pytest.approx(remote / pairs)
    assert read("cache.summary_share_pct") == pytest.approx(100 * 9 / 41)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_reader_finds_nothing_on_a_program_without_it(bench, monkeypatch,
                                                      name):
    """A parent commit, another model: no such span arg, kernel or part —
    the reader returns None and does not raise."""
    other = trace.Trace([
        (D0, OPS, _kernel("attn.paged_decode.54"), 0.0, 6.0),
        (D0, OPS, _kernel("attn.paged_chunk_prefill.7"), 6.0, 2.0)])
    hosts = ([], [_span("dstpu.sched.commit", tokens=3),
                  _span(ob.DECODE, live_slots=3, kv_pages=40,
                        kv_pages_table=290),
                  _span(ob.CHUNK, kv_pages=16)])
    monkeypatch.setattr(scopes, "by_part", lambda run, modules: None)
    for host in hosts:
        monkeypatch.setattr(spans, "host_spans", lambda *a, h=host: h)
        for tr in (other, None):
            assert bench.reader(name).read(_run(bench, tr)) is None
