"""BENCHMARK.json and the files it names: the contract's static rules, and
the proof that a later PR adds a configuration, a cell, a traffic kind and a
per-layer metric as NEW files plus appended entries, editing nothing."""

import copy
import json
import os
import shutil

import pytest

from benchmark import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def bench():
    return spec.Benchmark(ROOT)


def test_benchmark_json_is_valid(bench):
    assert spec.validate(bench) == []


def test_every_name_resolves_to_its_files(bench):
    assert spec.check_files(bench) == []


def test_two_configurations_and_one_four_chip_cell(bench):
    """The first two configurations, in their order (later PRs append), and
    the one cell that needs four chips."""
    assert [c["name"] for c in bench.doc["configs"]][:2] == \
        ["opt-1.3b", "opt-6.7b-l8"]
    four = [w["name"] for w in bench.doc["workloads"] if w["chips"] == 4]
    assert four == ["opt67b-zero3-4chip"]


def test_no_width_is_reduced(bench):
    for c in bench.doc["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        changed = {k for k, v in cfg["source_config"].items()
                   if cfg[k] != v}
        assert changed == set(c["reduced"])
        assert not any(spec.WIDTH_RE.search(k) for k in changed)


def test_each_cell_reports_what_its_layer_metrics_move(bench):
    for w in bench.doc["workloads"]:
        cell = bench.cell(w["name"])
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"], w["name"]
        for m in cell["per_layer"]:
            assert m["moves"] in reported, (w["name"], m["name"])


def test_run_seconds_fits_the_full_check(bench):
    # (2 + 14 x cells) runs of run_seconds + 60 s, 2 x 90 s a cell to
    # compile, 1200 s spare, at the full 24 cells, inside 43200 s
    rs = bench.doc["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def _break(doc, what):
    d = copy.deepcopy(doc)
    if what == "name_with_space":
        d["workloads"][0]["name"] = "chat cell"
    elif what == "unit_with_space":
        d["end_to_end"][0]["unit"] = "tokens per second"
    elif what == "greek_unit":
        d["end_to_end"][0]["unit"] = "µs"
    elif what == "bound_too_wide":
        d["end_to_end"][0]["bound"] = 0.2
    elif what == "moves_nothing":
        d["per_layer"][0]["moves"] = "nothing"
    elif what == "moves_unreported":
        d["per_layer"][0]["workloads"] = ["opt13b-sft-1chip"]
    elif what == "width_reduced":
        d["configs"][1]["reduced"].append("hidden_size")
    elif what == "two_four_chip_cells":
        # one more than the quarter of the cells, rounded down, that may
        for w in d["workloads"][:max(1, len(d["workloads"]) // 4) + 1]:
            w["chips"] = 4
    elif what == "extra_key_on_metric":
        d["per_layer"][0]["why"] = "because"
    elif what == "no_setup_s":
        d["end_to_end"] = [m for m in d["end_to_end"]
                           if m["name"] != "setup_s"]
    elif what == "command_outside_paths":
        d["command"] = ["python3", "bench.py"]
    elif what == "long_why":
        d["workloads"][0]["why"] = "x" * 201
    elif what == "pair_repeats":
        d["workloads"][1] = dict(d["workloads"][0], name="again")
    return d


@pytest.mark.parametrize("what", [
    "name_with_space", "unit_with_space", "greek_unit", "bound_too_wide",
    "moves_nothing", "moves_unreported", "width_reduced",
    "two_four_chip_cells", "extra_key_on_metric", "no_setup_s",
    "command_outside_paths", "long_why", "pair_repeats"])
def test_validator_catches(bench, what):
    broken = copy.copy(bench)
    broken.doc = _break(bench.doc, what)
    assert spec.validate(broken), what


TOY_DRIVER = '''
def run(ctx):
    return {"attempted": 1, "failed": 0, "checks": [],
            "end_to_end": {"toy_per_s": 1.0}, "observed": {"n": 3}}
'''
TOY_READER = '''
def read(run):
    return float(run.observed["n"])
'''


def test_a_later_pr_adds_files_and_edits_none(tmp_path, bench):
    """Drop a toy configuration, cell, traffic kind and per-layer metric
    into a copy; every file that was there stays byte-identical, and the
    loader finds the new ones by name."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    b = root / "benchmark"
    toy_cfg = json.load(open(b / "configs" / "opt-1.3b.json"))
    toy_cfg.update(name="toy", num_hidden_layers=2)
    (b / "configs" / "toy.json").write_text(json.dumps(toy_cfg))
    (b / "workloads" / "toy-cell.json").write_text(json.dumps(
        {"correct": {}, "defined_by": {}}))
    (b / "traffic" / "toy-mix.json").write_text(json.dumps(
        {"kind": "toy_kind", "n": 3}))
    (b / "drivers" / "toy_kind.py").write_text(TOY_DRIVER)
    (b / "layer_metrics" / "toy.count.py").write_text(TOY_READER)
    doc = json.load(open(root / "BENCHMARK.json"))
    doc["configs"].append({
        "name": "toy", "source": "https://example.org/toy",
        "file": "benchmark/configs/toy.json",
        "reduced": ["num_hidden_layers"], "why": "a toy"})
    doc["workloads"].append({"name": "toy-cell", "config": "toy",
                             "traffic": "toy-mix", "chips": 1,
                             "why": "shows a cell is data"})
    doc["end_to_end"].append({"name": "toy_per_s", "unit": "1/s",
                              "better": "higher", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": ["toy-cell"]})
    doc["per_layer"].append({"name": "toy.count", "unit": "n",
                             "better": "higher", "source": "program_counter",
                             "layer": "toy layer", "moves": "toy_per_s",
                             "workloads": ["toy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    new = spec.Benchmark(str(root))
    assert spec.validate(new) == [] and spec.check_files(new) == []
    cell = new.cell("toy-cell")
    assert cell["config"]["num_hidden_layers"] == 2
    assert {m["name"] for m in cell["end_to_end"]} == {"toy_per_s", "setup_s"}
    result = new.driver(cell["traffic"]["kind"]).run(None)
    run = type("Run", (), {"observed": result["observed"]})
    assert new.reader("toy.count").read(run) == 3.0
    # the old cells are untouched by the additions
    assert [m["name"] for m in new.cell("opt13b-serve-chat")["per_layer"]] \
        == [m["name"] for m in bench.cell("opt13b-serve-chat")["per_layer"]]
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_unknown_device_has_no_peak(bench):
    assert bench.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert bench.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bench.peaks("cpu")
