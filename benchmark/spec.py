"""Loaders for the benchmark's data files, found by the names in
``BENCHMARK.json``.  Nothing here imports JAX: the tests and the load
generator's process use it too.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
The harness resolves, by name and with no central table:

* ``benchmark/configs/<config>.json``      the sizes as run, with the family
                                           whose plain reference sits in
                                           ``benchmark/families/<family>.py``
* ``benchmark/workloads/<cell>.json``      how the system is set up for the
                                           cell, and the record of what
                                           defined it (sweeps, compiles)
* ``benchmark/traffic/<traffic>.json``     the mix's parameters, with the
                                           ``kind`` whose one general driver
                                           is ``benchmark/drivers/<kind>.py``
* ``benchmark/layer_metrics/<metric>.py``  one reader per per-layer metric

so a later PR adds a model, a cell, a mix or a metric as new files plus
appended entries, and edits nothing that exists.
"""

import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# a width may never be reduced (the contract's list)
WIDTH_RE = re.compile(
    r"(hidden|intermediate|latent|state|proj\w*|ffn\w*)_size|(_dim|_rank)$|"
    r"head_size|expansion|experts_per_tok")


def repo_root(start=None):
    """The directory that holds ``BENCHMARK.json`` — the benchmark never
    assumes ``/root/repo``."""
    here = os.path.abspath(start or os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if not os.path.isfile(os.path.join(here, "BENCHMARK.json")):
        raise FileNotFoundError(f"no BENCHMARK.json in {here}")
    return here


def _load_json(path):
    with open(path) as f:
        return json.load(f)


class Benchmark:
    """``BENCHMARK.json`` plus the files it names, all read lazily."""

    def __init__(self, root=None):
        self.root = repo_root(root)
        self.doc = _load_json(os.path.join(self.root, "BENCHMARK.json"))
        self.dir = os.path.join(self.root, self.doc["paths"][0])

    def _entry(self, section, name):
        for e in self.doc[section]:
            if e["name"] == name:
                return e
        known = [e["name"] for e in self.doc[section]]
        raise KeyError(f"{section} has no {name!r}; known: {known}")

    def cell(self, name):
        """Everything one run needs, as one dict of plain data."""
        w = self._entry("workloads", name)
        c = self._entry("configs", w["config"])
        config = _load_json(os.path.join(self.root, c["file"]))
        system = _load_json(os.path.join(self.dir, "workloads",
                                         name + ".json"))
        traffic = _load_json(os.path.join(self.dir, "traffic",
                                          w["traffic"] + ".json"))
        return {"name": name, "chips": w["chips"], "why": w["why"],
                "config_name": w["config"], "config": config,
                "traffic_name": w["traffic"], "traffic": traffic,
                "system": system,
                "end_to_end": [m for m in self.doc["end_to_end"]
                               if name in m.get("workloads", [name])],
                "per_layer": [m for m in self.doc["per_layer"]
                              if name in m.get("workloads", [name])]}

    # -- code found by file name ------------------------------------- #
    def _module(self, sub, name):
        path = os.path.join(self.dir, sub, name + ".py")
        if not os.path.isfile(path):
            raise FileNotFoundError(
                f"{sub} {name!r} needs the file {os.path.relpath(path, self.root)}")
        spec = importlib.util.spec_from_file_location(
            "_bench_" + re.sub(r"\W", "_", f"{sub}_{name}"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def driver(self, kind):
        return self._module("drivers", kind)

    def family(self, family):
        return self._module("families", family)

    def reader(self, metric):
        return self._module("layer_metrics", metric)

    def peaks(self, device_kind):
        """Published peaks of one chip; a device not in the table is an
        error, never a default."""
        table = _load_json(os.path.join(self.dir, "peaks.json"))
        for kind, row in table["devices"].items():
            if device_kind.lower().startswith(kind):
                return row
        raise KeyError(
            f"no published peak for device kind {device_kind!r} in "
            f"benchmark/peaks.json — add it with its source")


# --------------------------------------------------------------------- #
def validate(bench):
    """The contract's static rules that a unit test can hold the file to.
    Returns a list of faults (empty = valid)."""
    d, faults = bench.doc, []

    def bad(msg):
        faults.append(msg)

    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(d) != want:
        bad(f"top-level keys {sorted(d)} != {sorted(want)}")
        return faults
    if not (isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51):
        bad(f"run_seconds {d['run_seconds']!r} not a whole number in 1..51")
    if not 1 <= len(d["paths"]) <= 16:
        bad("paths: 1 to 16 directories")
    for p in d["paths"]:
        if p.startswith("/") or ".." in p.split("/") or \
                not re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p):
            bad(f"path {p!r}")
    under = lambda f: any(f == p or f.startswith(p + "/") for p in d["paths"])
    for word in d["command"]:
        if word.startswith("/") or ".." in word.split("/"):
            bad(f"command word {word!r} leads out of the repo")
        if os.path.exists(os.path.join(bench.root, word)) and not under(word):
            bad(f"command names {word!r}, a file outside paths")

    def names(section):
        seen = set()
        for e in d[section]:
            if not NAME_RE.match(e.get("name", "")):
                bad(f"{section}: name {e.get('name')!r}")
            if e.get("name") in seen:
                bad(f"{section}: duplicate name {e['name']!r}")
            seen.add(e.get("name"))
        return seen

    configs, cells = names("configs"), names("workloads")
    e2e, layer = names("end_to_end"), names("per_layer")
    if e2e & layer:
        bad(f"metric names in both sections: {sorted(e2e & layer)}")
    files = set()
    for c in d["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        if not under(c["file"]) or c["file"] in files:
            bad(f"config {c['name']}: file {c['file']!r}")
        files.add(c["file"])
        if len(c["reduced"]) > 16:
            bad(f"config {c['name']}: more than 16 reduced keys")
        for k in c["reduced"]:
            if not NAME_RE.match(k) or WIDTH_RE.search(k):
                bad(f"config {c['name']}: reduced names {k!r}")
        if not any(w["config"] == c["name"] for w in d["workloads"]):
            bad(f"config {c['name']} is used by no cell")
    pairs = set()
    for w in d["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad(f"cell {w.get('name')}: keys {sorted(w)}")
            continue
        if w["config"] not in configs:
            bad(f"cell {w['name']}: unknown config {w['config']!r}")
        if not NAME_RE.match(w["traffic"]):
            bad(f"cell {w['name']}: traffic {w['traffic']!r}")
        if w["chips"] not in (1, 4):
            bad(f"cell {w['name']}: chips {w['chips']!r}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad(f"cell {w['name']}: why is {len(w['why'])} characters")
        if (w["config"], w["traffic"]) in pairs:
            bad(f"cell {w['name']}: config and traffic pair repeats")
        pairs.add((w["config"], w["traffic"]))
    four = sum(w.get("chips") == 4 for w in d["workloads"])
    if four > max(1, len(d["workloads"]) // 4):
        bad(f"{four} four-chip cells of {len(d['workloads'])}")

    def metric_rules(m, section):
        keys = {"name", "unit", "better", "source"} | (
            {"bound"} if section == "end_to_end" else {"layer", "moves"})
        if set(m) - {"workloads"} != keys:
            bad(f"{section} {m.get('name')}: keys {sorted(m)}")
            return False
        if not UNIT_RE.match(m["unit"]):
            bad(f"{m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad(f"{m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            bad(f"{m['name']}: source {m['source']!r}")
        for c in m.get("workloads", []):
            if c not in cells:
                bad(f"{m['name']}: unknown cell {c!r}")
        return True

    for m in d["end_to_end"]:
        if not metric_rules(m, "end_to_end"):
            continue
        if m["source"] not in ("host_clock", "device_trace"):
            bad(f"{m['name']}: an end-to-end metric is host_clock or device_trace")
        if not 0 < m["bound"] <= 0.1:
            bad(f"{m['name']}: bound {m['bound']!r}")
    if "setup_s" not in e2e:
        bad("no setup_s among end_to_end")
    reports = {c: {m["name"] for m in d["end_to_end"]
                   if c in m.get("workloads", cells)} for c in cells}
    for m in d["per_layer"]:
        if not metric_rules(m, "per_layer"):
            continue
        if m["moves"] not in e2e:
            bad(f"{m['name']}: moves {m['moves']!r}, no end-to-end metric")
        if not 1 <= len(m["layer"]) <= 200 or "\n" in m["layer"]:
            bad(f"{m['name']}: layer {m['layer']!r}")
        for c in m.get("workloads", [c for c in cells
                                     if m["moves"] in reports[c]]):
            if m["moves"] not in reports.get(c, ()):
                bad(f"{m['name']}: cell {c} does not report {m['moves']}")
    for c in cells:
        if "setup_s" not in reports[c] or len(reports[c]) < 2:
            bad(f"cell {c}: needs setup_s and one more end-to-end metric")
        if not any(c in m.get("workloads", [c]) for m in d["per_layer"]):
            bad(f"cell {c}: reports no per-layer metric")
    return faults


def check_files(bench):
    """Every name in ``BENCHMARK.json`` resolves to its files."""
    faults = []
    for w in bench.doc["workloads"]:
        try:
            cell = bench.cell(w["name"])
            bench.driver(cell["traffic"]["kind"])
            bench.family(cell["config"]["family"])
        except (OSError, KeyError, ValueError) as e:
            faults.append(f"cell {w['name']}: {e}")
    for m in bench.doc["per_layer"]:
        if not os.path.isfile(os.path.join(
                bench.dir, "layer_metrics", m["name"] + ".py")):
            faults.append(f"per-layer metric {m['name']}: no reader file")
    for c in bench.doc["configs"]:
        cfg = _load_json(os.path.join(bench.root, c["file"]))
        src = cfg.get("source_config", {})
        for k, v in src.items():
            if cfg.get(k) != v and k not in c["reduced"]:
                faults.append(f"config {c['name']}: {k} differs from the "
                              f"source and is not in reduced")
    return faults
