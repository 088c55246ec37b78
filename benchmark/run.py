#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell by name from ``BENCHMARK.json`` and the files it names, builds
the system under test with weights from the seed, warms the cell's own
shapes, measures for ``--seconds``, decides ``correct`` against the plain
reference, and prints — as the LAST line of standard output — one JSON object
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"]}``.
Earlier lines are JSON objects too, one per phase and per check.  Without a
TPU, or with another number of chips than the cell's, it exits non-zero and
prints no result line.
"""

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402  (also starts setup_s's clock)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    ctx = harness.open_cell(ROOT, args.workload, args.seed, args.seconds,
                            bool(args.trace))
    bench, cell, device = ctx.bench, ctx.cell, ctx.device
    harness.say(phase="start", workload=cell["name"], seed=args.seed,
                seconds=args.seconds, trace=args.trace, device=device,
                compile_cache_dir=ctx.cache_dir)
    result = bench.driver(cell["traffic"]["kind"]).run(ctx)
    for check in result["checks"]:
        harness.say(**check)
    correct = all(c["ok"] for c in result["checks"]) and result["failed"] == 0

    values = dict(result["end_to_end"], setup_s=ctx.setup_s)
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"], "device": device}
    if args.trace:
        trace = ctx.profiler.reduce()
        run = types.SimpleNamespace(
            observed=result["observed"], trace=trace, cell=cell,
            peaks=ctx.peaks, family=ctx.family,
            slice_t0=ctx.profiler.t_started,
            slice_s=getattr(ctx.profiler, "traced_s", None))
        metrics = {}
        for m in cell["per_layer"]:
            value = bench.reader(m["name"]).read(run)
            if value is not None:       # nothing to read: left out
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        harness.say(phase="end_to_end_in_traced_run", **values)
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        line["breakdown"] = trace.breakdown()
    else:
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell["end_to_end"]}
    line["metrics"] = metrics
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
