#!/usr/bin/env python3
"""A serving cell's ``correct`` number read under controls of the family's
own naming, request by request, on the chip: what ``calibrate.py`` reads
for ``"float8"``, for any list of the family's precisions (``calibrate.py``
names its one control in code, and a PR that is not a ``benchmark`` PR
edits no file the benchmark has; that PR should fold this into it).

    python3 benchmark/calibrate_controls.py --workload olmoe-serve-gen-batch --seeds 201,...,208 --choosers float8_experts,float8 --requests 32 --slots 8

Per seed the program serves ``--requests`` requests of the cell's traffic;
for each request the sum of the reference's logit gaps over its generated
tokens, once for the tokens served and once for each chooser (the
precision whose argmax stands in the served token's place — see
``families/opt.py::chosen_gaps``).  The rows go to
``chiprun_out/calibrate_controls.<workload>.json`` so that the mean over
any sample of requests can be formed afterwards; a line a seed gives the
means over all of them.
"""

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, serving, trafficgen  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--choosers", required=True)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=None,
                    help="as calibrate.py's: room for a second set of "
                         "weights while seeds are swapped")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    sides = [None] + args.choosers.split(",")
    ctx = harness.open_cell(ROOT, args.workload, seeds[0])
    model, mix = ctx.cell["config"], ctx.cell["traffic"]
    z = ctx.family.sizes_of(model)
    system = ctx.cell["system"]["serving"]
    if args.slots:
        system["num_slots"] = args.slots
    engine, srv = serving.build_server(ctx, tracing=False)
    rows = []
    try:
        for seed in seeds:
            engine.set_params(ctx.family.program_params(
                engine.module, model, seed))
            live = {}
            for i, (p, o) in enumerate(trafficgen.sizes(mix, args.requests)):
                prompt = trafficgen.prompt_tokens(z["vocab"], p, seed, i)
                live[srv.submit(prompt, max_new_tokens=o)] = len(prompt)
            for rid, tokens in srv.drain().items():
                n = len(tokens) - live[rid]
                rows.append({"seed": seed, "tokens": n, **{
                    side or "program": float(ctx.family.chosen_gaps(
                        z, seed, np.asarray(tokens), live[rid], n,
                        system["max_cache_len"], side).sum())
                    for side in sides}})
            mine = [r for r in rows if r["seed"] == seed]
            total = sum(r["tokens"] for r in mine)
            harness.say(seed=seed, requests=len(mine), tokens=total, **{
                side or "program": sum(r[side or "program"] for r in mine)
                / total for side in sides})
    finally:
        srv.close()
        out = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(
                out, f"calibrate_controls.{args.workload}.json"), "w") as f:
            json.dump({"workload": args.workload, "device": ctx.device,
                       "requests": rows}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
