#!/usr/bin/env python3
"""A serving cell's ``correct`` number read seed by seed, sound and under
the family's controls, on the chip, for a configuration whose weights fill
MORE THAN HALF the chip: ``calibrate.py`` and ``calibrate_controls.py`` draw
the next seed's weights beside the last seed's (two sets of 8.17 GB do not
fit 16 GB), this one releases the old set first.

    python3 benchmark/calibrate_seeds.py --workload dots3-serve-longdoc-batch --seeds 101,...,106 --control-seeds 101,102,103 --choosers float8_latent,float8_experts --more-seeds 101 --more-choosers float8,recent_topk,held_dropped

Per seed the program serves as many requests of the cell's traffic as the
cell's ``sample_requests`` (``--requests`` for another number; the first of
the mix's fixed sizes), and every reading is ``serving.check_outputs``' own
record — the number a run of the cell compares with its limit — for the
tokens served and, on a control seed, with each chooser in their place
(``families/opt.py::chosen_gaps``).  A line a reading; all of them in
``chiprun_out/calibrate_seeds.<workload>.json``.  (A ``benchmark`` PR
should fold the three calibration scripts into one.)
"""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, serving, trafficgen  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--choosers", default="")
    ap.add_argument("--more-seeds", default="",
                    help="seeds that also read --more-choosers")
    ap.add_argument("--more-choosers", default="")
    ap.add_argument("--requests", type=int, default=0)
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x.strip()]
    names = lambda s: [c for c in s.split(",") if c]
    seeds = ints(args.seeds)
    sides = {s: [None] for s in seeds}
    for at, choosers in ((args.control_seeds, args.choosers),
                         (args.more_seeds, args.more_choosers)):
        for s in ints(at):
            sides[s] += names(choosers)
    ctx = harness.open_cell(ROOT, args.workload, seeds[0])
    model, mix = ctx.cell["config"], ctx.cell["traffic"]
    limits = ctx.cell["system"]["correct"]
    limits["sample_requests"] = args.requests or limits["sample_requests"]
    vocab = ctx.family.sizes_of(model)["vocab"]
    engine, srv = serving.build_server(ctx, tracing=False)
    sizes = trafficgen.sizes(mix, limits["sample_requests"])
    rows = []
    try:
        for n, seed in enumerate(seeds):
            ctx.seed = seed
            if n:           # the first seed's weights are the server's own
                engine.release_params()
                engine.set_params(ctx.family.program_params(
                    engine.module, model, seed))
            live, t0 = {}, time.monotonic()
            for i, (p, o) in enumerate(sizes):
                prompt = trafficgen.prompt_tokens(vocab, p, seed, i)
                live[srv.submit(prompt, max_new_tokens=o)] = len(prompt)
            done = [(np.asarray(t[:live[rid]]), np.asarray(t[live[rid]:]))
                    for rid, t in srv.drain().items()]
            serve_s = round(time.monotonic() - t0, 1)
            for side in sides[seed]:
                t1 = time.monotonic()
                check = serving.check_outputs(ctx, done, chooser=side)
                rows.append({"seed": seed, "side": side or "program",
                             "seconds": round(time.monotonic() - t1, 1),
                             "serve_s": serve_s, **check})
                harness.say(**rows[-1])
    finally:
        srv.close()
        out = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(
                out, f"calibrate_seeds.{args.workload}.json"), "w") as f:
            json.dump({"workload": args.workload, "device": ctx.device,
                       "readings": rows}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
