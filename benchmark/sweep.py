#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell, once, on the chip:

    python3 benchmark/sweep.py --workload <cell> --rates 4,6,8,10 --seconds 20

One server, one short window per rate.  A rate is SUSTAINED when no backlog
grows over the window: the requests still unfinished when arrivals stop are
no more than the slots can hold, and time to first token does not climb from
the window's first half to its second.  The knee is the highest sustained
rate; the cell's mix then fixes its rate at 0.8 x knee, and the table goes
into the cell's ``workloads`` file under ``defined_by``.  A later
``benchmark`` PR finds the knee again with this command.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, stats  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    ctx = harness.open_cell(ROOT, args.workload, args.seed, args.seconds)
    cell, device = ctx.cell, ctx.device
    driver = ctx.bench.driver(cell["traffic"]["kind"])
    srv, fe = driver.start(ctx)
    table = []
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = dict(cell["traffic"], rate_per_s=rate)
            records, schedule, _ = driver.window(ctx, srv, fe, mix,
                                                 args.seconds, args.seed + k)
            done = [r for r in records if r["status"] == "COMPLETED"]
            ttft = [(r["due_s"], r["token_s"][0] - r["due_s"]) for r in done]
            half = args.seconds / 2
            first = [t for d, t in ttft if d < half]
            second = [t for d, t in ttft if d >= half]
            backlog = sum(1 for r in records if not r["token_s"]
                          or r["token_s"][-1] > args.seconds)
            row = {"rate_per_s": rate, "requests": len(records),
                   "completed": len(done),
                   "unfinished_when_arrivals_stop": backlog,
                   "ttft_p50_first_half_ms": 1e3 * stats.percentile(first, 50),
                   "ttft_p50_second_half_ms": 1e3 * stats.percentile(second, 50),
                   "ttft_p95_ms": 1e3 * stats.percentile(
                       [t for _, t in ttft], 95),
                   "last_completion_s": max(r["token_s"][-1] for r in done),
                   "generator_late_p95_ms": 1e3 * stats.percentile(
                       [r["sent_s"] - r["due_s"] for r in records], 95)}
            row["sustained"] = bool(
                backlog <= srv.num_slots
                and row["ttft_p50_second_half_ms"]
                <= 1.5 * row["ttft_p50_first_half_ms"] + 100.0)
            table.append(row)
            harness.say(**row)
    finally:
        fe.shutdown(close_engine=True)
    knee = max((r["rate_per_s"] for r in table if r["sustained"]),
               default=None)
    print(json.dumps({"knee_per_s": knee,
                      "rate_at_0.8_knee": None if knee is None else 0.8 * knee,
                      "table": table, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
