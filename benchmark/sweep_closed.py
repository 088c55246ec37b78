#!/usr/bin/env python3
"""Find a closed-loop serving cell's admission settings, once, on the chip:

    python3 benchmark/sweep_closed.py --workload <cell> --chunks 128,256,512 --budgets default,4096,8192 --seconds 20

One set of weights; a server a ``prefill_chunk`` (its two programs compile
anew), and on it a short window a ``prefill_token_budget`` (``default``:
the program's own), each behind the mix's own ramp.  A line a window:
tokens per second as the cell counts them, requests completed, mean slots
live, iterations.  The cell's ``workloads`` file keeps the table under
``defined_by`` and the winner under ``serving``; a later ``benchmark`` PR
finds them again with this command.  (``sweep.py`` is the open loop's.)
"""

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, stats, trafficgen  # noqa: E402


class _Remembering:
    """The server, remembering what was submitted, so that a window's
    leftovers can be cancelled."""

    def __init__(self, srv):
        self._srv, self.rids = srv, []

    def __getattr__(self, name):
        return getattr(self._srv, name)

    def submit(self, *args, **kwargs):
        self.rids.append(self._srv.submit(*args, **kwargs))
        return self.rids[-1]


def window(drive, srv, mix, vocab, seed, seconds):
    """Ramp, then ``seconds`` of the cell's own closed loop (``drive`` of
    ``drivers/closed_loop_engine.py``, so the count is the cell's) on
    ``srv``; the server is drained of what is left, so the next window
    starts empty."""
    srv = _Remembering(srv)
    quiet = types.SimpleNamespace(poll=lambda now: None, finish=lambda: None)
    w, _ = drive(srv, trafficgen.closed_loop_requests(mix, vocab, seed),
                 mix, seconds, quiet, lambda t0: None)
    row = {"batch_tokens_per_s": stats.rate(w["credited_tokens"],
                                            w["credited_s"]),
           "completed": w["completed"], "iterations": w["iterations"],
           "slots_live_mean": sum(w["occupancy"])
           / max(len(w["occupancy"]), 1)}
    for rid in srv.rids:
        try:
            srv.cancel(rid)
        except KeyError:        # long gone
            pass
    srv.drain()
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--chunks", required=True)
    ap.add_argument("--budgets", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import deepspeed_tpu
    ctx = harness.open_cell(ROOT, args.workload, args.seed, args.seconds)
    model, mix = ctx.cell["config"], ctx.cell["traffic"]
    drive = ctx.bench.driver("closed_loop_engine").drive
    base = dict(ctx.cell["system"]["serving"])
    module = ctx.family.program_model(model, scan_layers=False)
    engine = deepspeed_tpu.init_inference(module, config={
        "dtype": "bfloat16", "prefill_chunk_size": None,
        "compile_cache": harness.compile_cache_block(ctx.cache_dir),
        "serving": {"enabled": True, **base}})
    engine.set_params(ctx.family.program_params(module, model, args.seed))
    table = []
    from deepspeed_tpu.inference.serving.config import ServingConfig
    default = ServingConfig().prefill_token_budget
    for chunk in (int(c) for c in args.chunks.split(",")):
        settings = {**base, "prefill_chunk": chunk}
        settings.pop("paged", None)
        srv = engine.serve(**settings)
        try:
            compile_s = round(sum(srv.warmup().values()), 1)
            for k, budget in enumerate(args.budgets.split(",")):
                # the budget is read every iteration: one server a chunk
                srv.config = srv.config.model_copy(update={
                    "prefill_token_budget": default if budget == "default"
                    else int(budget)})
                row = {"prefill_chunk": chunk, "prefill_token_budget": budget,
                       **window(drive, srv, mix, model["vocab_size"],
                                args.seed + k, args.seconds),
                       "compile_s": compile_s}
                table.append(row)
                harness.say(**row)
        finally:
            srv.close()
    best = max(table, key=lambda r: r["batch_tokens_per_s"])
    print(json.dumps({"best": best, "table": table, "device": ctx.device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
