#!/usr/bin/env python3
"""Read the two numbers a ``correct`` limit is set from, on the chip, in one
process: the largest that sound runs of the program give over a dozen seeds,
and the smallest that the control gives — the plain reference put in the
program's place and computed in float8, the nearest precision below the
configurations' bfloat16.

    python3 benchmark/calibrate.py --workload <cell> --seeds 101,...,112 --control-seeds 101,102,103

The limit then goes into ``benchmark/workloads/<cell>.json`` under
``correct``, above the first reading and below the second, and both readings
into PERF.md.  The benchmark's own runs never run the control; the test
``tests/benchmark/test_benchmark_control.py`` keeps it at a size a test run
can hold.
"""

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, stats, trafficgen  # noqa: E402


def serving_readings(ctx, seeds, control_seeds, args):
    from benchmark import serving
    model, mix = ctx.cell["config"], ctx.cell["traffic"]
    z = ctx.family.sizes_of(model)
    pad_to = ctx.cell["system"]["serving"]["max_cache_len"]
    if args.slots:
        ctx.cell["system"]["serving"]["num_slots"] = args.slots
    engine, srv = serving.build_server(ctx, tracing=False)
    ctx.cell["system"]["correct"]["sample_requests"] = args.requests
    out = {"program": {}, "control": {}}
    try:
        for seed in seeds:
            ctx.seed = seed
            engine.set_params(ctx.family.program_params(
                engine.module, model, seed))
            sizes = trafficgen.sizes(mix, args.requests)
            live = {}
            for i, (p, o) in enumerate(sizes):
                prompt = trafficgen.prompt_tokens(z["vocab"], p, seed, i)
                live[srv.submit(prompt, max_new_tokens=o)] = (prompt, o)
            done = [(live[rid][0], np.asarray(outp[len(live[rid][0]):]))
                    for rid, outp in srv.drain().items()]
            check = serving.check_outputs(ctx, done)
            out["program"][seed] = check["mean_logit_gap"]
            harness.say(seed=seed, side="program", **check)
            if seed in control_seeds:
                # the same requests, each decision made by float8 logits
                low = serving.check_outputs(ctx, done, chooser="float8")
                out["control"][seed] = low["mean_logit_gap"]
                harness.say(seed=seed, side="control_float8", **low)
    finally:
        srv.close()
    # and the control as a generator of its own, on a few short requests
    for seed in control_seeds[:args.control_generations]:
        gaps = []
        for i, (p, _) in enumerate(trafficgen.sizes(mix,
                                                    args.control_requests)):
            prompt = trafficgen.prompt_tokens(z["vocab"], p, seed, i)
            toks = ctx.family.greedy(z, seed, prompt, args.control_tokens,
                                     pad_to, "float8")
            gaps.append(ctx.family.chosen_gaps(z, seed, toks, len(prompt),
                                               args.control_tokens, pad_to))
        gaps = np.concatenate(gaps)
        harness.say(seed=seed, side="control_float8_generating",
                    tokens=int(len(gaps)), mean_logit_gap=float(gaps.mean()),
                    max_logit_gap=float(gaps.max()))
    return out


def training_readings(ctx, seeds, control_seeds, args):
    """Per seed, in the order of a run: the reference on an empty device, a
    new engine (its moments must be zero), the forward comparison, ONE
    step, the gradient comparison.  The control is the reference alone, in
    float8 against float32; ``--control-only`` skips the program (a
    four-chip cell's control needs one chip)."""
    import gc
    driver = ctx.bench.driver("train_steps")
    mix = ctx.cell["traffic"]
    z, rows = ctx.family.sizes_of(ctx.cell["config"]), driver.rows_per_step(ctx)
    out = {"loss_rms": {"program": {}, "control": {}},
           "gradient_rel_error": {"program": {}, "control": {}}}
    for seed in sorted(set(seeds) | set(control_seeds)):
        ctx.seed = seed
        first = next(trafficgen.train_batches(mix, z["vocab"], rows, seed))
        step_batch, unique = driver.step_check_batch(ctx, first)
        positions = driver.check_positions(ctx, *first.shape)
        ref_losses = None if args.step_only else \
            driver.reference_call_losses(ctx, first, positions)
        ref_step = driver.reference_step(ctx, unique)
        if seed in control_seeds:
            if not args.step_only:
                low = driver.reference_call_losses(ctx, first, positions,
                                                   "float8")
                out["loss_rms"]["control"][seed] = float(
                    stats.rms(low - ref_losses))
            low = driver.reference_step(ctx, unique, "float8")
            e1 = ctx.family.relative_error(low["gradients"],
                                           ref_step["gradients"])
            e2 = ctx.family.relative_error(
                *({n: np.abs(g) for n, g in side["gradients"].items()}
                  for side in (low, ref_step)))
            out["gradient_rel_error"]["control"][seed] = max(e1, e2)
            harness.say(seed=seed, side="control_float8",
                        rms_difference=out["loss_rms"]["control"].get(seed),
                        moment1_relative_error=e1, moment2_relative_error=e2,
                        control_loss=low["loss"], reference_loss=ref_step["loss"],
                        control_grad_norm=low["grad_norm"],
                        reference_grad_norm=ref_step["grad_norm"])
            del low
        if seed in seeds and not args.control_only:
            engine = driver.build_engine(ctx)
            if not args.step_only:
                check = driver.first_loss_check(ctx, engine, first, ref_losses)
                out["loss_rms"]["program"][seed] = check["rms_difference"]
                harness.say(seed=seed, side="program", **check)
            loss = float(engine.train_batch(
                batch={"input_ids": step_batch[None]}))
            check = driver.first_step_check(ctx, engine, loss, ref_step)
            out["gradient_rel_error"]["program"][seed] = check["relative_error"]
            harness.say(seed=seed, side="program", **check)
            engine.destroy()
            del engine
        del ref_step
        gc.collect()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--requests", type=int, default=8,
                    help="serving: requests served and compared per seed")
    ap.add_argument("--slots", type=int, default=None,
                    help="serving: fewer KV slots than the cell's, so that "
                         "a second set of weights fits beside the pool while "
                         "seeds are swapped (a row's arithmetic does not "
                         "depend on how many rows a step has)")
    ap.add_argument("--control-requests", type=int, default=3)
    ap.add_argument("--control-tokens", type=int, default=16)
    ap.add_argument("--control-only", action="store_true",
                    help="training: read the control alone (the reference "
                         "in float8 against itself in float32)")
    ap.add_argument("--step-only", action="store_true",
                    help="training: read the gradient comparison alone")
    ap.add_argument("--chips", type=int, default=None,
                    help="with --control-only: the chips of the machine at "
                         "hand, where the cell's own are not needed")
    ap.add_argument("--control-generations", type=int, default=1,
                    help="serving: control seeds on which the float8 "
                         "reference also generates by itself")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x.strip()]
    seeds, control_seeds = ints(args.seeds), ints(args.control_seeds)
    ctx = harness.open_cell(ROOT, args.workload, seeds[0],
                            chips=args.chips if args.control_only else None)
    cell, device = ctx.cell, ctx.device
    if "serving" in cell["system"]:
        out = {"mean_logit_gap": serving_readings(ctx, seeds, control_seeds,
                                                  args)}
    else:
        out = training_readings(ctx, seeds, control_seeds, args)
    for number, sides in out.items():
        prog, ctrl = sides["program"].values(), sides["control"].values()
        print(json.dumps({
            "workload": args.workload, "device": device, "number": number,
            "program_largest": max(prog, default=None),
            "program_all": sides["program"],
            "control_smallest": min(ctrl, default=None),
            "control_all": sides["control"],
            "ratio": min(ctrl) / max(prog) if prog and ctrl and max(prog)
            else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
