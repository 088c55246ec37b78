"""Traffic kind ``closed_loop_engine``: offline callers that keep the engine
saturated.  ``callers`` requests are always outstanding: each caller submits
its next request in-process (``srv.submit``) the moment its last one
completes, so the queue is never empty and no front end is involved.  The
metric is work completed per second of window.

Mix parameters: ``callers``, ``prompt_len``, ``output_len``, ``cycle`` (size
of the fixed multiset of request sizes), ``base_seed``, ``ramp_s`` (seconds
run before the window so that it opens on a steady state).
"""

import time

import numpy as np

from benchmark import harness, serving, stats, trafficgen


def run(ctx):
    mix = ctx.cell["traffic"]
    vocab = ctx.cell["config"]["vocab_size"]
    engine, srv = serving.build_server(ctx, tracing=False)
    stream = trafficgen.closed_loop_requests(mix, vocab, ctx.seed)
    live, done_in_window = {}, []

    def submit_one():
        idx, prompt, new = next(stream)
        live[srv.submit(prompt, max_new_tokens=new)] = (prompt, new)

    try:
        for _ in range(mix["callers"]):
            submit_one()
        # ramp: compiles the admit program on first use and fills the slots
        t_ramp = time.monotonic()
        while time.monotonic() - t_ramp < mix["ramp_s"]:
            for rid in srv.step():
                live.pop(rid)
                submit_one()
        t0 = time.monotonic()
        ctx.window_started(t0)
        stats0, occ0 = dict(srv.stats), len(srv.occupancy_trace)
        prof, failed = ctx.profiler, 0
        while True:
            now = time.monotonic() - t0
            if now >= ctx.seconds:
                break
            prof.poll(now)
            for rid, output in srv.step().items():
                prompt, new = live.pop(rid)
                if output is None or len(output) != len(prompt) + new:
                    failed += 1
                else:
                    done_in_window.append((prompt, np.asarray(
                        output[len(prompt):], np.int32)))
                submit_one()
        window = time.monotonic() - t0
        prof.finish()
        stats1 = dict(srv.stats)
        occupancy = [n for _, n in srv.occupancy_trace[occ0:]]
    finally:
        srv.close()

    tokens = sum(len(p) + len(n) for p, n in done_in_window)
    harness.say(phase="window", window_s=window,
                completed=len(done_in_window), failed=failed, tokens=tokens,
                decode_tokens=stats1["decode_tokens"] - stats0["decode_tokens"],
                prefill_tokens=stats1["prefill_tokens"]
                - stats0["prefill_tokens"],
                iterations=stats1["iterations"] - stats0["iterations"],
                sync_secs=stats1["sync_secs"] - stats0["sync_secs"],
                paged_attention_fallback=stats1["paged_attention_fallback"])
    check = serving.check_outputs(ctx, done_in_window)
    return {
        "attempted": len(done_in_window) + failed, "failed": failed,
        "checks": [check],
        "end_to_end": {"batch_tokens_per_s": stats.rate(tokens, window)},
        "observed": {"occupancy": occupancy, "num_slots": srv.num_slots},
    }
