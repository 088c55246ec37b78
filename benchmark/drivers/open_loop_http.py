"""Traffic kind ``open_loop_http``: independent users.  Requests arrive on a
schedule fixed by the mix (rate, lengths) and the seed, over HTTP to
loopback with streaming, from a load generator in a process of its own;
each is timed from when it was DUE.  Arrivals stop at ``--seconds``; a
bounded drain follows.

Mix parameters: ``rate_per_s``, ``arrivals`` (``poisson``),
``prompt_len`` and ``output_len`` (distributions), ``base_seed``,
``drain_grace_s``, ``warmup`` (a few (prompt, output) pairs sent before the
window so that every program has run once).
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmark import harness, serving, stats, trafficgen


def _loadgen(ctx, port, schedule, start_at, seconds, grace, tag):
    tmp = os.path.join(ctx.root, ".bench_tmp")
    os.makedirs(tmp, exist_ok=True)
    job, out = (os.path.join(tmp, f"{tag}.{k}.json") for k in ("job", "out"))
    with open(job, "w") as f:
        json.dump({"port": port, "start_at": start_at, "seconds": seconds,
                   "drain_grace_s": grace, "schedule": schedule}, f)
    script = os.path.join(ctx.bench.dir, "loadgen.py")
    return subprocess.Popen([sys.executable, script, job, out]), out


def _finish(proc, out, timeout):
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise RuntimeError("the load generator outlived its deadline")
    if rc != 0:
        raise RuntimeError(f"the load generator exited with {rc}")
    with open(out) as f:
        return json.load(f)["records"]


def start(ctx):
    """The server behind its HTTP front end, warmed: the fused admit program
    compiles on first use, and every program should have run once before a
    window opens."""
    from deepspeed_tpu.inference.serving.frontend import serve_http
    mix = ctx.cell["traffic"]
    vocab = ctx.cell["config"]["vocab_size"]
    engine, srv = serving.build_server(ctx, tracing=ctx.trace)
    fe = serve_http(srv)
    try:
        warm = [{"index": i, "due_s": 0.0, "max_new_tokens": o,
                 "input_ids": trafficgen.prompt_tokens(
                     vocab, p, ctx.seed, 10**6 + i).tolist()}
                for i, (p, o) in enumerate(mix["warmup"])]
        proc, out = _loadgen(ctx, fe.port, warm, time.monotonic(), 0.0,
                             300.0, "warmup")
        for rec in _finish(proc, out, 330):
            if rec["status"] != "COMPLETED":
                raise RuntimeError(f"warm-up request failed: {rec}")
    except BaseException:
        fe.shutdown(close_engine=True)
        raise
    return srv, fe


def window(ctx, srv, fe, mix, seconds, seed):
    """One open-loop window at the mix's rate: the generator's records, the
    schedule they answer, the window's start on the monotonic clock."""
    vocab = ctx.cell["config"]["vocab_size"]
    schedule = trafficgen.open_loop_schedule(mix, vocab, seconds, seed)
    t0 = time.monotonic() + 1.0              # the generator's start-up
    proc, out = _loadgen(ctx, fe.port, schedule, t0, seconds,
                         mix["drain_grace_s"], "window")
    ctx.window_started(t0)
    prof = ctx.profiler
    while proc.poll() is None:
        now = time.monotonic() - t0
        prof.poll(now)
        time.sleep(0.05)
    prof.finish()
    return _finish(proc, out, 5), schedule, t0


def run(ctx):
    mix = ctx.cell["traffic"]
    srv, fe = start(ctx)
    try:
        stats0 = dict(srv.stats)
        records, schedule, t0 = window(ctx, srv, fe, mix, ctx.seconds,
                                       ctx.seed)
        stats1 = dict(srv.stats)
        engine_side = {}
        for rec in records:
            if rec["rid"] is not None:
                res = srv.result(rec["rid"])
                if res is not None:
                    engine_side[rec["index"]] = {
                        "ttft_s": res.ttft_s, "queue_s": res.queue_s}
    finally:
        fe.shutdown(close_engine=True)

    end = ctx.seconds + mix["drain_grace_s"]
    ttft, tbt, late, completed, failed = [], [], [], [], 0
    for rec, req in zip(records, schedule):
        good = (rec["status"] == "COMPLETED"
                and len(rec["tokens"]) == req["max_new_tokens"])
        if rec["sent_s"] is not None:
            late.append(rec["sent_s"] - rec["due_s"])
        if not good:
            failed += 1              # missing every latency
            ttft.append(end - rec["due_s"])
            continue
        ttft.append(rec["token_s"][0] - rec["due_s"])
        tbt.extend(np.diff(rec["token_s"]).tolist())
        completed.append((np.asarray(req["input_ids"], np.int32),
                          np.asarray(rec["tokens"], np.int32)))
    harness.say(phase="window", requests=len(records), failed=failed,
                ttft_samples=len(ttft), tbt_samples=len(tbt),
                ttft_p95_ms=1e3 * stats.percentile(ttft, 95),
                tbt_p50_ms=1e3 * stats.percentile(tbt, 50),
                generator_late_p95_ms=1e3 * stats.percentile(late, 95),
                last_completion_s=max((r["token_s"][-1] for r in records
                                       if r["token_s"]), default=None),
                decode_tokens=stats1["decode_tokens"] - stats0["decode_tokens"],
                prefill_tokens=stats1["prefill_tokens"]
                - stats0["prefill_tokens"],
                admission_stalls=stats1["admission_stalls"]
                - stats0["admission_stalls"],
                paged_attention_fallback=stats1["paged_attention_fallback"])
    check = serving.check_outputs(ctx, completed)
    return {
        "attempted": len(records), "failed": failed,
        "checks": [check],
        "end_to_end": {"ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
                       "tbt_p95_ms": 1e3 * stats.percentile(tbt, 95)},
        "observed": {"records": records, "engine_side": engine_side,
                     "late_s": late, "ttft_s": ttft, "window_t0": t0},
    }
