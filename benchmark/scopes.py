"""Device time by the program's own parts, for the per-layer readers that
split the XLA half of a program — the fusions that carry no name of the
program's in the trace.

A device event's name is its HLO instruction's text (``%fusion.158 = ...``);
the instruction's ``op_name`` — the flax module path and every
``jax.named_scope`` — is the compiled module's, and the profiler stores it in
the trace's metadata, program by program.  The PROGRAM reads that table and
owns the join and the table of parts
(``profiling/flops_profiler/profiler.py``: ``trace_scopes``,
``device_time_by_scope``, ``SCOPE_PARTS``; documented in
``docs/observability.md``).  This helper hands the join ``run.trace``'s
events and module executions, first device, as the other kernel readers do.

    python3 benchmark/scopes.py [.bench_trace]

prints the whole by-part table of a finished run by hand.

Every function returns None where the program has no such join — a parent
commit from before it, a run without a trace — and never raises for that.
"""

import os
import sys
import time
from collections import defaultdict

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from benchmark import harness, spans, stats, trace  # noqa: E402

TRAIN = ("jit_train_step",)
SERVE = ("jit_decode_block", "jit_chunk_step")


def _profiler():
    """The program's profiler module where it has the join, else None."""
    try:
        from deepspeed_tpu.profiling.flops_profiler import profiler
    except ImportError:
        return None
    return profiler if hasattr(profiler, "trace_scopes") else None


def join(tr, directory, modules, profiler):
    """The program's join of ``tr``'s first device over the programs named
    ``modules``, against the tables the trace under ``directory`` stores;
    None where none of them executes in the slice."""
    plane = tr.device_planes[0] if tr.device_planes else None
    return profiler.device_time_by_scope(
        [(e[2], e[3], e[4]) for e in tr.device_ops()],
        [(e[2], e[3], e[4]) for e in tr.events
         if e[0] == plane and e[1] == trace.MODULES_LINE],
        profiler.trace_scopes(directory), modules)


def by_part(run, modules):
    """``join`` of ``run``'s trace over ``modules``, computed once a run.
    None without a trace, or on a program without the join."""
    memo = run.__dict__.setdefault("_scopes_by_part", {})
    if modules in memo:
        return memo[modules]
    memo[modules] = None
    profiler = _profiler()
    if profiler is None or not run.trace or not run.trace.window_s:
        return None
    t0 = time.monotonic()
    memo[modules] = join(run.trace, spans.trace_dir(), modules, profiler)
    # what the new metrics cost, after the window
    harness.say(phase="scope_join", modules=list(modules),
                join_s=time.monotonic() - t0)
    return memo[modules]


def part_seconds(run, modules, *parts):
    """Summed own device seconds of ``parts`` (every phase), or None."""
    joined = by_part(run, modules)
    if joined is None:
        return None
    return sum(s for (part, _), s in joined["parts"].items() if part in parts)


def train_steps(tr):
    """The steps a slice holds, as ``spans.kernel_ms_per_train_step``
    counts them: the executions' summed time over their median."""
    durations = [e - s for s, e in tr.module_intervals("train_step")]
    return sum(durations) / stats.percentile(durations, 50) \
        if durations else 0.0


def part_ms_per_train_step(run, *parts):
    seconds = part_seconds(run, TRAIN, *parts)
    steps = train_steps(run.trace) if seconds is not None else 0.0
    return 1e3 * seconds / steps if steps else None


def part_share_pct(run, modules, *parts):
    """``parts``' own time as a share of the traced slice."""
    seconds = part_seconds(run, modules, *parts)
    return None if seconds is None else 100.0 * seconds / run.trace.window_s


def unattributed_pct(run, modules):
    joined = by_part(run, modules)
    if joined is None:
        return None
    return 100.0 * joined["unattributed_s"] / run.trace.window_s


# --------------------------------------------------------------------- #
# the table by hand
# --------------------------------------------------------------------- #
def summarize(directory):
    profiler = _profiler()
    if profiler is None:
        sys.exit("this checkout's program has no join of device time by "
                 "part (deepspeed_tpu.profiling.flops_profiler.profiler"
                 ".device_time_by_scope)")
    tr = trace.Trace(trace.read_events(directory))
    joined = join(tr, directory, TRAIN + SERVE, profiler)
    if joined is None:
        sys.exit(f"no execution of {TRAIN + SERVE} in the trace")
    steps = train_steps(tr)
    print(f"slice {tr.window_s:.4f} s, busy {tr.busy_s():.4f} s; own time "
          f"inside {joined['executions']} executions of {TRAIN + SERVE}: "
          f"{joined['total_s']:.4f} s"
          + (f"; {steps:.2f} train steps" if steps else ""))
    print(f"{'part':<22}" + "".join(f"{p + ' s':>11}" for p in profiler.PHASES)
          + f"{'all s':>11}{'of slice':>10}" + ("   ms/step" if steps else ""))
    grouped = defaultdict(dict)
    for (part, phase), s in joined["parts"].items():
        grouped[part][phase] = s
    rows = sorted(grouped.items(), key=lambda kv: -sum(kv[1].values()))
    rows.append(("unattributed", {"fwd": joined["unattributed_s"]}))
    for part, phases in rows:
        total = sum(phases.values())
        print(f"{part:<22}"
              + "".join(f"{phases.get(p, 0.0):>11.4f}"
                        for p in profiler.PHASES)
              + f"{total:>11.4f}{100.0 * total / tr.window_s:>9.2f}%"
              + (f"{1e3 * total / steps:>10.3f}" if steps else ""))
    print("largest unattributed instructions (module:name, s, op_name):")
    for name, s, op_name in joined["top_unattributed"]:
        print(f"  {s:.5f}  {name}  {op_name or '-'}")


if __name__ == "__main__":
    summarize(sys.argv[1] if len(sys.argv) > 1 else spans.trace_dir())
