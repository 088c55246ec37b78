"""The program's set-up spans, for the per-layer readers that split
``setup_s`` (``layer_metrics/setup.*_s.py``; layer "entry points").

The program keeps every span of category ``setup`` — ``dstpu.setup.*``,
tabled in ``docs/observability.md`` "Start-up" — in a small list of its own,
ring or no ring (``deepspeed_tpu/monitor/trace.py::setup_spans``), stamped on
``time.monotonic``: the clock of ``harness.T_PROCESS_START`` and of
``run.slice_t0``.  A reader counts the spans that CLOSED before the
profiler's slice opened, as ``setup.trace_lower_s`` counts compile events.

Every function returns ``None`` where the program has no such span — a
parent commit from before them — and never raises for that.

    python3 benchmark/setup_spans.py        # the arithmetic on a made-up list
"""

import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from benchmark import spans, stats  # noqa: E402

PREFIX = "dstpu.setup."
# host construction that is neither weights nor a compile — the OWN time of
# these spans (the last two are named parts of the first three) —, then the
# program kinds each compile metric sums
BUILD = ("engine", "serve", "warmup", "pools", "lazy_import")
CHUNK = ("prefill_chunk",)
BLOCK = ("decode", "spec_block", "spec_verify")
ADMIT = ("admit",)
TRAIN_STEP = ("train_step",)


def closed_before(until=None):
    """The program's set-up spans that closed by the monotonic instant
    ``until`` (all of them for None), oldest first, in the shape of
    ``spans.host_spans`` — ``[{name, start_s, dur_s, thread, stats}]`` —
    with the prefix left off the name.  None when the program keeps none."""
    try:
        from deepspeed_tpu.monitor import trace as program_trace
    except ImportError:
        return None
    read = getattr(program_trace, "setup_spans", None)
    if read is None:
        return None
    rows = [{"name": n[len(PREFIX):], "start_s": t0, "dur_s": t1 - t0,
             "thread": track, "stats": args}
            for n, t0, t1, track, args in read()
            if n.startswith(PREFIX) and (until is None or t1 <= until)]
    return sorted(rows, key=lambda r: (r["start_s"], -r["dur_s"])) or None


def summed(rows, *names):
    """Summed duration of the spans called one of ``names``; None when
    there is none."""
    took = [r["dur_s"] for r in rows or () if r["name"] in names]
    return sum(took) if took else None


def ready_at(rows):
    """When the engine's last warm-up closed; None without one."""
    closes = [r["start_s"] + r["dur_s"] for r in rows or ()
              if r["name"] == "warmup"]
    return max(closes) if closes else None


def outside_program_s(run, t_start):
    """``t_start`` -> the last warm-up's close, less every set-up span in
    that stretch (the import among them): the caller's time before ready."""
    rows = closed_before(run.slice_t0)
    ready = ready_at(rows)
    if ready is None:
        return None
    inside = stats.union_seconds(
        [(max(r["start_s"], t_start), min(r["start_s"] + r["dur_s"], ready))
         for r in rows
         if r["start_s"] + r["dur_s"] > t_start and r["start_s"] < ready])
    return ready - t_start - inside


def engine_build_s(run):
    """Own time (``spans.self_seconds``: a span less the spans nested in it
    on its thread) of the engine's construction, the server's and the
    warm-up's, the pools' allocation and the lazy imports inside them."""
    own = spans.self_seconds(closed_before(run.slice_t0) or ())
    took = [own[n] for n in BUILD if n in own]
    return sum(took) if took else None


def compile_s(run, programs):
    """Summed ``dstpu.setup.compile`` spans of the given program kinds that
    closed before the slice; None when no such program was compiled."""
    took = [r["dur_s"] for r in closed_before(run.slice_t0) or ()
            if r["name"] == "compile"
            and r["stats"].get("program") in programs]
    return sum(took) if took else None


def compile_after_warmup_s(run):
    """JAX's compile-phase seconds (all three phases, the program's
    ``compile_events``) stamped after the last warm-up closed and before the
    slice ended: the ramp and the window together."""
    ready = ready_at(closed_before(run.slice_t0))
    if ready is None:
        return None
    from deepspeed_tpu.runtime import compile_cache
    until = run.slice_t0 + (run.slice_s or 0.0)
    return sum(seconds for t, _event, seconds
               in list(compile_cache.stats().compile_events)
               if ready < t <= until)


if __name__ == "__main__":
    # import 0-4; engine 5-7 holding weights 5.5-6.5; warmup 9-20 holding a
    # compile 9.5-19.5: own times 4, 1, 1, 1, 10
    made_up = [("import", 0, 4), ("engine", 5, 7), ("weights", 5.5, 6.5),
               ("warmup", 9, 20), ("compile", 9.5, 19.5)]
    print(spans.self_seconds([{"name": n, "start_s": a, "dur_s": b - a,
                               "thread": "MainThread"}
                              for n, a, b in made_up]))
