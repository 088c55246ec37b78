"""The program's own spans, for the per-layer readers that explain a judged
metric by its parts.  The program marks host work with ONE helper,
``deepspeed_tpu/monitor/trace.py::span`` (names ``dstpu.<layer>.<what>``,
tabled in ``docs/observability.md``); a span reaches a reader two ways:

* **the profiler's trace** — every span is a ``jax.profiler.TraceAnnotation``,
  so in a traced run it lies in the ``.xplane.pb`` that
  ``harness.ProfilerSlice`` leaves in ``<root>/.bench_trace``, on a host
  thread's line, on the device events' own clock, its arguments as event
  stats.  ``host_spans`` reads them (``trace.Trace.events`` keeps names and
  times only); ``inside`` and ``self_seconds`` nest them by thread.
* **the program's ring** — with ``serving.tracing`` on (the traced run of the
  open-loop cell turns it on) the same spans, and the per-request phase
  spans, are kept by the process's one tracer; ``ring_spans`` reads them
  after the server is closed.

Every function returns nothing (``[]`` / ``None``) where the program has no
such span — a parent commit from before the spans, a run without a trace —
and never raises for that.

    python3 benchmark/spans.py [.bench_trace]     # look at a run's spans by hand

prints each span's count and time, the scheduler's host time per iteration,
the share of device idle time that lies under some span, and whether each
decode block's device execution starts inside or after the span that
dispatched it (one clock).
"""

import os
import sys
from collections import defaultdict

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from benchmark import spec, stats, trace  # noqa: E402

PREFIX = "dstpu."
COMPILE_TRACE = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_BACKEND = "/jax/core/compile/backend_compile_duration"


def trace_dir():
    """Where ``harness.ProfilerSlice`` leaves the profiler's files."""
    return os.path.join(spec.repo_root(), ".bench_trace")


def host_spans(path=None):
    """The program's spans in a profiler trace: ``[{name, start_s, dur_s,
    thread, stats}]`` sorted by start, ``thread`` a key that is equal for
    events of one host thread.  ``[]`` when there is no trace."""
    from jax.profiler import ProfileData
    try:
        data = ProfileData.from_file(trace.find_xplane(path or trace_dir()))
    except (FileNotFoundError, OSError):
        return []
    out = []
    for pi, plane in enumerate(data.planes):
        if plane.name.startswith("/device:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append({"name": ev.name, "start_s": ev.start_ns * 1e-9,
                                "dur_s": ev.duration_ns * 1e-9,
                                "thread": (pi, li), "stats": dict(ev.stats)})
    return sorted(out, key=lambda e: e["start_s"])


def inside(parent, spans, name=None):
    """The spans of ``parent``'s thread that lie within it (itself left
    out), optionally only those called ``name``."""
    lo, hi = parent["start_s"], parent["start_s"] + parent["dur_s"]
    return [e for e in spans
            if e is not parent and e["thread"] == parent["thread"]
            and (name is None or e["name"] == name)
            and e["start_s"] >= lo - 1e-9
            and e["start_s"] + e["dur_s"] <= hi + 1e-9]


def self_seconds(spans):
    """Each span's own time — its duration less its direct children's on
    the same thread — summed by name."""
    by_name = defaultdict(float)
    by_thread = defaultdict(list)
    for e in spans:
        by_thread[e["thread"]].append(e)
    for evs in by_thread.values():
        stack = []                      # (end, name)
        for e in sorted(evs, key=lambda e: (e["start_s"], -e["dur_s"])):
            while stack and stack[-1][0] <= e["start_s"] + 1e-12:
                stack.pop()
            if stack:
                by_name[stack[-1][1]] -= e["dur_s"]
            by_name[e["name"]] += e["dur_s"]
            stack.append((e["start_s"] + e["dur_s"], e["name"]))
    return dict(by_name)


def step_host_seconds(spans):
    """Per scheduler iteration, the host's part: each ``dstpu.sched.step``
    less the ``dstpu.sched.wait_device`` spans inside it (the blocking
    reads of device results).  One number per whole iteration in the
    trace."""
    steps = [e for e in spans if e["name"] == "dstpu.sched.step"]
    return [s["dur_s"] - sum(w["dur_s"] for w in inside(
        s, spans, "dstpu.sched.wait_device")) for s in steps]


def host_ms_per_iter(run):
    """What both ``sched.host_ms_per_iter.*`` readers compute."""
    if not run.trace:
        return None
    host = step_host_seconds(host_spans())
    return 1e3 * stats.percentile(host, 50) if host else None


def idle_cover_share(tr, spans):
    """Share of the device's idle time (gaps between device operations of
    the first device, inside the trace's window) that lies under at least
    one of ``spans``; None when the device was never idle."""
    if not tr or not tr.window or not tr.device_planes:
        return None
    idle = stats.gaps([(e[3], e[3] + e[4]) for e in tr.device_ops()],
                      *tr.window)
    total = sum(t - s for s, t in idle)
    if total <= 0:
        return None
    covered = 0.0
    iv = sorted((e["start_s"], e["start_s"] + e["dur_s"]) for e in spans)
    for s, t in idle:
        covered += stats.union_seconds(
            [(max(a, s), min(b, t)) for a, b in iv if b > s and a < t])
    return covered / total


# --------------------------------------------------------------------- #
# the ring
# --------------------------------------------------------------------- #
def ring_spans(name=None, cat=None):
    """Finished spans of the program's tracer as ``[{name, cat, t0, t1,
    track, args}]`` (instants left out); ``[]`` when the program has no
    process tracer or it is off."""
    try:
        from deepspeed_tpu.monitor import trace as program_trace
    except ImportError:
        return []
    tracer = getattr(program_trace, "tracer", lambda: None)()
    if tracer is None:
        return []
    rows, _added = tracer.span_snapshot()
    return [{"name": n, "cat": c, "t0": t0, "t1": t1, "track": track,
             "args": args}
            for n, c, t0, t1, track, args in rows
            if t1 is not None and (name is None or n == name)
            and (cat is None or c == cat)]


def window_rids(run):
    """Engine request ids of the requests due in the window."""
    return {rec["rid"] for rec in run.observed.get("records", [])
            if rec.get("rid") is not None}


def request_median_ms(run, name, cat=None, value=None):
    """Median over the window's requests of one ring span per request:
    its duration, or ``value(span)``.  None when the ring has none."""
    rids = window_rids(run)
    vals = [value(s) if value else s["t1"] - s["t0"]
            for s in ring_spans(name, cat) if s["args"].get("rid") in rids]
    vals = [v for v in vals if v is not None]
    return 1e3 * stats.percentile(vals, 50) if vals else None


# --------------------------------------------------------------------- #
# kernels by name, compile phases
# --------------------------------------------------------------------- #
def kernel_seconds(tr, *names, module=None):
    """Summed device time and count of the Mosaic kernels whose
    instruction name holds one of ``names`` (the ``name=`` of the
    ``pallas_call``; transforms may wrap it, ``jvp_attn.flash_fwd_``)."""
    def match(hlo):
        head = hlo.partition(" = ")[0]
        return trace.is_pallas(hlo) and any(n in head for n in names)
    return tr.op_seconds(match, module=module)


def kernel_ms_per_train_step(run, *names):
    """Device time of the named kernels inside ``train_step`` executions
    of the first device, per step.  The slice cuts a step at each edge, so
    the steps it holds are counted as the executions' summed time over
    their median, not as the number of executions."""
    if not run.trace:
        return None
    executions = run.trace.module_intervals("train_step")
    seconds, calls = kernel_seconds(run.trace, *names, module="train_step")
    if not executions or not calls:
        return None
    durations = [e - s for s, e in executions]
    steps = sum(durations) / stats.percentile(durations, 50)
    return 1e3 * seconds / steps


def compile_phase_seconds(until=None):
    """JAX's compile-phase seconds summed from the program's
    ``compile_cache.stats().compile_events`` up to the monotonic instant
    ``until``: ``{trace, lower, backend}`` (an event's seconds are what it
    added to the program's own sum: a jit traced inside another's trace is
    counted once).  None when the program keeps no such events."""
    try:
        from deepspeed_tpu.runtime import compile_cache
    except ImportError:
        return None
    events = getattr(compile_cache.stats(), "compile_events", None)
    if events is None:
        return None
    out = {"trace": 0.0, "lower": 0.0, "backend": 0.0}
    phase = {COMPILE_TRACE: "trace", COMPILE_LOWER: "lower",
             COMPILE_BACKEND: "backend"}
    for t, event, seconds in list(events):
        if until is not None and t > until:
            break
        if event in phase:
            out[phase[event]] += seconds
    return out


# --------------------------------------------------------------------- #
def summarize(path):
    spans = host_spans(path)
    tr = trace.Trace(trace.read_events(path))
    agg = defaultdict(lambda: [0, 0.0])
    for e in spans:
        agg[e["name"]][0] += 1
        agg[e["name"]][1] += e["dur_s"]
    own = self_seconds(spans)
    print("span | count | summed s | own s")
    for n, (c, d) in sorted(agg.items(), key=lambda kv: -kv[1][1]):
        print(f"  {n} | {c} | {d:.4f} | {own.get(n, 0.0):.4f}")
    host = step_host_seconds(spans)
    if host:
        print(f"scheduler host time per iteration: median "
              f"{1e3 * stats.percentile(host, 50):.3f} ms, p95 "
              f"{1e3 * stats.percentile(host, 95):.3f} ms over {len(host)}")
    print(f"device idle {tr.idle_pct():.4f}% of {tr.window_s:.3f} s; share of "
          f"idle time under a {PREFIX}* span: {idle_cover_share(tr, spans)}")
    dispatch = [e for e in spans if e["name"] == "dstpu.sched.dispatch.decode"]
    blocks = tr.module_intervals("decode_block")
    if dispatch and blocks:
        lag = []
        for d in dispatch:
            later = [b for b in blocks if b[0] >= d["start_s"]]
            if later:
                lag.append(later[0][0] - d["start_s"])
        print(f"decode blocks: {len(blocks)} executions, {len(dispatch)} "
              f"dispatch spans; next execution starts "
              f"{1e3 * stats.percentile(lag, 50):.3f} ms (median) after its "
              f"dispatch span opens; kv_positions of the first: "
              f"{dispatch[0]['stats'].get('kv_positions')}")


if __name__ == "__main__":
    summarize(sys.argv[1] if len(sys.argv) > 1 else trace_dir())
