"""The scheduler's host work per iteration in the closed-loop cell: each
``dstpu.sched.step`` span in the profiler's trace less the
``dstpu.sched.wait_device`` spans inside it, median over the slice's
iterations.  An UPPER bound: it is read in the traced run, whose Python tracer
slows host code; the untraced figure (``stats["wall_secs"] -
stats["sync_secs"]`` per iteration) is in PERF.md §5."""
from benchmark import spans


def read(run):
    return spans.host_ms_per_iter(run)
