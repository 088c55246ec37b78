"""Median device duration of one execution of the paged prefill-chunk
program (one request's ``prefill_chunk`` tokens), from the profiler's
trace."""
from benchmark import stats


def read(run):
    d = run.trace.module_durations("chunk_step") if run.trace else []
    return 1e3 * stats.percentile(d, 50) if d else None
