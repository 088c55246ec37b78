"""Median device span of one fused train step, from the profiler's trace."""
from benchmark import stats


def read(run):
    d = run.trace.module_durations("train_step") if run.trace else []
    return 1e3 * stats.percentile(d, 50) if d else None
