"""Median device duration of one execution of the decode-block program
(``decode_block`` tokens for every slot), from the profiler's trace."""
from benchmark import stats


def read(run):
    d = run.trace.module_durations("decode_block") if run.trace else []
    return 1e3 * stats.percentile(d, 50) if d else None
