"""Median device duration of one execution of the decode-block program
(``decode_block`` tokens for every slot, live or not), from the profiler's
trace — in the closed-loop cell, where it is paid once an iteration beside
the iteration's prefill chunks."""
from benchmark import stats


def read(run):
    d = run.trace.module_durations("decode_block") if run.trace else []
    return 1e3 * stats.percentile(d, 50) if d else None
