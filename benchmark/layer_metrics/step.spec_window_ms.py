"""Device time of ONE self-drafted verify window: the median device
duration of the ``spec_block`` program's executions over the windows a
dispatch carries (the cell's ``decode_block``).  A window is one pass of
the main model over two rows a lane, the commit, and one pass of the
multi-token-prediction module over two rows a lane."""
from benchmark import stats


def read(run):
    d = run.trace.module_durations("spec_block") if run.trace else []
    if not d:
        return None
    windows = run.cell["system"]["serving"]["decode_block"]
    return 1e3 * stats.percentile(d, 50) / windows
