"""Share of the traced slice that the device spends in the grouped expert
kernel of the chunks (``moe.experts_grouped``: rows sorted by held expert,
real rows only)."""
from benchmark import spans


def read(run):
    if not run.trace or not run.trace.window_s:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "moe.experts_grouped")
    return 100.0 * seconds / run.trace.window_s if calls else None
