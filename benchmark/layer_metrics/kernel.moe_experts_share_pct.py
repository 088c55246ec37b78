"""Share of the traced slice that the device spends in the routed experts'
matmul kernels, found by name (``moe.experts*``, the ``name=`` of the
``pallas_call``)."""
from benchmark import spans


def read(run):
    if not run.trace or not run.trace.window_s:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "moe.experts")
    return 100.0 * seconds / run.trace.window_s if calls else None
