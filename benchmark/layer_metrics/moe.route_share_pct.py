"""Share of the traced slice that the device spends routing around the
expert matmuls: the kernels named ``moe.route*`` (router matmul, softmax,
top-k, the dense combine matrix, the per-expert counts).  The weighted
combine itself is the expert kernel's accumulation and costs nothing of its
own; the few [experts]-sized operations that turn the counts into the
kernel's fetch plan are XLA's, carry no name, and are not counted."""
from benchmark import spans


def read(run):
    if not run.trace or not run.trace.window_s:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "moe.route")
    return 100.0 * seconds / run.trace.window_s if calls else None
