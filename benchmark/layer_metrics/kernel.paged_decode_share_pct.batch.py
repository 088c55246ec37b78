"""Share of the traced slice that the device spends in the paged-decode
attention kernel, found by its own name (``attn.paged_decode``, the
``name=`` of its ``pallas_call``) — no longer by the program it runs in."""
from benchmark import spans


def read(run):
    if not run.trace or not run.trace.window_s:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "attn.paged_decode")
    return 100.0 * seconds / run.trace.window_s if calls else None
