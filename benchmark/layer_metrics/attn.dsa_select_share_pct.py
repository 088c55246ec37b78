"""Share of the traced slice that the device spends SELECTING keys for a
chunk: the kernels named ``attn.dsa*`` — ``attn.dsa_index`` (the index
scores) and ``attn.dsa_topk`` (the k-th largest score a query, by
bisection).  The one pass that turns the threshold into the mask, and a
decode step's scores and ``lax.top_k``, are XLA's, carry no kernel name and
are not counted: ``step.decode_share_pct`` bounds the decode side."""
from benchmark import spans


def read(run):
    if not run.trace or not run.trace.window_s:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "attn.dsa")
    return 100.0 * seconds / run.trace.window_s if calls else None
