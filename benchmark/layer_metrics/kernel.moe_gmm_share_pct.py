"""Share of the traced slice that the device spends in the DECODE steps'
expert kernel (``moe.experts_gmm``: every touched held expert over the
live lanes' rows) of a model whose chunks take the grouped form — where
``moe.experts_grouped`` runs, ``moe.experts_gmm`` is the decode blocks'
alone.  None for a program without the grouped kernel
(``kernel.moe_experts_share_pct`` is that program's metric)."""
from benchmark import spans


def read(run):
    if not run.trace or not run.trace.window_s:
        return None
    if not spans.kernel_seconds(run.trace, "moe.experts_grouped")[1]:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "moe.experts_gmm")
    return 100.0 * seconds / run.trace.window_s if calls else None
