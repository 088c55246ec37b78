"""Operations and bytes a routed expert layer NEEDS, and the expert load
the program's spans carry — the numerators of the ``moe`` per-layer
metrics.  Kept with the benchmark so that no PR that claims a gain can
change the count.  (``opsbytes.py`` holds the dense and attention
counts.)"""

from benchmark import spans

READERS = ("kernel.moe_experts_roofline", "kernel.moe_experts_share_pct",
           "moe.route_share_pct", "moe.load_max_over_mean")

LOAD_ARGS = ("moe_assignments", "moe_experts_touched",
             "moe_max_expert_tokens", "moe_calls")


def experts_bytes(experts_touched, hidden, width, matrices=3,
                  bytes_per_value=2):
    """Bytes the expert matmuls must read from HBM: the ``matrices``
    (gate, up, down) of every TOUCHED expert — one that a live token
    chose — once a call.  ``experts_touched`` is summed over calls.
    Activations (tokens x hidden) are noise beside them."""
    return experts_touched * matrices * hidden * width * bytes_per_value


def experts_flops(assignments, hidden, width, matrices=3):
    """Operations the chosen (token, expert) pairs require: one row
    through each of the expert's ``matrices``, 2 a multiply-add.  Rows an
    implementation computes for experts a token did not choose are not
    required work and are not counted."""
    return assignments * matrices * 2 * hidden * width


def span_load(path=None):
    """The expert load the program put on its spans, summed over the
    profiler slice: ``{moe_assignments, moe_experts_touched,
    moe_max_expert_tokens, moe_calls}`` (docs/observability.md), or None
    where no span carries them — a dense model, a parent commit from
    before them, a run without a trace."""
    total, found = dict.fromkeys(LOAD_ARGS, 0), False
    for e in spans.host_spans(path):
        if "moe_experts_touched" in e["stats"]:
            found = True
            for k in LOAD_ARGS:
                total[k] += int(e["stats"].get(k, 0))
    return total if found else None

