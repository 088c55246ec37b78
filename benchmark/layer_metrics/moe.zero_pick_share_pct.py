"""Share of the router's live choices that fell on zero-compute experts:
the spans' ``moe_zero_picks`` over all choices (zero, held, and real ones
held elsewhere), summed over the slice.  256 of the 768 router outputs are
zero experts and the family's selection bias is balanced over all of them:
33.3 by construction.  A zero choice costs no expert weights here and no
exchange in a deployment.  None on a program whose spans carry no
``moe_zero_picks``."""
from benchmark import opsbytes_longcat as ob


def read(run):
    n = ob.picks() if run.trace else None
    if not n:
        return None
    total = n["moe_zero_picks"] + n["moe_assignments"] \
        + n["moe_assignments_elsewhere"]
    return 100.0 * n["moe_zero_picks"] / total if total else None
