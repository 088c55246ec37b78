"""The work speculation wastes: verify rows whose token was not committed
(a rejected draft's row, or one past a request's budget), of all verify
rows — two a window —, from the self-drafting dispatch spans' counters
(``opsbytes_glm5.py``).  Each such row went through every layer of the
main model and was routed to its experts for nothing."""
from benchmark import opsbytes_glm5 as ob


def read(run):
    sums = ob.window_sums() if run.trace else None
    return 100.0 * sums["rows_rejected"] / (2 * sums["windows"]) \
        if sums else None
