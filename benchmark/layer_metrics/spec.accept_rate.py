"""Share of the multi-token-prediction module's drafts that the model
reproduced and committed: the self-drafting dispatch spans' ``accepted``
over ``proposed`` (``opsbytes_glm5.py``), over the slice.  One draft a
window, so a window commits ``1 + accept_rate`` tokens on average (less
what budgets cut at a request's last window)."""
from benchmark import opsbytes_glm5 as ob


def read(run):
    sums = ob.window_sums() if run.trace else None
    return sums["accepted"] / sums["proposed"] if sums else None
