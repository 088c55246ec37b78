"""Share of the traced slice that the device spends in the final norm and
the vocabulary head, by the scope ``head.logits`` (``models/trinity.py``:
200,192 outputs, 0.82 GB of weights read a decode step).  None on a program
without the scope."""
from benchmark import opsbytes_trinity as ob


def read(run):
    return ob.scope_share_pct(run, "head.logits")
