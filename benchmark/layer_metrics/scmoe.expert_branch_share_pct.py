"""Share of the traced slice that the device spends in the shortcut's
expert branch: own device time of the slot programs' instructions under the
scope ``scmoe.experts`` (``models/longcat.py``: the router, the held
experts' kernels, the zero experts' identity part).  In a deployment this
branch — with its exchange — runs beside the first dense FFN and the whole
second attention; on one chip it is in line.  None on a program without
the scope."""
from benchmark import opsbytes_longcat as ob


def read(run):
    return ob.scope_share_pct(run, ob.BRANCH)
