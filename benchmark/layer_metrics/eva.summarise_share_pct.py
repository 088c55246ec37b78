"""Share of the traced slice that the device spends POOLING chunks into
summary rows (``models/evabyte.py::summarise``, scope ``eva.summarise``: a
16-wide softmax of ``s k.phi`` and two weighted sums a chunk — XLA's; in a
decode step after a gather of the ring's last 16 rows a lane): part
``eva.summarise`` of the slot programs.  None on a program without the join
or the part."""
from benchmark import scopes


def read(run):
    return scopes.part_share_pct(run, scopes.SERVE, "eva.summarise")
