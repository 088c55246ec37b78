"""Share of the traced slice that the device spends routing, where the
router is XLA's and no kernel carries its name (``moe/dropless.py``
``route_scored``, scope ``moe.route``: float32 sigmoid scores, top-k of
score + bias, the gates over the chosen): part ``moe.route`` of the slot
programs.  ``moe.route_share_pct`` reads the kernels named ``moe.route*``
and finds nothing in these cells.  None on a program without the
join."""
from benchmark import scopes


def read(run):
    return scopes.part_share_pct(run, scopes.SERVE, "moe.route")
