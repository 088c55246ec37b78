"""Share of the traced slice that the device spends in EVA attention proper
(``models/evabyte.py``): part ``attn.eva`` of the slot programs — the
kernels ``attn.eva_decode`` (a lane's ring pages and visible summary pages
under one online softmax, its K/V row written) and ``attn.eva_chunk`` (a
chunk's queries against the same two sets).  The projections, rope, the
ring's page-run write and the pooling are other parts.  None on a program
without the join or the part."""
from benchmark import scopes


def read(run):
    return scopes.part_share_pct(run, scopes.SERVE, "attn.eva")
