"""Share of the traced slice that the device spends in the SLIDING layers'
attention over their K/V rings (``models/trinity.py``): own device time
under the scope ``attn.window`` of the slot programs — the ring's row write,
``attn.paged_decode`` over the ring's table (a decode step) and
``attn.gqa_window_chunk`` (a chunk).  The projections, QK-norm, rope and the
output gate are other parts.  None on a program without the scope."""
from benchmark import opsbytes_trinity as ob


def read(run):
    return ob.scope_share_pct(run, "attn.window")
