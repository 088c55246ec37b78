"""Share of the traced slice that the device spends in the FULL layers'
attention over their lane pages (``models/trinity.py``): own device time
under the scope ``attn.full`` of the slot programs — ``attn.paged_decode``
with its fused write (a decode step), the page-run write and
``attn.paged_chunk_prefill`` (a chunk).  None on a program without the
scope."""
from benchmark import opsbytes_trinity as ob


def read(run):
    return ob.scope_share_pct(run, "attn.full")
