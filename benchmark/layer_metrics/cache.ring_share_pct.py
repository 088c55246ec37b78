"""Share of the cache's bytes held that is K/V RINGS and not lane pages:
the decode dispatch spans' ``ring_bytes_held`` — the reserved slots' rings,
held whole whatever the context — over those plus ``kv_bytes_mapped`` — the
lane pages slots hold —, mean over the slice's dispatches.  The cache
manager computes both (``paging.SlotPages``); None for a model that names
no ring pools, or a program from before the spans carried them."""
from benchmark import opsbytes_trinity as ob


def read(run):
    share = ob.ring_share() if run.trace else None
    return None if share is None else 100.0 * share
