"""Share of the cache's bytes in use that is FIXED-SIZE STATE a slot (a
conv layer's last rows) and not pages: the decode dispatch spans'
``state_bytes`` — the state rows slots hold — over those plus
``kv_bytes_mapped`` — the pages slots hold, every pool the page table
indexes —, mean over the slice's dispatches.  The cache manager computes
both (``paging.SlotPages``); None for a model with no such state, or a
program from before the spans carried them."""
from benchmark import opsbytes_lfm2


def read(run):
    share = opsbytes_lfm2.state_share() if run.trace else None
    return None if share is None else 100.0 * share
