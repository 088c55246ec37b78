"""Share of the cache's bytes in use that is chunk SUMMARIES and not ring:
the decode dispatch spans' ``summary_bytes_mapped`` — the lane pages the
live slots' summaries reach — over those plus ``ring_bytes_held`` — the live
slots' K/V rings, held whole whatever the context —, mean over the slice's
dispatches.  The module computes both from positions
(``models/evabyte.py::block_work``); None for a model with no such split, or
a program from before the spans carried them."""
from benchmark import opsbytes_evabyte


def read(run):
    share = opsbytes_evabyte.summary_share() if run.trace else None
    return None if share is None else 100.0 * share
