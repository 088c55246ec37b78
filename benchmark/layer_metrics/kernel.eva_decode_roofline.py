"""The EVA decode kernel's share of its roofline (``attn.eva_decode``).

Needed, a call: K and V of the ring rows and the summary rows its lanes
attend — ``opsbytes_evabyte.row_bytes`` of the decode spans'
``eva_ring_rows`` + ``eva_summary_rows``, 16,384 B a row — against 819 GB/s
(memory-bound: 2 operations a byte).  Time: the summed device time of the
kernels so named.  Spans and kernel events are cut by the slice at different
blocks, so both sides are taken PER CALL: a decode span covers
``decode_block`` calls a layer.  The kernel fetches whole 64-row pages, so
a lane 10 rows into a ring page costs it the page."""
from benchmark import opsbytes, opsbytes_evabyte as ob, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "attn.eva_decode")
    work = ob.span_sums(ob.DECODE, ob.ROWS)
    if not calls or not work:
        return None
    z = run.family.sizes_of(run.cell["config"])
    block = run.cell["system"]["serving"]["decode_block"]
    per_call = 1.0 / (work["spans"] * z["layers"] * block)
    pct, _bound = opsbytes.roofline_pct(
        0.0, per_call * ob.row_bytes(sum(work[k] for k in ob.ROWS), z["h"]),
        seconds / calls, run.peaks)
    return pct
