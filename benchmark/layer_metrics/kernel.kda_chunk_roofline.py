"""The chunk scan kernel's share of its roofline (``kda.chunk_scan``).

Needed, a call: the RECURRENCE over the chunk's real positions —
``opsbytes_solar.scan_flops`` of the chunk spans' ``kda_scan_rows``, 64 heads
of ``6 x 128 x 128`` — against 197 TFLOP/s, and ``scan_bytes``: q, k, v, o,
the log-decay and beta a real row plus the float32 state read and written
once a call, against 819 GB/s; the larger binds (compute, at a chunk of
hundreds of rows).  Time: the summed device time of the kernels so named;
both sides PER CALL (a chunk span covers one call a KDA layer).  The kernel
computes whole 64-row blocks, the intra-block score matrices and a
triangular solve: what it does beyond the recurrence is not needed work and
is not counted."""
from benchmark import opsbytes, opsbytes_solar as ob, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, ob.CHUNK_KERNEL)
    work = ob.span_sums(ob.CHUNK, ob.ROWS)
    if not calls or not work or not work["kda_state_rows"]:
        return None
    z = run.family.sizes_of(run.cell["config"])
    per_call = 1.0 / work["kda_state_rows"]      # one state row a call
    pct, _bound = opsbytes.roofline_pct(
        per_call * ob.scan_flops(work["kda_scan_rows"], z["kda_heads"],
                                 z["kda_d"]),
        per_call * ob.scan_bytes(work["kda_scan_rows"],
                                 work["kda_state_rows"], z["kda_heads"],
                                 z["kda_d"]),
        seconds / calls, run.peaks)
    return pct
