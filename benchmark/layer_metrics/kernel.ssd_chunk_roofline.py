"""The chunk scan kernel's share of its roofline (``ssd.chunk_scan``).

Needed, a call: the RECURRENCE over the chunk's real positions —
``opsbytes_granite.scan_flops`` of the chunk spans' ``ssd_scan_rows``, 128
heads of ``4 x 64 x 128`` — against 197 TFLOP/s, and ``scan_bytes``: x in
and y out, B, C and the step size a real row plus the float32 state read and
written once a call, against 819 GB/s; the larger binds (the bytes, at a
chunk of hundreds of rows: 8 MiB of state a call).  Time: the summed device
time of the kernels so named; both sides PER CALL (a chunk span covers one
call a Mamba layer).  The kernel computes whole 128-row blocks through the
block's ``C B^T`` scores and a masked decay matrix a head: what it does
beyond the recurrence is not needed work and is not counted."""
from benchmark import opsbytes, opsbytes_granite as ob, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, ob.CHUNK_KERNEL)
    work = ob.span_sums(ob.CHUNK, ob.ROWS)
    if not calls or not work or not work["ssd_state_rows"]:
        return None
    z = run.family.sizes_of(run.cell["config"])
    dims = (z["ssm_heads"], z["ssm_d"], z["ssm_n"])
    per_call = 1.0 / work["ssd_state_rows"]      # one state row a call
    pct, _bound = opsbytes.roofline_pct(
        per_call * ob.scan_flops(work["ssd_scan_rows"], *dims),
        per_call * ob.scan_bytes(work["ssd_scan_rows"],
                                 work["ssd_state_rows"], *dims),
        seconds / calls, run.peaks)
    return pct
