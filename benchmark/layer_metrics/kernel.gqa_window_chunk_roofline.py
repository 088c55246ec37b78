"""The window chunk kernel's share of its roofline (``attn.gqa_window_chunk``).

Needed, a call: softmax attention over the band's REAL (query, key) pairs —
``opsbytes_trinity.attention_flops`` of the chunk spans' ``window_keys``, 32
heads of 128 + 128 — against 197 TFLOP/s, and the bytes of the ring rows in
the band and of the chunk's own rows (``window_chunk_bytes`` of
``window_ring_rows`` and ``window_chunk_rows``) against 819 GB/s; the larger
binds (compute, at a chunk of hundreds of rows under a 2,048-key window).
Time: the summed device time of the kernels so named; both sides PER CALL (a
chunk span covers one call a sliding layer).  The kernel computes whole
512-key blocks and a padded tail's queries: what it does beyond the band is
not needed work and is not counted."""
from benchmark import opsbytes, opsbytes_trinity as ob, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, ob.KERNEL)
    work = ob.span_sums(ob.CHUNK, ob.CHUNK_ROWS)
    if not calls or not work:
        return None
    z = run.family.sizes_of(run.cell["config"])
    per_call = 1.0 / (work["spans"] * z["kinds"].count("sliding_attention"))
    pct, _bound = opsbytes.roofline_pct(
        per_call * ob.attention_flops(work["window_keys"], z["heads"],
                                      z["d"]),
        per_call * ob.window_chunk_bytes(
            work["window_ring_rows"], work["window_chunk_rows"], z["heads"],
            z["kv_heads"], z["d"]),
        seconds / calls, run.peaks)
    return pct
