"""The indexer kernel's share of its roofline (``attn.dsa_index``).

Needed, a call: the index scores of the chunk's causal (query, key) pairs —
``opsbytes_dots3.index_flops`` of the chunk spans' ``dsa_keys_scored`` —
against 197 TFLOP/s, or the cached keys' bytes against 819 GB/s, whichever
binds (compute, at 64 heads a key).  Time: the summed device time of the
kernels so named.  Spans and kernel events are cut by the slice at
different chunks, so both sides are taken PER CALL: a chunk span covers one
call a full layer.  The kernel scores whole key blocks up to the chunk's
last position, so the chunk's own triangle costs it twice what is needed."""
from benchmark import opsbytes, opsbytes_dots3 as ob, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "attn.dsa_index")
    work = ob.span_sums(ob.CHUNK, ("dsa_keys_scored", "latent_rows_read"))
    if not calls or not work:
        return None
    z = run.family.sizes_of(run.cell["config"])
    a = dict(z["full"])
    layers = sum(k == "full_attention" for k in z["kinds"])
    per_call = 1.0 / (work["spans"] * layers)
    pct, _bound = opsbytes.roofline_pct(
        per_call * ob.index_flops(work["dsa_keys_scored"], a["index_heads"],
                                  a["index_dim"]),
        per_call * ob.index_bytes(work["latent_rows_read"], a["index_dim"]),
        seconds / calls, run.peaks)
    return pct
