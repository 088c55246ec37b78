"""The EVA chunk kernel's share of its roofline (``attn.eva_chunk``).

Needed, a call: softmax attention over the chunk's (query, key) pairs of
both kinds — ``opsbytes_evabyte.attention_flops`` of the chunk spans'
``eva_local_pairs`` + ``eva_remote_pairs``, 32 heads of 128 + 128 — against
197 TFLOP/s (compute-bound).  Time: the summed device time of the kernels so
named; both sides PER CALL (a chunk span covers one call a layer).  The
kernel computes whole 512-key blocks: the diagonal block costs it twice what
is needed, and a padded tail's queries are computed for nothing."""
from benchmark import opsbytes, opsbytes_evabyte as ob, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "attn.eva_chunk")
    work = ob.span_sums(ob.CHUNK, ob.PAIRS)
    if not calls or not work:
        return None
    z = run.family.sizes_of(run.cell["config"])
    per_call = 1.0 / (work["spans"] * z["layers"])
    pct, _bound = opsbytes.roofline_pct(
        per_call * ob.attention_flops(sum(work[k] for k in ob.PAIRS),
                                      z["heads"], z["d"]),
        0.0, seconds / calls, run.peaks)
    return pct
