"""The chunk's latent-attention kernel's share of its roofline
(``attn.mla_chunk_prefill``).

Needed, a call: softmax attention over the KEPT (query, key) pairs only —
``opsbytes_dots3.attention_flops`` of the chunk spans' ``dsa_keys_kept``,
128 heads of 192 + 128 — against 197 TFLOP/s (the latent rows' bytes are
counted too and never bind).  Time: the summed device time of the kernels
so named; both sides PER CALL (a chunk span covers one call a full layer).
This first version is the masked dense form: it computes every causal pair
and masks what the selection dropped, so at the cell's contexts it can
reach about kept / scored of its roofline at best."""
from benchmark import opsbytes, opsbytes_dots3 as ob, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "attn.mla_chunk_prefill")
    work = ob.span_sums(ob.CHUNK, ("dsa_keys_kept", "latent_rows_read"))
    if not calls or not work:
        return None
    z = run.family.sizes_of(run.cell["config"])
    a = dict(z["full"])
    layers = sum(k == "full_attention" for k in z["kinds"])
    per_call = 1.0 / (work["spans"] * layers)
    pct, _bound = opsbytes.roofline_pct(
        per_call * ob.attention_flops(work["dsa_keys_kept"], a["heads"],
                                      a["nope"] + a["rope"], a["v"]),
        per_call * ob.latent_bytes(work["latent_rows_read"],
                                   a["kv_rank"] + a["rope"]),
        seconds / calls, run.peaks)
    return pct
