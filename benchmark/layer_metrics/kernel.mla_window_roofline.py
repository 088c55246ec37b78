"""The window layers' latent-attention kernel's share of its roofline
(``attn.mla_window``).

Needed, a call: softmax attention over the band's (query, key) pairs —
``opsbytes_dots3.attention_flops`` of the chunk spans' ``window_keys``, 64
heads of 256 + 128 — against 197 TFLOP/s.  Time: the summed device time of
the kernels so named; both sides PER CALL (a chunk span covers one call a
window layer).  The kernel walks the chunk's keys and its 512 predecessors
in 512-key blocks under a band mask, so it computes about chunk / 513 of
the tiles for nothing: a few percent is what this form can reach."""
from benchmark import opsbytes, opsbytes_dots3 as ob, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "attn.mla_window")
    work = ob.span_sums(ob.CHUNK, ("window_keys",))
    if not calls or not work:
        return None
    z = run.family.sizes_of(run.cell["config"])
    a = dict(z["swa"])
    layers = sum(k == "sliding_attention" for k in z["kinds"])
    per_call = 1.0 / (work["spans"] * layers)
    pct, _bound = opsbytes.roofline_pct(
        per_call * ob.attention_flops(work["window_keys"], a["heads"],
                                      a["nope"] + a["rope"], a["v"]),
        0.0, seconds / calls, run.peaks)
    return pct
