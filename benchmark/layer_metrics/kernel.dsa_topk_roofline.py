"""The top-k kernel's share of its roofline (``attn.dsa_topk``).

Needed, a call: the chunk's causal index scores read ONCE —
``opsbytes_dots3.topk_bytes`` of the chunk spans' ``dsa_keys_scored`` —
against 819 GB/s; the 32 compare-and-count passes of the bisection are not
matrix work and have no peak here, so the share says how far the kernel is
from one pass over its input.  Time: the summed device time of the kernels
so named, PER CALL as in ``kernel.dsa_index_roofline``: a chunk span covers
one call a full layer.  The kernel reads whole rows of the slot's lane,
live or not, so a short context costs it what a long one does."""
from benchmark import opsbytes, opsbytes_dots3 as ob, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "attn.dsa_topk")
    work = ob.span_sums(ob.CHUNK, ("dsa_keys_scored",))
    if not calls or not work:
        return None
    z = run.family.sizes_of(run.cell["config"])
    layers = sum(k == "full_attention" for k in z["kinds"])
    pct, _bound = opsbytes.roofline_pct(
        0.0, ob.topk_bytes(work["dsa_keys_scored"])
        / (work["spans"] * layers), seconds / calls, run.peaks)
    return pct
