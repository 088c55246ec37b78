"""The routed experts' matmul kernel's share of its roofline.

Needed: the three matrices of every expert a live token chose, read once a
call (``opsbytes_moe.experts_bytes`` of the spans' ``moe_experts_touched``),
and the chosen pairs' operations (``experts_flops`` of ``moe_assignments``);
the larger of the two bounds binds — memory (819 GB/s) at the serving
programs' token counts.  Time: the summed device time of the kernels named
``moe.experts*``.  Host spans and device events are cut by the slice at
different blocks, so both sides are taken PER CALL: needed bytes over the
spans' ``moe_calls`` against kernel time over kernel events."""
from benchmark import opsbytes, opsbytes_moe, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "moe.experts")
    load = opsbytes_moe.span_load()
    if not calls or not load or not load["moe_calls"]:
        return None
    z = run.family.sizes_of(run.cell["config"])
    per_call = 1.0 / load["moe_calls"]
    pct, _bound = opsbytes.roofline_pct(
        per_call * opsbytes_moe.experts_flops(load["moe_assignments"],
                                              z["h"], z["f"]),
        per_call * opsbytes_moe.experts_bytes(load["moe_experts_touched"],
                                              z["h"], z["f"]),
        seconds / calls, run.peaks)
    return pct
