"""The grouped expert kernel's share of its roofline in a cell whose chip
holds a thirty-second of the experts (``moe.experts_grouped``; the count is
``kernel.moe_grouped_roofline``'s, whose list
``tests/benchmark/test_benchmark_dots3.py`` holds to that cell alone).

Needed, a call: the three matrices of every HELD expert a live token of the
chunk chose, read once (``opsbytes_dots3.grouped_bytes`` of the admit
waits' ``moe_experts_touched``) against 819 GB/s, or the real rows'
operations (``grouped_flops`` of ``moe_assignments``) against 197 TFLOP/s,
whichever binds — memory at this cell's ~8 rows an expert a chunk.  Time:
the summed device time of the kernels so named; both sides PER CALL (the
spans' ``moe_calls`` against kernel events)."""
from benchmark import opsbytes, opsbytes_dots3 as ob, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "moe.experts_grouped")
    load = ob.span_sums(ob.ADMIT_WAIT, ("moe_zero_picks", "moe_assignments",
                                        "moe_experts_touched", "moe_calls"))
    if not calls or not load or not load["moe_calls"]:
        return None
    z = run.family.sizes_of(run.cell["config"])
    per_call = 1.0 / load["moe_calls"]
    pct, _bound = opsbytes.roofline_pct(
        per_call * ob.grouped_flops(load["moe_assignments"], z["h"], z["ef"]),
        per_call * ob.grouped_bytes(load["moe_experts_touched"], z["h"],
                                    z["ef"]),
        seconds / calls, run.peaks)
    return pct
