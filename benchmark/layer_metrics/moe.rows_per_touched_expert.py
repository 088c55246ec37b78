"""Rows a touched expert sees a call: the spans' ``moe_assignments`` over
``moe_experts_touched``, summed over the slice — chunks and decode blocks
together.  It is the regime the expert kernels work in: ~8 in a 64-lane
decode step at 8 of 64 experts a token, ~16 at 256 lanes and 4 of 64, ~64
in a chunk's grouped form."""
from benchmark import opsbytes_moe


def read(run):
    load = opsbytes_moe.span_load() if run.trace else None
    if not load or not load["moe_experts_touched"]:
        return None
    return load["moe_assignments"] / load["moe_experts_touched"]
