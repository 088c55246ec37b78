"""Rows a touched HELD expert sees a call, in a cell whose router also has
zero experts: the spans' ``moe_assignments`` over ``moe_experts_touched``,
summed over the slice — chunks and decode blocks together.  It is the
regime the expert kernels work in: ~2 in a 128-lane decode step at 8 real
choices of 512 experts and 16 held, where 32 chips' batches would send an
expert ~64.  None on a program whose spans carry no ``moe_zero_picks``."""
from benchmark import opsbytes_longcat as ob


def read(run):
    n = ob.picks() if run.trace else None
    if not n or not n["moe_experts_touched"]:
        return None
    return n["moe_assignments"] / n["moe_experts_touched"]
