"""The sorted expert kernel's share of its roofline over UN-GATED experts
(``moe.experts_grouped`` in ``nemotron3-serve-thinkgen-batch``: a chunk
dispatch's rows sorted by held expert).

Needed, a call: the TWO matrices of every held expert a real row of the
chunk chose at the PUBLISHED width, read once
(``opsbytes_nemotron.ungated_bytes`` of the admit waits'
``moe_experts_touched``) against 819 GB/s, or the real held pairs'
operations (``ungated_flops`` of ``moe_assignments``) against 197 TFLOP/s,
whichever binds.  Time: the summed device time of the kernels so named; both
sides PER CALL.  None where no chunk takes the sorted form (a prefill chunk
under ``GROUPED_MIN_ROWS`` rows), on a program without the kernel or the
spans."""
from benchmark import opsbytes_nemotron as ob


def read(run):
    return ob.roofline_pct(run, ob.GROUPED)
