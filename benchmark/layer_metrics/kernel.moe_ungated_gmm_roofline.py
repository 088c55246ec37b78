"""The dense expert kernel's share of its roofline over UN-GATED experts
(``moe.experts_gmm`` in ``nemotron3-serve-thinkgen-batch``: a decode step's
rows through every touched held expert).

Needed, a call: the TWO matrices of every held expert a live lane chose at
the PUBLISHED width, read once (``opsbytes_nemotron.ungated_bytes`` of the
decode commits' ``moe_experts_touched``: 19.96 MB an expert) against 819
GB/s, or the chosen held pairs' operations (``ungated_flops`` of
``moe_assignments``) against 197 TFLOP/s, whichever binds — memory at ~9
rows an expert.  Time: the summed device time of the kernels so named; both
sides PER CALL (the spans' ``moe_calls`` against kernel events).  None on a
program without the kernel or the spans."""
from benchmark import opsbytes_nemotron as ob


def read(run):
    return ob.roofline_pct(run, ob.GMM)
