"""Share of the traced slice that the device spends in the two routed-expert
kernels over UN-GATED experts (``moe.experts_gmm`` + ``moe.experts_grouped``
in ``nemotron3-serve-thinkgen-batch``): the number that says the
configuration's new mechanism — 64 two-matrix experts streamed in each of 6
blocks a step — does the work.  None on a program without the kernels."""
from benchmark import opsbytes_nemotron as ob


def read(run):
    return ob.kernels_share_pct(run)
