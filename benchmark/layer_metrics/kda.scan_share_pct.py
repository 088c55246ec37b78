"""Share of the traced slice that the device spends in the two state kernels
of the gated delta rule, found by their own names (``kda.chunk_scan``, a
prefill chunk's scan with the state carried; ``kda.decode_step``, a decode
step's read-update-write of every live lane's state row).  None on a program
without them."""
from benchmark import opsbytes_solar as ob


def read(run):
    return ob.kernels_share_pct(run)
