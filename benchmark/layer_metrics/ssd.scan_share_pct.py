"""Share of the traced slice that the device spends in the two state kernels
of the Mamba-2 scan, found by their own names (``ssd.chunk_scan``, a prefill
chunk's scan with the state carried; ``ssd.decode_step``, a decode step's
read-update-write of every live lane's state row).  None on a program
without them."""
from benchmark import opsbytes_granite as ob


def read(run):
    return ob.kernels_share_pct(run)
