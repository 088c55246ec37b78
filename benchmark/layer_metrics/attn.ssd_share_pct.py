"""Share of the traced slice that the device spends in the Mamba-2
state-space mixers (``models/granite_hybrid.py``): own device time under the
scope ``attn.ssd`` of the slot programs — ``in_proj``, the short convolution
over ``x``, ``B`` and ``C``, the discretisation, the state kernels
(``ssd.chunk_scan``, ``ssd.decode_step``), the gate and the norm,
``out_proj``.  None on a program without the scope."""
from benchmark import opsbytes_granite as ob


def read(run):
    return ob.scope_share_pct(run, "attn.ssd")
