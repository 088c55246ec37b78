"""Share of the traced slice that the device spends in the gated delta-rule
(KDA) mixers (``models/solar_open2.py``): own device time under the scope
``attn.kda`` of the slot programs — the q / k / v projections and their
short convolutions, the decay's and the gate's low-rank projections, the
state kernels (``kda.chunk_scan``, ``kda.decode_step``), the per-head norm,
the gate and ``o_proj``.  None on a program without the scope."""
from benchmark import opsbytes_solar as ob


def read(run):
    return ob.scope_share_pct(run, "attn.kda")
