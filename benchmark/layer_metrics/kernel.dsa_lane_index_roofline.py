"""The window's indexer kernel's share of its roofline
(``attn.dsa_lane_index``).

Needed, a call (``opsbytes_glm5.lane_work``): the index scores of the
window rows' (query, key) pairs — ``opsbytes_dots3.index_flops`` of
``dsa_keys_scored`` — against 197 TFLOP/s, or the lanes' cached index keys
read ONCE a lane against 819 GB/s, whichever binds (memory: two rows a lane
score a key that is read once).  Time: the summed device time of the
kernels so named, per call."""
from benchmark import opsbytes, opsbytes_dots3, opsbytes_glm5 as ob, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "attn.dsa_lane_index")
    work = ob.lane_work(run) if calls else None
    if not work:
        return None
    a = dict(run.family.sizes_of(run.cell["config"])["full"])
    pct, _bound = opsbytes.roofline_pct(
        opsbytes_dots3.index_flops(work["scored"], a["index_heads"],
                                   a["index_dim"]),
        opsbytes_dots3.index_bytes(work["rows"], a["index_dim"]),
        seconds / calls, run.peaks)
    return pct
