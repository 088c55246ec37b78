"""The window's attention kernel's share of its roofline
(``attn.mla_lane_decode``).

Needed, a call (one layer, one window, every live lane —
``opsbytes_glm5.lane_work``): the absorbed softmax over the KEPT (query,
key) pairs only — ``opsbytes_dots3.attention_flops`` of ``dsa_keys_kept``,
64 heads of 576 + 512 — against 197 TFLOP/s, or the lanes' live latent rows
read ONCE a lane against 819 GB/s, whichever binds.  Time: the summed
device time of the kernels so named, per call.  The kernel computes every
live pair of a lane and masks what the selection dropped, and fetches
whole 512-key blocks, so kept / scored of the compute peak is its best."""
from benchmark import opsbytes, opsbytes_dots3, opsbytes_glm5 as ob, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, "attn.mla_lane_decode")
    work = ob.lane_work(run) if calls else None
    if not work:
        return None
    a = dict(run.family.sizes_of(run.cell["config"])["full"])
    pct, _bound = opsbytes.roofline_pct(
        opsbytes_dots3.attention_flops(work["kept"], a["heads"],
                                       a["kv_rank"] + a["rope"],
                                       a["kv_rank"]),
        opsbytes_dots3.latent_bytes(work["rows"], a["kv_rank"] + a["rope"]),
        seconds / calls, run.peaks)
    return pct
