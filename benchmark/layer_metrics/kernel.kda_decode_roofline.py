"""The decode step kernel's share of its roofline (``kda.decode_step``).

Needed, a call: bytes alone — every live lane's float32 state row read once
and written once, ``opsbytes_solar.state_bytes`` of the decode spans'
``kda_state_rows`` (2 x 4 MiB a row at 64 heads of 128 x 128), against 819
GB/s; the step's q, k, v, decay and output rows are noise beside it and its
operations (``6 d d`` a head) a hundredth of the MXU's second.  Time: the
summed device time of the kernels so named; both sides PER CALL (a decode
span covers ``block`` steps x KDA layers calls).  A dead lane's row through
the kernel is not needed work."""
from benchmark import opsbytes, opsbytes_solar as ob, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, ob.DECODE_KERNEL)
    work = ob.span_sums(ob.DECODE, ob.ROWS)
    if not calls or not work:
        return None
    z = run.family.sizes_of(run.cell["config"])
    block = run.cell["system"]["serving"]["decode_block"]
    linear = z["kinds"].count("linear_attention")
    per_call = 1.0 / (work["spans"] * block * linear)
    pct, _bound = opsbytes.roofline_pct(
        0.0, per_call * ob.state_bytes(work["kda_state_rows"],
                                       z["kda_heads"], z["kda_d"]),
        seconds / calls, run.peaks)
    return pct
