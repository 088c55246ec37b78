"""The decode step kernel's share of its roofline (``ssd.decode_step``).

Needed, a call: bytes alone — every live lane's float32 state row read once
and written once, ``opsbytes_granite.state_bytes`` of the decode spans'
``ssd_state_rows`` (2 x 4 MiB a row at 128 heads of 64 x 128), against 819
GB/s; the step's x, B, C, step-size and output rows are noise beside it and
its operations (``4 P N`` a head) a hundredth of the MXU's second.  Time:
the summed device time of the kernels so named; both sides PER CALL (a
decode span covers ``block`` steps x Mamba layers calls).  A dead lane's row
through the kernel is not needed work."""
from benchmark import opsbytes, opsbytes_granite as ob, spans


def read(run):
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, ob.DECODE_KERNEL)
    work = ob.span_sums(ob.DECODE, ob.ROWS)
    if not calls or not work:
        return None
    z = run.family.sizes_of(run.cell["config"])
    block = run.cell["system"]["serving"]["decode_block"]
    mamba = z["kinds"].count("state_space")
    per_call = 1.0 / (work["spans"] * block * mamba)
    pct, _bound = opsbytes.roofline_pct(
        0.0, per_call * ob.state_bytes(work["ssd_state_rows"],
                                       z["ssm_heads"], z["ssm_d"],
                                       z["ssm_n"]),
        seconds / calls, run.peaks)
    return pct
