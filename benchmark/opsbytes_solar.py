"""Operations and bytes the solar_open2 family's two state kernels NEED, and
where its per-layer readers find the program's counters and scopes — kept
with the benchmark so that no PR that claims a gain can change the count.

What the spans carry (``docs/observability.md``), counted on the host over
REAL positions only, each summed over the gated delta-rule (KDA) layers:
``dstpu.sched.dispatch.prefill_chunk`` and ``.decode`` — ``kda_scan_rows``
(positions x KDA layers the state scan advanced over) and ``kda_state_rows``
(state rows read and written: one a chunk and KDA layer, one a live lane,
step and KDA layer of a decode block).  The program's scopes: ``attn.kda``
(the whole KDA mixer: projections, convolutions, gates, scan, norm-gate,
``o_proj``), ``attn.full`` and ``head.logits`` (``opsbytes_trinity.py``).  A
program without them — another model, a parent commit — has none: every
function here then returns None and the metric is left out.

Needed work is the RECURRENCE's, whatever chunking implements it: a position
of a head decays its ``d x d`` state, reads ``S^T k``, adds the rank-one
correction and reads ``S^T q`` — three ``d x d`` products, 2 a multiply-add.
A padded tail and a chunked form's extra products (the intra-chunk score
matrices, the triangular solve) are not needed work."""

from benchmark import spans
from benchmark.opsbytes_dots3 import CHUNK, DECODE, span_sums  # noqa: F401
from benchmark.opsbytes_longcat import scope_share_pct  # noqa: F401

CHUNK_KERNEL, DECODE_KERNEL = "kda.chunk_scan", "kda.decode_step"
ROWS = ("kda_scan_rows", "kda_state_rows")


def scan_flops(scan_rows, heads, head_dim):
    """The recurrence over ``scan_rows`` (position, layer) pairs: ``6 d d``
    a head."""
    return 6 * head_dim * head_dim * heads * scan_rows


def state_bytes(state_rows, heads, head_dim, bytes_per_value=4):
    """``state_rows`` float32 states ``[heads, d, d]`` read once and written
    once: 2 x 4 MiB a row at 64 heads of 128."""
    return 2 * state_rows * heads * head_dim * head_dim * bytes_per_value


def scan_bytes(scan_rows, state_rows, heads, head_dim):
    """What the chunk kernel must move: ``q``, ``k``, ``v`` in and ``o`` out
    at 2 B, the log-decay at 4 B and ``beta`` a real row, plus the state
    read and written once a call."""
    row = heads * (4 * head_dim * 2 + head_dim * 4 + 4)
    return scan_rows * row + state_bytes(state_rows, heads, head_dim)


def kernels_share_pct(run):
    """The two kernels' summed device time over the slice's."""
    if not run.trace or not run.trace.window_s:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, CHUNK_KERNEL,
                                          DECODE_KERNEL)
    return 100.0 * seconds / run.trace.window_s if calls else None
