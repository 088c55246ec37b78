"""Operations and bytes the granite_hybrid family's two state-space kernels
NEED, and where its per-layer readers find the program's counters and scopes
— kept with the benchmark so that no PR that claims a gain can change the
count.

What the spans carry (``docs/observability.md``), counted on the host over
REAL positions only, each summed over the Mamba-2 layers:
``dstpu.sched.dispatch.prefill_chunk`` and ``.decode`` — ``ssd_scan_rows``
(positions x Mamba layers the scan advanced over) and ``ssd_state_rows``
(state rows read and written: one a chunk and Mamba layer, one a live lane,
step and Mamba layer of a decode block).  The program's scopes: ``attn.ssd``
(the whole Mamba-2 mixer: ``in_proj``, convolution, discretisation, scan,
gate-norm, ``out_proj``), ``attn.full`` and ``head.logits``
(``opsbytes_trinity.py``).  A program without them — another model, a parent
commit — has none: every function here then returns None and the metric is
left out.

Needed work is the RECURRENCE's, whatever chunking implements it: a position
of a head decays its ``P x N`` state and adds the rank-one input (``2 P N``)
and reads ``S C`` out (``2 P N``).  A padded tail and a chunked form's extra
products (the block's ``C B^T`` scores, the masked decay matrix) are not
needed work."""

from benchmark import spans
from benchmark.opsbytes_dots3 import CHUNK, DECODE, span_sums  # noqa: F401
from benchmark.opsbytes_longcat import scope_share_pct  # noqa: F401

CHUNK_KERNEL, DECODE_KERNEL = "ssd.chunk_scan", "ssd.decode_step"
ROWS = ("ssd_scan_rows", "ssd_state_rows")


def scan_flops(scan_rows, heads, head_dim, states):
    """The recurrence over ``scan_rows`` (position, layer) pairs: ``4 P N``
    a head."""
    return 4 * head_dim * states * heads * scan_rows


def state_bytes(state_rows, heads, head_dim, states, bytes_per_value=4):
    """``state_rows`` float32 states ``[heads, P, N]`` read once and written
    once: 2 x 4 MiB a row at 128 heads of 64 x 128."""
    return 2 * state_rows * heads * head_dim * states * bytes_per_value


def scan_bytes(scan_rows, state_rows, heads, head_dim, states):
    """What the chunk kernel must move: ``x`` in and ``y`` out at 2 B a
    channel, ``B`` and ``C`` at 2 B and the step size at 4 B a head, a real
    row, plus the state read and written once a call."""
    row = heads * (2 * head_dim * 2 + 4) + 2 * states * 2
    return scan_rows * row + state_bytes(state_rows, heads, head_dim, states)


def kernels_share_pct(run):
    """The two kernels' summed device time over the slice's."""
    if not run.trace or not run.trace.window_s:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, CHUNK_KERNEL,
                                          DECODE_KERNEL)
    return 100.0 * seconds / run.trace.window_s if calls else None
