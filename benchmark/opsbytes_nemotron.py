"""Operations and bytes the nemotron_h family's UN-GATED routed experts NEED
(``relu(h U)^2 D``: TWO matrices an expert, where ``opsbytes_moe.py`` and
``opsbytes_dots3.py`` count a gated expert's three), and where its readers
find the program's counters — kept with the benchmark so that no PR that
claims a gain can change the count.

What the spans carry (``docs/observability.md``): ``dstpu.sched.commit`` —
a decode block's expert load over the HELD experts (``moe_assignments``,
``moe_experts_touched``, ``moe_calls``: expert blocks x steps) —;
``dstpu.sched.wait_device`` of an admit — the chunk dispatches' likewise
(``moe_calls``: one an expert block a dispatch).  The kernels by their
``name=``: ``moe.experts_gmm`` (every touched held expert over every row: a
decode step's, and a chunk's under ``GROUPED_MIN_ROWS`` rows) and
``moe.experts_grouped`` (rows sorted by expert: a chunk's from there on).  A
program without them — another model, a parent commit — has none: every
function here then returns None and the metric is left out.

Needed work is counted at the PUBLISHED expert width, whatever width the
program stores (zero padding would show as a lower share, not as work)."""

from benchmark import opsbytes, spans
from benchmark.opsbytes_dots3 import ADMIT_WAIT, span_sums

COMMIT = "dstpu.sched.commit"
GMM, GROUPED = "moe.experts_gmm", "moe.experts_grouped"
LOAD = ("moe_experts_touched", "moe_assignments", "moe_calls")


def ungated_bytes(experts_touched, hidden, width, bytes_per_value=2):
    """The TWO matrices (up, down) of every touched held expert, read once
    a call: 2 x 2688 x 1856 x 2 B = 19.96 MB an expert at the published
    widths.  ``experts_touched`` is summed over calls."""
    return experts_touched * 2 * hidden * width * bytes_per_value


def ungated_flops(assignments, hidden, width):
    """One real row through an expert's two matrices a chosen held pair, 2
    a multiply-add."""
    return assignments * 2 * 2 * hidden * width


def kernel_load(run, kernel):
    """``(seconds, calls, load)`` of one of the two expert kernels: its
    summed device time and events, and the spans' summed load of the calls
    that take it — the decode blocks' commits for ``moe.experts_gmm``, with
    the admit waits' where no chunk takes the sorted form; the admit waits'
    for ``moe.experts_grouped``.  None where either side has nothing."""
    if not run.trace:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, kernel)
    sorted_calls = spans.kernel_seconds(run.trace, GROUPED)[1]
    names = (ADMIT_WAIT,) if kernel == GROUPED \
        else (COMMIT,) if sorted_calls else (COMMIT, ADMIT_WAIT)
    sums = [s for s in (span_sums(n, LOAD) for n in names) if s]
    load = {k: sum(s[k] for s in sums) for k in LOAD}
    if not calls or not load["moe_calls"]:
        return None
    return seconds, calls, load


def roofline_pct(run, kernel):
    """The kernel's share of its roofline, both sides PER CALL (host spans
    and device events are cut by the slice at different blocks): the two
    matrices of every touched held expert against the memory peak, the
    chosen held pairs' operations against the matmul peak, the larger
    bound."""
    found = kernel_load(run, kernel)
    if found is None:
        return None
    seconds, calls, load = found
    z = run.family.sizes_of(run.cell["config"])
    per_call = 1.0 / load["moe_calls"]
    pct, _bound = opsbytes.roofline_pct(
        per_call * ungated_flops(load["moe_assignments"], z["h"], z["ef"]),
        per_call * ungated_bytes(load["moe_experts_touched"], z["h"],
                                 z["ef"]),
        seconds / calls, run.peaks)
    return pct


def kernels_share_pct(run):
    """The two expert kernels' summed device time over the slice's."""
    if not run.trace or not run.trace.window_s:
        return None
    seconds, calls = spans.kernel_seconds(run.trace, GMM, GROUPED)
    return 100.0 * seconds / run.trace.window_s if calls else None
