"""Operations and bytes the EvaByte family's two attention kernels NEED, and
the counters the program's spans carry for them — the numerators of the
``attn.eva*`` / ``eva.*`` / ``cache.summary_share_pct`` per-layer metrics.
Kept with the benchmark so that no PR that claims a gain can change the
count.

What the spans carry (``docs/observability.md``), counted from positions
on the host over REAL positions only, each summed over the layers:
``dstpu.sched.dispatch.prefill_chunk`` and ``.decode`` — ``eva_ring_rows``
(K/V ring rows the queries' window holds up to them: what a decode step
reads, a row a (query, key) pair; for a chunk the rows up to its last real
position, once), ``eva_summary_rows`` (summary rows visible: all chunks of
all earlier windows), ``eva_local_pairs`` / ``eva_remote_pairs`` ((query,
key) pairs under the one softmax, by key kind), ``eva_summaries_written``;
``.decode`` alone — ``ring_bytes_held`` (the live slots' rings, held whole)
and ``summary_bytes_mapped`` (the lane pages their summaries reach).  A
program without them — another model, a parent commit — has none: every
function here then returns None and the metric is left out."""

from benchmark import spans
from benchmark.opsbytes_dots3 import CHUNK, DECODE, span_sums  # noqa: F401

PAIRS = ("eva_local_pairs", "eva_remote_pairs")
ROWS = ("eva_ring_rows", "eva_summary_rows")


def row_bytes(rows, hidden, bytes_per_value=2):
    """K and V of ``rows`` cache rows (ring or summary alike: ``hidden``
    values each), read once: 16,384 B a row at hidden 4096."""
    return 2 * rows * hidden * bytes_per_value


def attention_flops(pairs, heads, head_dim):
    """Softmax attention over ``pairs`` (query, key) pairs: a score and a
    value product a head, 2 a multiply-add — 2 x 32 x 256 a pair."""
    return 2 * heads * 2 * head_dim * pairs


def remote_share(path=None):
    """Summaries among the keys attended: ``eva_remote_pairs`` over all
    pairs, chunks and decode blocks of the slice together."""
    sums = [s for s in (span_sums(CHUNK, PAIRS, path),
                        span_sums(DECODE, PAIRS, path)) if s]
    pairs = sum(s[k] for s in sums for k in PAIRS)
    return sum(s["eva_remote_pairs"] for s in sums) / pairs if pairs \
        else None


def summary_share(path=None):
    """Mean over the slice's decode dispatches of ``summary_bytes_mapped /
    (summary_bytes_mapped + ring_bytes_held)``."""
    shares = []
    for e in spans.host_spans(path):
        if e["name"] == DECODE and "summary_bytes_mapped" in e["stats"]:
            summ = int(e["stats"]["summary_bytes_mapped"])
            total = summ + int(e["stats"].get("ring_bytes_held", 0))
            if total:
                shares.append(summ / total)
    return sum(shares) / len(shares) if shares else None
