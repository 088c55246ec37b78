"""Operations and bytes the trinity family's window chunk kernel NEEDS, and
where its per-layer readers find the program's counters and scopes — kept
with the benchmark so that no PR that claims a gain can change the count.

What the spans carry (``docs/observability.md``), counted from positions on
the host over REAL positions only, each summed over the layers of its kind:
``dstpu.sched.dispatch.prefill_chunk`` and ``.decode`` — ``window_keys`` and
``full_keys`` ((query, key) pairs the sliding and the full layers attend);
``.prefill_chunk`` alone — ``window_ring_rows`` (ring rows the chunk's
queries can see: the positions before it inside its first query's band) and
``window_chunk_rows`` (its own rows); ``.decode`` alone —
``ring_bytes_held`` (the reserved slots' K/V rings, held whole) and
``kv_bytes_mapped`` (the lane pages slots hold).  The program's scopes:
``attn.window`` / ``attn.full`` (a sliding / a full layer's cache write and
attention kernels) and ``head.logits`` (the final norm and the head).  A
program without them — another model, a parent commit — has none: every
function here then returns None and the metric is left out."""

from benchmark import spans
from benchmark.opsbytes_dots3 import CHUNK, DECODE, span_sums  # noqa: F401
from benchmark.opsbytes_longcat import scope_share_pct  # noqa: F401

KERNEL = "attn.gqa_window_chunk"
KEYS = ("window_keys", "full_keys")
CHUNK_ROWS = ("window_keys", "window_ring_rows", "window_chunk_rows")


def attention_flops(pairs, heads, head_dim):
    """Softmax attention over ``pairs`` (query, key) pairs: a score and a
    value product a head, 2 a multiply-add — 2 x 32 x 256 a pair."""
    return 2 * heads * 2 * head_dim * pairs


def window_chunk_bytes(ring_rows, chunk_rows, heads, kv_heads, head_dim,
                       bytes_per_value=2):
    """What a window chunk call must move: K and V of the ring rows in the
    band and of the chunk's own rows, read once, and the chunk's queries in
    and outputs out — 2,048 B a cached row, 16,384 B a query row at 32 heads
    and 4 KV heads of 128."""
    kv = 2 * kv_heads * head_dim * bytes_per_value
    return (ring_rows + chunk_rows) * kv \
        + chunk_rows * 2 * heads * head_dim * bytes_per_value


def keys_ratio(sliding, full, path=None):
    """``window_keys / full_keys`` a layer of each kind, chunks and decode
    blocks of the slice together: 1.0 while no context has passed the
    window, falling as the tail grows."""
    sums = [s for s in (span_sums(CHUNK, KEYS, path),
                        span_sums(DECODE, KEYS, path)) if s]
    full_keys = sum(s["full_keys"] for s in sums)
    if not full_keys or not sliding or not full:
        return None
    return sum(s["window_keys"] for s in sums) * full / (sliding * full_keys)


def ring_share(path=None):
    """Mean over the slice's decode dispatches of ``ring_bytes_held /
    (ring_bytes_held + kv_bytes_mapped)``."""
    shares = []
    for e in spans.host_spans(path):
        if e["name"] == DECODE and "ring_bytes_held" in e["stats"]:
            ring = int(e["stats"]["ring_bytes_held"])
            total = ring + int(e["stats"].get("kv_bytes_mapped", 0))
            if total:
                shares.append(ring / total)
    return sum(shares) / len(shares) if shares else None
