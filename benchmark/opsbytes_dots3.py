"""Operations and bytes the dots3 family's new kernels NEED, and the
counters the program's spans carry for them — the numerators of the
``attn.*`` / ``dsa.*`` / grouped-expert per-layer metrics.  Kept with the
benchmark so that no PR that claims a gain can change the count.

What the spans carry (``docs/observability.md``):
``dstpu.sched.dispatch.prefill_chunk`` and ``.decode`` — ``dsa_keys_scored``
((query, key) pairs the indexer scores: the causal ones), ``dsa_keys_kept``
(pairs the softmax runs over: at most ``index_topk`` a query),
``latent_rows_read``, ``window_pages``, ``window_keys`` (pairs the window
layers attend), each summed over the layers of its kind;
``dstpu.sched.wait_device`` of an admit — the chunks' expert load for the
HELD experts (``moe_assignments``, ``moe_experts_touched``, ``moe_calls``)
and ``moe_assignments_elsewhere``.  A parent commit from before them has
none: every function here then returns None and the metric is left out."""

from benchmark import spans

CHUNK = "dstpu.sched.dispatch.prefill_chunk"
DECODE = "dstpu.sched.dispatch.decode"
ADMIT_WAIT = "dstpu.sched.wait_device"


def span_sums(name, keys, path=None):
    """``{key: sum, "spans": n}`` over the slice's spans called ``name``
    that carry ``keys[0]``; None where none does."""
    total, n = dict.fromkeys(keys, 0), 0
    for e in spans.host_spans(path):
        if e["name"] == name and keys[0] in e["stats"]:
            n += 1
            for k in keys:
                total[k] += int(e["stats"].get(k, 0))
    return dict(total, spans=n) if n else None


def index_flops(pairs, heads, dim):
    """The indexer's score of ``pairs`` (query, key) pairs: one ``dim``-
    wide product a head, 2 a multiply-add (the relu, the head weights and
    their sum are noise beside it)."""
    return 2 * heads * dim * pairs


def index_bytes(keys, dim, bytes_per_value=2):
    """Cached indexer keys read once: ``keys`` rows of ``dim``."""
    return keys * dim * bytes_per_value


def topk_bytes(pairs, bytes_per_value=4):
    """The index scores of ``pairs`` (query, key) pairs read once, float32
    — what a per-query top-k must at least look at."""
    return pairs * bytes_per_value


def attention_flops(pairs, heads, qk_dim, v_dim):
    """Softmax attention over ``pairs`` (query, key) pairs: the score and
    the value product a head."""
    return 2 * heads * (qk_dim + v_dim) * pairs


def latent_bytes(rows, row_width, bytes_per_value=2):
    """Latent cache rows read once."""
    return rows * row_width * bytes_per_value


def grouped_bytes(experts_touched, hidden, width, bytes_per_value=2):
    """The three matrices of every HELD expert a live token chose, read
    once a call."""
    return experts_touched * 3 * hidden * width * bytes_per_value


def grouped_flops(assignments, hidden, width):
    """One real row through an expert's three matrices, a held pair."""
    return assignments * 3 * 2 * hidden * width
