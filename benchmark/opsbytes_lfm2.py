"""What the LFM2 family's per-layer metrics read beside the expert load
(``opsbytes_moe.py``): the cache's split between pages and fixed-size state
on the decode dispatch spans.  Kept with the benchmark so that no PR that
claims a gain can change the count.

``dstpu.sched.dispatch.decode`` (``docs/observability.md``) carries, for a
model with a state kind: ``state_rows`` (state rows the block reads and
writes: one a live slot and step), ``state_bytes`` (bytes of the state rows
slots hold) and ``kv_bytes_mapped`` (bytes of the pages slots hold).  A
program without them — another model, a parent commit — has none: the
function returns None and the metric is left out."""

from benchmark import spans

DECODE = "dstpu.sched.dispatch.decode"


def state_share(path=None):
    """Mean over the slice's decode dispatches of ``state_bytes /
    (state_bytes + kv_bytes_mapped)``; None where no span carries them."""
    shares = []
    for e in spans.host_spans(path):
        if e["name"] == DECODE and "state_bytes" in e["stats"]:
            state = int(e["stats"]["state_bytes"])
            total = state + int(e["stats"].get("kv_bytes_mapped", 0))
            if total:
                shares.append(state / total)
    return sum(shares) / len(shares) if shares else None
