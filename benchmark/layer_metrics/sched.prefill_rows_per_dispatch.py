"""Mean live chunk rows a prefill dispatch over the slice: the ``rows`` of
each ``dstpu.sched.dispatch.prefill_chunk`` span in the profiler's trace (a
dispatch is one pass of the weights for up to ``rows_cap`` chunks of
``prefill_chunk`` tokens; ``step.prefill_chunk_ms`` over this number is the
device time a chunk).  None for a program whose spans carry no ``rows`` — a
commit from before the chunk program took several."""
from benchmark import spans

DISPATCH = "dstpu.sched.dispatch.prefill_chunk"


def read(run):
    if not run.trace:
        return None
    rows = [int(e["stats"]["rows"]) for e in spans.host_spans()
            if e["name"] == DISPATCH and "rows" in e["stats"]]
    return sum(rows) / len(rows) if rows else None
