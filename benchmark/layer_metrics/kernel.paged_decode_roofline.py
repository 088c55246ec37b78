"""The paged-decode attention kernel's share of its roofline.

Bytes the calls must read (``opsbytes.paged_decode_bytes``): for every token
a decode step produced inside the traced slice, K and V of the positions it
attended (prompt + tokens so far), every layer — taken from the load
generator's token timestamps, which share the profiler's monotonic clock.
Time: the summed device time of the Mosaic kernel's events inside
``decode_block`` executions.  The bound is memory (819 GB/s): a decode step
does 2 operations per byte read.  Edges blur by a block or two of ~30 a
second; the kernels carry no ``name=``, so they are told from the chunk
kernel by the program they run in."""
from benchmark import opsbytes, trace


def read(run):
    if not run.trace or run.slice_t0 is None or "records" not in run.observed:
        return None
    seconds, calls = run.trace.op_seconds(
        lambda n: trace.is_pallas(n, "attn"), module="decode_block")
    if not calls:
        return None
    lo = run.slice_t0 - run.observed["window_t0"]
    hi = lo + run.slice_s
    z = run.family.sizes_of(run.cell["config"])
    contexts = []
    for rec in run.observed["records"]:
        # token 0 comes from the prefill's admit; decode steps produce 1..
        for i, t in enumerate(rec["token_s"][1:], start=1):
            if lo <= t < hi:
                contexts.append(rec["prompt_len"] + i)
    needed = z["layers"] * opsbytes.paged_decode_bytes(
        contexts, z["heads"], z["d"])
    pct, _bound = opsbytes.roofline_pct(0, needed, seconds, run.peaks)
    return pct
