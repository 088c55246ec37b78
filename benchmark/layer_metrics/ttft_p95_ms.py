"""The tail of time to first token: first streamed token seen - time the
request was DUE, 95th percentile over all requests due in the window (a
failed or unfinished request counts with the drain limit).  Made by queueing
for a scheduler iteration and the prefill budget; it swings 5-7% between
identical runs of 320 requests, so it is read here and the median is judged
(PERF.md §2)."""
from benchmark import stats


def read(run):
    ttft = run.observed.get("ttft_s")
    return 1e3 * stats.percentile(ttft, 95) if ttft else None
