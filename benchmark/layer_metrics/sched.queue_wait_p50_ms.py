"""Submit to admission start, median over the requests due in the window: the
ring's per-request ``queue`` phase span (the part of the judged median first
token that is waiting for a scheduler iteration and the prefill budget)."""
from benchmark import spans


def read(run):
    return spans.request_median_ms(run, "queue", cat="phase")
