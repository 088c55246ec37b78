"""Admission start to the fused admit's dispatch, median over the requests due
in the window: the ring's per-request ``prefill`` phase span (the request's
chunks, and the decode blocks that run between them)."""
from benchmark import spans


def read(run):
    return spans.request_median_ms(run, "prefill", cat="phase")
