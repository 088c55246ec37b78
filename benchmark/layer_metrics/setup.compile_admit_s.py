"""The fused admit program's compile (``program`` ``admit``): on first use,
inside the ramp or the warm-up requests, not in ``warmup()``."""
from benchmark import setup_spans


def read(run):
    return setup_spans.compile_s(run, setup_spans.ADMIT)
