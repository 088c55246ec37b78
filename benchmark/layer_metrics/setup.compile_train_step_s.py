"""The fused train step's compile (``program`` ``train_step``), every rung
of the remat ladder that was tried: a cache hit's load counts here too."""
from benchmark import setup_spans


def read(run):
    return setup_spans.compile_s(run, setup_spans.TRAIN_STEP)
