"""Weights onto the device, and in training the optimizer's state beside
them: ``dstpu.setup.weights`` + ``dstpu.setup.optimizer_state`` (host time:
cast, plan, placement and the allocation's dispatch)."""
from benchmark import setup_spans


def read(run):
    return setup_spans.summed(setup_spans.closed_before(run.slice_t0),
                              "weights", "optimizer_state")
