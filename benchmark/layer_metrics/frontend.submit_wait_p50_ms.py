"""What the way into the engine costs a request: median, over the requests due
in the window, of the ring span ``dstpu.frontend.submit`` — from the loop
thread's hand-off to an executor thread until ``srv.submit`` has returned, so
executor queueing and the wait for the engine lock are inside it.  Needs
``serving.tracing`` (on in the traced run)."""
from benchmark import spans


def read(run):
    return spans.request_median_ms(run, "dstpu.frontend.submit")
