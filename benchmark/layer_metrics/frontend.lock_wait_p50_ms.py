"""How much of a submit is the engine lock: median, over the requests due in
the window, of ``lock_wait_s`` on the ring span ``dstpu.frontend.submit`` —
that submit's own wait in ``InstrumentedRLock.acquire``, which the scheduler
thread holds across a whole iteration, its blocking reads of device results
included."""
from benchmark import spans


def read(run):
    return spans.request_median_ms(
        run, "dstpu.frontend.submit",
        value=lambda s: s["args"].get("lock_wait_s"))
