"""Submit-to-admission-start wait per request (``RequestResult.queue_s``,
from the engine's request spans; present only with ``serving.tracing`` on,
which the traced run turns on), 95th percentile."""
from benchmark import stats


def read(run):
    waits = [e["queue_s"] for e in run.observed.get("engine_side", {}).values()
             if e["queue_s"] is not None]
    return 1e3 * stats.percentile(waits, 95) if waits else None
