"""The part of set-up spent in the backend compiler (or loading its result
from the persistent cache): JAX's ``backend_compile_duration`` summed by the
program's ``compile_cache.stats()`` over every jit, up to the moment the
profiler's slice opened."""
from benchmark import spans


def read(run):
    phases = spans.compile_phase_seconds(until=run.slice_t0)
    return phases["backend"] if phases else None
