"""The part of set-up spent in Python tracing and lowering: JAX's
``jaxpr_trace_duration`` + ``jaxpr_to_mlir_module_duration`` summed by the
program's ``compile_cache.stats()`` over every jit, up to the moment the
profiler's slice opened (nothing compiles in the window; the comparison with
the reference afterwards is left out)."""
from benchmark import spans


def read(run):
    phases = spans.compile_phase_seconds(until=run.slice_t0)
    return phases["trace"] + phases["lower"] if phases else None
