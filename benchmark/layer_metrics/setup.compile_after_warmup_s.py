"""JAX's compile-phase seconds (tracing, lowering, backend compile: the
program's ``compile_events``) stamped after the engine's last
``dstpu.setup.warmup`` closed and before the slice ended — the ramp and the
window together.  Today: the admit program and whatever small jits compile
on first use; a recompile inside the window lands here."""
from benchmark import setup_spans


def read(run):
    return setup_spans.compile_after_warmup_s(run)
