"""Profiler range annotation — reference ``deepspeed/utils/nvtx.py``
(``instrument_w_nvtx`` wrapping hot functions in NVTX ranges).

TPU analog: the reference's names, routed through the one span helper
(``monitor/trace.py::span``) — a ``jax.profiler.TraceAnnotation`` range in
the XLA/xprof trace exactly where NVTX ranges show up in nsys, and a span
in the process tracer's ring when that is on."""

import functools

from deepspeed_tpu.monitor.trace import span


def instrument_w_nvtx(func):
    """Decorator: record ``func``'s span in profiler traces."""

    @functools.wraps(func)
    def wrapped(*args, **kwargs):
        with span(func.__qualname__):
            return func(*args, **kwargs)

    return wrapped


def range_push(name):
    """Imperative range open (reference ``accelerator.range_push``)."""
    _stack.append(span(name).__enter__())


def range_pop():
    if _stack:
        _stack.pop().__exit__(None, None, None)


_stack = []
