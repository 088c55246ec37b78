"""The prefill chunk program's compile: the ``dstpu.setup.compile`` spans
whose ``program`` is ``prefill_chunk`` (trace, lower and backend compile of
ONE program, whoever asked for it), closed before the slice."""
from benchmark import setup_spans


def read(run):
    return setup_spans.compile_s(run, setup_spans.CHUNK)
