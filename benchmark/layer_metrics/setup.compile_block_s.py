"""The decode side's compile: the ``dstpu.setup.compile`` spans whose
``program`` is ``decode``, ``spec_block`` or ``spec_verify`` (a server has
one of the three), closed before the slice."""
from benchmark import setup_spans


def read(run):
    return setup_spans.compile_s(run, setup_spans.BLOCK)
