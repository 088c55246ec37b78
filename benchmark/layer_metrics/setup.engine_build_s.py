"""Host construction and allocation that is neither weights nor a compile:
the OWN time of ``dstpu.setup.engine`` + ``dstpu.setup.serve`` +
``dstpu.setup.warmup`` (each less the spans nested in it) plus the named
parts of it, ``dstpu.setup.pools`` and ``dstpu.setup.lazy_import`` (orbax
inside ``initialize``, torch inside ``init_inference``), for the spans that
closed before the slice."""
from benchmark import setup_spans


def read(run):
    return setup_spans.engine_build_s(run)
