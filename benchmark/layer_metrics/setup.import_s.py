"""The package's import: ``dstpu.setup.import``, first line to last line of
``deepspeed_tpu/__init__.py`` (JAX's own import falls inside it where the
package is what imports JAX first, as in ``benchmark/run.py``)."""
from benchmark import setup_spans


def read(run):
    return setup_spans.summed(setup_spans.closed_before(run.slice_t0),
                              "import")
