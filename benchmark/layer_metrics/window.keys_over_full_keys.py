"""How much the window saves on this traffic: the dispatch spans'
``window_keys`` over ``full_keys``, a layer of each kind (the sums are over
each kind's layers), chunks and decode blocks of the slice together.  1.0
while no context has passed the window; at the cell's lognormal contexts
about a third.  None for a program whose spans do not carry them."""
from benchmark import opsbytes_trinity as ob


def read(run):
    if not run.trace:
        return None
    kinds = run.family.sizes_of(run.cell["config"])["kinds"]
    return ob.keys_ratio(kinds.count("sliding_attention"),
                         kinds.count("full_attention"))
