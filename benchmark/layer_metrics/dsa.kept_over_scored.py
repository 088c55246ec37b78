"""What the learned selection keeps of what it scores: the dispatch spans'
``dsa_keys_kept`` over ``dsa_keys_scored``, chunks and decode blocks of the
slice together.  1 while every context is under ``index_topk``; at the
cell's 8k-16k contexts about a quarter."""
from benchmark import opsbytes_dots3 as ob


def read(run):
    if not run.trace:
        return None
    keys = ("dsa_keys_scored", "dsa_keys_kept")
    sums = [s for s in (ob.span_sums(ob.CHUNK, keys),
                        ob.span_sums(ob.DECODE, keys)) if s]
    scored = sum(s["dsa_keys_scored"] for s in sums)
    return sum(s["dsa_keys_kept"] for s in sums) / scored if scored else None
