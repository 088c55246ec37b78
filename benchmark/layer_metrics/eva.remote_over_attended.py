"""The share of attended keys that are SUMMARIES: the dispatch spans'
``eva_remote_pairs`` over ``eva_local_pairs`` + ``eva_remote_pairs``, chunks
and decode blocks of the slice together.  0 while every context is inside
its first window; at the cell's 4k-13k contexts about a third.  None for a
program whose spans do not carry them."""
from benchmark import opsbytes_evabyte


def read(run):
    return opsbytes_evabyte.remote_share() if run.trace else None
