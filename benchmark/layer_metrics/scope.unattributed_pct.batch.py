"""Share of the traced slice that the slot programs' instructions
(``jit_decode_block`` and ``jit_chunk_step``) spend under no part of the
program's table — see ``scope.unattributed_pct.train``.  None on a program
without the join."""
from benchmark import scopes


def read(run):
    return scopes.unattributed_pct(run, scopes.SERVE)
