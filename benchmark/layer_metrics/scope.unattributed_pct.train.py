"""Share of the traced slice that ``jit_train_step``'s instructions spend
under no part of the program's table: no ``op_name`` in the compiled
module, or none a row of ``profiler.SCOPE_PARTS`` knows.  ``python3 benchmark/scopes.py
.bench_trace`` lists the largest of them, so that the table can be
extended.  None on a program without the join."""
from benchmark import scopes


def read(run):
    return scopes.unattributed_pct(run, scopes.TRAIN)
