"""Share of the traced slice that the self-drafting programs'
instructions (``opsbytes_glm5.PROGRAMS``) spend under no part of the
program's table — ``scope.unattributed_pct.batch`` for a server whose
decode program is the window block."""
from benchmark import opsbytes_glm5 as ob, scopes


def read(run):
    return scopes.unattributed_pct(run, ob.PROGRAMS)
