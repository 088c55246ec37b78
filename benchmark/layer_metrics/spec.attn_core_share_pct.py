"""Share of the traced slice that the self-drafting programs (the window
block and the prefill chunk, ``opsbytes_glm5.PROGRAMS``) spend in part
``attn.core`` of the program's table: the selection and the attention
themselves — the index scores, the k-th score and the mask, the lane's
absorbed softmax, the chunk's flash attention — outside the projections.
The program's own join of device time by ``op_name``
(``benchmark/scopes.py``); ``scopes.SERVE`` names the plain decode block,
which a self-drafting server never runs."""
from benchmark import opsbytes_glm5 as ob, scopes


def read(run):
    return scopes.part_share_pct(run, ob.PROGRAMS, "attn.core")
