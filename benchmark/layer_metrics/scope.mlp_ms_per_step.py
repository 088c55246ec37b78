"""Own device time of the MLP per train step, first device: every XLA
instruction of ``jit_train_step`` whose ``op_name`` the program's table
(``profiler.SCOPE_PARTS``) files under part ``mlp`` — up- and
down-projection and what XLA fuses into them (a fusion counts where its
root is: the down-projection swallows the next LayerNorm's sums) —,
forward, backward and remat's replay together.  Steps are counted as
``kernel.flash_*_ms_per_step`` counts them.  None on a program without the
join (``profiler.device_time_by_scope``)."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_train_step(run, "mlp")
