"""Own device time of the optimizer per train step, first device: part
``optim`` of ``jit_train_step`` — the scopes ``optim.clip`` (gradient norm,
unscale and clip, the overflow check), ``optim.update`` (the optimizer's
update of parameters and moments, the master casts) and
``optim.accumulate`` that ``runtime/engine.py`` puts around them.  None on
a program without the join."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_train_step(run, "optim")
