"""Own device time of the attention PROJECTIONS per train step, first
device: part ``attn.proj`` of ``jit_train_step`` (the q / k / v / o
projections; the flash kernels, read by ``kernel.flash_*_ms_per_step``, and
the module's reshapes and copies around them are part ``attn.core``), every
phase.  None on a program without the join."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_train_step(run, "attn.proj")
