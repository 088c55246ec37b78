"""Own device time of the two ends of the model per train step, first
device: parts ``head`` (final norm, the vocabulary-wide head matmul of the
chunked loss and its transposes), ``loss`` (log-sum-exp, the gold logit,
the chunk loop's copies) and ``embed`` (token and position tables, the
scatter-add of their gradients) of ``jit_train_step``, every phase.  None
on a program without the join."""
from benchmark import scopes


def read(run):
    return scopes.part_ms_per_train_step(run, "head", "loss", "embed")
