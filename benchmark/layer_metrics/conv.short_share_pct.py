"""Share of the traced slice that the device spends in the gated
short-convolution OPERATOR of a model that has one (``models/lfm2.py``
``ShortConv``): part ``conv.short`` of the slot programs — the module's
``in_proj`` and ``out_proj`` and, under the scope ``conv.short``, ``B * X``,
the taps, the state's read and write and ``C * conv`` (XLA fuses those into
the projections' fusions, so the operator is read whole).  The metric PR 33
left out: the scope is in the compiled module, not in the trace's event
names.  None on a program without the join."""
from benchmark import scopes


def read(run):
    return scopes.part_share_pct(run, scopes.SERVE, "conv.short")
