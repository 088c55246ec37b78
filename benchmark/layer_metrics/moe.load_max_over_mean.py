"""Imbalance of the routing: the busiest expert's live tokens over the mean
tokens an expert, per expert-layer call, over the slice's spans
(``moe_max_expert_tokens`` x experts / ``moe_assignments``).  1 is a
perfectly even load; dropless routing computes whatever it is."""
from benchmark import opsbytes_moe


def read(run):
    load = opsbytes_moe.span_load() if run.trace else None
    if not load or not load["moe_assignments"]:
        return None
    experts = run.family.sizes_of(run.cell["config"])["experts"]
    return load["moe_max_expert_tokens"] * experts / load["moe_assignments"]
