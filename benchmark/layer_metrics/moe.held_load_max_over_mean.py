"""Imbalance of the routing over the experts a chip HOLDS: the busiest
held expert's live tokens over the mean tokens a held expert, per
expert-layer call, over the slice's spans (``moe_max_expert_tokens`` x held
experts / ``moe_assignments``; both count the held experts only, the
choices that fell elsewhere are ``moe_assignments_elsewhere``).  The held
count comes from the configuration (``held_experts``);
``moe.load_max_over_mean`` divides by all the experts a router scores and
is the metric of a model that holds them all.  None where no span carries
``moe_assignments_elsewhere``."""
from benchmark import opsbytes_dots3 as ob, opsbytes_moe


def read(run):
    if not run.trace:
        return None
    shared = any(ob.span_sums(name, ("moe_assignments_elsewhere",))
                 for name in (ob.ADMIT_WAIT, "dstpu.sched.commit"))
    load = opsbytes_moe.span_load() if shared else None
    if not load or not load["moe_assignments"]:
        return None
    held = run.family.sizes_of(run.cell["config"])["held"][1]
    return load["moe_max_expert_tokens"] * held / load["moe_assignments"]
