"""Mean share of KV slots that hold a live request, over the scheduler
iterations of the window (``srv.occupancy_trace``)."""


def read(run):
    occ = run.observed.get("occupancy")
    if not occ:
        return None
    return 100.0 * sum(occ) / len(occ) / run.observed["num_slots"]
