"""How late the load generator sent requests (actual send - due), 95th
percentile, on the generator's own clock: a starved generator must not be
read as a fast server."""
from benchmark import stats


def read(run):
    late = run.observed.get("late_s")
    return 1e3 * stats.percentile(late, 95) if late else None
