"""What the HTTP front end adds to a first token: median over completed
requests of (first token seen by the client - request sent) minus the
engine's own ``ttft_s`` (submit to first token processed)."""
from benchmark import stats


def read(run):
    engine = run.observed.get("engine_side")
    if not engine:
        return None
    over = [rec["token_s"][0] - rec["sent_s"] - engine[rec["index"]]["ttft_s"]
            for rec in run.observed["records"]
            if rec["token_s"] and rec["index"] in engine
            and engine[rec["index"]]["ttft_s"] is not None]
    return 1e3 * stats.percentile(over, 50) if over else None
