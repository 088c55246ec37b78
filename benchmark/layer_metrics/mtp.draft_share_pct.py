"""Share of the traced slice that the device spends in the multi-token-
prediction module: own device time of the instructions whose ``op_name``
lies under one of the program's ``mtp.*`` scopes (``mtp.combine``,
``mtp.block``, ``mtp.head``), in the window block and in the prefill chunk
that fills the module's rows — the program's own join of device time by
``op_name`` (``benchmark/scopes.py``).  What self-drafting pays for its
drafts; the rejected verify rows are ``spec.rejected_row_share_pct``."""
from benchmark import opsbytes_glm5 as ob, scopes


def read(run):
    joined = scopes.by_part(run, ob.PROGRAMS)
    if joined is None:
        return None
    seconds = sum(s for op_name, s in joined["by_op_name"].items()
                  if any(frame.startswith("mtp.")
                         for frame in op_name.split("/")))
    return 100.0 * seconds / run.trace.window_s if seconds else None
