"""Share of the traced window in which no operation ran on the device (1 -
union of device-op intervals / window, averaged over the chips)."""


def read(run):
    return run.trace.idle_pct() if run.trace and run.trace.window_s else None
