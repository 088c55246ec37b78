"""Share of the traced window in which a collective runs on device 0 and no
other operation does — ZeRO-3's communication that compute did not hide."""


def read(run):
    if not run.trace or len(run.trace.device_planes) < 2:
        return None
    return run.trace.exposed_collective_pct()[0]
