"""Share of the traced span in which no operation ran on the device (%):
100 * (1 - union of operation intervals / span)."""


def read(run):
    if run.trace is None:
        return None
    idle = run.trace.idle_share()
    return None if idle is None else 100.0 * idle
