"""Device time of one fused decoding step (ms): the summed device time of
the step program's executions in the traced span over their count."""

STEP_PROGRAM = "jit_step"


def read(run):
    if run.trace is None:
        return None
    secs, n = run.trace.module_time(STEP_PROGRAM)
    return 1e3 * secs / n if n else None
