"""Host time to build one fused step's batch and upload it (ms): the mean
length of the program's `asr.assemble` spans (`AsrEngine._step_slots`:
the gather of each slot's buffered windows into the padded batch, and
its upload) in the traced span.  Read only where a chip ran the steps:
without one, the host's time would hold the device's work too."""

SPAN = "asr.assemble"


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    durs = [e.dur for e in run.trace.host if e.name == SPAN]
    return 1e3 * sum(durs) / len(durs) if durs else None
