"""Host time to harvest one finished utterance (ms): the mean length of
the program's `engine.harvest` spans (`Engine._harvest` around
`AsrEngine._finalize_slot`: the final readout's dispatch and the copies
of its result to the host) in the traced span.  Read only where a chip
ran the steps: without one, the host's time would hold the device's
work too."""

SPAN = "engine.harvest"


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    durs = [e.dur for e in run.trace.host if e.name == SPAN]
    return 1e3 * sum(durs) / len(durs) if durs else None
