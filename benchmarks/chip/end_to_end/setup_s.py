"""Seconds from the start of the process to the opening of the measured
window: JAX start, data and weights from the seed, engine build, the
warm-up of every step entry, and the traffic's ramp."""


def read(run):
    return run.setup_s
