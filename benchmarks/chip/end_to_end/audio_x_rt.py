"""Seconds of audio decoded in the window, over the window's seconds:
each utterance's audio spread evenly over its time in the engine, and
the share of that time inside the window counted (bulk.py)."""


def read(run):
    return run.measured["audio_s"] / run.measured["window_s"]
