"""Model FLOP utilization of the traced span (%): the acoustic model's
FLOPs for the audio that the span decoded, at one 80 ms window per
step of the model, over the span's length times the chip's bf16 peak.
The audio is the benchmark's own count (bulk.py: each utterance's audio
spread evenly over its time in the engine, and the share of that time
inside the span taken), so no program counter or kernel enters it."""


def read(run):
    audio_s = run.measured.get("trace_audio_s")
    if run.trace is None or not audio_s or not run.trace.chips:
        return None
    cost = run.cost("tds_forward")
    flops = audio_s / cost.WINDOW_S * cost.flops_per_window(run.sizes)
    return 100.0 * flops / (run.trace.window_s
                            * run.peak["bf16_flops_per_s"])
