"""`tds_conv` share of its roofline (%): over the fused step's
executions that the traced span holds whole, the least time the chip
needs for their `tds_conv` calls (per call the larger of operations over
the bf16 peak and bytes over HBM bandwidth, from costs/tds_conv.py at
unpadded shapes for the step's slot rows and windows, which the calls'
own output shapes give), over those calls' summed device time.  An
execution whose call count is not the one the costs expect is left out;
nothing to read where none is left."""

KERNEL = "tds_conv"
STEP_PROGRAM = "jit_step"


def read(run):
    if run.trace is None:
        return None
    cost = run.cost(KERNEL)
    least = secs = 0.0
    for ex in run.trace.executions(STEP_PROGRAM):
        got = ex.calls.get(KERNEL, [])
        shape = cost.step_shape(run.sizes, [s for _d, s in got])
        if shape is None:
            continue
        calls = cost.calls(run.sizes, *shape)
        if len(calls) != len(got):
            continue
        least += sum(max(f / run.peak["bf16_flops_per_s"],
                         nbytes / run.peak["hbm_bytes_per_s"])
                     for f, nbytes in calls)
        secs += sum(d for d, _s in got)
    return 100.0 * least / secs if secs else None
