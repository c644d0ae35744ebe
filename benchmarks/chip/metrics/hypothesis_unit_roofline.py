"""`hypothesis_unit` share of its roofline (%): over the fused step's
executions that the traced span holds whole, the least time the chip
needs for their `hypothesis_unit` calls (bytes over HBM bandwidth, from
costs/hypothesis_unit.py at unpadded shapes: one call per decoded frame
over the step's slot rows, which each call's output shape (rows, beam)
gives), over those calls' summed device time."""

KERNEL = "hypothesis_unit"
STEP_PROGRAM = "jit_step"


def read(run):
    if run.trace is None:
        return None
    cost = run.cost(KERNEL)
    least = secs = 0.0
    for ex in run.trace.executions(STEP_PROGRAM):
        for d, shape in ex.calls.get(KERNEL, []):
            if len(shape) != 2:
                continue
            (f, nbytes), = cost.calls(run.sizes, run.dec, shape[0], 1)
            least += max(f / run.peak["bf16_flops_per_s"],
                         nbytes / run.peak["hbm_bytes_per_s"])
            secs += d
    return 100.0 * least / secs if secs else None
