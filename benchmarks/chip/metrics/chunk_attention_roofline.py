"""`chunk_attention` share of its roofline (%): over the fused step's
executions that the traced span holds whole, the least time the chip
needs for their `chunk_attention` calls (per call the larger of
operations over the bf16 peak and bytes over HBM bandwidth, from
costs/chunk_attention.py at unpadded shapes for the step's slot rows
and windows), over those calls' summed device time.

The trace reduction labels only the kernels it lists, so this reader
finds the kernel's calls itself: device operations named
`chunk_attention.<n>` inside each execution's interval.  The step's
slot rows and windows come from its `hypothesis_unit` calls, one per
decoded frame (a window's chunk of frames) over the step's rows.  An
execution whose call count is not the one the costs expect is left
out; nothing to read where none is left."""
import bisect
import re

KERNEL = "chunk_attention"
FRAMES = "hypothesis_unit"
STEP_PROGRAM = "jit_step"
NAME = re.compile(KERNEL + r"(\.\d+)?$")


def read(run):
    if run.trace is None:
        return None
    cost = run.cost(KERNEL)
    chunk = cost.shape(run.sizes)[0]
    least = secs = 0.0
    for ops, mods in zip(run.trace.ops, run.trace.modules):
        starts = [e.start for e in ops]
        for m in [m for m in mods if m.name == STEP_PROGRAM][1:-1]:
            got, rows = [], []
            i = bisect.bisect_left(starts, m.start)
            while i < len(ops) and ops[i].start < m.end:
                e = ops[i]
                if NAME.match(e.name):
                    got.append(e.dur)
                elif e.label == FRAMES and len(e.shape) == 2:
                    rows.append(e.shape[0])
                i += 1
            if not rows or len(rows) % chunk:
                continue
            calls = cost.calls(run.sizes, rows[0], len(rows) // chunk)
            if len(calls) != len(got):
                continue
            least += sum(max(f / run.peak["bf16_flops_per_s"],
                             nbytes / run.peak["hbm_bytes_per_s"])
                         for f, nbytes in calls)
            secs += sum(got)
    return 100.0 * least / secs if secs else None
