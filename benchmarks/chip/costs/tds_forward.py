"""Model FLOPs of the TDS acoustic model per 80 ms decoding window.

Counted: every multiply-add of the convs, the FC blocks and the head
(2 FLOP each), at the frames each layer sees in one window.  Not
counted: LayerNorm, ReLU, residual adds, log-softmax and the MFCC front
end, which are elementwise or small.  `sizes` is a configuration's
"model" group."""
from __future__ import annotations

FRAMES_PER_WINDOW = 8          # 10 ms MFCC frames in an 80 ms window
WINDOW_S = FRAMES_PER_WINDOW * 0.010


def layers(sizes: dict) -> list:
    """(kind, frames out per window, MACs per output frame) per layer."""
    w = sizes["feat"]
    t = FRAMES_PER_WINDOW
    stages = sizes["stages"]            # [n_blocks, channels, kernel, sub]
    c0 = stages[0][1]
    out = [("conv", t, w * stages[0][2] * 1 * c0)]
    c_prev = c0
    for n_blocks, c, k, sub in stages:
        t //= sub
        out.append(("conv", t, w * sizes["sub_kernel"] * c_prev * c))
        for _ in range(n_blocks):
            out.append(("conv", t, w * k * c * c))
            out += [("fc", t, (w * c) ** 2)] * 2
        c_prev = c
    out.append(("head", t, w * c_prev * sizes["vocab"]))
    return out


def flops_per_window(sizes: dict) -> float:
    return float(sum(2 * t * macs for _, t, macs in layers(sizes)))
