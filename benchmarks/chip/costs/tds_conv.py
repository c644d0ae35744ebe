"""Operations and bytes of each `tds_conv` call in one fused step.

A step over `b` slot rows and `w` windows calls the kernel once per conv
(front, three subsampling, one per TDS block).  Each call reads its input
with the k - 1 frames of left context, (b, k - 1 + t_in, W, c_in), the
weights (k, c_in, c_out) and bias, the residual (b, t_out, W, c_out) for
block convs, and writes (b, t_out, W, c_out); all float32.  Shapes are
unpadded: the kernel's (t, C, W) tiles pad W = 80 to 128 lanes and C to
8 sublanes, and that padding shows as lost roofline share.  Operations:
2 per multiply-add, b * t_out * W * k * c_in * c_out multiply-adds."""
from __future__ import annotations

F32 = 4
FRAMES_PER_WINDOW = 8


def calls(sizes: dict, b: int, w: int) -> list:
    """[(flops, bytes)] of the step's conv calls, in order."""
    W = sizes["feat"]
    stages = sizes["stages"]
    out = []

    def conv(t_in, k, stride, c_in, c_out, residual):
        t_out = t_in // stride
        flops = 2 * b * t_out * W * k * c_in * c_out
        nbytes = F32 * (b * (k - 1 + t_in) * W * c_in + k * c_in * c_out
                        + c_out + b * t_out * W * c_out
                        * (2 if residual else 1))
        out.append((float(flops), float(nbytes)))
        return t_out

    t = conv(FRAMES_PER_WINDOW * w, stages[0][2], 1, 1, stages[0][1], False)
    c_prev = stages[0][1]
    for n_blocks, c, k, sub in stages:
        t = conv(t, sizes["sub_kernel"], sub, c_prev, c, False)
        for _ in range(n_blocks):
            conv(t, k, 1, c, c, True)
        c_prev = c
    return out


def step_shape(sizes: dict, shapes: list):
    """(slot rows b, windows w) of one fused step, from the output shapes
    (b, frames, channels, W) of its conv calls: the front conv keeps all
    8 w frames.  None where no call carries a shape."""
    shapes = [s for s in shapes if len(s) == 4]
    if not shapes:
        return None
    front = max(shapes, key=lambda s: s[1])
    if front[1] % FRAMES_PER_WINDOW:
        return None
    return front[0], front[1] // FRAMES_PER_WINDOW
