"""Operations and bytes of each `chunk_attention` call in one fused step.

The streaming Conformer calls the kernel once per layer per step, over
`b` slot rows and `w` windows (one chunk of `chunk` frames each).  Per
slot row and head it reads the step's queries (T = w * chunk frames),
the keys and values of the `left` cached frames and the step's frames,
the projected encodings of the `left + 2 chunk - 1` relative distances a
chunk uses, u and v, and the key bias; it writes the context (T frames);
all float32.  Shapes are unpadded: the kernel holds the distances in a
128-row tile, and d_head = 64 fills half of a 128-lane vector register.
Operations: 2 per multiply-add of the three products of each chunk's
queries: (q + u) . keys and p . values over `left + chunk` keys, and
(q + v) . encodings over the distances.  Softmax is not counted."""
from __future__ import annotations

F32 = 4


def shape(sizes: dict) -> tuple:
    """(chunk, left, heads, d_head) of a configuration's sizes."""
    left, right = sizes["att_context_size"]
    h = sizes["n_heads"]
    return right + 1, left, h, sizes["d_model"] // h


def calls(sizes: dict, b: int, w: int) -> list:
    """[(flops, bytes)] of the step's chunk_attention calls, one per
    layer."""
    chunk, left, h, dh = shape(sizes)
    t = w * chunk
    keys = left + chunk
    dists = left + 2 * chunk - 1
    flops = 2 * b * h * w * chunk * dh * (2 * keys + dists)
    nbytes = F32 * (b * h * dh * (2 * t + 2 * (left + t)) + h * dists * dh
                    + 2 * h * dh + b * (left + t))
    return [(float(flops), float(nbytes))] * sizes["n_layers"]
