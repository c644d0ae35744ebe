"""Model FLOPs of the streaming FastConformer-CTC per decoding window
(one chunk: `chunk` encoder frames, 8 x as many 10 ms feature frames).

Counted: every multiply-add (2 FLOP each) of the subsampling convs (3x3
taps per channel, pointwise convs, the output projection), of each
block's feed-forward modules, attention projections, attention products
(scores against `left + chunk` keys and the `left + 2 chunk - 1`
relative encodings, the weighted values) and conv module (pointwise,
depthwise, pointwise), and of the CTC head, at the frames each layer
sees in one window.  Not counted: LayerNorm, activations, softmax,
log-softmax, the log-mel front end, and the projection of the relative
encodings, which depends on the weights alone.  `sizes` is a
configuration's "model" group."""
from __future__ import annotations

FRAME_S = 0.010


def chunk_of(sizes: dict) -> tuple:
    left, right = sizes["att_context_size"]
    return right + 1, left


def window_s(sizes: dict) -> float:
    """Audio seconds of one window: 1.12 s at chunk 14."""
    return chunk_of(sizes)[0] * 2 ** sizes["sub_layers"] * FRAME_S


def macs_per_window(sizes: dict) -> dict:
    """Multiply-adds of one window by part."""
    t, left = chunk_of(sizes)
    D, C = sizes["d_model"], sizes["sub_channels"]
    d_ff = D * sizes["ff_expansion"]
    f = sizes["n_mels"]
    sub = 0
    t_in = t * 2 ** sizes["sub_layers"]
    c_in = 1
    for i in range(sizes["sub_layers"]):
        t_in, f = t_in // 2, f // 2
        sub += t_in * f * C * 9                 # 3x3 taps per channel
        if i:
            sub += t_in * f * C * C             # pointwise
        c_in = C
    sub += t * c_in * f * D                     # flatten -> d_model
    keys, dists = left + t, left + 2 * t - 1
    block = (2 * t * 2 * D * d_ff               # two feed-forward modules
             + 4 * t * D * D                    # q, k, v, out
             + t * (2 * keys + dists) * D       # scores, encodings, values
             + t * D * 2 * D + t * sizes["conv_kernel"] * D + t * D * D)
    return {"subsample": sub, "blocks": block * sizes["n_layers"],
            "head": t * D * sizes["vocab"]}


def flops_per_window(sizes: dict) -> float:
    return float(2 * sum(macs_per_window(sizes).values()))
