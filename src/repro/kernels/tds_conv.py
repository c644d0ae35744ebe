"""TDS time-convolution Pallas kernel (causal, strided, slot-batched).

The conv kernels of the acoustic-scoring phase (paper §4.2).  Input blocks
overlap by the (k-1)-frame left halo — each grid step slices its context
out of the resident input, exactly like the shared-memory input windows
the ASRPU setup threads retain between kernels.  Channel mixing is
per-w-column (k taps of (Cin x Cout) matmuls on the MXU), and the conv
epilogue — bias, ReLU, TDS residual — is fused into the kernel so the
activation never round-trips to HBM between conv and epilogue.

A leading slot axis maps to a batch grid dimension: the serving engine
runs every concurrent stream's conv in ONE pallas_call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, b_ref, *rest, k, stride, bt, Cin, Cout, relu):
    # x_ref holds one slot's whole padded input (ASRPU keeps conv inputs
    # resident in shared memory between kernels; TDS inputs are small
    # enough that the VMEM analogue is exact).  Each grid step produces a
    # bt-row tile of one slot.  Tiles are (t, C, W): the 80 W columns
    # sit on lanes and the channels on sublanes, so each tap is a
    # t-batched (Cout, Cin) @ (Cin, W) matmul read straight from the ref
    # along its untiled time axis, with no relayout in the kernel.
    res_ref, o_ref = (rest if len(rest) == 2 else (None, rest[0]))
    start = pl.program_id(1) * (bt * stride)
    acc = jnp.zeros((bt, Cout, x_ref.shape[-1]), jnp.float32)
    for j in range(k):
        xj = x_ref[0, pl.ds(start + j, bt * stride)]   # (bt*stride, Cin, W)
        if stride > 1:
            xj = xj.reshape(bt, stride, Cin, xj.shape[-1])[:, 0]
        wj = jnp.broadcast_to(w_ref[j].astype(jnp.float32)[None],
                              (bt, Cout, Cin))
        acc += jax.lax.dot_general(wj, xj.astype(jnp.float32),
                                   (((2,), (1,)), ((0,), (0,))),
                                   preferred_element_type=jnp.float32)
    acc = acc + b_ref[...][None]
    if relu:
        acc = jnp.maximum(acc, 0.0)
    if res_ref is not None:
        acc = acc + res_ref[0]
    o_ref[0] = acc


@functools.partial(jax.jit,
                   static_argnames=("stride", "bt", "relu", "interpret"))
def tds_conv_pallas(x, w, b, res=None, *, stride=1, bt=32, relu=False,
                    interpret=False):
    """x: (B, k-1+T, W, Cin) left-padded input (a 3-D (k-1+T, W, Cin)
    input is treated as B=1); w: (k, Cin, Cout); b: (Cout,); optional
    res: (B, T // stride, W, Cout) residual added after the ReLU.

    Returns (B, T // stride, W, Cout) (batch squeezed for 3-D inputs),
    matching ref.tds_conv_fused.  Output t consumes
    x[:, t*stride : t*stride + k] (causal window ending at t*stride +
    k - 1 in padded coords).  `bt` is halved until it divides the output
    length (same fallback as ops.int8_matmul's bm), so frame counts that
    are not a multiple of the tile still run.

    The kernel sees (t, C, W) blocks: W (80) on lanes pads each tile by
    128/80, where C (1-23 channels) on lanes would pad it 5.6-128x.  The
    swaps of the last two axes around the call are XLA's.
    """
    squeeze = x.ndim == 3
    if squeeze:
        x = x[None]
        res = None if res is None else res[None]
    k, Cin, Cout = w.shape
    B, Tp, W, _ = x.shape
    T = Tp - (k - 1)
    assert T % stride == 0
    t_out = T // stride
    bt = min(bt, t_out)
    while t_out % bt:
        bt //= 2
    in_specs = [
        pl.BlockSpec((1, Tp, Cin, W), lambda s, i: (s, 0, 0, 0)),
        pl.BlockSpec((k, Cout, Cin), lambda s, i: (0, 0, 0)),
        pl.BlockSpec((Cout, 1), lambda s, i: (0, 0)),
    ]
    args = [x.swapaxes(2, 3), w.swapaxes(1, 2), b.reshape(Cout, 1)]
    tile = pl.BlockSpec((1, bt, Cout, W), lambda s, i: (s, i, 0, 0))
    if res is not None:
        in_specs.append(tile)
        args.append(res.swapaxes(2, 3))
    out = pl.pallas_call(
        functools.partial(_kernel, k=k, stride=stride, bt=bt, Cin=Cin,
                          Cout=Cout, relu=relu),
        grid=(B, t_out // bt),
        in_specs=in_specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((B, t_out, Cout, W), jnp.float32),
        interpret=interpret,
        name="tds_conv",
    )(*args).swapaxes(2, 3)
    return out[0] if squeeze else out
