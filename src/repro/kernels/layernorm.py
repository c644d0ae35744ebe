"""Fused LayerNorm / RMSNorm Pallas kernel (f32 statistics, row-tiled).

One pass per row block: mean/var reduction + normalize + affine, fused so
x is read from HBM once (the separate mean/var/normalize HLO chain reads
it three times — this is a memory-roofline kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, s_ref, b_ref, o_ref, *, eps, kind):
    x = x_ref[...].astype(jnp.float32)
    if kind == "layernorm":
        mu = x.mean(axis=-1, keepdims=True)
        var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps)
    else:
        var = (x ** 2).mean(axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + eps)
    y = y * s_ref[...][None, :]
    if b_ref is not None:
        y = y + b_ref[...][None, :]
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kind", "eps", "bt", "interpret"))
def norm_pallas(x, scale, bias=None, *, kind="layernorm", eps=1e-5, bt=256,
                interpret=False):
    """x: (T, D); scale/bias: (D,). kind: layernorm | rmsnorm.

    Rows are independent, so T is padded up to a multiple of the row
    tile (zero rows normalize to finite values under the eps guard) and
    the pad is sliced off — any row count runs, not just multiples of
    `bt`."""
    T, D = x.shape
    bt = min(bt, T)
    pad = (-T) % bt
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    args = [x, scale] + ([bias] if bias is not None else [])
    in_specs = [pl.BlockSpec((bt, D), lambda i: (i, 0)),
                pl.BlockSpec((D,), lambda i: (0,))]
    if bias is not None:
        in_specs.append(pl.BlockSpec((D,), lambda i: (0,)))
        kernel = functools.partial(_kernel, eps=eps, kind=kind)
    else:
        def kernel(x_ref, s_ref, o_ref):
            _kernel(x_ref, s_ref, None, o_ref, eps=eps, kind=kind)
    Tp = T + pad
    out = pl.pallas_call(
        kernel,
        grid=(Tp // bt,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, D), x.dtype),
        interpret=interpret,
        name=kind,
    )(*args)
    return out[:T]
