"""Fused log-mel + DCT Pallas kernel: power @ fb -> log -> @ dct.

Without the DCT matrix the kernel stops at the log and returns log-mel
features (the Conformer's front end); with it, the MFCC tail (TDS's).

The post-FFT tail of MFCC extraction (paper Fig. 3) fused into one VMEM
round-trip; the filterbank and DCT matrices are small enough to reside in
VMEM whole (80 x 257 and 80 x 80 — they are the "model memory" residents
of the feature kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(p_ref, fb_ref, dct_ref, o_ref):
    mel = jax.lax.dot(p_ref[...], fb_ref[...])
    lg = jnp.log(jnp.maximum(mel, 1e-10))
    o_ref[...] = lg if dct_ref is None else jax.lax.dot(lg, dct_ref[...])


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def logmel_pallas(power, fb, dct=None, *, bt=128, interpret=False):
    """power: (T, F) f32; fb: (F, M); dct: (M, C) -> (T, C) f32.  Without
    `dct` the kernel stops at the log: (T, M) log-mel features."""
    T, F = power.shape
    M = fb.shape[1]
    C = M if dct is None else dct.shape[1]
    bt = min(bt, T)
    # pad T to a multiple of bt (frames are independent rows)
    pad = (-T) % bt
    if pad:
        power = jnp.pad(power, ((0, pad), (0, 0)), constant_values=1.0)
    Tp = T + pad
    in_specs = [pl.BlockSpec((bt, F), lambda i: (i, 0)),
                pl.BlockSpec((F, M), lambda i: (0, 0))]
    args = [power, fb]
    if dct is None:
        def kernel(p_ref, fb_ref, o_ref):
            _kernel(p_ref, fb_ref, None, o_ref)
    else:
        kernel = _kernel
        in_specs.append(pl.BlockSpec((M, C), lambda i: (0, 0)))
        args.append(dct)
    out = pl.pallas_call(
        kernel,
        grid=(Tp // bt,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, C), jnp.float32),
        interpret=interpret,
        name="logmel",
    )(*args)
    return out[:T]
