"""Fused log-mel + DCT Pallas kernel: power @ fb -> log -> @ dct.

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
    o_ref[...] = jax.lax.dot(lg, dct_ref[...])


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def logmel_pallas(power, fb, dct, *, bt=128, interpret=False):
    """power: (T, F) f32; fb: (F, M); dct: (M, C) -> (T, C) f32."""
    T, F = power.shape
    M = fb.shape[1]
    C = dct.shape[1]
    bt = min(bt, T)
    # pad T to a multiple of bt (frames are independent rows)
    pad = (-T) % bt
    if pad:
        power = jnp.pad(power, ((0, pad), (0, 0)), constant_values=1.0)
    Tp = T + pad
    out = pl.pallas_call(
        _kernel,
        grid=(Tp // bt,),
        in_specs=[pl.BlockSpec((bt, F), lambda i: (i, 0)),
                  pl.BlockSpec((F, M), lambda i: (0, 0)),
                  pl.BlockSpec((M, C), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bt, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, C), jnp.float32),
        interpret=interpret,
        name="logmel",
    )(power, fb, dct)
    return out[:T]
