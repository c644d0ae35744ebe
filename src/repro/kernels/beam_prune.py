"""Hypothesis-unit beam-threshold prune as a Pallas kernel (paper §3.5).

Two grid passes over the candidate score vector: pass 0 reduces the
global max into SMEM scratch; pass 1 masks scores below (max - beam).
This standalone threshold stage predates the fused hypothesis unit
(kernels/hypothesis_unit.py merges + thresholds + top-k selects in one
pallas_call — the decode hot path uses that); it survives as the
minimal two-pass reduction example and is still parity-tested.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


MASK = -1e30


def _kernel(s_ref, o_ref, best_ref, *, beam):
    phase = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when((phase == 0) & (i == 0))
    def _init():
        best_ref[0] = -jnp.inf

    @pl.when(phase == 0)
    def _reduce():
        best_ref[0] = jnp.maximum(best_ref[0], jnp.max(s_ref[...]))

    @pl.when(phase == 1)
    def _mask():
        thr = best_ref[0] - beam
        s = s_ref[...]
        o_ref[...] = jnp.where(s >= thr, s, MASK)


@functools.partial(jax.jit, static_argnames=("beam", "bn", "interpret"))
def beam_prune_pallas(scores, beam, *, bn=1024, interpret=False):
    """scores: (N,) f32 -> pruned scores (entries < max - beam -> -1e30)."""
    N = scores.shape[0]
    bn = min(bn, N)
    pad = (-N) % bn
    if pad:
        scores = jnp.pad(scores, (0, pad), constant_values=MASK)
    Np = N + pad
    beam = float(beam)  # static
    out = pl.pallas_call(
        functools.partial(_kernel, beam=beam),
        grid=(2, Np // bn),
        in_specs=[pl.BlockSpec((bn,), lambda p, i: (i,))],
        out_specs=pl.BlockSpec((bn,), lambda p, i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Np,), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="beam_prune",
    )(scores)
    return out[:N]
