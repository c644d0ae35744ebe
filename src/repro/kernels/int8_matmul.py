"""int8 x int8 -> int32 matmul Pallas kernel with per-row/col scales.

TPU mapping of ASRPU's 8-wide int8 MAC with fp32 accumulation (paper §3.4):
the MXU is the 128x128 systolic generalization.  The paper's "partition FC
layers into <=1MB model-memory kernels" (§5.2) is exactly the BlockSpec
HBM->VMEM tiling here: each (bk x bn) weight tile is staged into VMEM and
double-buffered by the Pallas pipeline — same insight, TPU memory sizes.

Grid (M/bm, N/bn, K/bk), K innermost; int32 accumulator in VMEM scratch;
scales applied at the final K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(xq_ref, wq_ref, xs_ref, ws_ref, o_ref, acc_ref, *, nk):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        xq_ref[...], wq_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(ik == nk - 1)
    def _done():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * xs_ref[...] * ws_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul_pallas(xq, wq, xs, ws, *, bm=128, bn=128, bk=128,
                       interpret=False):
    """xq: (M,K) i8; wq: (K,N) i8; xs: (M,) f32; ws: (N,) f32 -> (M,N) f32."""
    M, K = xq.shape
    N = wq.shape[1]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    nk = K // bk
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            # scales as 2-D (bm, 1) / (1, bn) tiles: a 1-D block of a
            # longer vector gets an XLA layout Mosaic refuses
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="int8_matmul",
    )(xq, wq, xs.reshape(M, 1), ws.reshape(1, N))
