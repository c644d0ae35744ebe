"""Flash-attention Pallas kernel (fwd): online softmax in VMEM, causal +
sliding-window masking, block-skipping for fully-masked KV tiles.

This is the TPU execution path for `models.layers.attention_chunked`
(which is also its oracle, via ref.flash_attention).  Unlike the pure-JAX
scan (which must visit every (q, kv) chunk and mask), the kernel skips
out-of-causal-range and out-of-window KV blocks entirely via pl.when —
the "useful ratio" the §Roofline analysis attributes to the Pallas path.

Grid: (B*H, nq, nkv), kv innermost (sequential); scratch: m, l, acc.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


MASK = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, block_q, block_kv, nkv, q_offset):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q + q_offset           # absolute q positions
    kv_start = ik * block_kv
    # block-level skip: causal (kv entirely after q) / window (entirely before)
    run = jnp.bool_(True)
    if causal:
        run &= kv_start <= q_start + block_q - 1
    if window is not None:
        run &= kv_start + block_kv - 1 >= q_start - window + 1

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)        # (bq, D)
        k = k_ref[0].astype(jnp.float32)        # (bkv, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_kv), 0)
        kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 1)
        mask = jnp.ones((block_q, block_kv), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, MASK)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(ik == nkv - 1)
    def _out():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_kv", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=None,
                           block_q=128, block_kv=128, interpret=False):
    """q: (B,H,Sq,D); k,v: (B,H,Skv,D) (GQA pre-expanded) -> (B,H,Sq,D).

    When Sq < Skv (decode tail), q positions are right-aligned to the end
    of kv (q_offset = Skv - Sq), matching ref.flash_attention.
    """
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0
    nq, nkv = Sq // block_q, Skv // block_kv
    scale = 1.0 / (D ** 0.5)
    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * H, Skv, D)
    vr = v.reshape(B * H, Skv, D)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          block_q=block_q, block_kv=block_kv, nkv=nkv,
                          q_offset=Skv - Sq),
        grid=(B * H, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention",
    )(qr, kr, vr)
    return out.reshape(B, H, Sq, D)
