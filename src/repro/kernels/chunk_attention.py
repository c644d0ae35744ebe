"""Chunked relative-position self-attention of a cache-aware streaming
Conformer, as ONE Pallas kernel.

Each chunk of `chunk` queries attends to the `left` cached frames before
it and to its own chunk (NeMo's `chunked_limited` attention: a frame sees
its chunk and the chunks before it inside the left context).  Scores are
Transformer-XL's relative form (arXiv:1901.02860 §3.3, as the Conformer
uses it):

    s[i, j] = ((q_i + u) . k_j + (q_i + v) . pos[i - j]) / sqrt(d_head)

plus an additive key bias (-1e30 on cache rows from before the
utterance's start).  The grid walks (slot row, head); the kernel body
loops over the step's chunks, each over its own window of
`left + chunk` keys.

The positional term is the kernel's own trick: `pos` holds the projected
encodings by DEcreasing distance in `POS_ROWS` = 128 rows (one lane
tile), row m for distance left + chunk - 1 - m, so query row r of a
chunk needs lanes r' = (chunk - 1 - r) + key of (q_r + v) . pos^T — a
per-row shift.  Two lane rotations do it: a strided roll shifts row r
right by r, then a uniform roll shifts every row left by chunk - 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

POS_ROWS = 128                  # one lane tile of relative distances


def _dot_t(a, b):
    """a (m, d) . b (n, d)^T at full f32 precision."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(q_ref, k_ref, v_ref, p_ref, u_ref, vb_ref, m_ref, o_ref, *,
            chunk, left):
    n_chunks = q_ref.shape[2] // chunk
    n_keys = left + chunk
    scale = 1.0 / (q_ref.shape[3] ** 0.5)
    pos = p_ref[0]                                   # (POS_ROWS, d)
    u = u_ref[0]                                     # (1, d)
    vb = vb_ref[0]
    for c in range(n_chunks):
        lo = c * chunk
        q = q_ref[0, 0, lo:lo + chunk, :]            # (chunk, d)
        k = k_ref[0, 0, lo:lo + n_keys, :]           # (n_keys, d)
        v = v_ref[0, 0, lo:lo + n_keys, :]
        bias = m_ref[0, :, lo:lo + n_keys]           # (1, n_keys)
        ac = _dot_t(q + u, k)
        bd = _dot_t(q + vb, pos)                     # (chunk, POS_ROWS)
        bd = pltpu.roll(pltpu.roll(bd, 0, 1, stride=1, stride_axis=0),
                        POS_ROWS - (chunk - 1), 1)[:, :n_keys]
        s = (ac + bd) * scale + bias
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = e / jnp.sum(e, axis=-1, keepdims=True)
        o_ref[0, 0, lo:lo + chunk, :] = jax.lax.dot(
            p, v, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def chunk_attention_pallas(q, k, v, pos, u, vb, bias, *, chunk,
                           interpret=False):
    """q: (B, H, T, d), T a whole number of chunks; k, v: (B, H, left + T,
    d), the cached frames first; pos: (H, POS_ROWS, d); u, vb: (H, 1,
    d); bias: (B, 1, left + T) additive key bias.  Returns (B, H, T, d)
    float32."""
    B, H, T, d = q.shape
    S = k.shape[2]
    left = S - T
    assert T % chunk == 0, (T, chunk)
    assert pos.shape[1] == POS_ROWS, pos.shape
    # the rolled lanes must not wrap: distances span left + 2 chunk - 1
    assert left + 2 * chunk - 1 <= POS_ROWS, (left, chunk)
    row = lambda b, h: (b, h, 0, 0)                  # noqa: E731
    head = lambda b, h: (h, 0, 0)                    # noqa: E731
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, left=left),
        grid=(B, H),
        in_specs=[pl.BlockSpec((1, 1, T, d), row),
                  pl.BlockSpec((1, 1, S, d), row),
                  pl.BlockSpec((1, 1, S, d), row),
                  pl.BlockSpec((1, POS_ROWS, d), head),
                  pl.BlockSpec((1, 1, d), head),
                  pl.BlockSpec((1, 1, d), head),
                  pl.BlockSpec((1, 1, S), lambda b, h: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, T, d), row),
        out_shape=jax.ShapeDtypeStruct((B, H, T, d), jnp.float32),
        interpret=interpret,
        name="chunk_attention",
    )(q, k, v, pos, u, vb, bias)
