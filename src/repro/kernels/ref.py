"""Pure-jnp oracles for every Pallas kernel (the `ref.py` contract).

Each function is the semantic ground truth its kernel is allclose-tested
against (tests/test_kernels.py sweeps shapes + dtypes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MASK = -1e30


def int8_matmul(xq, wq, xs, ws):
    """xq: (M,K) i8, wq: (K,N) i8, xs: (M,) f32, ws: (N,) f32 -> (M,N) f32.

    int8 x int8 -> int32 accumulate (the MXU path), then per-row/col scales
    — ASRPU's 8-wide int8 MAC with fp32 accumulation, MXU-sized.
    """
    acc = jax.lax.dot_general(
        xq, wq, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs[:, None] * ws[None, :]


def flash_attention(q, k, v, *, causal=True, window=None, scale=None):
    """q: (B,H,Sq,D); k,v: (B,H,Skv,D) (GQA pre-expanded). f32 softmax."""
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jnp.arange(Sq)[:, None] + (Skv - Sq)
    kpos = jnp.arange(Skv)[None, :]
    m = jnp.ones((Sq, Skv), bool)
    if causal:
        m &= kpos <= qpos
    if window is not None:
        m &= (qpos - kpos) < window
    s = jnp.where(m[None, None], s, MASK)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def layernorm(x, scale, bias, eps=1e-5):
    """x: (T, D) any float dtype; f32 statistics."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def rmsnorm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), -1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def logmel(power, fb, dct):
    """power: (T,F) f32, fb: (F,M), dct: (M,C) -> (T,C) MFCC tail."""
    return jnp.log(jnp.maximum(power @ fb, 1e-10)) @ dct


def beam_prune(scores, beam, mask_value=MASK):
    """scores: (N,) f32 -> scores with entries < max - beam set to MASK."""
    best = jnp.max(scores)
    return jnp.where(scores >= best - beam, scores, mask_value)


# ---------------------------------------------------------------------------
# fused hypothesis unit (paper §3.5): hash-merge + beam threshold + top-k
# ---------------------------------------------------------------------------
NEG_INF = -1e30                      # matches core/hypothesis.py
HASH_SENTINEL = np.uint32(0xFFFFFFFF)    # > any 31-bit prefix hash


def _seg_lse(v, ids, num_segments):
    """Per-segment logsumexp of flat `v`, broadcast back per position:
    out[j] = logsumexp(v over j's whole segment).

    max + exp + segment-sum (scatter) instead of a log2(n)-pass
    logaddexp scan — the scan's transcendentals dominated the
    decode-hot-path merge.  Segment terms accumulate in original index
    order — the order in which the sorted-row kernel
    (kernels/hypothesis_unit.py) folds each segment into its head,
    which is what keeps the two bit-identical.  An all-dead channel
    stays exactly NEG_INF (the exp(0)=1 terms of -1e30 entries would
    drift it by +log(count) ulps otherwise)."""
    m = jax.ops.segment_max(v, ids, num_segments=num_segments)
    s = jax.ops.segment_sum(jnp.exp(v - m[ids]), ids,
                            num_segments=num_segments)
    out = (m + jnp.log(s))[ids]
    return jnp.where(out > NEG_INF / 2, out, NEG_INF)


def hypothesis_unit(hashes, pb, pnb, *, k: int, beam: float):
    """Batched fused hypothesis unit, pure jnp (the kernel's oracle).

    hashes: (B, N) int32 31-bit prefix hashes; pb/pnb: (B, N) f32.
    Returns dict of (B, k) arrays: `idx` (selected candidate index into
    the ORIGINAL row — the first occurrence of the selected hash; 0 for
    pruned slots), merged `pb`/`pnb`, and boolean `valid`.

    Sort-free formulation of the same merge: candidates never move.
    A single-operand key sort (XLA's fast path — the (key, iota) pair
    sort behind `argsort` is ~8x slower on CPU) + `searchsorted` assign
    every ORIGINAL position its segment id, the per-segment logsumexp is
    a max + exp + segment-sum over unmoved positions (accumulating in
    original index order, exactly the order the sorted-row kernel path
    sums — the two stay bit-identical), and top-k reads original
    positions directly, so the argsort permutation, its three payload
    gathers, and the order re-mapping all disappear from the decode hot
    path.
    """
    B, n = hashes.shape
    valid_in = jnp.logaddexp(pb, pnb) > NEG_INF / 2
    key = jnp.where(valid_in, hashes.astype(jnp.uint32), HASH_SENTINEL)
    key_sorted = jnp.sort(key, axis=-1)
    ids = jax.vmap(
        lambda ks, kk: jnp.searchsorted(ks, kk, side="left"))(key_sorted, key)
    gids = (ids + jnp.arange(B, dtype=ids.dtype)[:, None] * n).reshape(-1)
    iota = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (B, n))

    pb_m = _seg_lse(pb.reshape(-1), gids, B * n).reshape(B, n)
    pnb_m = _seg_lse(pnb.reshape(-1), gids, B * n).reshape(B, n)
    first = jax.ops.segment_min(iota.reshape(-1), gids, num_segments=B * n)
    rep = (iota == first[gids].reshape(B, n)) & (key != HASH_SENTINEL)
    tot = jnp.where(rep, jnp.logaddexp(pb_m, pnb_m), NEG_INF)
    best = jnp.max(tot, axis=-1, keepdims=True)
    top, pos = jax.lax.top_k(tot, k)
    valid = (top > NEG_INF / 2) & (top >= best - beam)
    idx = jnp.where(valid, pos.astype(jnp.int32), 0)
    opb = jnp.where(valid, jnp.take_along_axis(pb_m, pos, axis=-1), NEG_INF)
    opnb = jnp.where(valid, jnp.take_along_axis(pnb_m, pos, axis=-1),
                     NEG_INF)
    return {"idx": idx, "pb": opb, "pnb": opnb, "valid": valid}


def tds_conv(x, w, b, stride=1):
    """Causal strided time conv. x: (T_pad, W, Cin) already left-padded by
    k-1; w: (k, Cin, Cout); returns (T_out, W, Cout) with
    T_out = (T_pad - k + 1 + stride - 1) // stride ... callers pass
    T_pad = k - 1 + T_in with T_in % stride == 0, giving T_in // stride."""
    k = w.shape[0]
    T_in = x.shape[0] - (k - 1)
    t_out = T_in // stride
    off = (jnp.arange(t_out) * stride)[:, None] + jnp.arange(k)[None, :]
    win = x[off]                                    # (t_out, k, W, Cin)
    return jnp.einsum("tkwc,kcd->twd", win, w) + b


def tds_conv_fused(x, w, b, *, stride=1, relu=False, res=None):
    """Slot-batched causal conv with the ASRPU conv epilogue fused in.

    x: (B, k-1+T, W, Cin); w: (k, Cin, Cout); b: (Cout,); optional
    res: (B, T//stride, W, Cout) residual added AFTER the ReLU (the TDS
    block order).  Returns (B, T//stride, W, Cout).

    One k-tap loop of (B*t_out*W, Cin) x (Cin, Cout) matmuls — the MXU
    sees the slot axis folded into the row dimension — instead of the
    gather-window einsum, which materializes a (t_out, k, W, Cin) window
    tensor per conv per slot.
    """
    B, Tp, W, Cin = x.shape
    k, _, Cout = w.shape
    t_out = (Tp - (k - 1)) // stride
    acc = jnp.zeros((B * t_out * W, Cout), jnp.float32)
    for j in range(k):
        # tap j of output t reads x[:, stride*t + j]
        xj = jax.lax.slice_in_dim(x, j, j + stride * (t_out - 1) + 1,
                                  stride=stride, axis=1)
        acc = acc + xj.reshape(B * t_out * W, Cin).astype(jnp.float32) @ \
            w[j].astype(jnp.float32)
    y = acc.reshape(B, t_out, W, Cout) + b
    if relu:
        y = jnp.maximum(y, 0.0)
    if res is not None:
        y = y + res
    return y
