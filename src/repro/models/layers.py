"""Core NN layers: norms, RoPE (standard / 2d / M-RoPE), GQA attention.

Attention has two execution paths:
  * `attention_chunked` — prefill/training: lax.scan over q-chunks with an
    inner online-softmax scan over kv-chunks (flash-attention structure in
    pure JAX; the Pallas kernel in `repro.kernels.flash_attention` is the
    TPU-optimized equivalent and is validated against this).
  * `attention_decode`  — single-query attention against a (ring-buffer)
    KV cache with absolute per-slot positions, supporting causal masking
    and sliding windows.

All softmax math is fp32 regardless of the activation dtype.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

MASK_VALUE = -1e30


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def init_norm(d: int, kind: str, dtype=jnp.float32) -> dict:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p: dict, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(var + eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# linear
# ---------------------------------------------------------------------------
def init_linear(key, d_in: int, d_out: int, bias: bool = False,
                dtype=jnp.bfloat16) -> dict:
    std = 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * std).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: dict, x: jax.Array) -> jax.Array:
    if "wq" in p:
        # int8 serving weights (ASRPU's 8-bit MAC): stored/gathered as
        # int8 + per-output-channel scales, dequantized at use
        w = p["wq"].astype(x.dtype) * p["wscale"].astype(x.dtype)[None, :]
        y = jnp.einsum("...d,df->...f", x, w)
    else:
        y = jnp.einsum("...d,df->...f", x, p["w"])
    if "b" in p:
        y = y + p["b"]
    return y


def quantize_linear(p: dict) -> dict:
    """{'w': (din,dout), 'b'?} -> {'wq': int8, 'wscale': (dout,) f32, 'b'?}.

    Symmetric per-output-channel int8 (the serving-weight format: 4x less
    HBM residency and 4x less FSDP-gather wire than bf16-upcast-to-f32)."""
    w = p["w"].astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=0) / 127.0
    q = jnp.clip(jnp.round(w / jnp.maximum(scale[None, :], 1e-12)),
                 -127, 127).astype(jnp.int8)
    out = {"wq": q, "wscale": scale}
    if "b" in p:
        out["b"] = p["b"]
    return out


def quantize_params_for_serving(params: dict) -> dict:
    """Quantize every >=2D dense linear 'w' in an LM param tree to int8
    (embeddings, norms, MoE expert tensors and SSM params stay as-is)."""
    # skip: embeddings (lookup), router (fp32 by design), depthwise conv,
    # and the SSD dt/B/C projections — exp(cumsum(dt·A)) amplifies their
    # quantization error (jamba logits drifted 46% with them int8; they
    # are <1% of parameters)
    skip = ("embed", "router", "conv_x", "w_dt", "w_B", "w_C")

    def rec(tree, path=()):
        if isinstance(tree, dict):
            if any(s in path for s in skip):
                return {k: rec(v, path + (k,)) for k, v in tree.items()} \
                    if isinstance(tree, dict) else tree
            if "w" in tree and getattr(tree["w"], "ndim", 0) == 2:
                return quantize_linear(tree)
            if "w" in tree and getattr(tree["w"], "ndim", 0) == 3:
                # stacked-layer linear (leading repeat axis)
                w = tree["w"].astype(jnp.float32)
                scale = jnp.max(jnp.abs(w), axis=1) / 127.0   # (R, dout)
                q = jnp.clip(jnp.round(w / jnp.maximum(scale[:, None, :],
                                                       1e-12)),
                             -127, 127).astype(jnp.int8)
                out = {"wq": q, "wscale": scale}
                if "b" in tree:
                    out["b"] = tree["b"]
                return out
            return {k: rec(v, path + (k,)) for k, v in tree.items()}
        return tree
    return rec(params)


def activation(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# RoPE family
# ---------------------------------------------------------------------------
def _rope_rotate(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate all of x's last dim. x: (..., S, H, D); pos: broadcastable (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))          # (half,)
    ang = pos.astype(jnp.float32)[..., None, None] * freqs  # (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


def apply_rope(x: jax.Array, positions: jax.Array, mode: str,
               theta: float) -> jax.Array:
    """x: (B, S, H, D). positions: (B, S) int, or (B, S, 3) for mrope."""
    if mode == "none":
        return x
    if mode == "rope":
        return _rope_rotate(x, positions, theta)
    if mode == "rope2d":
        # chatglm: rotary on the first half of the head dim only
        d = x.shape[-1]
        rot = _rope_rotate(x[..., : d // 2], positions, theta)
        return jnp.concatenate([rot, x[..., d // 2:]], axis=-1)
    if mode == "mrope":
        # positions: (B, S, 3) (temporal, h, w); split head dim in 3 sections
        d = x.shape[-1]
        s0 = (d // 3) & ~1   # even sections
        s1 = s0
        s2 = d - s0 - s1
        parts, off = [], 0
        for i, sec in enumerate((s0, s1, s2)):
            parts.append(_rope_rotate(x[..., off:off + sec],
                                      positions[..., i], theta))
            off += sec
        return jnp.concatenate(parts, axis=-1)
    raise ValueError(mode)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def _mask(qpos, kpos, causal: bool, window: Optional[int]):
    """qpos: (B, Sq), kpos: (B, Skv) -> bool (B, Sq, Skv). kpos<0 = invalid."""
    m = kpos[:, None, :] >= 0
    if causal:
        m &= kpos[:, None, :] <= qpos[:, :, None]
    if window is not None:
        m &= (qpos[:, :, None] - kpos[:, None, :]) < window
    return m


def attention_chunked(q, k, v, qpos, kpos, *, causal=True,
                      window: Optional[int] = None,
                      chunk_q: int = 512, chunk_kv: int = 1024,
                      sharder=None) -> jax.Array:
    """Flash-structured attention.

    q: (B, Sq, H, D); k, v: (B, Skv, K, D) with K | H (GQA).
    qpos: (B, Sq) int32 absolute positions; kpos: (B, Skv).
    Returns (B, Sq, H, D).
    """
    B, Sq, H, D = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(D)
    cq = min(chunk_q, Sq)
    ckv = min(chunk_kv, Skv)
    assert Sq % cq == 0 and Skv % ckv == 0, (Sq, cq, Skv, ckv)
    nq, nkv = Sq // cq, Skv // ckv

    qg = q.reshape(B, nq, cq, K, G, D).transpose(1, 0, 3, 4, 2, 5)  # nq,B,K,G,cq,D
    qp = qpos.reshape(B, nq, cq).transpose(1, 0, 2)                 # nq,B,cq
    kc = k.reshape(B, nkv, ckv, K, D).transpose(1, 0, 3, 2, 4)      # nkv,B,K,ckv,D
    vc = v.reshape(B, nkv, ckv, K, D).transpose(1, 0, 3, 2, 4)
    kp = kpos.reshape(B, nkv, ckv).transpose(1, 0, 2)               # nkv,B,ckv
    if sharder is not None:
        # shard the intra-tile cq dim over 'model', replicate kv chunks:
        # every tensor inside the two scans is then local (see Sharder)
        qg = sharder.attn_q(qg)
        kc = sharder.attn_kv_chunks(kc)
        vc = sharder.attn_kv_chunks(vc)

    # flash-attention backward = recompute: checkpoint both loop levels so
    # the (cq x ckv) score/prob tiles are never saved as scan residuals
    # (without this, training residuals are O(S^2) and blow past HBM).
    @jax.checkpoint
    def q_block(args):
        qi, qpi = args  # (B,K,G,cq,D), (B,cq)

        @jax.checkpoint
        def kv_step(carry, xs):
            m_i, l_i, acc = carry
            ki, vi, kpi = xs
            # bf16 x bf16 -> f32 on the MXU (preferred_element_type);
            # upcasting ki/vi materialized f32 copies of every kv chunk
            # per (q-chunk, kv-chunk, layer) — 84 TB/device on 72b prefill
            s = jnp.einsum("bkgqd,bkcd->bkgqc", qi, ki,
                           preferred_element_type=jnp.float32) * scale
            msk = _mask(qpi, kpi, causal, window)[:, None, None]     # B,1,1,cq,ckv
            s = jnp.where(msk, s, MASK_VALUE)
            m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_i - m_new)
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(msk, p, 0.0)
            l_new = l_i * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqc,bkcd->bkgqd", p.astype(vi.dtype), vi,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), None

        init = (jnp.full((B, K, G, cq), -jnp.inf, jnp.float32),
                jnp.zeros((B, K, G, cq), jnp.float32),
                jnp.zeros((B, K, G, cq, D), jnp.float32))
        (m_f, l_f, acc), _ = lax.scan(kv_step, init, (kc, vc, kp))
        return acc / jnp.maximum(l_f, 1e-30)[..., None]  # (B,K,G,cq,D)

    outs = lax.map(q_block, (qg, qp))                      # nq,B,K,G,cq,D
    if sharder is not None:
        outs = sharder.attn_q(outs)
    outs = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, D)
    return outs.astype(q.dtype)


def attention_decode(q, k_cache, v_cache, qpos, kpos, *,
                     window: Optional[int] = None,
                     k_new=None, v_new=None) -> jax.Array:
    """Single-token attention against a cache.

    q: (B, 1, H, D); caches: (B, Sc, K, D); qpos: (B,) int32;
    kpos: (Sc,) int32 absolute positions of cache slots (-1 = empty), or
    (B, Sc) when each batch row tracks its own positions (per-slot
    serving cache with staggered admission).

    If k_new/v_new (B, 1, K, D) are given, the current token is attended
    as a separate logit column (two-part softmax) so the cache tensor is
    never concatenated/copied — the caller writes the new KV into the
    cache once, outside the layer loop.
    """
    B, _, H, D = q.shape
    Sc, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, K, G, D)
    # mixed-precision dots: never materialize an f32 copy of the KV cache
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    kp = kpos if kpos.ndim == 2 else kpos[None, :]         # (B|1, Sc)
    valid = (kp >= 0) & (kp <= qpos[:, None])
    if window is not None:
        valid &= (qpos[:, None] - kp) < window
    s = jnp.where(valid[:, None, None, :], s, MASK_VALUE)
    if k_new is not None:
        s_self = jnp.einsum("bkgd,bkd->bkg", qg, k_new[:, 0],
                            preferred_element_type=jnp.float32) * scale
        m = jnp.maximum(jnp.max(s, axis=-1), s_self)
        p = jnp.exp(s - m[..., None])
        p_self = jnp.exp(s_self - m)
        denom = p.sum(-1) + p_self
        out = (jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                          preferred_element_type=jnp.float32)
               + p_self[..., None] * v_new[:, 0].astype(jnp.float32)[:, :, None])
        out = out / denom[..., None]
    else:
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                         preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, D).astype(q.dtype)


def attention_decode_sharded(q, k_cache, v_cache, qpos, kpos, *,
                             window=None, k_new=None, v_new=None,
                             sharder=None) -> jax.Array:
    """Flash-decoding: the KV cache stays sequence-sharded over 'model';
    each shard computes a partial online softmax over its local slice and
    the shards combine (pmax/psum of (m, l, acc) — O(B·H·D) wire instead
    of all-gathering the cache).  The current token's KV joins afterwards
    as a separate logit column."""
    from jax.sharding import PartitionSpec as P
    mesh = sharder.mesh
    B, _, H, D = q.shape
    Sc, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(D)
    b = sharder.batch if (sharder.batch and
                          B % _prod(mesh, sharder.batch) == 0) else ()

    def local(qg, kc, vc, qp, kp):
        s = jnp.einsum("bkgd,bskd->bkgs", qg, kc,
                       preferred_element_type=jnp.float32) * scale
        valid = (kp[None, :] >= 0) & (kp[None, :] <= qp[:, None])
        if window is not None:
            valid &= (qp[:, None] - kp[None, :]) < window
        s = jnp.where(valid[:, None, None, :], s, MASK_VALUE)
        m_loc = jnp.max(s, axis=-1)                        # (B,K,G)
        p = jnp.exp(s - m_loc[..., None])
        p = jnp.where(valid[:, None, None, :], p, 0.0)
        l_loc = p.sum(-1)
        acc_loc = jnp.einsum("bkgs,bskd->bkgd", p.astype(vc.dtype), vc,
                             preferred_element_type=jnp.float32)
        m_g = jax.lax.pmax(m_loc, "model")
        w = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * w, "model")
        acc_g = jax.lax.psum(acc_loc * w[..., None], "model")
        return m_g, l_g, acc_g

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b, None, None, None), P(b, "model", None, None),
                  P(b, "model", None, None), P(b), P("model")),
        out_specs=(P(b, None, None), P(b, None, None),
                   P(b, None, None, None)),
        check_vma=False)
    qg = q.reshape(B, K, G, D)
    m, l, acc = fn(qg, k_cache, v_cache, qpos, kpos)
    if k_new is not None:
        s_self = jnp.einsum("bkgd,bkd->bkg", qg, k_new[:, 0],
                            preferred_element_type=jnp.float32) * scale
        m2 = jnp.maximum(m, s_self)
        w = jnp.exp(m - m2)
        p_self = jnp.exp(s_self - m2)
        l = l * w + p_self
        acc = acc * w[..., None] \
            + p_self[..., None] * v_new[:, 0].astype(jnp.float32)[:, :, None]
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, 1, H, D).astype(q.dtype)


def _prod(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# gated MLP
# ---------------------------------------------------------------------------
def init_mlp(key, d: int, f: int, dtype=jnp.bfloat16) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": init_linear(k1, d, f, dtype=dtype),
            "w_up": init_linear(k2, d, f, dtype=dtype),
            "w_down": init_linear(k3, f, d, dtype=dtype)}


def apply_mlp(p: dict, x: jax.Array, act: str) -> jax.Array:
    return linear(p["w_down"],
                  activation(linear(p["w_gate"], x), act) * linear(p["w_up"], x))
