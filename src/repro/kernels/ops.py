"""jit'd public wrappers for the Pallas kernels, dispatched by KernelPolicy.

Each wrapper takes an optional `policy: KernelPolicy` (threaded from
`EngineConfig.kernels` by the serving engines) and resolves it to one of
three modes (see kernels/policy.py):

  ref        pure-jnp oracle from ref.py, XLA-compiled
  interpret  the Pallas kernel under the interpreter (kernel body runs
             in Python per grid step — how correctness is validated
             against ref.py on CPU)
  mosaic     the same pallas_call compiled to Mosaic on TPU

`auto` resolves per backend, with the backend probe hoisted into the
policy module (one `jax.default_backend()` read per process instead of
one per call).  On CPU it keeps today's behavior for the standalone
validation kernels (interpret) but routes the decode hot path — the
fused `hypothesis_unit` — through `ref`.

`int8_matmul(x, w)` takes float tensors and performs the full ASRPU int8
path: blockless per-row/col symmetric quantization + int8 MXU matmul +
fp32 rescale (core/quant holds the block-wise variant used by the
optimizer).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import (beam_prune as _bp, chunk_attention as _ca,
                           flash_attention as _fa, hypothesis_unit as _hu,
                           int8_matmul as _im, layernorm as _ln,
                           logmel as _lm, ref as _ref, tds_conv as _tc)
from repro.kernels.policy import (DEFAULT_POLICY, KernelPolicy,  # noqa: F401
                                  resolve)


def quantize_rows(x):
    """Symmetric per-row int8: x (M, K) -> (q i8, scale f32 (M,))."""
    xf = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(xf), axis=1) / 127.0
    q = jnp.clip(jnp.round(xf / jnp.maximum(s[:, None], 1e-12)),
                 -127, 127).astype(jnp.int8)
    return q, s


def shard_local_cols(x, kloc, axis):
    """Model-parallel contraction helper: slice the activation columns
    matching this device's feature-axis weight shard — rows
    [i*kloc, (i+1)*kloc) of the full weight, where i is the device's
    index along the shard_map mesh `axis`.  Shared by the fp32
    (`tds.forward_batched`) and int8 (`int8_matmul_prepared`) paths so
    the slicing rule cannot diverge between them; callers detect a
    sharded weight by shape (w.shape[0] != x.shape[1]) and psum the
    local partial products."""
    i = jax.lax.axis_index(axis)
    return jax.lax.dynamic_slice_in_dim(x, i * kloc, kloc, axis=1)


def overlap_splits(n: int, n_chunks: int = 2):
    """Static [lo, hi) output-column chunk boundaries for the
    latency-hiding psum split (`psum_overlap_matmul`).  Python ints so
    every slice is static under jit; degenerates to one full-width
    chunk when n < n_chunks."""
    n_chunks = max(1, min(int(n_chunks), int(n)))
    return [(i * n // n_chunks, (i + 1) * n // n_chunks)
            for i in range(n_chunks)]


def psum_overlap_matmul(xloc, wm, axis, n_chunks: int = 2):
    """Latency-hiding model-parallel contraction: xloc (M, K/n) local
    activation columns, wm (K/n, N) this device's feature-axis weight
    shard -> the full (M, N) all-reduced product.

    The output columns are split into static chunks and emitted as
    matmul(c0), psum(c0), matmul(c1), psum(c1), ...: chunk c+1's local
    matmul has no data dependence on chunk c's all-reduce, so a backend
    with async collectives starts c's psum and computes c+1's partial
    products under it (the coprocessor-scaling overlap trick; see
    ROADMAP item 4).  Each output element is still ONE local dot +
    ONE psum — the reduction structure per element is identical to the
    synchronous `psum(xloc @ wm, axis)` — but XLA may tile the narrower
    per-chunk matmuls differently, so parity with the synchronous path
    is numerical (~1e-6), not bitwise; the sync path stays the parity
    reference and this variant sits behind `EngineConfig.overlap_psum`.
    On CPU host devices there is no async-collective win: correctness
    coverage only."""
    parts = []
    for lo, hi in overlap_splits(wm.shape[1], n_chunks):
        wc = jax.lax.slice_in_dim(wm, lo, hi, axis=1)
        parts.append(jax.lax.psum(xloc @ wc, axis))
    return jnp.concatenate(parts, axis=1)


def prepare_int8_weights(w):
    """Quantize a static weight matrix ONCE: w (K, N) float ->
    (wq (K, N) i8, ws (N,) f32 per-output-channel scales).

    The serving engines call this at build time (`tds.quantize_params`)
    so the decode hot path only quantizes activations — re-quantizing a
    static weight every `int8_matmul` call is pure waste."""
    wq_t, ws = quantize_rows(w.T)
    return wq_t.T, ws


def int8_matmul_prepared(x, wq, ws, *, bm=128, bn=128, bk=128, policy=None,
                         hot=False, axis=None, overlap=False):
    """x: (M, K) float; wq/ws from `prepare_int8_weights` -> (M, N) f32.

    The hot-path half of the int8 pipeline: per-row activation
    quantization + int8 MXU matmul + fp32 rescale, with the weight-side
    quantization already done.

    `axis` names the shard_map mesh axis of a model-parallel caller
    (the sharded serving step): when `wq` arrives as a feature-axis
    shard — (K/n_model, N), detected by shape against `x` — the
    activations are quantized on their FULL rows first (so the per-row
    scales match the unsharded path exactly), the matching xq columns
    are sliced locally, and the rescaled partial products are psummed
    over `axis`.  `overlap` applies the `psum_overlap_matmul`
    output-column split to the sharded path (per-chunk dispatch + psum
    so the all-reduces hide under the next chunk's matmul); the per-row
    activation scales are computed once on the full rows either way."""
    mode = resolve(policy, hot=hot)
    xq, xs = quantize_rows(x)
    if axis is not None and wq.shape[0] != xq.shape[1]:
        xloc = shard_local_cols(xq, wq.shape[0], axis)
        if overlap:
            parts = []
            for lo, hi in overlap_splits(wq.shape[1]):
                parts.append(jax.lax.psum(
                    _int8_dispatch(xloc, wq[:, lo:hi], xs, ws[lo:hi],
                                   mode, bm=bm, bn=bn, bk=bk), axis))
            return jnp.concatenate(parts, axis=1)
        return jax.lax.psum(
            _int8_dispatch(xloc, wq, xs, ws, mode, bm=bm, bn=bn, bk=bk),
            axis)
    return _int8_dispatch(xq, wq, xs, ws, mode, bm=bm, bn=bn, bk=bk)


def _int8_dispatch(xq, wq, xs, ws, mode, *, bm, bn, bk):
    """Mode-resolved int8 matmul core on (possibly shard-local) operands."""
    if mode == "ref":
        return _ref.int8_matmul(xq, wq, xs, ws)
    M, K = xq.shape
    N = wq.shape[1]
    pad_m, pad_n, pad_k = (-M) % 8, (-N) % 128, (-K) % 128
    if pad_m or pad_k:
        xq = jnp.pad(xq, ((0, pad_m), (0, pad_k)))
        xs = jnp.pad(xs, (0, pad_m))
    if pad_n or pad_k:
        wq = jnp.pad(wq, ((0, pad_k), (0, pad_n)))
        ws = jnp.pad(ws, (0, pad_n))
    bm_ = min(bm, xq.shape[0])
    while xq.shape[0] % bm_:
        bm_ //= 2
    out = _im.int8_matmul_pallas(xq, wq, xs, ws, bm=bm_, bn=bn, bk=bk,
                                 interpret=mode != "mosaic")
    return out[:M, :N]


def int8_matmul(x, w, *, bm=128, bn=128, bk=128, policy=None, hot=False):
    """x: (M, K) float; w: (K, N) float -> (M, N) f32 (int8 MXU path).

    Quantizes BOTH operands on every call — correct for one-shot use,
    but callers with static weights should `prepare_int8_weights` once
    and use `int8_matmul_prepared` on the hot path."""
    wq, ws = prepare_int8_weights(w)
    return int8_matmul_prepared(x, wq, ws, bm=bm, bn=bn, bk=bk,
                                policy=policy, hot=hot)


def flash_attention(q, k, v, *, causal=True, window=None,
                    block_q=128, block_kv=128, policy=None):
    mode = resolve(policy)
    if mode == "ref":
        return _ref.flash_attention(q, k, v, causal=causal, window=window)
    return _fa.flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      block_q=block_q, block_kv=block_kv,
                                      interpret=mode != "mosaic")


def layernorm(x, scale, bias, *, eps=1e-5, policy=None, hot=False):
    mode = resolve(policy, hot=hot)
    if mode == "ref":
        return _ref.layernorm(x, scale, bias, eps=eps)
    return _ln.norm_pallas(x, scale, bias, kind="layernorm", eps=eps,
                           interpret=mode != "mosaic")


def rmsnorm(x, scale, *, eps=1e-6, policy=None, hot=False):
    mode = resolve(policy, hot=hot)
    if mode == "ref":
        return _ref.rmsnorm(x, scale, eps=eps)
    return _ln.norm_pallas(x, scale, None, kind="rmsnorm", eps=eps,
                           interpret=mode != "mosaic")


def logmel(power, fb, dct=None, policy=None, *, hot=False):
    """Log-mel features (`dct` None) or the MFCC tail, from power
    spectra (T, F)."""
    mode = resolve(policy, hot=hot)
    if mode == "ref":
        return _ref.logmel(power, fb, dct)
    return _lm.logmel_pallas(power, fb, dct, interpret=mode != "mosaic")


def chunk_attention(q, k, v, pos, u, vb, bias, *, chunk, policy=None,
                    hot=False):
    """Chunked relative-position self-attention of a streaming Conformer
    (see kernels/chunk_attention.py for the layouts)."""
    mode = resolve(policy, hot=hot)
    if mode == "ref":
        return _ref.chunk_attention(q, k, v, pos, u, vb, bias, chunk=chunk)
    return _ca.chunk_attention_pallas(q, k, v, pos, u, vb, bias,
                                      chunk=chunk,
                                      interpret=mode != "mosaic")


def beam_prune(scores, beam, policy=None):
    mode = resolve(policy)
    if mode == "ref":
        return _ref.beam_prune(scores, beam)
    return _bp.beam_prune_pallas(scores, beam, interpret=mode != "mosaic")


def tds_conv(x, w, b, *, stride=1, relu=False, res=None, policy=None,
             hot=False):
    """Causal strided TDS conv with the fused bias+ReLU+residual
    epilogue.  x: (B, k-1+T, W, Cin) slot-batched (3-D = B=1)."""
    mode = resolve(policy, hot=hot)
    squeeze = x.ndim == 3
    if squeeze:
        x = x[None]
        res = None if res is None else res[None]
    if mode == "ref":
        out = _ref.tds_conv_fused(x, w, b, stride=stride, relu=relu,
                                  res=res)
    else:
        out = _tc.tds_conv_pallas(x, w, b, res, stride=stride, relu=relu,
                                  interpret=mode != "mosaic")
    return out[0] if squeeze else out


# ---------------------------------------------------------------------------
# fused hypothesis unit (decode hot path)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("k", "beam", "mode"))
def _hypothesis_unit(hashes, pb, pnb, *, k, beam, mode):
    assert hashes.shape[1] >= k, (hashes.shape, k)
    if mode == "ref":
        return _ref.hypothesis_unit(hashes, pb, pnb, k=k, beam=beam)
    return _hu.hypothesis_unit_pallas(hashes, pb, pnb, k=k, beam=beam,
                                      interpret=mode != "mosaic")


def hypothesis_unit(hashes, pb, pnb, k, beam, policy=None):
    """Fused hypothesis unit over a batch of candidate rows.

    hashes: (B, N) int32 31-bit prefix hashes; pb/pnb: (B, N) f32 CTC
    channels.  Merges duplicate hashes (channel-wise logsumexp), applies
    the beam threshold, and selects the top-`k` per row.  Returns a dict
    of (B, k) arrays: `idx` (index of each selected representative into
    the original row — callers gather their payload fields with it),
    merged `pb`/`pnb` (NEG_INF where pruned), and boolean `valid`.
    """
    mode = resolve(policy, hot=True)
    return _hypothesis_unit(hashes, pb, pnb, k=k, beam=float(beam),
                            mode=mode)
