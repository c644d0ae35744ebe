"""MFCC feature extraction (paper §2.1 / Fig. 3), pure JAX.

Pipeline: pre-emphasis -> 25ms/10ms framing -> Hamming window -> |FFT|^2
-> mel filterbank (80 banks) -> log -> DCT-II -> 80-dim MFCC.
The hot post-FFT stages (mel matmul + log + DCT matmul) have a fused
Pallas kernel (kernels/logmel.py); this module is the reference/driver.
`logmel` stops before the DCT: the Conformer's 80 log-mel inputs.

Streaming: `frames_producible` is the setup-thread arithmetic (paper §3.2)
— how many whole frames fit in the buffered signal; `extract_frames`
consumes exactly that many shifts and returns the leftover samples.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.tds_asr import FeatureConfig

# shared default (defaults are evaluated once anyway; a named constant
# keeps that explicit and call-free — flake8-bugbear B008)
DEFAULT_FEATURE_CONFIG = FeatureConfig()


def hz_to_mel(f):
    return 2595.0 * np.log10(1.0 + f / 700.0)


def mel_to_hz(m):
    return 700.0 * (10.0 ** (m / 2595.0) - 1.0)


@functools.lru_cache()
def mel_filterbank(cfg: FeatureConfig) -> np.ndarray:
    """(n_fft//2+1, n_mels) triangular filterbank."""
    n_bins = cfg.n_fft // 2 + 1
    freqs = np.linspace(0, cfg.sample_rate / 2, n_bins)
    mels = np.linspace(hz_to_mel(cfg.fmin), hz_to_mel(cfg.fmax), cfg.n_mels + 2)
    pts = mel_to_hz(mels)
    fb = np.zeros((n_bins, cfg.n_mels), np.float32)
    for m in range(cfg.n_mels):
        lo, c, hi = pts[m], pts[m + 1], pts[m + 2]
        up = (freqs - lo) / max(c - lo, 1e-9)
        down = (hi - freqs) / max(hi - c, 1e-9)
        fb[:, m] = np.maximum(0.0, np.minimum(up, down))
    return fb


@functools.lru_cache()
def dct_matrix(n_in: int, n_out: int) -> np.ndarray:
    """Orthonormal DCT-II, (n_in, n_out)."""
    k = np.arange(n_out)[None, :]
    n = np.arange(n_in)[:, None]
    m = np.cos(np.pi * k * (2 * n + 1) / (2 * n_in)) * math.sqrt(2.0 / n_in)
    m[:, 0] *= 1.0 / math.sqrt(2.0)
    return m.astype(np.float32)


def frames_producible(n_samples: int, cfg: FeatureConfig) -> int:
    """Setup-thread arithmetic: whole frames extractable from n samples."""
    if n_samples < cfg.frame_len:
        return 0
    return 1 + (n_samples - cfg.frame_len) // cfg.frame_shift


def consumed_samples(n_frames: int, cfg: FeatureConfig) -> int:
    """Samples that can be retired after emitting n_frames (keep overlap)."""
    return n_frames * cfg.frame_shift


def power_spectrum(signal: jax.Array,
                   cfg: FeatureConfig = DEFAULT_FEATURE_CONFIG) -> jax.Array:
    """signal: (..., n_samples) f32 -> (..., n_frames, n_fft//2+1) power
    spectra: pre-emphasis, 25 ms / 10 ms framing, Hamming window,
    |rfft|^2."""
    n = frames_producible(signal.shape[-1], cfg)
    assert n > 0, "not enough samples for one frame"
    # pre-emphasis
    sig = jnp.concatenate(
        [signal[..., :1], signal[..., 1:] - cfg.preemphasis * signal[..., :-1]],
        axis=-1)
    idx = (jnp.arange(n)[:, None] * cfg.frame_shift
           + jnp.arange(cfg.frame_len)[None, :])
    frames = jnp.take(sig, idx, axis=-1)             # (..., n, frame_len)
    win = jnp.asarray(np.hamming(cfg.frame_len).astype(np.float32))
    frames = frames * win
    spec = jnp.fft.rfft(frames, n=cfg.n_fft, axis=-1)
    return jnp.square(jnp.abs(spec)).astype(jnp.float32)


def mfcc(signal: jax.Array, cfg: FeatureConfig = DEFAULT_FEATURE_CONFIG,
         use_pallas: bool = False, kernels=None,
         hot: bool = False) -> jax.Array:
    """signal: (..., n_samples) f32 -> (..., n_frames, n_mfcc) f32.

    Leading axes are batch (the serving engine extracts every slot's
    window in one call — B slots fold into the logmel matmul's row
    dimension).  use_pallas routes the mel+log+DCT tail through the
    fused logmel kernel, dispatched by the `kernels` KernelPolicy
    (None = auto; `hot` marks the call as decode-hot-path so auto never
    picks the interpreter)."""
    power = power_spectrum(signal, cfg)              # (..., n, n_bins)
    fb = jnp.asarray(mel_filterbank(cfg))
    dct = jnp.asarray(dct_matrix(cfg.n_mels, cfg.n_mfcc))
    if use_pallas:
        from repro.kernels import ops
        rows = power.reshape(-1, power.shape[-1])
        out = ops.logmel(rows, fb, dct, policy=kernels, hot=hot)
        return out.reshape(power.shape[:-1] + (out.shape[-1],))
    logmel = jnp.log(jnp.maximum(power @ fb, 1e-10))
    return logmel @ dct


def logmel(signal: jax.Array, cfg: FeatureConfig = DEFAULT_FEATURE_CONFIG,
           kernels=None, hot: bool = False) -> jax.Array:
    """signal: (..., n_samples) f32 -> (..., n_frames, n_mels) log-mel
    features, through the fused logmel kernel without its DCT (the
    Conformer's front end).  No per-utterance normalisation: a stream
    never sees its whole utterance."""
    from repro.kernels import ops
    power = power_spectrum(signal, cfg)
    rows = power.reshape(-1, power.shape[-1])
    out = ops.logmel(rows, jnp.asarray(mel_filterbank(cfg)), policy=kernels,
                     hot=hot)
    return out.reshape(power.shape[:-1] + (out.shape[-1],))


def deltas(feats: jax.Array, window: int = 2) -> jax.Array:
    """Regression-based dynamic features (paper §2.1: "dynamic features,
    such as delta and delta-delta, can be appended").

    feats: (T, C) -> (T, C) delta coefficients:
        d_t = sum_n n·(x_{t+n} - x_{t-n}) / (2·sum_n n^2),  edge-padded.
    """
    T, C = feats.shape
    denom = 2.0 * sum(n * n for n in range(1, window + 1))
    padded = jnp.concatenate([
        jnp.repeat(feats[:1], window, axis=0), feats,
        jnp.repeat(feats[-1:], window, axis=0)], axis=0)
    out = jnp.zeros_like(feats)
    for n in range(1, window + 1):
        out = out + n * (padded[window + n:window + n + T]
                         - padded[window - n:window - n + T])
    return out / denom


def mfcc_with_deltas(signal: jax.Array,
                     cfg: FeatureConfig = DEFAULT_FEATURE_CONFIG) -> jax.Array:
    """(n_frames, 3*n_mfcc): static + delta + delta-delta."""
    static = mfcc(signal, cfg)
    d1 = deltas(static)
    d2 = deltas(d1)
    return jnp.concatenate([static, d1, d2], axis=-1)
