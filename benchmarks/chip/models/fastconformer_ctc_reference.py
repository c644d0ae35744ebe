"""Plain reference of the cache-aware streaming FastConformer-CTC
recognizer.

Written from the published description (Conformer block: Gulati et al.,
arXiv:2005.08100; FastConformer subsampling and widths: Rekesh et al.,
arXiv:2305.05084; chunked streaming attention: Noroozi et al.,
arXiv:2312.17279), not from the program: it imports nothing of `repro`.
It runs each utterance whole, with the chunk mask and causal convs, and
no cache, kernel or batching trick; under chunked masks that equals the
chunk-by-chunk streaming the program does.

* Features: the utterance is cut into decoding windows of 112 frames
  (1.12 s: 18160 samples at a 17920-sample shift; a last partial window
  zero-padded to full length, as the engine's flush does), each window
  framed on its own: pre-emphasis, Hamming window, |rfft(512)|^2, 80
  mel bands, log.  No normalisation.
* Subsampling by 8: conv 3x3 stride 2 (1 -> C), ReLU; twice a depthwise
  conv 3x3 stride 2, a pointwise conv, ReLU.  Time padded with 2 zero
  frames on the left only (causal), frequency with 1 bin each side; the
  (C, F') map flattened channel-major, then Linear -> d_model, times
  sqrt(d_model).
* Blocks: x + FFN/2, x + MHSA, x + Conv, x + FFN/2, LayerNorm (eps
  1e-5); FFN = W2 swish(W1 LN(x)); MHSA scores
  ((q_i + u) . k_j + (q_i + v) . Wpos pe(i - j)) / sqrt(d_head) with
  sinusoidal pe, allowed iff floor(i/c) - n_left <= floor(j/c) <=
  floor(i/c) (c = 14, n_left = 5); Conv = PW(2D), GLU, causal depthwise
  conv k = 9, LayerNorm, swish, PW.
* Head: log_softmax(W x + b), blank id 0 (NeMo puts blank last: a
  relabelling).
* Search: `tds_ctc_reference.search` (CTC prefix beam search over the
  lexicon trie with the bigram word LM), unchanged.

`forward` takes `dtype` and `precision`: float32 under "highest" is the
reference; "high" (three bfloat16 passes) is the control.
"""
from __future__ import annotations

import math

import numpy as np

import tds_ctc_reference as tds_ref
from tds_ctc_reference import (Beam, Trie, best, bigram_table,  # noqa: F401
                               hypotheses, search, served_form)

SAMPLE_RATE = tds_ref.SAMPLE_RATE
FRAME_LEN, FRAME_SHIFT = tds_ref.FRAME_LEN, tds_ref.FRAME_SHIFT
N_FFT, PREEMPHASIS = tds_ref.N_FFT, tds_ref.PREEMPHASIS
MAX_TOKENS, MAX_WORDS = tds_ref.MAX_TOKENS, tds_ref.MAX_WORDS
LN_EPS = 1e-5
SUBSAMPLE = 8


def chunk_of(sizes: dict) -> tuple:
    """(chunk, left chunks) of `att_context_size` [left, right]."""
    left, right = sizes["att_context_size"]
    return right + 1, left // (right + 1)


def window_frames(sizes: dict) -> int:
    """Feature frames of one decoding window: one chunk, before the 8x
    subsampling."""
    return chunk_of(sizes)[0] * SUBSAMPLE


def window_samples(sizes: dict) -> int:
    return FRAME_LEN + (window_frames(sizes) - 1) * FRAME_SHIFT


# ---- weights ----------------------------------------------------------------

def init_params(key, sizes: dict):
    """Random weights: matrices N(0, 1/fan_in), biases N(0, 0.05^2),
    norm scales 1 + N(0, 0.1^2) and shifts N(0, 0.1^2), u and v
    N(0, 0.1^2).  The tree the program takes: block leaves stacked over
    layers (leading axis n_layers).  One jittable function of the key."""
    import jax
    import jax.numpy as jnp

    D, C = sizes["d_model"], sizes["sub_channels"]
    L, H = sizes["n_layers"], sizes["n_heads"]
    F = sizes["n_mels"] // SUBSAMPLE
    d_ff = D * sizes["ff_expansion"]
    k_conv = sizes["conv_kernel"]
    keys = iter(jax.random.split(key, 64))

    def mat(shape, fan_in):
        return jax.random.normal(next(keys), shape) / math.sqrt(fan_in)

    def vec(shape, scale=0.05):
        return scale * jax.random.normal(next(keys), shape)

    def lin(lead, n_in, n_out):
        return {"w": mat(lead + (n_in, n_out), n_in),
                "b": vec(lead + (n_out,))}

    def norm(lead):
        return {"scale": 1.0 + vec(lead + (D,), 0.1),
                "bias": vec(lead + (D,), 0.1)}

    lay = (L,)
    p = {"sub": {"conv1": {"w": mat((3, 3, C), 9), "b": vec((C,))},
                 "dw2": {"w": mat((3, 3, C), 9), "b": vec((C,))},
                 "pw2": lin((), C, C),
                 "dw3": {"w": mat((3, 3, C), 9), "b": vec((C,))},
                 "pw3": lin((), C, C),
                 "out": lin((), C * F, D)},
         "blocks": {
             "ff1_ln": norm(lay), "ff1_in": lin(lay, D, d_ff),
             "ff1_out": lin(lay, d_ff, D),
             "att_ln": norm(lay), "q": lin(lay, D, D), "k": lin(lay, D, D),
             "v": lin(lay, D, D), "att_out": lin(lay, D, D),
             "pos": {"w": mat(lay + (D, D), D)},
             "pos_u": vec(lay + (H, D // H), 0.1),
             "pos_v": vec(lay + (H, D // H), 0.1),
             "conv_ln": norm(lay), "pw1": lin(lay, D, 2 * D),
             "dw": {"w": mat(lay + (k_conv, D), k_conv),
                    "b": vec(lay + (D,))},
             "conv_norm": norm(lay), "pw2": lin(lay, D, D),
             "ff2_ln": norm(lay), "ff2_in": lin(lay, D, d_ff),
             "ff2_out": lin(lay, d_ff, D),
             "out_ln": norm(lay)},
         "head": lin((), D, sizes["vocab"])}
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


# ---- features ---------------------------------------------------------------

def windows_of(audio: np.ndarray, sizes: dict) -> np.ndarray:
    """The decoding windows a stream of `audio` is cut into: every whole
    window at a shift of its frames, then, where more than the framing
    overlap is left, one last window zero-padded to full length (the
    tail the engine flushes at end of input).  (n_windows, samples)."""
    need = window_samples(sizes)
    shift = window_frames(sizes) * FRAME_SHIFT
    audio = np.asarray(audio, np.float32)
    out, off = [], 0
    while off + need <= audio.size:
        out.append(audio[off:off + need])
        off += shift
    rest = audio[off:]
    if rest.size > need - shift:
        out.append(np.concatenate(
            [rest, np.zeros(need - rest.size, np.float32)]))
    return np.stack(out) if out else np.zeros((0, need), np.float32)


def logmel(windows, sizes: dict, dtype, precision):
    """(B, n_win, samples) -> (B, n_win * frames, n_mels), each window
    framed on its own."""
    import jax.numpy as jnp

    nfr = window_frames(sizes)
    x = windows.astype(jnp.float32)
    x = jnp.concatenate([x[..., :1], x[..., 1:] - PREEMPHASIS * x[..., :-1]],
                        axis=-1)
    idx = (np.arange(nfr)[:, None] * FRAME_SHIFT
           + np.arange(FRAME_LEN)[None, :])
    frames = x[..., idx] * np.hamming(FRAME_LEN).astype(np.float32)
    power = (jnp.abs(jnp.fft.rfft(frames, n=N_FFT, axis=-1)) ** 2
             ).astype(dtype)
    mel = jnp.matmul(power, jnp.asarray(tds_ref.mel_bank(sizes["n_mels"]),
                                        dtype), precision=precision)
    out = jnp.log(jnp.maximum(mel, jnp.asarray(1e-10, dtype)))
    b, n = windows.shape[:2]
    return out.reshape(b, n * nfr, sizes["n_mels"])


# ---- the encoder ------------------------------------------------------------

def sinusoid(dist: np.ndarray, d: int) -> np.ndarray:
    """pe(dist): (n, d), sin at even and cos at odd features."""
    div = np.exp(np.arange(0, d, 2) * -(math.log(10000.0) / d))
    pe = np.zeros((dist.size, d))
    pe[:, 0::2] = np.sin(dist[:, None] * div)
    pe[:, 1::2] = np.cos(dist[:, None] * div)
    return pe.astype(np.float32)


def chunk_mask(t: int, chunk: int, n_left: int) -> np.ndarray:
    """(t, t) bool: frame i may attend to frame j."""
    c = np.arange(t) // chunk
    return (c[None, :] <= c[:, None]) & (c[None, :] >= c[:, None] - n_left)


def forward(params, sizes: dict, windows, dtype="float32",
            precision="highest"):
    """Log-probs (B, n_windows * chunk, vocab) over whole utterances cut
    into `windows` (B, n_windows, samples)."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    D, H = sizes["d_model"], sizes["n_heads"]
    dh = D // H
    chunk, n_left = chunk_of(sizes)

    def mm(a, b):
        return jnp.matmul(a, b, precision=precision)

    def lin(x, q):
        return mm(x, q["w"]) + q["b"]

    def ln(x, q):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + LN_EPS) * q["scale"] + q["bias"]

    def swish(x):
        return x * jax.nn.sigmoid(x)

    def conv3x3_s2(x, w, b):
        # x (B, T, F, Cx): causal in time, padded 1 bin each side in freq
        t, f = x.shape[1], x.shape[2]
        xp = jnp.pad(x, ((0, 0), (2, 0), (1, 1), (0, 0)))
        out = b
        for dt in range(3):
            for df in range(3):
                out = out + xp[:, dt:dt + t - 1:2, df:df + f - 1:2] * w[dt, df]
        return out

    x = logmel(windows, sizes, dtype, precision)          # (B, T, mels)
    s = p["sub"]
    x = jax.nn.relu(conv3x3_s2(x[..., None], s["conv1"]["w"],
                               s["conv1"]["b"]))
    for i in (2, 3):
        x = conv3x3_s2(x, s[f"dw{i}"]["w"], s[f"dw{i}"]["b"])
        x = jax.nn.relu(lin(x, s[f"pw{i}"]))
    bsz, t = x.shape[:2]
    x = jnp.swapaxes(x, 2, 3).reshape(bsz, t, -1)          # channel-major
    x = lin(x, s["out"]) * math.sqrt(D)

    allowed = jnp.asarray(chunk_mask(t, chunk, n_left))
    pe = jnp.asarray(sinusoid(np.arange(t - 1, -t, -1.0), D), dtype)
    # rel[i, j] = index into pe of distance i - j
    rel = jnp.asarray((t - 1) - (np.arange(t)[:, None]
                                 - np.arange(t)[None, :]))
    blocks = p["blocks"]
    for layer in range(sizes["n_layers"]):
        q = jax.tree.map(lambda a: a[layer], blocks)

        def ffn(x, i):
            return lin(swish(lin(ln(x, q[f"ff{i}_ln"]), q[f"ff{i}_in"])),
                       q[f"ff{i}_out"])

        def split(y):
            return y.reshape(bsz, -1, H, dh).transpose(0, 2, 1, 3)

        x = x + 0.5 * ffn(x, 1)
        h = ln(x, q["att_ln"])
        qh, kh, vh = (split(lin(h, q[n])) for n in ("q", "k", "v"))
        ph = mm(pe, q["pos"]["w"]).reshape(-1, H, dh).transpose(1, 0, 2)
        ac = jnp.einsum("bhid,bhjd->bhij", qh + q["pos_u"][None, :, None],
                        kh, precision=precision)
        bd_all = jnp.einsum("bhid,hmd->bhim", qh + q["pos_v"][None, :, None],
                            ph, precision=precision)
        bd = jnp.take_along_axis(
            bd_all, jnp.broadcast_to(rel, bd_all.shape[:2] + rel.shape),
            axis=-1)
        sc = (ac + bd) / math.sqrt(dh)
        sc = jnp.where(allowed, sc, jnp.asarray(-1e30, sc.dtype))
        att = jax.nn.softmax(sc.astype(jnp.float32), axis=-1).astype(dtype)
        ctx = jnp.einsum("bhij,bhjd->bhid", att, vh, precision=precision)
        x = x + lin(ctx.transpose(0, 2, 1, 3).reshape(bsz, t, D),
                    q["att_out"])
        g = lin(ln(x, q["conv_ln"]), q["pw1"])
        g = g[..., :D] * jax.nn.sigmoid(g[..., D:])
        k = sizes["conv_kernel"]
        gp = jnp.pad(g, ((0, 0), (k - 1, 0), (0, 0)))
        y = q["dw"]["b"] + sum(gp[:, j:j + t] * q["dw"]["w"][j]
                               for j in range(k))
        x = x + lin(swish(ln(y, q["conv_norm"])), q["pw2"])
        x = x + 0.5 * ffn(x, 2)
        x = ln(x, q["out_ln"])
    logits = lin(x, p["head"])
    return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
