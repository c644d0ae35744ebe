"""Plain reference of the TDS + CTC lexicon beam-search recognizer.

Written from the description of the system (arXiv:2202.04971 §4: 80-dim
MFCC every 10 ms, a wav2letter TDS acoustic model of 18 conv, 29 FC and
32 LayerNorm kernels, CTC prefix beam search over a lexicon trie with a
bigram word LM), not from the program: it imports nothing of `repro`.

* Features: each 80 ms decoding window sees 1520 samples (8 frames of
  25 ms every 10 ms); pre-emphasis, Hamming window, |rfft(512)|^2, 80 mel
  bands, log, orthonormal DCT-II -> 80 coefficients.
* Acoustic model: time-only causal convs (zero left context at the start
  of an utterance), LayerNorm over each frame's (w * c) vector, FC blocks
  with a residual over the block, log-softmax head.  Run over the whole
  utterance at once; causality makes that equal to window-by-window.
* Search: CTC prefix beam search.  Each hypothesis makes one "stay"
  candidate (blank into the blank channel, repeat into the non-blank
  one), one "continue" candidate per trie child and one "commit"
  candidate per word-final child (LM score times `lm_weight` plus
  `word_score`, back to the root).  Equal prefixes merge by log-sum-exp
  per channel, the best K within `beam_threshold` of the best survive.
  At the end, hypotheses on a word-final node commit that word.
  Hypotheses are told apart by their whole history (a 64-bit chain
  hash), and scores are summed in float64.

`forward` takes `dtype` and `precision`: float32 under "highest" is the
reference; bfloat16 is the control (the acoustic model one precision
below what the configuration states).
"""
from __future__ import annotations

import math

import numpy as np

SAMPLE_RATE = 16000
FRAME_LEN = 400          # 25 ms
FRAME_SHIFT = 160        # 10 ms
FRAMES_PER_WINDOW = 8    # 80 ms
WINDOW_SAMPLES = FRAME_LEN + (FRAMES_PER_WINDOW - 1) * FRAME_SHIFT  # 1520
WINDOW_SHIFT = FRAMES_PER_WINDOW * FRAME_SHIFT                      # 1280
N_FFT = 512
PREEMPHASIS = 0.97
FMIN, FMAX = 20.0, 7800.0
LN_EPS = 1e-5
MAX_TOKENS = 256         # the history rows a served result carries
MAX_WORDS = 64


# ---- model layout ---------------------------------------------------------

def kernel_list(sizes: dict) -> list:
    """(name, kind, k, stride, c_in, c_out, width) of every kernel, in
    order.  `width` is w * c_out for convs/FCs and w * c for LayerNorm."""
    w = sizes["feat"]
    stages = sizes["stages"]          # [n_blocks, channels, kernel, subsample]
    out = []
    c0 = stages[0][1]
    out.append(("front_conv", "conv", stages[0][2], 1, 1, c0, w * c0))
    c_prev = c0
    for si, (n_blocks, c, k, sub) in enumerate(stages):
        out.append((f"s{si}_subsample", "conv", sizes["sub_kernel"], sub,
                    c_prev, c, w * c))
        out.append((f"s{si}_sub_ln", "ln", 0, 1, c, c, w * c))
        for b in range(n_blocks):
            out.append((f"s{si}b{b}_conv", "conv", k, 1, c, c, w * c))
            out.append((f"s{si}b{b}_ln1", "ln", 0, 1, c, c, w * c))
            out.append((f"s{si}b{b}_fc1", "fc", 0, 1, w * c, w * c, w * c))
            out.append((f"s{si}b{b}_fc2", "fc", 0, 1, w * c, w * c, w * c))
            out.append((f"s{si}b{b}_ln2", "ln", 0, 1, c, c, w * c))
        c_prev = c
    width = w * c_prev
    out.append(("final_ln", "ln", 0, 1, c_prev, c_prev, width))
    out.append(("head", "head", 0, 1, width, sizes["vocab"], sizes["vocab"]))
    return out


def init_params(key, sizes: dict):
    """Random weights: conv/FC weights N(0, 1/fan_in), biases N(0, 0.05),
    LayerNorm scale 1 + N(0, 0.1) and bias N(0, 0.1).  One jittable
    function of the key, so the weights are made on the device."""
    import jax
    import jax.numpy as jnp

    params = {}
    for name, kind, k, _s, c_in, c_out, width in kernel_list(sizes):
        key, k1, k2 = jax.random.split(key, 3)
        if kind == "ln":
            params[name] = {
                "scale": 1.0 + 0.1 * jax.random.normal(k1, (width,)),
                "bias": 0.1 * jax.random.normal(k2, (width,))}
        elif kind == "conv":
            params[name] = {
                "w": jax.random.normal(k1, (k, c_in, c_out))
                / math.sqrt(k * c_in),
                "b": 0.05 * jax.random.normal(k2, (c_out,))}
        else:
            params[name] = {
                "w": jax.random.normal(k1, (c_in, c_out)) / math.sqrt(c_in),
                "b": 0.05 * jax.random.normal(k2, (c_out,))}
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


# ---- features -------------------------------------------------------------

def _mel(f):
    return 2595.0 * np.log10(1.0 + f / 700.0)


def _hz(m):
    return 700.0 * (10.0 ** (m / 2595.0) - 1.0)


def mel_bank(n_mels: int) -> np.ndarray:
    """(N_FFT/2+1, n_mels) triangular filters, evenly spaced in mel."""
    freqs = np.linspace(0, SAMPLE_RATE / 2, N_FFT // 2 + 1)
    pts = _hz(np.linspace(_mel(FMIN), _mel(FMAX), n_mels + 2))
    bank = np.zeros((freqs.size, n_mels), np.float32)
    for m in range(n_mels):
        lo, mid, hi = pts[m], pts[m + 1], pts[m + 2]
        rise = (freqs - lo) / max(mid - lo, 1e-9)
        fall = (hi - freqs) / max(hi - mid, 1e-9)
        bank[:, m] = np.maximum(0.0, np.minimum(rise, fall))
    return bank


def dct2(n_in: int, n_out: int) -> np.ndarray:
    """Orthonormal DCT-II as an (n_in, n_out) matrix."""
    n = np.arange(n_in)[:, None]
    k = np.arange(n_out)[None, :]
    m = np.cos(np.pi * k * (2 * n + 1) / (2 * n_in)) * math.sqrt(2.0 / n_in)
    m[:, 0] /= math.sqrt(2.0)
    return m.astype(np.float32)


def windows_of(audio: np.ndarray) -> np.ndarray:
    """The decoding windows a stream of `audio` is cut into: every whole
    1520-sample window at a 1280-sample shift, then, where more than the
    framing overlap is left, one last window zero-padded to full length
    (the tail decoded at end of input).  (n_windows, 1520) float32."""
    audio = np.asarray(audio, np.float32)
    out = []
    off = 0
    while off + WINDOW_SAMPLES <= audio.size:
        out.append(audio[off:off + WINDOW_SAMPLES])
        off += WINDOW_SHIFT
    rest = audio[off:]
    if rest.size > WINDOW_SAMPLES - WINDOW_SHIFT:
        out.append(np.concatenate(
            [rest, np.zeros(WINDOW_SAMPLES - rest.size, np.float32)]))
    return (np.stack(out) if out
            else np.zeros((0, WINDOW_SAMPLES), np.float32))


def mfcc(windows, n_mels: int, n_mfcc: int, dtype, precision):
    """(B, n_win, 1520) -> (B, n_win * 8, n_mfcc), each window on its own
    (its first sample has no predecessor for the pre-emphasis)."""
    import jax
    import jax.numpy as jnp

    x = windows.astype(jnp.float32)
    x = jnp.concatenate([x[..., :1], x[..., 1:] - PREEMPHASIS * x[..., :-1]],
                        axis=-1)
    idx = (np.arange(FRAMES_PER_WINDOW)[:, None] * FRAME_SHIFT
           + np.arange(FRAME_LEN)[None, :])
    frames = x[..., idx] * np.hamming(FRAME_LEN).astype(np.float32)
    power = jnp.abs(jnp.fft.rfft(frames, n=N_FFT, axis=-1)) ** 2
    power = power.astype(dtype)
    mel = jnp.matmul(power, jnp.asarray(mel_bank(n_mels), dtype),
                     precision=precision)
    logmel = jnp.log(jnp.maximum(mel, jnp.asarray(1e-10, dtype)))
    out = jnp.matmul(logmel, jnp.asarray(dct2(n_mels, n_mfcc), dtype),
                     precision=precision)
    b, n = windows.shape[:2]
    return out.reshape(b, n * FRAMES_PER_WINDOW, n_mfcc)


def forward(params, sizes: dict, windows, dtype="float32",
            precision="highest"):
    """Log-probs (B, n_windows, vocab) of the acoustic model over whole
    utterances cut into `windows` (B, n_windows, 1520)."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    w = sizes["feat"]
    x = mfcc(windows, sizes["n_mels"], sizes["n_mfcc"], dtype, precision)
    x = x[..., None]                                  # (B, T, w, 1)

    def mm(a, b):
        return jnp.matmul(a, b, precision=precision)

    fc_in = None
    for name, kind, k, s, _cin, c_out, _width in kernel_list(sizes):
        q = p[name]
        bsz, t = x.shape[:2]
        if kind == "conv":
            xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0), (0, 0)))
            t_out = t // s
            y = sum(mm(xp[:, j:j + s * (t_out - 1) + 1:s], q["w"][j])
                    for j in range(k)) + q["b"]
            y = jnp.maximum(y, 0)
            if s == 1 and x.shape[-1] == c_out and name != "front_conv":
                y = y + x                             # block conv residual
            x = y
        elif kind == "ln":
            v = x.reshape(bsz, t, -1)
            mu = v.mean(-1, keepdims=True)
            var = ((v - mu) ** 2).mean(-1, keepdims=True)
            v = (v - mu) / jnp.sqrt(var + LN_EPS) * q["scale"] + q["bias"]
            x = v.reshape(x.shape)
        elif kind == "fc":
            v = x.reshape(bsz, t, -1)
            if name.endswith("fc1"):
                fc_in = v
                v = jnp.maximum(mm(v, q["w"]) + q["b"], 0)
            else:
                v = mm(v, q["w"]) + q["b"] + fc_in    # residual over block
            x = v.reshape(bsz, t, w, -1)
        else:                                         # head
            v = mm(x.reshape(bsz, t, -1), q["w"]) + q["b"]
            return jax.nn.log_softmax(v.astype(jnp.float32), axis=-1)
    raise AssertionError("no head")


# ---- lexicon, LM and search ----------------------------------------------

class Trie:
    """Padded trie over tokens: children sorted by token."""

    def __init__(self, words: list, fanout: int):
        kids = [{}]
        word_of = [-1]
        for wid, toks in enumerate(words):
            node = 0
            for t in toks:
                if t not in kids[node]:
                    kids[node][t] = len(kids)
                    kids.append({})
                    word_of.append(-1)
                node = kids[node][t]
            word_of[node] = wid
        n = len(kids)
        self.child = np.full((n, fanout), -1, np.int64)
        self.token = np.full((n, fanout), -1, np.int64)
        for i, ks in enumerate(kids):
            for j, (t, c) in enumerate(sorted(ks.items())):
                self.child[i, j] = c
                self.token[i, j] = t
        self.word = np.asarray(word_of, np.int64)


def bigram_table(counts: np.ndarray, alpha: float = 0.5) -> np.ndarray:
    """log P(w | prev) from raw counts with additive smoothing; row
    n_words is the sentence start."""
    c = counts.astype(np.float64) + alpha
    return np.log(c / c.sum(axis=1, keepdims=True)).astype(np.float32)


_M1 = np.uint64(0x9E3779B97F4A7C15)
_M2 = np.uint64(0xBF58476D1CE4E5B9)


def _chain(h, x):
    """64-bit hash of a history `h` extended by the event `x`."""
    with np.errstate(over="ignore"):
        z = (h ^ (x.astype(np.uint64) + _M1)) * _M2
        return z ^ (z >> np.uint64(31))


def _lse(a, b):
    with np.errstate(invalid="ignore"):
        return np.logaddexp(a, b)


class Beam:
    """A beam: arrays of the live hypotheses, best first."""

    def __init__(self, n_words: int):
        self.key = np.array([np.uint64(1)])
        self.pb = np.array([0.0])
        self.pnb = np.array([-np.inf])
        self.node = np.array([0])
        self.lm = np.array([n_words])
        self.last = np.array([-1])
        self.toks = [()]
        self.words = [()]

    def total(self):
        return _lse(self.pb, self.pnb)


def search(logp: np.ndarray, trie: Trie, lm: np.ndarray, dec: dict) -> Beam:
    """CTC prefix beam search over `logp` (T, V); returns the final beam
    after the end-of-utterance word commits, best first."""
    k_max, thr = dec["beam_size"], dec["beam_threshold"]
    lw, ws, blank = dec["lm_weight"], dec["word_score"], dec["blank_id"]
    fan = trie.child.shape[1]
    beam = Beam(lm.shape[1])
    logp = np.asarray(logp, np.float64)
    for lp in logp:
        n = beam.key.size
        tot = beam.total()
        # stay: blank, and the repeat of the last token
        rep = np.where(beam.last >= 0, lp[np.maximum(beam.last, 0)], -np.inf)
        s_pb = tot + lp[blank]
        s_pnb = beam.pnb + rep
        # continue / commit, one per (hypothesis, child)
        child = trie.child[beam.node]                  # (n, fan)
        tok = trie.token[beam.node]
        has = child >= 0
        tok_s = np.maximum(tok, 0)
        lp_ext = np.where(has, lp[tok_s], -np.inf)
        base = np.where(tok_s == beam.last[:, None], beam.pb[:, None],
                        tot[:, None])
        c_pnb = base + lp_ext
        wid = np.where(has, trie.word[np.maximum(child, 0)], -1)
        is_w = wid >= 0
        lm_sc = lm[beam.lm[:, None], np.maximum(wid, 0)]
        m_pnb = np.where(is_w, c_pnb + lw * lm_sc + ws, -np.inf)
        c_key = _chain(beam.key[:, None], 2 * tok_s)
        m_key = _chain(_chain(beam.key[:, None], 2 * tok_s + 1),
                       np.maximum(wid, 0) + 2 ** 40)
        # candidates in the layout [stay | continue | commit]
        par = np.concatenate([np.arange(n), np.repeat(np.arange(n), fan),
                              np.repeat(np.arange(n), fan)])
        kind = np.concatenate([np.zeros(n, int), np.ones(n * fan, int),
                               np.full(n * fan, 2)])
        ctok = np.concatenate([np.full(n, -1), tok_s.ravel(), tok_s.ravel()])
        cwid = np.concatenate([np.full(n, -1), np.full(n * fan, -1),
                               np.where(is_w, wid, -1).ravel()])
        key = np.concatenate([beam.key, c_key.ravel(), m_key.ravel()])
        pb = np.concatenate([s_pb, np.full(2 * n * fan, -np.inf)])
        pnb = np.concatenate([s_pnb, c_pnb.ravel(), m_pnb.ravel()])
        live = np.flatnonzero(_lse(pb, pnb) > -np.inf)
        # merge equal histories (first occurrence represents), then keep
        # the best k_max within the threshold of the best
        order = live[np.argsort(key[live], kind="stable")]
        ks = key[order]
        start = np.flatnonzero(np.r_[True, ks[1:] != ks[:-1]])
        rep_i = order[start]
        with np.errstate(invalid="ignore"):
            m_pb = np.logaddexp.reduceat(pb[order], start)
            m_pnb = np.logaddexp.reduceat(pnb[order], start)
        m_tot = _lse(m_pb, m_pnb)
        rank = np.lexsort((rep_i, -m_tot))[:k_max]
        rank = rank[m_tot[rank] >= m_tot[rank[0]] - thr]
        sel = rep_i[rank]
        new = Beam.__new__(Beam)
        new.key = key[sel]
        new.pb, new.pnb = m_pb[rank], m_pnb[rank]
        p, kd = par[sel], kind[sel]
        node_c = np.concatenate([beam.node, child.ravel(),
                                 np.zeros(n * fan, int)])
        lm_c = np.concatenate([beam.lm, np.repeat(beam.lm, fan),
                               np.maximum(wid, 0).ravel()])
        new.node = node_c[sel]
        new.lm = lm_c[sel]
        new.last = np.where(kd == 0, beam.last[p], ctok[sel])
        new.toks = [beam.toks[pi] + ((ctok[si],) if kd_ else ())
                    for pi, si, kd_ in zip(p, sel, kd)]
        new.words = [beam.words[pi] + ((cwid[si],) if kd_ == 2 else ())
                     for pi, si, kd_ in zip(p, sel, kd)]
        beam = new
    # end of utterance: hypotheses on a word-final node commit the word
    wid = trie.word[beam.node]
    pend = (wid >= 0) & (beam.node != 0)
    bonus = lw * lm[beam.lm, np.maximum(wid, 0)] + ws
    beam.pb = np.where(pend, beam.pb + bonus, beam.pb)
    beam.pnb = np.where(pend, beam.pnb + bonus, beam.pnb)
    beam.words = [w + ((int(i),) if p_ else ())
                  for w, i, p_ in zip(beam.words, wid, pend)]
    return beam


def served_form(seq, cap: int) -> tuple:
    """A history as a served result carries it: `cap` rows, every entry
    past the last row written into that row."""
    seq = tuple(int(v) for v in seq)
    return seq if len(seq) <= cap else seq[:cap - 1] + seq[-1:]


def hypotheses(beam: Beam) -> dict:
    """{(tokens, words) in served form: total score} of a final beam."""
    out = {}
    for t, w, s in zip(beam.toks, beam.words, beam.total()):
        key = (served_form(t, MAX_TOKENS), served_form(w, MAX_WORDS))
        out[key] = max(out.get(key, -np.inf), float(s))
    return out


def best(beam: Beam) -> dict:
    tot = beam.total()
    i = int(np.argmax(tot))
    return {"tokens": served_form(beam.toks[i], MAX_TOKENS),
            "words": served_form(beam.words[i], MAX_WORDS),
            "score": float(tot[i])}
