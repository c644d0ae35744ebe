"""Cache-aware streaming FastConformer encoder with a CTC head.

The encoder (configs/fastconformer.py), batched over the engine's
gathered slots and streamed one decoding window (one 14-frame chunk) at
a time through per-slot caches:

    x = Linear(C*F'->D)(flatten(DWSub8(logmel))) * sqrt(D)
    per block:  x = x + 0.5 * FFN1(LN(x))
                x = x + MHSA_rel(LN(x))      # chunked, over cached K/V
                x = x + Conv(LN(x))          # PW, GLU, causal DW, LN,
                                             # swish, PW
                x = x + 0.5 * FFN2(LN(x));  x = LN(x)
    logp = log_softmax(W_ctc x + b)

* Subsampling (`dw_striding`): a 3x3 stride-2 conv to C channels, then
  two depthwise 3x3 stride-2 + pointwise convs, ReLU after each.  Time
  is causal (two frames of left context per conv, zeros at an
  utterance's start), frequency is padded one bin each side; the
  C x F' map is flattened channel-major.
* Attention: relative-position MHSA (`kernels/chunk_attention.py`): a
  frame sees its own chunk and the `left` frames before it, never a
  cached frame from before its utterance's start.
* Conv module: its depthwise conv is causal over `conv_kernel - 1`
  cached frames.

Per slot, the carried state holds the subsampling convs' last two input
frames, each layer's keys and values of the last `left` frames, each
layer's last `conv_kernel - 1` depthwise-conv inputs, and how many
encoder frames (up to `left`) the slot has seen.  A step may carry w
consecutive chunks of a slot (window fusion): chunk c attends to the
cache and to chunks 0..c of the step, exactly as w steps of one chunk
would.  Layers run under `lax.scan` over stacked weights.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.fastconformer import ConformerConfig
from repro.core import treeutil
from repro.kernels import ops
from repro.kernels.chunk_attention import POS_ROWS
from repro.kernels.ref import MASK

# the acoustic-model contract (models/acoustic.py)
CONFIG = ConformerConfig
FRONT_END = "logmel"
SCOPE = "conformer"

SUB_CONTEXT = 2                 # left frames of a 3x3 stride-2 causal conv


def validate(cfg: ConformerConfig, feat_cfg, use_int8: bool = False,
             mesh=None) -> None:
    """The Conformer runs float32 weights on one device, over the front
    end's log-mel bins."""
    if use_int8:
        raise ValueError("int8 weights are implemented for the TDS "
                         "acoustic model only, not for the Conformer")
    if mesh is not None:
        raise ValueError("mesh serving shards the TDS acoustic model "
                         "only; a Conformer engine runs on one device "
                         "(mesh=None)")
    if cfg.n_mels != feat_cfg.n_mels:
        raise ValueError(
            f"the Conformer takes {cfg.n_mels} log-mel inputs, the front "
            f"end makes {feat_cfg.n_mels}")


def front_end(samples: jax.Array, feat_cfg, kernels=None) -> jax.Array:
    """Log-mel features of each window (`features.logmel`)."""
    from repro.core import features

    return features.logmel(samples, feat_cfg, kernels=kernels, hot=True)


def window_frames(cfg: ConformerConfig) -> tuple:
    """(feature frames, CTC frames) of one decoding window: one chunk."""
    return cfg.chunk * cfg.subsample, cfg.chunk


def plan_kernels(cfg: ConformerConfig, feat_cfg) -> list:
    """The step plan's entries: the log-mel front end, subsampling, each
    block, the CTC head, with their multiply-adds per output frame and
    f32 weight bytes."""
    from repro.core.stepplan import PlannedKernel, front_end_kernel

    feat_frames = window_frames(cfg)[0]
    t = feat_frames // cfg.subsample
    D, C, F = cfg.d_model, cfg.sub_channels, cfg.sub_freq
    block_w = 2 * 2 * D * cfg.d_ff + 5 * D * D + 2 * D * D + D * D \
        + cfg.conv_kernel * D
    sub_macs = (4 * 4 * F * 9 * C                    # conv1, per frame
                + 2 * 2 * F * (9 * C + C * C)        # dw2 + pw2
                + F * (9 * C + C * C) + C * F * D)   # dw3 + pw3 + out
    out = [front_end_kernel("logmel", feat_cfg, feat_frames),
           PlannedKernel("subsample", "conv", n_threads=t * D, n_frames=t,
                         macs_per_thread=sub_macs // D,
                         weight_bytes=4 * (C * F * D + 2 * C * C),
                         n_subkernels=1)]
    for i in range(cfg.n_layers):
        out.append(PlannedKernel(
            f"block{i}", "conformer", n_threads=t * D, n_frames=t,
            macs_per_thread=block_w // D, weight_bytes=4 * block_w,
            n_subkernels=1))
    out.append(PlannedKernel("head", "fc", n_threads=t * cfg.vocab_size,
                             n_frames=t, macs_per_thread=D,
                             weight_bytes=4 * D * cfg.vocab_size,
                             n_subkernels=1))
    return out


# ---------------------------------------------------------------------------
# parameters and carried state
# ---------------------------------------------------------------------------
def init_stream_state(cfg: ConformerConfig) -> dict:
    """One slot's carried state at an utterance's start."""
    D, C, L = cfg.d_model, cfg.sub_channels, cfg.n_layers
    z = functools.partial(jnp.zeros, dtype=jnp.float32)
    return {"sub1": z((SUB_CONTEXT, cfg.n_mels)),
            "sub2": z((SUB_CONTEXT, cfg.n_mels // 2, C)),
            "sub3": z((SUB_CONTEXT, cfg.n_mels // 4, C)),
            "k": z((L, cfg.left, D)), "v": z((L, cfg.left, D)),
            "conv": z((L, cfg.conv_kernel - 1, D)),
            "seen": jnp.zeros((), jnp.int32)}


def init_batched_stream_state(cfg: ConformerConfig, batch: int) -> dict:
    return treeutil.batch_tree(init_stream_state(cfg), batch)


def reset_stream_slot(state: dict, slot, cfg: ConformerConfig) -> dict:
    """Make one slot fresh: zero caches, nothing seen (utterance start)."""
    return treeutil.set_slot(state, slot, init_stream_state(cfg))


@functools.lru_cache()
def pos_table(cfg: ConformerConfig) -> np.ndarray:
    """(POS_ROWS, d_model) sinusoidal encodings of the relative distances
    a chunk's attention uses, by decreasing distance: row m encodes
    left + chunk - 1 - m, down to -(chunk - 1); the rows after are zero
    (the kernel's lane tile)."""
    D = cfg.d_model
    n = cfg.left + 2 * cfg.chunk - 1
    dist = (cfg.left + cfg.chunk - 1 - np.arange(n)).astype(np.float64)
    div = np.exp(np.arange(0, D, 2) * -(math.log(10000.0) / D))
    pe = np.zeros((POS_ROWS, D))
    pe[:n, 0::2] = np.sin(dist[:, None] * div)
    pe[:n, 1::2] = np.cos(dist[:, None] * div)
    return pe.astype(np.float32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _conv3x3_s2(x, w, b):
    """Stride-2 3x3 conv applied per channel.  x: (B, 2 + T, F, Cx), the
    two left-context frames first; w: (3, 3, C) taps (Cx is 1 or C);
    returns (B, T/2, F/2, C): output (j, f) reads input frames
    2j - 2 .. 2j and bins 2f - 1 .. 2f + 1 (zero outside)."""
    _, tp, f, _ = x.shape
    t = tp - SUB_CONTEXT
    xp = jnp.pad(x, ((0, 0), (0, 0), (1, 1), (0, 0)))
    out = b
    for dt in range(3):
        for df in range(3):
            out = out + xp[:, dt:dt + t - 1:2, df:df + f - 1:2] * w[dt, df]
    return out


def _subsample(p, feats, state, cfg: ConformerConfig):
    """(B, T, n_mels) log-mel -> (B, T/8, d_model), and the new
    subsampling context."""
    B, t, _ = feats.shape
    x = jnp.concatenate([state["sub1"], feats], axis=1)
    new = {"sub1": x[:, -SUB_CONTEXT:]}
    y = jax.nn.relu(_conv3x3_s2(x[..., None], p["conv1"]["w"],
                                p["conv1"]["b"]))
    for i in (2, 3):
        x = jnp.concatenate([state[f"sub{i}"], y], axis=1)
        new[f"sub{i}"] = x[:, -SUB_CONTEXT:]
        y = _conv3x3_s2(x, p[f"dw{i}"]["w"], p[f"dw{i}"]["b"])
        y = jax.nn.relu(y @ p[f"pw{i}"]["w"] + p[f"pw{i}"]["b"])
    t8 = t // cfg.subsample
    y = jnp.swapaxes(y, 2, 3).reshape(B, t8, -1)       # channel-major
    return y @ p["out"]["w"] + p["out"]["b"], new


def _block(x, p, kc, vc, cc, bias, table, cfg: ConformerConfig, kernels):
    """One Conformer block over x (B, T, D) with its layer's caches."""
    B, T, D = x.shape
    H, dh = cfg.n_heads, cfg.d_head

    def ln(y, q):
        return ops.layernorm(y.reshape(-1, D), q["scale"], q["bias"],
                             eps=cfg.ln_eps, policy=kernels,
                             hot=True).reshape(y.shape)

    def lin(y, q):
        return y @ q["w"] + q["b"]

    def ffn(y, i):
        h = jax.nn.silu(lin(ln(y, p[f"ff{i}_ln"]), p[f"ff{i}_in"]))
        return lin(h, p[f"ff{i}_out"])

    def heads(y):                                   # (B, S, D) -> (B,H,S,dh)
        return y.reshape(B, -1, H, dh).transpose(0, 2, 1, 3)

    x = x + 0.5 * ffn(x, 1)
    h = ln(x, p["att_ln"])
    k = jnp.concatenate([kc, lin(h, p["k"])], axis=1)
    v = jnp.concatenate([vc, lin(h, p["v"])], axis=1)
    pos = (table @ p["pos"]["w"]).reshape(POS_ROWS, H, dh).transpose(1, 0, 2)
    ctx = ops.chunk_attention(heads(lin(h, p["q"])), heads(k), heads(v), pos,
                              p["pos_u"][:, None], p["pos_v"][:, None], bias,
                              chunk=cfg.chunk, policy=kernels, hot=True)
    x = x + lin(ctx.transpose(0, 2, 1, 3).reshape(B, T, D), p["att_out"])
    g = lin(ln(x, p["conv_ln"]), p["pw1"])
    g = g[..., :D] * jax.nn.sigmoid(g[..., D:])     # GLU
    g = jnp.concatenate([cc, g], axis=1)            # causal left context
    kw = cfg.conv_kernel
    y = p["dw"]["b"]
    for j in range(kw):
        y = y + g[:, j:j + T] * p["dw"]["w"][j]
    y = lin(jax.nn.silu(ln(y, p["conv_norm"])), p["pw2"])
    x = x + y
    x = x + 0.5 * ffn(x, 2)
    return (ln(x, p["out_ln"]), k[:, -cfg.left:], v[:, -cfg.left:],
            g[:, -(kw - 1):])


def forward_batched(params, cfg: ConformerConfig, feats: jax.Array,
                    state: dict, use_int8: bool = False, kernels=None,
                    prepared=None, axis=None, overlap: bool = False):
    """feats: (B, T, n_mels) log-mel, T a whole number of windows
    (chunk * 8 frames each); state: the batched stream state.  Returns
    (log_probs (B, T/8, vocab), new_state).  Float32 weights on one
    device: the int8 and mesh options of the contract stay unset
    (`validate` refuses them at build; `overlap` is a no-op without a
    mesh)."""
    assert not (use_int8 or prepared is not None or axis), \
        "the Conformer takes no int8 weights and no mesh"
    B, t, _ = feats.shape
    T = t // cfg.subsample
    with jax.named_scope("subsample"):
        x, new = _subsample(params["sub"], feats, state, cfg)
    x = x * math.sqrt(cfg.d_model)
    # key j of [cache | step] lies left - j frames before the step: one
    # from before the utterance's start is masked
    j = jnp.arange(cfg.left + T)
    bias = jnp.where(j[None, :] >= cfg.left - state["seen"][:, None],
                     0.0, MASK)[:, None, :]
    table = jnp.asarray(pos_table(cfg))

    def layer(x, xs):
        p, kc, vc, cc = xs
        x, kc, vc, cc = _block(x, p, kc, vc, cc, bias, table, cfg, kernels)
        return x, (kc, vc, cc)

    by_layer = functools.partial(jnp.swapaxes, axis1=0, axis2=1)
    x, (k, v, cc) = jax.lax.scan(
        layer, x, (params["blocks"], by_layer(state["k"]),
                   by_layer(state["v"]), by_layer(state["conv"])))
    logits = x @ params["head"]["w"] + params["head"]["b"]
    new.update(k=by_layer(k), v=by_layer(v), conv=by_layer(cc),
               seen=jnp.minimum(state["seen"] + T, cfg.left))
    return jax.nn.log_softmax(logits, axis=-1), new
