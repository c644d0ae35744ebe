"""TDS acoustic model (paper §4.2) as an explicit ASRPU kernel sequence.

The paper implements the wav2letter TDS network as a sequence of 79
kernels: 18 CONV, 29 FC, 32 LayerNorm (each with its setup thread).  This
module builds exactly that kernel list — the list is both the executable
model (offline + streaming, causal convs with carried left context) and
the artifact the evaluation reproduces (Fig. 9 layer sizes, Fig. 11
per-kernel times via the instruction-count model).

Views follow TDS: activations are (T, w, c) "2-D" maps; convs are
time-only (kernel k x 1) with full c x c channel mixing; FC blocks operate
on the flattened (w*c) vector.  All convs are causal so streaming
decoding steps produce bit-identical outputs to offline decoding
(property-tested).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.tds_asr import TDSConfig
from repro.core import treeutil

# the acoustic-model contract (models/acoustic.py)
CONFIG = TDSConfig
FRONT_END = "mfcc"
SCOPE = "tds_forward"


@dataclass(frozen=True)
class KernelSpec:
    """One ASRPU kernel (paper §3.1): name, kind, and the setup-thread
    metadata needed by the controller and the performance model."""
    name: str
    kind: str              # conv | fc | layernorm | head
    n_in: int              # inputs per output neuron (MACs) — 0 for LN
    n_out: int             # neurons == kernel threads per output frame
    kernel: int = 1        # time-kernel width (convs)
    stride: int = 1
    weight_bytes: int = 0  # int8 weight footprint (model-memory residency)
    residual: bool = False
    activation: str = "none"   # relu | none

    @property
    def n_subkernels(self) -> int:
        """FC layers are partitioned into <=1MB sub-kernels (paper §5.2)."""
        limit = 1 << 20
        return max(1, -(-self.weight_bytes // limit))


def build_kernel_specs(cfg: TDSConfig) -> List[KernelSpec]:
    specs: List[KernelSpec] = []
    w = cfg.stages[0].feat
    c_prev = 1
    c0 = cfg.stages[0].channels
    # front conv (stride 1)
    specs.append(KernelSpec("front_conv", "conv", n_in=cfg.stages[0].kernel * c_prev,
                            n_out=w * c0, kernel=cfg.stages[0].kernel,
                            weight_bytes=cfg.stages[0].kernel * c_prev * c0,
                            activation="relu"))
    c_prev = c0
    for si, st in enumerate(cfg.stages):
        # stage-entry subsampling conv + LN
        specs.append(KernelSpec(
            f"s{si}_subsample", "conv", n_in=cfg.sub_kernel * c_prev,
            n_out=w * st.channels, kernel=cfg.sub_kernel, stride=st.subsample,
            weight_bytes=cfg.sub_kernel * c_prev * st.channels,
            activation="relu"))
        specs.append(KernelSpec(f"s{si}_sub_ln", "layernorm", 0,
                                w * st.channels))
        width = w * st.channels
        for b in range(st.n_blocks):
            specs.append(KernelSpec(
                f"s{si}b{b}_conv", "conv", n_in=st.kernel * st.channels,
                n_out=width, kernel=st.kernel,
                weight_bytes=st.kernel * st.channels * st.channels,
                residual=True, activation="relu"))
            specs.append(KernelSpec(f"s{si}b{b}_ln1", "layernorm", 0, width))
            specs.append(KernelSpec(
                f"s{si}b{b}_fc1", "fc", n_in=width, n_out=width,
                weight_bytes=width * width, activation="relu"))
            specs.append(KernelSpec(
                f"s{si}b{b}_fc2", "fc", n_in=width, n_out=width,
                weight_bytes=width * width, residual=True))
            specs.append(KernelSpec(f"s{si}b{b}_ln2", "layernorm", 0, width))
        c_prev = st.channels
    width = w * cfg.stages[-1].channels
    specs.append(KernelSpec("final_ln", "layernorm", 0, width))
    specs.append(KernelSpec("head", "fc", n_in=width, n_out=cfg.vocab_size,
                            weight_bytes=width * cfg.vocab_size))
    return specs


def validate(cfg: TDSConfig, feat_cfg, use_int8: bool = False,
             mesh=None) -> None:
    """TDS serves every program option: int8 weights, a 'model' mesh."""


def front_end(samples: jax.Array, feat_cfg, kernels=None) -> jax.Array:
    """MFCC of each window, the mel+log+DCT tail through the fused
    logmel kernel."""
    from repro.core import features

    return features.mfcc(samples, feat_cfg, use_pallas=True,
                         kernels=kernels, hot=True)


def window_frames(cfg: TDSConfig) -> tuple:
    """(feature frames, CTC frames) of one decoding window: the frames
    of one output frame, 80 ms at the paper's 8x subsampling."""
    return cfg.total_subsample, 1


def plan_kernels(cfg: TDSConfig, feat_cfg) -> list:
    """The step plan's setup-thread entries: the MFCC front end, then
    the TDS kernel sequence: one thread per output neuron per frame
    (paper §3.1), LayerNorms one per frame."""
    from repro.core.stepplan import PlannedKernel, front_end_kernel

    t = window_frames(cfg)[0]
    out = [front_end_kernel("mfcc", feat_cfg, t, n_dct=feat_cfg.n_mfcc)]
    for spec in build_kernel_specs(cfg):
        t_out = t // spec.stride
        if spec.kind == "layernorm":
            out.append(PlannedKernel(
                spec.name, spec.kind, n_threads=t_out, n_frames=t_out,
                macs_per_thread=2 * spec.n_out, weight_bytes=0,
                n_subkernels=1))
        else:
            out.append(PlannedKernel(
                spec.name, spec.kind, n_threads=t_out * spec.n_out,
                n_frames=t_out, macs_per_thread=spec.n_in,
                weight_bytes=spec.weight_bytes,
                n_subkernels=spec.n_subkernels))
        t = t_out
    return out


def kernel_census(cfg: TDSConfig) -> dict:
    specs = build_kernel_specs(cfg)
    return {
        "conv": sum(s.kind == "conv" for s in specs),
        "fc": sum(s.kind in ("fc", "head") for s in specs),
        "layernorm": sum(s.kind == "layernorm" for s in specs),
    }


# ---------------------------------------------------------------------------
# parameters + forward
# ---------------------------------------------------------------------------
def init_tds(key, cfg: TDSConfig, dtype=jnp.float32) -> dict:
    params = {}
    for spec in build_kernel_specs(cfg):
        key, k = jax.random.split(key)
        if spec.kind == "layernorm":
            params[spec.name] = {"scale": jnp.ones((spec.n_out,), jnp.float32),
                                 "bias": jnp.zeros((spec.n_out,), jnp.float32)}
        elif spec.kind == "conv":
            c_out = spec.n_out // cfg.stages[0].feat
            c_in = spec.n_in // spec.kernel
            std = 1.0 / math.sqrt(spec.n_in)
            params[spec.name] = {
                "w": (jax.random.normal(k, (spec.kernel, c_in, c_out),
                                        jnp.float32) * std).astype(dtype),
                "b": jnp.zeros((c_out,), dtype)}
        else:
            std = 1.0 / math.sqrt(spec.n_in)
            params[spec.name] = {
                "w": (jax.random.normal(k, (spec.n_in, spec.n_out),
                                        jnp.float32) * std).astype(dtype),
                "b": jnp.zeros((spec.n_out,), dtype)}
    return params


def init_stream_state(cfg: TDSConfig) -> dict:
    """Left-context ring buffers — the scratchpad the paper keeps in the
    512KB shared memory between decoding steps (~275KB; see DESIGN.md)."""
    state = {}
    w = cfg.stages[0].feat
    for spec in build_kernel_specs(cfg):
        if spec.kind == "conv":
            c_in = spec.n_in // spec.kernel
            state[spec.name] = jnp.zeros((spec.kernel - 1, w, c_in),
                                         jnp.float32)
    return state


def init_batched_stream_state(cfg: TDSConfig, batch: int) -> dict:
    """Stream state for `batch` concurrent utterances: (B, k-1, w, c_in)
    per conv — the per-slot left context of a multi-stream slot pool."""
    return treeutil.batch_tree(init_stream_state(cfg), batch)


def reset_stream_slot(state: dict, slot, cfg: TDSConfig) -> dict:
    """Zero one slot's left context (utterance boundary in that stream)."""
    return treeutil.set_slot(state, slot, init_stream_state(cfg))


def state_bytes(cfg: TDSConfig, bytes_per_el: int = 1) -> int:
    st = init_stream_state(cfg)
    return sum(int(np.prod(a.shape)) * bytes_per_el
               for a in jax.tree.leaves(st))


def quantize_params(params, cfg: TDSConfig) -> dict:
    """Pre-quantize every FC/head weight matrix ONCE (int8 + per-output
    scales): {kernel name: {"wq", "ws"}}.  The serving engine builds
    this at engine-construction time so the decode hot path only ever
    quantizes activations (`ops.int8_matmul_prepared`) instead of
    re-quantizing static weights on every decoding step."""
    from repro.kernels import ops
    prepared = {}
    for spec in build_kernel_specs(cfg):
        if spec.kind in ("fc", "head"):
            wq, ws = ops.prepare_int8_weights(params[spec.name]["w"])
            prepared[spec.name] = {"wq": wq, "ws": ws}
    return prepared


def forward_batched(params, cfg: TDSConfig, feats: jax.Array, state: dict,
                    use_int8: bool = False, kernels=None,
                    prepared: Optional[dict] = None,
                    axis: Optional[str] = None,
                    overlap: bool = False):
    """Slot-native TDS forward.  feats: (B, T, n_mfcc); state: the
    batched stream state ((B, k-1, w, c_in) per conv).  Returns
    (log_probs (B, T', V), new_state).

    The slot axis is folded into the row dimension of every matmul —
    (B*T, w*c) rows for FC/head/LayerNorm, (B*T*w, c_in) rows for each
    conv tap — so the MXU sees ONE large matmul per kernel instead of B
    independent small ones (the old path vmapped the whole forward per
    slot).  Convs, LayerNorms, and the int8 FC path dispatch through
    `kernels` (a KernelPolicy) as hot-path ops: pure-jnp ref on CPU,
    the Pallas kernels (conv epilogue fused: bias+ReLU+residual) under
    interpret/Mosaic.  `prepared` (from `quantize_params`) supplies
    pre-quantized int8 weights; without it the use_int8 path quantizes
    weights on the fly (offline/one-shot use).

    `axis` names the shard_map mesh axis this forward runs under (the
    serving engine's model-parallel step).  FC/head weights then arrive
    as feature-axis shards — (K/n_model, N) per device — and the
    contraction becomes a local partial matmul + psum over `axis`; the
    B*T row fold, convs, and LayerNorms are untouched (replicated).
    Activations stay replicated, so only the weight reads are split.
    Weights left whole (non-divisible feature dim) are detected by
    shape and contract locally, bit-identical to axis=None.

    `overlap` (sharded path only) routes each contraction through
    `ops.psum_overlap_matmul`'s output-column split so layer l's
    all-reduce chunks hide under the matmuls still being issued —
    numerically ~1e-6-equal to the synchronous reference, which stays
    the parity path (see `psum_overlap_matmul`).
    """
    from repro.kernels import ops

    specs = build_kernel_specs(cfg)
    new_state = dict(state)
    w = cfg.stages[0].feat
    B = feats.shape[0]
    x = feats[:, :, :, None]                         # (B, T, w, 1)

    def matmul(xm, name, p):
        if use_int8:
            if prepared is not None and name in prepared:
                pq = prepared[name]
                return ops.int8_matmul_prepared(xm, pq["wq"], pq["ws"],
                                                policy=kernels, hot=True,
                                                axis=axis,
                                                overlap=overlap) + p["b"]
            return ops.int8_matmul(xm, p["w"], policy=kernels,
                                   hot=True) + p["b"]
        wm = p["w"]
        if axis is not None and wm.shape[0] != xm.shape[1]:
            # model-parallel contraction: slice the activation columns
            # matching this device's weight shard, contract locally,
            # all-reduce the partial sums; bias added post-reduction
            xloc = ops.shard_local_cols(xm, wm.shape[0], axis)
            if overlap:
                return ops.psum_overlap_matmul(xloc, wm, axis) + p["b"]
            return jax.lax.psum(xloc @ wm, axis) + p["b"]
        return xm @ wm + p["b"]

    for spec in specs:
        p = params[spec.name]
        if spec.kind == "conv":
            k, s = spec.kernel, spec.stride
            m = x.shape[1]
            assert m % s == 0, (m, s)
            xp = jnp.concatenate([state[spec.name], x], axis=1)
            res = x if (spec.residual and s == 1
                        and x.shape[-1] == spec.n_out // w) else None
            x = ops.tds_conv(xp, p["w"], p["b"], stride=s,
                             relu=spec.activation == "relu", res=res,
                             policy=kernels, hot=True)
            new_state[spec.name] = xp[:, -(k - 1):] if k > 1 \
                else state[spec.name]
        elif spec.kind == "layernorm":
            t = x.shape[1]
            xm = ops.layernorm(x.reshape(B * t, -1), p["scale"], p["bias"],
                               policy=kernels, hot=True)
            x = xm.reshape(x.shape)
        else:  # fc / head
            t = x.shape[1]
            xm = x.reshape(B * t, -1)
            if spec.activation == "relu":      # fc1: start of the FC block
                fc_res = xm
            y = matmul(xm, spec.name, p)
            if spec.activation == "relu":
                y = jax.nn.relu(y)
            if spec.residual and y.shape == fc_res.shape:
                y = y + fc_res                 # TDS residual: whole FC block
            if spec.name == "head":
                logp = jax.nn.log_softmax(y, axis=-1)
                return logp.reshape(B, t, -1), new_state
            c = spec.n_out // w
            x = y.reshape(B, t, w, c)
    raise AssertionError("head kernel missing")


def forward(params, cfg: TDSConfig, feats: jax.Array,
            state: Optional[dict] = None, use_int8: bool = False,
            kernels=None, prepared: Optional[dict] = None,
            axis: Optional[str] = None):
    """feats: (T, n_mfcc). Returns (log_probs (T', V), new_state).

    state=None => offline (zero left context).  T must be divisible by the
    total subsample.  use_int8 routes FC/head matmuls through the int8
    quantized path — ASRPU's 8-bit MAC (`prepared` from
    `quantize_params` skips the per-call weight quantization); `kernels`
    is the KernelPolicy dispatching the Pallas-backed ops (None = auto).

    This is exactly the B=1 slice of `forward_batched` — single-stream
    and slot-pooled decoding share ONE code path, which is what keeps
    the streaming-vs-offline and multi-stream parity tests bit-honest.
    """
    st_in = state if state is not None else init_stream_state(cfg)
    bst = jax.tree.map(lambda a: a[None], st_in)
    logp, ns = forward_batched(params, cfg, feats[None], bst,
                               use_int8=use_int8, kernels=kernels,
                               prepared=prepared, axis=axis)
    return logp[0], jax.tree.map(lambda a: a[0], ns)
