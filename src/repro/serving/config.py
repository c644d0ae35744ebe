"""Declarative serving configuration: one frozen `Program` per workload.

The paper's accelerator is configured by a mutable command sequence
(ConfigureASR_AcousticScoring -> ConfigureASR_HypExpansion ->
ConfigureBeamWidth).  The serving engine replaces that with a single
frozen spec: an `AsrProgram` (acoustic model + hypothesis expansion +
decoding step geometry, compiled into a static `StepPlan`) or an
`LmProgram` (LM arch + cache/generation budget), wrapped in an
`EngineConfig` that adds the slot-pool size.  A configured engine never
mutates its program — reconfiguration means building a new engine.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional, Tuple, Union

import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.configs.tds_asr import (DECODER_CONFIG, FEATURE_CONFIG,
                                   DecoderConfig, FeatureConfig)
from repro.core.lexicon import BigramLM, Lexicon
from repro.core.stepplan import StepPlan, make_step_plan
from repro.kernels.policy import KernelPolicy
from repro.models.acoustic import AcousticConfig, model_of


@dataclass(frozen=True)
class AsrProgram:
    """The streaming ASR decoding program (paper §3: one small decoder
    program per stage — acoustic scoring then hypothesis expansion).

    `acoustic` is the acoustic model's configuration; its type picks the
    model (`models.acoustic.model_of`): the paper's TDS or the
    cache-aware streaming Conformer.  The model fixes the decoding
    window (`step_ms`: 80 ms for TDS, one 14-frame chunk, 1.12 s, for
    the Conformer) and the front end (MFCC or log-mel)."""
    acoustic: AcousticConfig
    lex: Lexicon
    lm: BigramLM
    feat_cfg: FeatureConfig = FEATURE_CONFIG
    dec_cfg: DecoderConfig = DECODER_CONFIG
    use_int8: bool = False
    # Upper bound on how many buffered windows ONE fused decoding step
    # may consume (powers of two below it are the step buckets, like
    # LmProgram.prefill_buckets).  Live streaming still steps window by
    # window; bulk decoding (whole utterances buffered) folds up to this
    # many windows into the acoustic forward's row dimension, reading
    # each weight matrix once per multi-window step instead of once per
    # window.  1 disables fusion.
    max_windows_per_step: int = 4
    # On finish(), a session whose buffer still holds samples no decoded
    # frame has covered (more than the frame_len - frame_shift overlap a
    # step retains) gets that trailing partial window zero-padded and
    # decoded by one last step before finalize — without it, up to
    # ~step_ms of tail audio (often the end of the last word) is
    # silently dropped.  The deprecated ASRPU command shims disable it:
    # the paper's DecodingStep/best commands have no end-of-input signal
    # and only ever decode whole windows.
    flush_tail: bool = True
    # Per-push input cap (samples): one push may not exceed this many
    # audio samples (default ~60 s at the paper's 16 kHz).  Admission
    # validation, not a stream-length bound — a session may push many
    # capped chunks.
    max_push_samples: int = 960_000

    def __post_init__(self):
        # a config no model serves raises TypeError, an option the model
        # lacks ValueError
        self.model.validate(self.acoustic, self.feat_cfg,
                            use_int8=self.use_int8)

    @property
    def model(self):
        """The acoustic model's module (the contract of
        models/acoustic.py)."""
        return model_of(self.acoustic)

    @property
    def step_ms(self) -> float:
        """Audio one decoding window advances by, fixed by the model:
        its feature frames per window times the frame shift."""
        return (self.model.window_frames(self.acoustic)[0]
                * self.feat_cfg.shift_ms)

    def step_buckets(self) -> Tuple[int, ...]:
        """Descending window counts a fused step may take (one jit entry
        each, traced lazily on first use)."""
        out, b = [], 1
        while b <= self.max_windows_per_step:
            out.append(b)
            b *= 2
        return tuple(reversed(out))

    def step_plan(self) -> StepPlan:
        """The static setup-thread schedule for one decoding step."""
        return make_step_plan(self.acoustic, self.feat_cfg,
                              beam_k=self.dec_cfg.beam_size)

    def prepare_params(self, params, mesh=None):
        """Build-time weight preparation for the decoding step, returning
        `(params, prepared)`:

          * int8 programs quantize every FC/head weight matrix ONCE
            (`tds.quantize_params`) into `prepared` so the hot path only
            quantizes activations; fp32 programs get `prepared=None`.
          * with a `mesh` (the engine's model-parallel spec), both trees
            are PLACED with `param_shardings`-style NamedShardings —
            FC/head matmuls (and their int8 `wq` payloads) split on the
            feature axis over the 'model' mesh axis, everything else
            replicated — so each device of the sharded engine step holds
            only its weight shard (`parallel.sharding.tds_param_specs`).

        The engine passes both results straight into the model's
        `forward_batched`.  Both are TDS-only: another acoustic model's
        `validate` refuses int8 (`__post_init__`) and a mesh
        (`EngineConfig`), and it gets its params back as they are."""
        prepared = None
        if self.use_int8:
            from repro.models import tds
            prepared = tds.quantize_params(params, self.acoustic)
        if mesh is not None:
            from repro.parallel import sharding as shlib
            params = shlib.place_tree(
                params, shlib.tds_param_specs(self.acoustic, mesh), mesh)
            if prepared is not None:
                prepared = shlib.place_tree(
                    prepared, shlib.tds_prepared_specs(self.acoustic, mesh),
                    mesh)
        return params, prepared

    def validate_input(self, chunk: np.ndarray) -> None:
        """Admission-time validation of one pushed audio chunk: the
        fused step trusts its inputs (a NaN sample poisons the slot's
        beam scores irrecoverably and a huge chunk is an allocation
        attack), so the session front-end rejects bad input HERE —
        before anything is buffered — instead of letting it fault the
        co-batched step later."""
        chunk = np.asarray(chunk)
        if chunk.ndim != 1:
            raise ValueError(
                f"audio chunk must be 1-D samples, got shape "
                f"{chunk.shape}")
        if not np.issubdtype(chunk.dtype, np.floating):
            raise ValueError(
                f"audio chunk must be float samples, got dtype "
                f"{chunk.dtype}")
        if chunk.shape[0] > self.max_push_samples:
            raise ValueError(
                f"audio chunk of {chunk.shape[0]} samples exceeds "
                f"max_push_samples={self.max_push_samples}")
        if chunk.shape[0] and not np.isfinite(chunk).all():
            raise ValueError("audio chunk contains NaN/Inf samples")

    def with_beam_width(self, beam: float) -> "AsrProgram":
        """ConfigureBeamWidth as a pure derivation, not a mutation."""
        return replace(self, dec_cfg=replace(self.dec_cfg,
                                             beam_threshold=beam))


@dataclass(frozen=True)
class LmProgram:
    """Batched LM serving program: arch + pooled-cache geometry.

    `prefill_buckets` bounds admission-time compilation: prompts are
    right-padded to the smallest covering bucket and prefilled through
    one jit entry per bucket (a masked multi-row prefill), instead of
    one jit entry per distinct prompt length.  Empty = derive powers of
    two from 8 up to the first one covering the longest legal prompt.
    """
    model_cfg: ModelConfig
    cache_len: int
    max_new: int
    prefill_buckets: Tuple[int, ...] = ()

    @property
    def max_prompt_len(self) -> int:
        return self.cache_len - self.max_new

    def buckets(self) -> Tuple[int, ...]:
        if self.prefill_buckets:
            bs = tuple(sorted(set(int(b) for b in self.prefill_buckets)))
            if bs[-1] < self.max_prompt_len:
                raise ValueError(
                    f"largest prefill bucket {bs[-1]} does not cover the "
                    f"longest legal prompt ({self.max_prompt_len})")
        else:
            out, b = [8], 8
            while b < self.max_prompt_len:
                b *= 2
                out.append(b)
            bs = tuple(out)
        # prefill chunking (attention chunks, SSD chunk size) requires
        # every bucket S to satisfy S % min(chunk, S) == 0
        chunks = [self.model_cfg.attn_chunk_q, self.model_cfg.attn_chunk_kv]
        if self.model_cfg.ssm is not None:
            chunks.append(self.model_cfg.ssm.chunk_size)
        for b in bs:
            for c in chunks:
                if b % min(c, b):
                    raise ValueError(
                        f"prefill bucket {b} not divisible by chunk {c}")
        return bs

    def validate_prompt(self, prompt_len: int) -> None:
        if prompt_len < 1:
            raise ValueError("prompt must contain at least one token")
        if prompt_len + self.max_new > self.cache_len:
            raise ValueError(
                f"prompt_len={prompt_len} + max_new={self.max_new} exceeds "
                f"cache_len={self.cache_len}")

    def validate_input(self, prompt: np.ndarray) -> None:
        """Admission-time validation of a pushed prompt: token ids must
        be an integral 1-D vector inside the vocabulary — an
        out-of-range id indexes garbage through the embedding gather
        (or faults the device) inside the shared prefill batch, so it
        is rejected before it can be co-batched."""
        prompt = np.asarray(prompt)
        if prompt.ndim != 1:
            raise ValueError(
                f"prompt must be a 1-D token vector, got shape "
                f"{prompt.shape}")
        if not np.issubdtype(prompt.dtype, np.integer):
            raise ValueError(
                f"prompt must hold integer token ids, got dtype "
                f"{prompt.dtype}")
        self.validate_prompt(prompt.shape[0])
        vocab = self.model_cfg.vocab_size
        if prompt.size and (prompt.min() < 0 or prompt.max() >= vocab):
            raise ValueError(
                f"prompt token ids must be in [0, {vocab}), got range "
                f"[{prompt.min()}, {prompt.max()}]")


Program = Union[AsrProgram, LmProgram]


@dataclass(frozen=True)
class EngineConfig:
    """A program plus the slot-pool size it is served over.

    `kernels` selects how Pallas-backed decode ops execute (ref /
    interpret / Mosaic, resolved per backend by default) — it replaced
    the old per-call `use_pallas_prune` bool threaded through the
    decoder; see repro.kernels.policy.KernelPolicy.

    `mesh` is the parallel spec: a `jax.sharding.Mesh` with a 'model'
    axis, and optionally a 'data' axis.  The ASR engine then places
    FC/head weights as feature-axis shards over 'model' and runs its
    fused step under `shard_map`, so each device reads only its weight
    shard (the B=1 fp32 step is bound by the per-window FC weight
    traffic; see ROADMAP).  With a 'data' axis the SLOT POOL is sharded
    too: each data shard holds `n_slots / n_data` slots' stream state,
    beam, and gathered sub-batch rows end-to-end (beam expansion is
    embarrassingly parallel across slots, so the only collectives stay
    the 'model'-axis psums), which is what scales serve throughput with
    device count instead of just splitting weight reads.  `n_slots`
    must divide evenly over the 'data' axis.  None (the default) keeps
    the exact single-device step — not a 1-device mesh, the same
    unsharded jit as before — and 1D ('model',) meshes keep PR 5's
    replicated-pool step bitwise.

    `overlap_psum` switches the sharded step's model-parallel
    contractions to the latency-hiding output-column split
    (`ops.psum_overlap_matmul`): each layer's all-reduce is chunked so
    it can complete under the next chunk's local matmul on backends
    with async collectives.  Numerically ~1e-6-equal to the default
    synchronous psum, which stays the parity reference.  A no-op
    without a mesh (there is nothing to overlap).

    `max_queue` is the admission backpressure bound: with every slot
    busy and this many sessions already queued, `Engine.open()` raises
    `AdmissionRejected` (a typed error the network front-end maps to
    503) instead of queueing unboundedly.  None (default) keeps the
    unbounded in-process behavior; 0 means "never queue — reject unless
    a slot is free".

    Fault-tolerance knobs (see README "Fault tolerance"):

    `session_deadline` — wall-clock seconds a session may live from
    `open()` before the pump reaps it (`DeadlineExceeded`, a typed
    `SessionFaulted`), freeing its slot/queue entry.  None = no
    deadline.

    `worker_watchdog` — seconds an `EngineWorker`'s heartbeat may age
    before the server's supervisor declares the worker wedged, fails
    its in-flight futures, rebuilds the pool, and restarts the thread.
    None disables the wedge detection (a DEAD thread is still detected
    and restarted).

    `faults` — an armed `repro.serving.faults.FaultPolicy` consulted at
    the engine's injection sites; None (production) skips every check.
    """
    program: Program
    n_slots: int = 1
    kernels: KernelPolicy = field(default_factory=KernelPolicy)
    mesh: Optional[Mesh] = None
    max_queue: Optional[int] = None
    overlap_psum: bool = False
    session_deadline: Optional[float] = None
    worker_watchdog: Optional[float] = None
    faults: Optional[object] = None    # FaultPolicy; object() keeps the
                                       # config module import-light

    def __post_init__(self):
        if self.n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {self.n_slots}")
        if self.max_queue is not None and self.max_queue < 0:
            raise ValueError(
                f"max_queue must be None or >= 0, got {self.max_queue}")
        if self.session_deadline is not None and self.session_deadline <= 0:
            raise ValueError(
                f"session_deadline must be None or > 0, got "
                f"{self.session_deadline}")
        if self.worker_watchdog is not None and self.worker_watchdog <= 0:
            raise ValueError(
                f"worker_watchdog must be None or > 0, got "
                f"{self.worker_watchdog}")
        if self.mesh is not None and isinstance(self.program, AsrProgram):
            prog = self.program
            prog.model.validate(prog.acoustic, prog.feat_cfg,
                                use_int8=prog.use_int8, mesh=self.mesh)
        if self.mesh is not None:
            from repro.parallel.sharding import auto_axes
            # the pool's resets and readouts run outside shard_map
            object.__setattr__(self, "mesh", auto_axes(self.mesh))
            if "model" not in self.mesh.axis_names:
                raise ValueError(
                    f"serving mesh needs a 'model' axis, got {self.mesh}")
            extra = [a for a in self.mesh.axis_names
                     if a not in ("data", "model")]
            if extra:
                raise ValueError(
                    f"serving mesh axes must be ('data', 'model') or "
                    f"('model',), got extra axes {extra} in {self.mesh}")
            if "data" in self.mesh.axis_names:
                nd = self.mesh.shape["data"]
                if self.n_slots % nd != 0:
                    raise ValueError(
                        f"n_slots={self.n_slots} must divide evenly over "
                        f"the 'data' mesh axis (size {nd}): each data "
                        f"shard owns n_slots/n_data pool slots")


def make_engine(config: EngineConfig, params):
    """Build the engine matching `config.program`'s workload type."""
    from repro.serving.asr import AsrEngine
    from repro.serving.lm import LmEngine

    if isinstance(config.program, AsrProgram):
        return AsrEngine(config, params)
    if isinstance(config.program, LmProgram):
        return LmEngine(config, params)
    raise TypeError(f"unknown program type: {type(config.program)!r}")
