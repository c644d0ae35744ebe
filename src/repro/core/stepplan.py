"""Static decoding-step schedule — the paper's setup threads (§3.2).

JAX needs static shapes, so the per-kernel setup arithmetic — how many
outputs are producible from buffered inputs, what to retire, how many
threads to launch — runs at plan time and fixes the steady-state
schedule; a step whose buffers cannot produce a single output returns
early exactly like a setup thread returning zero.  The plan doubles as
the driver for the paper's instruction-count performance model
(benchmarks/asrpu_model.py) and as the `Program` schedule of the serving
engine (repro.serving): one `StepPlan` per configured acoustic program,
whose window and kernel list come from the acoustic model
(models/acoustic.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from repro.configs.tds_asr import FEATURE_CONFIG, TDS_CONFIG, FeatureConfig
from repro.models.acoustic import model_of


@dataclass
class PlannedKernel:
    """One kernel execution inside a decoding step (Fig. 6)."""
    name: str
    kind: str
    n_threads: int          # threads launched by the ASR controller
    n_frames: int           # output frames this step
    macs_per_thread: int    # inner-loop MACs (setup thread metadata)
    weight_bytes: int
    n_subkernels: int


@dataclass
class StepPlan:
    """Static steady-state decoding-step schedule (the setup threads)."""
    samples_per_step: int
    feat_frames_per_step: int
    acoustic_frames_per_step: int   # hyp-expansion repetitions (Fig. 6)
    kernels: List[PlannedKernel]

    def total_threads(self) -> int:
        return sum(k.n_threads for k in self.kernels)


def front_end_kernel(name: str, feat_cfg: FeatureConfig, n_frames: int,
                     n_dct: int = 0) -> PlannedKernel:
    """The front end's entry: one thread per feature frame (window and
    pre-emphasis, FFT, mel filterbank, and a DCT to `n_dct` outputs if
    the model takes cepstra)."""
    return PlannedKernel(
        name, "feature", n_threads=n_frames, n_frames=n_frames,
        macs_per_thread=(feat_cfg.frame_len
                         + feat_cfg.n_fft * int(np.log2(feat_cfg.n_fft))
                         + (feat_cfg.n_fft // 2 + 1) * feat_cfg.n_mels
                         + feat_cfg.n_mels * n_dct),
        weight_bytes=0, n_subkernels=1)


def make_step_plan(acoustic=TDS_CONFIG,
                   feat_cfg: FeatureConfig = FEATURE_CONFIG,
                   step_ms: Optional[float] = None,
                   beam_k: int = 128) -> StepPlan:
    """The setup-thread arithmetic for one steady-state decoding step of
    the acoustic model `acoustic` (a TDSConfig or ConformerConfig): its
    window and its kernel list, front end first.  `step_ms`, if given,
    must be the model's own window (80 ms for TDS, 1.12 s for the
    streaming Conformer's 14-frame chunk)."""
    model = model_of(acoustic)
    feat_frames, out_frames = model.window_frames(acoustic)
    if step_ms is not None and step_ms != feat_frames * feat_cfg.shift_ms:
        raise ValueError(
            f"step_ms={step_ms} is not the {type(acoustic).__name__} "
            f"window of {feat_frames * feat_cfg.shift_ms} ms")
    kernels = model.plan_kernels(acoustic, feat_cfg)
    assert kernels[-1].n_frames == out_frames, (kernels[-1], out_frames)
    return StepPlan(feat_frames * feat_cfg.frame_shift, feat_frames,
                    out_frames, kernels)
