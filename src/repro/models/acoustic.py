"""The ASR engine's acoustic-model contract, and which model serves a
configuration.

An acoustic model is a module that supplies, for its configuration type
`CONFIG`:

  FRONT_END      the named scope of its front end in the fused step
  SCOPE          the named scope of its forward in the fused step
  validate(cfg, feat_cfg, use_int8=False, mesh=None)
                 raises ValueError for a program the model cannot serve
                 (int8 weights, a 'model' mesh, a front end of another
                 width)
  front_end(samples, feat_cfg, kernels=None) -> (..., frames, features)
                 its features of each window's samples (`core.features`)
  window_frames(cfg) -> (feature frames, CTC frames) per decoding window
  plan_kernels(cfg, feat_cfg) -> the step plan's kernel list, front end
                 first (`core.stepplan`)
  init_batched_stream_state(cfg, batch) -> per-slot carried state, every
                 leaf with a leading slot axis
  reset_stream_slot(state, slot, cfg) -> state with one slot made fresh
  forward_batched(params, cfg, feats, state, use_int8=False,
                  kernels=None, prepared=None, axis=None, overlap=False)
                 -> (log-probs (B, T', V), new state); feats (B, T, F)

`AsrProgram`, `make_step_plan` and `AsrEngine` reach the model only
through these, so the model is chosen by its configuration's type.
"""
from __future__ import annotations

from typing import Union

from repro.configs.fastconformer import ConformerConfig
from repro.configs.tds_asr import TDSConfig

AcousticConfig = Union[TDSConfig, ConformerConfig]


def model_of(cfg):
    """The module that implements the contract for `cfg`'s type."""
    from repro.models import conformer, tds

    for mod in (tds, conformer):
        if isinstance(cfg, mod.CONFIG):
            return mod
    raise TypeError(
        f"no acoustic model serves a {type(cfg).__name__} configuration")
