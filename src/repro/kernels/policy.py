"""Kernel dispatch policy: ref / interpret / Mosaic, resolved per backend.

Every public wrapper in `kernels/ops.py` takes an optional `KernelPolicy`
(threaded from `EngineConfig.kernels` by the serving engines) and picks
one of three execution modes:

  * ``ref``       — the pure-jnp oracle in `kernels/ref.py`, compiled by
                    XLA.  The fast path on CPU for ops in the decode hot
                    loop (interpret mode runs the kernel body in Python
                    per grid step, which is debug-speed only).
  * ``interpret`` — the Pallas kernel under the interpreter.  How kernel
                    correctness is validated against ref.py on CPU.
  * ``mosaic``    — the same pallas_call compiled by Mosaic (TPU).

``auto`` (the default) resolves per backend: ``mosaic`` on an
accelerator; on CPU, ``interpret`` for the standalone validation kernels
but ``ref`` for hot-path ops (the fused hypothesis unit runs inside the
per-frame decode scan).  The backend probe is hoisted out of the call
path — `jax.default_backend()` is read once per process, not per call
(it used to be re-queried by every op via `ops._interpret`).

Dispatch composes with `shard_map` (the mesh-sharded serving step runs
every hot-path op inside a per-device program): resolution happens at
Python trace time, outside any mesh axis, so ``ref``/``interpret``
lower to ordinary per-device XLA/Pallas calls on the shard-local
shapes, and ``mosaic`` keeps one pallas_call per device.  Only the
model-parallel matmul wrappers themselves (ops.int8_matmul_prepared's
``axis=``, tds.forward_batched's contraction) ever touch the mesh axis
— kernels never psum internally (Mosaic-under-shard_map shares the
real-TPU caveat tracked in ROADMAP.md).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax

MODES = ("auto", "ref", "interpret", "mosaic")


@functools.lru_cache(maxsize=1)
def _default_backend() -> str:
    return jax.default_backend()


@dataclass(frozen=True)
class KernelPolicy:
    """Frozen kernel-dispatch spec carried by `EngineConfig`."""
    mode: str = "auto"

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {self.mode!r}")

    def resolve(self, *, hot: bool = False) -> str:
        """Concrete mode for one op.  `hot=True` marks ops on the decode
        hot path, which `auto` never sends through the interpreter."""
        if self.mode != "auto":
            return self.mode
        if _default_backend() == "cpu":
            return "ref" if hot else "interpret"
        return "mosaic"


DEFAULT_POLICY = KernelPolicy()


def resolve(policy: KernelPolicy | None, *, hot: bool = False) -> str:
    return (policy if policy is not None else DEFAULT_POLICY).resolve(hot=hot)


# Kernel contract registry, consumed by `python -m repro.analysis`
# (rules RPL002 + RPL007): every module under kernels/ with a
# `pl.pallas_call` site declares its ref.py twin, the interpret-parity
# test that pins kernel==ref, the public "entry" wrapper whose
# signature must stay call-compatible with a ref twin (RPL007 checks
# parity and that the divisibility guard dominates each pallas_call in
# the entry's reach), and how its grid/BlockSpec divisibility
# assumption is handled — "checked" means the module itself guards it
# with a divisibility check (assert / pad / tile-halving),
# "fallback: ..." documents why no in-module check is needed.  Must
# stay a pure dict literal: the analyzer reads it with
# ast.literal_eval, never imports.
KERNEL_REGISTRY = {
    "tds_conv": {
        "ref": ["tds_conv", "tds_conv_fused"],
        "entry": "tds_conv_pallas",
        "test": "tests/test_kernels.py",
        "shape_guard": "checked",   # stride assert + bt halved to divide
    },
    "layernorm": {
        "ref": ["layernorm", "rmsnorm"],
        "entry": "norm_pallas",
        "test": "tests/test_kernels.py",
        "shape_guard": "checked",   # rows padded to the bt tile
    },
    "logmel": {
        "ref": "logmel",
        "entry": "logmel_pallas",
        "test": "tests/test_kernels.py",
        "shape_guard": "checked",   # frames padded to the bt tile
    },
    "flash_attention": {
        "ref": "flash_attention",
        "entry": "flash_attention_pallas",
        "test": "tests/test_kernels.py",
        "shape_guard": "checked",   # asserts Sq/Sk divisible by blocks
    },
    "beam_prune": {
        "ref": "beam_prune",
        "entry": "beam_prune_pallas",
        "test": "tests/test_kernels.py",
        "shape_guard": "checked",   # candidates padded to the bn tile
    },
    "int8_matmul": {
        "ref": "int8_matmul",
        "entry": "int8_matmul_pallas",
        "test": "tests/test_kernels.py",
        "shape_guard": "checked",   # bm/bn/bk asserted or halved to fit
    },
    "hypothesis_unit": {
        "ref": "hypothesis_unit",
        "entry": "hypothesis_unit_pallas",
        "test": "tests/test_hypothesis_unit.py",
        "shape_guard": "checked",   # asserts the padded (rows, 128) tiles
    },
    "chunk_attention": {
        "ref": "chunk_attention",
        "entry": "chunk_attention_pallas",
        "test": "tests/test_kernels.py",
        "shape_guard": "checked",   # asserts whole chunks, 128 pos rows
    },
}
