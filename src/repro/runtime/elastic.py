"""Elastic re-meshing: resume a job on a different topology.

Checkpoints store logical host arrays (ckpt/checkpoint.py), never device
layouts, so a restart can build whatever mesh the surviving fleet
supports and re-place state with that mesh's shardings.  This module is
the policy layer: pick a mesh from an available chip count, rescale the
data-parallel stream, and re-place a restored state.

On a real cluster the coordinator calls `plan_remesh` after failure
detection (runtime/fault.StepWatchdog escalation) with the surviving
chip count; here it is exercised by tests/test_elastic.py on host
devices.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax

from repro.parallel import sharding as shlib


@dataclass(frozen=True)
class RemeshPlan:
    data: int
    model: int
    pod: Optional[int] = None

    @property
    def n_devices(self) -> int:
        return self.data * self.model * (self.pod or 1)

    def axis_names(self):
        return (("pod", "data", "model") if self.pod else ("data", "model"))

    def shape(self):
        return ((self.pod, self.data, self.model) if self.pod
                else (self.data, self.model))


def plan_remesh(n_devices: int, *, model_parallel: int,
                global_batch: int) -> RemeshPlan:
    """Choose (data, model) for the surviving fleet.

    model_parallel is preserved (weights layouts assume it); the data
    axis absorbs the loss.  The global batch must stay divisible so the
    deterministic data stream re-partitions exactly (data/pipeline.py is
    a pure function of (seed, step, shard))."""
    assert n_devices % model_parallel == 0, (n_devices, model_parallel)
    data = n_devices // model_parallel
    while data > 1 and global_batch % data != 0:
        data -= 1            # shrink to a divisor of the global batch
    return RemeshPlan(data=data, model=model_parallel)


def build_mesh(plan: RemeshPlan):
    return shlib.make_mesh(plan.shape(), plan.axis_names())


def mesh_invariant_rng() -> None:
    """Elastic precondition: `jax.random` must produce the same LOGICAL
    values whatever mesh a jitted init runs under.  jax's legacy
    threefry lowering is sharding-dependent — `jax.jit(init,
    out_shardings=...)` on a 4x2 mesh and on a 2x2 mesh produce
    *different parameters from the same key* (observed ~0.5 max delta
    on the danube tiny config), so a resumed job could never be
    compared against — or reproduce — a straight run on the surviving
    topology.  Partitionable threefry makes generation
    placement-invariant (delta exactly 0).  Called by the training
    launcher before any RNG use; restarts therefore re-derive identical
    logical state regardless of the remesh plan."""
    jax.config.update("jax_threefry_partitionable", True)


def replace_state(cfg, checkpointer, state_template, mesh, step=None):
    """Restore a checkpoint INTO the new mesh's shardings (the elastic
    restart path: topology changed, logical state identical).

    Optimizer moments get their OWN sharding tree
    (`launch.steps._opt_shardings_like`): moments inherit parameter
    rules by path, which also covers int8 moment payloads
    ({'q','scale'} leaves) — the old code re-used the raw param
    shardings for 'm'/'v', which mis-places (and crashes on) quantized
    moment trees after `plan_remesh` shrinks the data axis."""
    if mesh is None:
        return checkpointer.restore(state_template, step=step)
    from repro.launch.steps import _opt_shardings_like
    p_sh = shlib.param_shardings(cfg, state_template["params"], mesh)
    o_sh = _opt_shardings_like(cfg, state_template["opt"], mesh)
    return checkpointer.restore(
        state_template, step=step,
        shardings={"params": p_sh, "opt": o_sh, "step": None})
