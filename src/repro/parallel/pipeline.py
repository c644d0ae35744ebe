"""Pipeline parallelism: GPipe-style microbatch schedule over a 'stage'
mesh axis with collective_permute hops, inside shard_map.

Composable feature for depth-dominated deployments (the production
dry-run mesh uses DP x TP, which is the right config for the assigned
sizes; PP becomes necessary past ~1T params or very small per-chip HBM).
Autodiff through the schedule is valid (ppermute transposes to the
reverse permute), giving pipelined backward for free (GPipe semantics,
bubble fraction (S-1)/(M+S-1)).

Tested on a multi-device host platform subprocess (tests/test_pipeline.py).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x_micro, mesh: Mesh,
                   axis: str = "stage"):
    """Run x through `n_stages` chained applications of stage_fn.

    stage_fn: (params_one_stage, x) -> y   (same shape as x)
    stage_params: pytree with leading axis n_stages (sharded over `axis`)
    x_micro: (n_micro, mb, ...) microbatched input (replicated)
    Returns (n_micro, mb, ...) output of the last stage.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    steps = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_stage(params, xm):
        # params: leading axis 1 (this stage's slice); xm: (n_micro, mb, ...)
        p = jax.tree.map(lambda a: a[0], params)
        sid = lax.axis_index(axis)
        buf = jnp.zeros_like(xm[0])                   # current stage input
        outs = jnp.zeros_like(xm)

        def step(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (others ignore)
            inject = jnp.where(t < n_micro, t, n_micro - 1)
            buf = jnp.where(sid == 0, xm[inject], buf)
            y = stage_fn(p, buf)
            # last stage emits microbatch t - (n_stages - 1)
            out_t = t - (n_stages - 1)
            emit = (sid == n_stages - 1) & (out_t >= 0)
            safe_t = jnp.clip(out_t, 0, n_micro - 1)
            outs = jnp.where(
                emit,
                lax.dynamic_update_index_in_dim(outs, y, safe_t, 0),
                outs)
            buf = lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = lax.scan(step, (buf, outs), jnp.arange(steps))
        # gather last stage's outputs to all (replicated output contract)
        return lax.psum(jnp.where(sid == n_stages - 1, outs, 0.0), axis)

    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False)
    return fn(stage_params, x_micro)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
