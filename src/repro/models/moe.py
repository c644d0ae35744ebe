"""Mixture-of-Experts with capacity-based *scatter* dispatch.

Design notes (see DESIGN.md §5):
  * GShard-style one-hot dispatch einsums cost O(T·E·C·D) FLOPs — far more
    than the expert compute itself at our scales. We instead scatter tokens
    into an (E, C, D) buffer (O(T·D) data movement) and run grouped matmuls
    (O(T·k·cf·D·F) FLOPs == true active compute), so the roofline compute
    term reflects active parameters only.
  * Experts are sharded over the 'model' mesh axis when E % model == 0
    (expert parallelism); otherwise the expert f-dim is sharded
    (TP-within-expert).  The scatter/gather across the token<->expert
    resharding is what GSPMD lowers to the MoE all-to-all.
  * Router runs in fp32; auxiliary load-balancing loss is returned.
"""
from __future__ import annotations

import jax
from jax import lax
import jax.numpy as jnp

from repro.configs.base import MoESpec
from repro.models import layers


def init_moe(key, d: int, spec: MoESpec, dtype=jnp.bfloat16) -> dict:
    k_r, k_g, k_u, k_d, k_s = jax.random.split(key, 5)
    E, F = spec.n_experts, spec.expert_d_ff
    std = 1.0 / jnp.sqrt(d)
    p = {
        "router": {"w": (jax.random.normal(k_r, (d, E), jnp.float32) * std
                         ).astype(jnp.float32)},
        "w_gate": (jax.random.normal(k_g, (E, d, F), jnp.float32) * std).astype(dtype),
        "w_up": (jax.random.normal(k_u, (E, d, F), jnp.float32) * std).astype(dtype),
        "w_down": (jax.random.normal(k_d, (E, F, d), jnp.float32)
                   / jnp.sqrt(F)).astype(dtype),
    }
    if spec.shared_d_ff:
        p["shared"] = layers.init_mlp(k_s, d, spec.shared_d_ff, dtype)
    return p


def capacity(n_tokens: int, spec: MoESpec) -> int:
    c = int(n_tokens * spec.top_k * spec.capacity_factor / spec.n_experts)
    # multiple of 256 so the capacity dim shards evenly over the DP axes
    return max(256, -(-c // 256) * 256) if n_tokens >= 256 else max(8, c)


def apply_moe(p: dict, x: jax.Array, spec: MoESpec, act: str, sharder=None):
    """x: (B, S, D) -> (y, aux_loss)."""
    if sharder is None:
        from repro.parallel.sharding import Sharder
        sharder = Sharder(None)
    ep = (sharder.mesh is not None
          and spec.n_experts % sharder.mesh.shape.get("model", 1) == 0)

    def _divisible():
        nb = 1
        for a in sharder.batch:
            nb *= sharder.mesh.shape[a]
        return (x.shape[0] % max(nb, 1) == 0
                and x.shape[1] % sharder.mesh.shape["model"] == 0)

    if (ep and not getattr(sharder, "baseline", False) and x.shape[1] > 1
            and _divisible()):
        # hillclimb: explicit expert-parallel dispatch via shard_map
        # (GSPMD's guessed layout for the gather dispatch replicates the
        # (T*K, D) combine tensors — see EXPERIMENTS.md §Perf)
        return apply_moe_ep(p, x, spec, act, sharder)
    B, S, D = x.shape
    T = B * S
    E, K = spec.n_experts, spec.top_k
    C = capacity(T, spec)
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"]["w"])                       # (T, E) fp32
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                      # (T, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # --- sort-based dispatch: 1-D argsort + row gathers only.  (A scatter
    # formulation makes GSPMD materialize (T*K, D)-shaped u32 index tensors;
    # gathers partition cleanly and lower to the MoE all-to-all.) ---------
    flat_e = top_e.reshape(-1)                                  # (T*K,)
    order = jnp.argsort(flat_e, stable=True)                    # (T*K,)
    rank = jnp.argsort(order)            # rank of candidate i in expert order
    counts = jnp.bincount(flat_e, length=E)                     # (E,)
    starts = jnp.cumsum(counts) - counts                        # (E,)
    pos = rank - starts[flat_e]          # position of candidate within expert
    keep = pos < C

    # expert buffer (E, C, D) filled by *gather*: slot (e, c) takes the
    # candidate ranked starts[e] + c, masked when c >= counts[e]
    slot_rank = starts[:, None] + jnp.arange(C)[None, :]        # (E, C)
    slot_valid = jnp.arange(C)[None, :] < counts[:, None]
    cand_of_slot = jnp.take(order, jnp.minimum(slot_rank, T * K - 1), axis=0)
    tok_of_slot = cand_of_slot // K                             # (E, C)
    buf = jnp.take(xt, tok_of_slot.reshape(-1), axis=0).reshape(E, C, D)
    buf = jnp.where(slot_valid[..., None], buf, 0)
    buf = sharder.expert(buf, ep)

    h = (layers.activation(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]), act)
         * jnp.einsum("ecd,edf->ecf", buf, p["w_up"]))
    h = sharder.expert(h, ep)
    out = sharder.expert(jnp.einsum("ecf,efd->ecd", h, p["w_down"]), ep)
    out = out.reshape(E * C, D)

    # combine: candidate (t, k)'s slot is flat_e*C + pos (gather back)
    slot = jnp.minimum(flat_e * C + jnp.minimum(pos, C - 1), E * C - 1)
    gathered = jnp.take(out, slot, axis=0)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    y = (gathered.reshape(T, K, D)
         * top_p[..., None].astype(x.dtype)).sum(axis=1)

    if "shared" in p:
        y = y + layers.apply_mlp(p["shared"], xt, act)

    # load-balance aux loss (Switch-style)
    me = probs.mean(axis=0)                                     # (E,)
    ce = jnp.bincount(flat_e, length=E).astype(jnp.float32) / (T * K)
    aux = E * jnp.sum(me * ce)
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# explicit expert-parallel MoE (shard_map + all-to-all)
# ---------------------------------------------------------------------------
def _local_dispatch_combine(p, xl, spec: MoESpec, act: str, nm: int,
                            axis: str):
    """Per-shard MoE body: local routing + sort-gather dispatch, all-to-all
    over the expert axis, local-capacity (GShard local groups) semantics."""
    Tl, D = xl.shape
    E, K = spec.n_experts, spec.top_k
    E_loc = E // nm
    Cl = max(8, -(-int(Tl * K * spec.capacity_factor / E) // 8) * 8)

    logits = jnp.einsum("td,de->te", xl.astype(jnp.float32), p["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    rank = jnp.argsort(order)
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = rank - starts[flat_e]
    keep = pos < Cl

    slot_rank = starts[:, None] + jnp.arange(Cl)[None, :]
    slot_valid = jnp.arange(Cl)[None, :] < counts[:, None]
    cand = jnp.take(order, jnp.minimum(slot_rank, Tl * K - 1), axis=0)
    buf = jnp.take(xl, (cand // K).reshape(-1), axis=0).reshape(E, Cl, D)
    buf = jnp.where(slot_valid[..., None], buf, 0)

    # dispatch all-to-all: (nm, E_loc, Cl, D) -> rows from every shard
    buf = lax.all_to_all(buf.reshape(nm, E_loc, Cl, D), axis, 0, 0,
                         tiled=False)                     # (nm, E_loc, Cl, D)
    buf = buf.transpose(1, 0, 2, 3).reshape(E_loc, nm * Cl, D)

    h = (layers.activation(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]), act)
         * jnp.einsum("ecd,edf->ecf", buf, p["w_up"]))
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])      # (E_loc, nm*Cl, D)

    # return trip
    out = out.reshape(E_loc, nm, Cl, D).transpose(1, 0, 2, 3)
    out = lax.all_to_all(out, axis, 0, 0, tiled=False)    # (nm, E_loc, Cl, D)
    out = out.reshape(E * Cl, D)

    slot = jnp.minimum(flat_e * Cl + jnp.minimum(pos, Cl - 1), E * Cl - 1)
    gathered = jnp.take(out, slot, axis=0)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    y = (gathered.reshape(Tl, K, D)
         * top_p[..., None].astype(xl.dtype)).sum(axis=1)

    me = probs.mean(axis=0)
    ce = jnp.bincount(flat_e, length=E).astype(jnp.float32) / (Tl * K)
    aux = E * jnp.sum(me * ce)
    return y, aux


def apply_moe_ep(p: dict, x: jax.Array, spec: MoESpec, act: str, sharder):
    """Expert parallelism with explicit all-to-all (shard_map over 'model',
    vmapped over the DP axes): tokens stay in their DP row, expert weights
    live on their 'model' column (replicated across DP inside the column —
    storage stays FSDP-sharded; jax reshards at the shard_map boundary).

    vs the GSPMD path: no (T*K, D) replication, two all-to-alls per layer
    (the textbook MoE schedule).  Local-capacity drop semantics (GShard
    local groups).
    """
    from jax.sharding import PartitionSpec as P
    mesh = sharder.mesh
    nm = mesh.shape["model"]
    B, S, D = x.shape
    b_axes = sharder.batch

    def body(router_w, wg, wu, wd, shared, xl):
        pl = {"router": {"w": router_w}, "w_gate": wg, "w_up": wu,
              "w_down": wd}
        Bl, Sl, _ = xl.shape
        y, aux = _local_dispatch_combine(pl, xl.reshape(Bl * Sl, D), spec,
                                         act, nm, "model")
        if shared is not None:
            y = y + layers.apply_mlp(shared, xl.reshape(Bl * Sl, D), act)
        aux = lax.pmean(aux, "model")
        for a in b_axes:
            aux = lax.pmean(aux, a)
        return y.reshape(Bl, Sl, D), aux

    shared = p.get("shared")
    in_specs = (P(), P("model", None, None), P("model", None, None),
                P("model", None, None),
                None if shared is None else P(),
                P(b_axes if b_axes else None, "model", None))
    if shared is None:
        def body2(rw, wg, wu, wd, xl):
            return body(rw, wg, wu, wd, None, xl)
        fn = jax.shard_map(body2, mesh=mesh,
                        in_specs=in_specs[:4] + (in_specs[5],),
                        out_specs=(P(b_axes if b_axes else None,
                                     "model", None), P()),
                        check_vma=False)
        y, aux = fn(p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"], x)
    else:
        fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                        out_specs=(P(b_axes if b_axes else None,
                                     "model", None), P()),
                        check_vma=False)
        y, aux = fn(p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"],
                    shared, x)
    return y, aux
