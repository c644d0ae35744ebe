"""Sharding rules: DP/FSDP over ('pod','data'), TP/EP/SP over 'model'.

All rules are *path-based* over the params pytree produced by
`models.transformer.LM.init` (leading repeat axis on every 'layers' leaf is
never sharded).  Divisibility of every sharded dim for every assigned arch
is property-tested in tests/test_sharding.py; vocab is Megatron-padded.

Axis roles
  pod, data : batch DP + FSDP weight/optimizer sharding
  model     : tensor parallel (flattened head dim / d_ff / vocab),
              expert parallel (when n_experts % model == 0),
              sequence parallel for long KV caches (decode cells)
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def auto_axes(mesh: Optional[Mesh]) -> Optional[Mesh]:
    """`mesh` with every axis typed Auto.  `jax.make_mesh` types its axes
    Explicit, and then every op outside `shard_map` on an array sharded
    over such an axis must resolve its output sharding from types alone —
    a slot-reset scatter or an embedding gather raises ShardingTypeError.
    The programs here leave those to GSPMD, so meshes are Auto."""
    if mesh is None or all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_mesh(shape, axis_names) -> Mesh:
    """`jax.make_mesh` over the visible devices, axes typed Auto."""
    return auto_axes(jax.make_mesh(tuple(shape), tuple(axis_names)))


def batch_axes(mesh: Optional[Mesh]):
    if mesh is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


class Sharder:
    """Activation sharding-constraint helper threaded through the model.

    REPRO_BASELINE=1 disables the beyond-baseline layout optimizations
    (context-parallel attention) so §Perf can record before/after from
    the same code."""

    def __init__(self, mesh: Optional[Mesh], shard_batch: bool = True):
        import os
        self.mesh = mesh
        self.batch = batch_axes(mesh) if shard_batch else ()
        self.model = "model" if (mesh is not None
                                 and "model" in mesh.axis_names) else None
        self.baseline = os.environ.get("REPRO_BASELINE", "0") == "1"

    def _c(self, x, spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def act(self, x):
        """(B, S, D) activations: batch over DP axes + sequence parallelism
        over 'model' (Korthikanti et al.) — layer-boundary activations (and
        hence the layer-scan backward residual stack) are fully sharded;
        GSPMD inserts the all-gather/reduce-scatter pair around attention."""
        if self.mesh is None:
            return x
        b = self.batch if (self.batch and
                           x.shape[0] % _axsize(self.mesh, self.batch) == 0) else ()
        sp = None
        if (self.model and x.ndim >= 3
                and x.shape[1] % self.mesh.shape["model"] == 0):
            sp = self.model
        return self._c(x, P(b, sp, *([None] * (x.ndim - 2))))

    def seq(self, x):
        """(B, S, K, D) cache-layout kv: S over 'model' (sequence-parallel
        cache storage, matches cache_shardings)."""
        if self.mesh is None or x.ndim != 4 or self.baseline:
            return x
        b = self.batch if (self.batch and
                           x.shape[0] % _axsize(self.mesh, self.batch) == 0) else ()
        s = None
        if self.model and x.shape[1] % self.mesh.shape["model"] == 0:
            s = self.model
        return self._c(x, P(b, s, None, None))

    def attn_q(self, x):
        """(nq, B, K, G, cq, D) chunked-attention q tiles: shard the
        intra-tile cq dim over 'model'.  nq/nkv are *scan* axes (sharding
        them is meaningless — sequential); cq is a parallel dim present in
        every tile, so the (cq x ckv) score tiles shard over the full mesh
        and the inner scans stay collective-free for every arch (head
        counts 40/56/28/24 don't divide 16; cq does)."""
        if self.mesh is None or x.ndim != 6 or self.baseline:
            return x
        b = self.batch if (self.batch and
                           x.shape[1] % _axsize(self.mesh, self.batch) == 0) else ()
        c = None
        if self.model and x.shape[4] % self.mesh.shape["model"] == 0:
            c = self.model
        return self._c(x, P(None, b, None, None, c, None))

    def attn_kv_chunks(self, x):
        """(nkv, B, K, ckv, D) kv chunks: replicated over 'model' (each
        cq-shard needs every kv column)."""
        if self.mesh is None or x.ndim != 5 or self.baseline:
            return x
        b = self.batch if (self.batch and
                           x.shape[1] % _axsize(self.mesh, self.batch) == 0) else ()
        return self._c(x, P(None, b, None, None, None))

    def kv(self, x):
        """(B, Skv, K, D) k/v: batch-sharded, replicated over 'model'
        (one gather per layer instead of per inner step)."""
        if self.mesh is None or x.ndim != 4 or self.baseline:
            return x
        b = self.batch if (self.batch and
                           x.shape[0] % _axsize(self.mesh, self.batch) == 0) else ()
        return self._c(x, P(b, None, None, None))

    def heads(self, x):
        """(B, S, H, ...) mamba/SSD head-major activations: B over DP axes,
        heads over 'model' (mamba is naturally TP over d_inner: depthwise
        conv + per-head SSD never mix heads until out_proj)."""
        if self.mesh is None or x.ndim < 3:
            return x
        b = self.batch if (self.batch and
                           x.shape[0] % _axsize(self.mesh, self.batch) == 0) else ()
        h = None
        if self.model and x.shape[2] % self.mesh.shape["model"] == 0:
            h = self.model
        return self._c(x, P(b, None, h, *([None] * (x.ndim - 3))))

    def inner(self, x):
        """(B, S, d_inner) mamba conv activations: d_inner over 'model'."""
        if self.mesh is None or x.ndim != 3:
            return x
        b = self.batch if (self.batch and
                           x.shape[0] % _axsize(self.mesh, self.batch) == 0) else ()
        d = None
        if self.model and x.shape[2] % self.mesh.shape["model"] == 0:
            d = self.model
        return self._c(x, P(b, None, d))

    def expert(self, x, ep: bool):
        """(E, C, D|F) MoE expert buffers: E over 'model' when
        expert-parallel, capacity over the DP axes (otherwise the data
        axis idles through all expert compute), last dim over 'model'
        for TP-within-expert."""
        if self.mesh is None or x.ndim != 3:
            return x
        e = "model" if (ep and self.model) else None
        c = self.batch if (self.batch and
                           x.shape[1] % _axsize(self.mesh, self.batch) == 0) else None
        f = None
        if (not ep) and self.model and x.shape[2] % self.mesh.shape["model"] == 0:
            f = self.model
        return self._c(x, P(e, c, f))

    def tokens(self, x):
        """(T, ...) flat token-major tensors (MoE dispatch/combine sides).

        T = B*S is sharded over (DP axes, 'model') — the exact layout of a
        sequence-parallel (B, S, D) activation flattened, so dispatch
        entry/exit needs no reshard; GSPMD turns the expert-buffer
        gather/ungather into the MoE all-to-all."""
        if self.mesh is None:
            return x
        axes = self.batch
        if not axes or x.shape[0] % _axsize(self.mesh, axes) != 0:
            return x
        return self._c(x, P(axes, *([None] * (x.ndim - 1))))

    def logits(self, x):
        """(..., V) logits: vocab over model axis."""
        if self.mesh is None:
            return x
        b = self.batch if (self.batch and
                           x.shape[0] % _axsize(self.mesh, self.batch) == 0) else ()
        return self._c(x, P(b, *([None] * (x.ndim - 2)), self.model))


def _axsize(mesh: Mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def axis_size(mesh: Mesh, name: str):
    """Size of mesh axis `name`, or None when the mesh doesn't declare
    it: callers fall back to replicated instead of KeyErroring on a
    mesh without the axis (a 1D ('data',) serving mesh reaching the
    'model' rules, and vice versa)."""
    if name in mesh.axis_names:
        return mesh.shape[name]
    return None


# ---------------------------------------------------------------------------
# parameter sharding rules
# ---------------------------------------------------------------------------
def _param_rule(path: tuple, shape: tuple, cfg, mesh: Mesh) -> P:
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    # int8 serving weights: 'wq' shards like 'w'; 'wscale' (per-out-channel)
    # takes the w-rule with the contraction dim removed
    if names and names[-1] == "wq":
        names = names[:-1] + ["w"]
    elif names and names[-1] == "wscale":
        fake = tuple(shape[:-1]) + (1 << 22, shape[-1])
        spec_w = _param_rule(_names_path(names[:-1] + ["w"]), fake, cfg, mesh)
        return P(*(list(spec_w)[:-2] + [list(spec_w)[-1]]))
    fsdp = batch_axes(mesh)
    nm = axis_size(mesh, "model")
    in_layers = "layers" in names
    leaf = names[-1]
    parent = names[-2] if len(names) >= 2 else ""

    def spec(*dims):
        if in_layers:
            dims = (None,) + dims  # leading repeat axis
        # drop axes that don't divide evenly (safety net; tested exhaustively)
        out = []
        off = len(shape) - len(dims)
        assert off == 0, (names, shape, dims)
        for size, d in zip(shape, dims):
            if d is None:
                out.append(None)
                continue
            axes = d if isinstance(d, tuple) else (d,)
            if all(a in mesh.axis_names for a in axes) and \
                    _axsize(mesh, axes) and size % _axsize(mesh, axes) == 0:
                out.append(d)
            else:
                out.append(None)
        return P(*out)

    # --- embeddings / head -------------------------------------------------
    if "embed" in names:
        return spec("model", fsdp)
    if "lm_head" in names:
        if leaf == "b":
            return spec("model")
        return spec(fsdp, "model")
    # --- norms / small vectors ---------------------------------------------
    if leaf in ("scale", "bias", "A_log", "D", "dt_bias") or parent in (
            "norm1", "norm2", "final_norm", "norm_gate"):
        return spec(*([None] * (len(shape) - (1 if in_layers else 0))))
    # --- attention -----------------------------------------------------------
    if parent == "wqkv":
        return spec(fsdp, "model") if leaf == "w" else spec("model")
    if parent == "wo":
        return spec("model", fsdp) if leaf == "w" else spec(None)
    # --- MoE -----------------------------------------------------------------
    if "router" in names:
        return spec(fsdp, None)
    if leaf == "w" and parent in ("w_gate", "w_up", "w_down") and "shared" not in names:
        pass  # dense MLP handled below
    if names.count("mlp") and cfg is not None and cfg.moe is not None and \
            len(shape) - (1 if in_layers else 0) == 3:
        ep = nm is not None and cfg.moe.n_experts % nm == 0
        if leaf in ("w_gate", "w_up") or parent in ("w_gate", "w_up"):
            return spec("model", fsdp, None) if ep else spec(None, fsdp, "model")
        return spec("model", None, fsdp) if ep else spec(None, "model", fsdp)
    # --- dense MLP / shared expert / mamba projections -----------------------
    if parent in ("w_gate", "w_up", "w_z", "w_x", "w_B", "w_C", "w_dt"):
        return spec(fsdp, "model") if leaf == "w" else spec("model")
    if parent in ("w_down", "out_proj", "wo"):
        return spec("model", fsdp) if leaf == "w" else spec(None)
    if parent == "conv_x":
        return spec(None, "model") if leaf == "w" else spec("model")
    # fallback: replicate
    return P(*([None] * len(shape)))


class _NK:
    def __init__(self, key):
        self.key = key


def _names_path(names):
    return tuple(_NK(n) for n in names)


def param_shardings(cfg, param_shapes, mesh: Mesh):
    """pytree of NamedSharding matching `param_shapes`."""
    def f(path, leaf):
        return NamedSharding(mesh, _param_rule(path, leaf.shape, cfg, mesh))
    return jax.tree_util.tree_map_with_path(f, param_shapes)


# ---------------------------------------------------------------------------
# TDS serving: model-parallel weight shards (ASRPU pool-of-cores analogue)
# ---------------------------------------------------------------------------
def tds_param_specs(tds_cfg, mesh: Mesh) -> dict:
    """PartitionSpec tree for a TDS params pytree under the serving
    'model' axis: every FC/head weight matrix is split on its feature
    (contraction) axis — each device holds K/n_model weight rows and
    computes a partial sum, exactly ASRPU's pool-of-cores split where
    each program computes one slice of a layer — while convs, LayerNorm
    vectors, and biases stay replicated (they are KBs against the FCs'
    MBs).  Weights whose feature dim does not divide the axis fall back
    to replicated (same safety net as `_param_rule`), as does every
    weight when the mesh has no 'model' axis at all."""
    from repro.models.tds import build_kernel_specs
    nm = axis_size(mesh, "model")
    out = {}
    for s in build_kernel_specs(tds_cfg):
        if s.kind == "layernorm":
            out[s.name] = {"scale": P(), "bias": P()}
        elif s.kind == "conv":
            out[s.name] = {"w": P(), "b": P()}
        else:  # fc / head
            w = P("model", None) if nm and s.n_in % nm == 0 else P()
            out[s.name] = {"w": w, "b": P()}
    return out


def tds_prepared_specs(tds_cfg, mesh: Mesh) -> dict:
    """PartitionSpec tree for `tds.quantize_params` output: the int8
    payload `wq` shards exactly like its source `w` (feature axis); the
    per-output-channel scales `ws` are replicated — activation
    quantization runs on the full (replicated) rows, so the sharded int8
    path sees the same scales as the unsharded one."""
    from repro.models.tds import build_kernel_specs
    nm = axis_size(mesh, "model")
    return {s.name: {"wq": P("model", None) if nm and s.n_in % nm == 0
                     else P(),
                     "ws": P()}
            for s in build_kernel_specs(tds_cfg)
            if s.kind in ("fc", "head")}


def asr_state_specs(tree, mesh: Mesh):
    """PartitionSpec tree sharding the leading SLOT axis of an ASR
    serving state pytree over the 'data' mesh axis (ASRPU's pool of
    parallel decode workers, one sub-pool per data shard).

    Applies uniformly to every per-slot buffer the fused step carries:
    the TDS left-context `StreamState` ((B, k-1, w, c) per conv), the
    `BeamState` leaves ((B, K, ...)), and the gathered step inputs (the
    (b, w, spp) sample batch and the (b,) slot-index vector).  Trailing
    axes stay unsharded — beam expansion is embarrassingly parallel
    across slots, so a data shard holds its slots end-to-end and the
    step needs no cross-shard collectives outside the 'model'-axis
    psums of `tds_param_specs`-sharded matmuls (composes with those by
    construction: state never touches the 'model' axis).  Leaves whose
    leading dim does not divide the axis fall back to replicated (the
    engine enforces divisibility for the pool; this is the same safety
    net as `_param_rule`); so does everything on a mesh with no 'data'
    axis (the 1D model-parallel serving mesh)."""
    nd = axis_size(mesh, "data")

    def f(leaf):
        if nd and leaf.ndim >= 1 and leaf.shape[0] % nd == 0:
            return P("data", *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree.map(f, tree)


def place_tree(tree, spec_tree, mesh: Mesh):
    """device_put every leaf with its NamedSharding(mesh, spec)."""
    return jax.tree.map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
        tree, spec_tree)


# ---------------------------------------------------------------------------
# batch / cache shardings
# ---------------------------------------------------------------------------
def batch_shardings(batch_shapes, mesh: Mesh):
    """Shard dim 0 (global batch) over DP axes when divisible."""
    b_axes = batch_axes(mesh)

    def f(leaf):
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        if leaf.shape[0] % _axsize(mesh, b_axes) == 0 and b_axes:
            return NamedSharding(mesh, P(b_axes, *([None] * (leaf.ndim - 1))))
        return NamedSharding(mesh, P(*([None] * leaf.ndim)))
    return jax.tree.map(f, batch_shapes)


def cache_shardings(cfg, cache_shapes, mesh: Mesh, global_batch: int):
    """KV caches: batch over DP axes when divisible, else sequence-parallel
    over ('data','model'); SSM state heads over 'model'."""
    b_axes = batch_axes(mesh)
    nb = _axsize(mesh, b_axes)
    batch_ok = b_axes and global_batch % nb == 0
    nm = axis_size(mesh, "model")
    seq_axes = ("model",) if batch_ok and nm else tuple(
        a for a in ("data", "model") if a in mesh.axis_names)
    nseq = _axsize(mesh, seq_axes)

    def f(path, leaf):
        names = [getattr(k, "key", str(k)) for k in path]
        leafname = names[-1]
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        if leafname == "kpos":
            return NamedSharding(mesh, P(
                seq_axes if leaf.shape[0] % nseq == 0 else None))
        bspec = b_axes if batch_ok else None
        if leafname in ("k", "v"):           # (R, B, Sc, K, Dh)
            sseq = seq_axes if leaf.shape[2] % nseq == 0 else None
            return NamedSharding(mesh, P(None, bspec, sseq, None, None))
        if leafname == "ssm":                 # (R, B, H, P, N)
            sh = "model" if nm and leaf.shape[2] % nm == 0 else None
            return NamedSharding(mesh, P(None, bspec, sh, None, None))
        if leafname == "conv":                # (R, B, ck-1, di)
            sd = "model" if nm and leaf.shape[3] % nm == 0 else None
            return NamedSharding(mesh, P(None, bspec, None, sd))
        return NamedSharding(mesh, P(*([None] * leaf.ndim)))
    return jax.tree_util.tree_map_with_path(f, cache_shapes)
