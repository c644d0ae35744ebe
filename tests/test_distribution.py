"""Distribution tests: sharding-rule divisibility for every arch, tiny-mesh
compile in a subprocess (multi-device host platform), pipeline parallelism."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.models import LM


def _axsize(shape_map, axes):
    n = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        n *= shape_map[a]
    return n


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
@pytest.mark.parametrize("mesh_shape", [
    {"data": 16, "model": 16},
    {"pod": 2, "data": 16, "model": 16},
])
def test_param_rules_divisible(arch, mesh_shape):
    """Every sharded dim of every parameter divides its mesh axes —
    the production meshes never hit uneven-partition fallbacks on params."""
    from repro.parallel.sharding import _param_rule

    class FakeMesh:
        axis_names = tuple(mesh_shape)
        shape = mesh_shape

    cfg = get_config(arch)
    shapes = LM(cfg).param_shapes()
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    n_sharded = 0
    for path, leaf in flat:
        spec = _param_rule(path, leaf.shape, cfg, FakeMesh())
        assert len(spec) == len(leaf.shape), (path, spec, leaf.shape)
        for size, ax in zip(leaf.shape, spec):
            if ax is None:
                continue
            n_sharded += 1
            assert size % _axsize(mesh_shape, ax) == 0, (path, size, ax)
    # the big matrices must actually be sharded (no silent replication)
    assert n_sharded > 3 * cfg.n_layers / LM(cfg).R


@pytest.mark.parametrize("arch", ["qwen2-72b", "jamba-v0.1-52b",
                                  "mamba2-1.3b", "llama4-maverick-400b-a17b"])
def test_cache_rules_divisible(arch):
    from repro.launch.steps import cache_specs
    from repro.parallel.sharding import cache_shardings

    FakeMesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))

    cfg = get_config(arch)
    lm = LM(cfg)
    for shape in cfg.shapes():
        if not shape.is_decode:
            continue
        cs = cache_specs(lm, shape)
        sh = cache_shardings(cfg, cs, FakeMesh, shape.global_batch)
        for (path, leaf), s in zip(
                jax.tree_util.tree_flatten_with_path(cs)[0],
                jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec"))):
            for size, ax in zip(leaf.shape, s.spec):
                if ax is None:
                    continue
                assert size % _axsize(FakeMesh.shape, ax) == 0, (
                    arch, shape.name, path, size, ax)


SUBPROC_COMPILE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch.steps import build_cell

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_config("jamba-v0.1-52b").tiny()
    for shape in (ShapeSpec("t", 64, 4, "train"), ShapeSpec("p", 64, 4, "prefill"),
                  ShapeSpec("d", 64, 4, "decode")):
        jfn, args = build_cell(cfg, shape, mesh)
        with mesh:
            compiled = jfn.lower(*args).compile()
            ca = compiled.cost_analysis()
            assert float((ca[0] if isinstance(ca, list) else ca).get("flops", 0)) > 0
    print("SUBPROC_OK")
""")


@pytest.mark.slow
def test_multidevice_compile_subprocess():
    """lower+compile on an 8-device (pod,data,model) mesh in a subprocess
    (keeps this test process at 1 device)."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", SUBPROC_COMPILE], env=env,
                       capture_output=True, text=True, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert "SUBPROC_OK" in r.stdout, r.stdout + r.stderr


SUBPROC_PIPELINE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.parallel.pipeline import pipeline_apply

    mesh = jax.make_mesh((4,), ("stage",))
    S, M, mb, d = 4, 8, 2, 16
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (S, d, d)) * 0.1}
    x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    out = pipeline_apply(stage_fn, params, x, mesh, axis="stage")
    # sequential reference
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ params["w"][s])
    err = float(jnp.abs(out - ref).max())
    assert err < 1e-5, err
    # autodiff through the pipeline
    def loss(pp):
        return jnp.sum(pipeline_apply(stage_fn, pp, x, mesh, axis="stage") ** 2)
    g = jax.grad(loss)(params)
    gref = jax.grad(lambda pp: jnp.sum(
        jnp.tanh(jnp.tanh(jnp.tanh(jnp.tanh(x @ pp["w"][0]) @ pp["w"][1])
                          @ pp["w"][2]) @ pp["w"][3]) ** 2))(params)
    gerr = float(jnp.abs(g["w"] - gref["w"]).max())
    assert gerr < 1e-4, gerr
    print("PIPELINE_OK")
""")


@pytest.mark.slow
def test_pipeline_parallel_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", SUBPROC_PIPELINE], env=env,
                       capture_output=True, text=True, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr


def test_input_specs_cover_all_cells():
    from repro.launch.steps import input_specs
    n = 0
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape in cfg.shapes():
            specs = input_specs(cfg, shape)
            n += 1
            if cfg.embed_inputs:
                assert specs["tokens"].shape[0] == shape.global_batch
            else:
                assert specs["embeds"].shape[-1] == cfg.d_model
            if shape.is_decode:
                key = "tokens" if cfg.embed_inputs else "embeds"
                assert specs[key].shape[1] == 1
    assert n == 30   # 9*3 + 3 long_500k (sub-quadratic archs)


def test_long_500k_skips_documented():
    skips = [(a, s.name) for a in ASSIGNED_ARCHS
             for s in get_config(a).skipped_shapes()]
    assert len(skips) == 6
    assert all(s == "long_500k" for _, s in skips)
    assert ("mamba2-1.3b", "long_500k") not in skips
    assert ("jamba-v0.1-52b", "long_500k") not in skips
    assert ("h2o-danube-1.8b", "long_500k") not in skips
