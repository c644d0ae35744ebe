"""Compile rehearsals for a TPU v5e that is described, not attached.

The serving path's Pallas kernels and the fused decoding step are
compiled by the TPU compiler at the paper's widths (TDS_CONFIG,
DECODER_CONFIG) for slot buckets 1 and 8.  That finds what interpret
mode cannot — tiling and layout refusals, unsupported primitives in a
kernel body, compiler aborts — without a chip.  Nothing runs, so these
say nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every test that
needs it lives in this one file."""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.policy import KernelPolicy

MOSAIC = KernelPolicy("mosaic")
N_CAND = 128 * (2 * 32 + 1)       # beam x (2 x fanout + 1) candidates


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _kernels(fn, *args) -> set:
    """Compile `fn` for the described chip; names of the Pallas kernels
    that became Mosaic custom calls."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return {m.group(1) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r'op_name="[^"]*/(\w+)/pallas_call"', line)]
            if m}


def _spec(sharding):
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


# (padded frames, c_in, c_out, kernel, stride, residual) of the TDS
# convs one 80 ms window runs: the front conv, a subsampling conv, and
# TDS-block convs at the widest stage
CONVS = [(16, 1, 15, 9, 1, False), (17, 15, 15, 10, 2, False),
         (17, 19, 23, 10, 2, False), (9, 23, 23, 9, 1, True)]


@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("tp,cin,cout,k,stride,residual", CONVS)
def test_tds_conv_compiles(one_chip, b, tp, cin, cout, k, stride,
                           residual):
    S = _spec(one_chip)
    t_out = (tp - (k - 1)) // stride
    args = [S((b, tp, 80, cin)), S((k, cin, cout)), S((cout,))]
    if residual:
        args.append(S((b, t_out, 80, cout)))

    def conv(x, w, bias, res=None):
        return ops.tds_conv(x, w, bias, stride=stride, relu=True, res=res,
                            policy=MOSAIC)
    assert _kernels(conv, *args) == {"tds_conv"}


@pytest.mark.parametrize("b", [1, 8])
def test_hypothesis_unit_compiles(one_chip, b):
    S = _spec(one_chip)
    assert _kernels(
        lambda h, pb, pnb: ops.hypothesis_unit(h, pb, pnb, 128, 25.0,
                                               policy=MOSAIC),
        S((b, N_CAND), jnp.int32), S((b, N_CAND)),
        S((b, N_CAND))) == {"hypothesis_unit"}


@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("k,n", [(1200, 1200), (1840, 9000)])
def test_int8_matmul_compiles(one_chip, b, k, n):
    S = _spec(one_chip)
    assert _kernels(
        lambda x, wq, ws: ops.int8_matmul_prepared(x, wq, ws, policy=MOSAIC),
        S((b, k)), S((k, n), jnp.int8), S((n,))) == {"int8_matmul"}


@pytest.mark.parametrize("b", [1, 8])
def test_logmel_compiles(one_chip, b):
    S = _spec(one_chip)
    assert _kernels(lambda p, fb, dct: ops.logmel(p, fb, dct, policy=MOSAIC),
                    S((b * 8, 257)), S((257, 80)), S((80, 80))) == {"logmel"}


@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("d", [1200, 1520, 1840])
def test_layernorm_compiles(one_chip, b, d):
    S = _spec(one_chip)
    assert _kernels(lambda x, s, o: ops.layernorm(x, s, o, policy=MOSAIC),
                    S((b, d)), S((d,)), S((d,))) == {"layernorm"}


@pytest.fixture(scope="module")
def paper_engine():
    from repro.launch.serve import asr_demo_engine, paper_asr_system
    engine, _ = asr_demo_engine(8, MOSAIC, system=paper_asr_system(0))
    return engine


@pytest.mark.parametrize("b,w", [(1, 1), (2, 1), (8, 4)])
def test_fused_step_compiles(one_chip, paper_engine, b, w):
    """The whole decoding step at slot bucket b, window bucket w holds
    every serving kernel as Mosaic (2-row steps used to abort the
    compiler; see AsrEngine._step_fn)."""
    from repro.core import decoder as dec
    from repro.models import tds

    eng, S = paper_engine, _spec(one_chip)
    prog = eng.program
    pool = jax.eval_shape(lambda: (
        tds.init_batched_stream_state(prog.tds_cfg, eng.n_slots),
        dec.init_batched_state(eng.n_slots, prog.dec_cfg.beam_size,
                               prog.lm)))
    params, tables, stream, beam = jax.tree.map(
        lambda a: S(a.shape, a.dtype), (eng.params, eng._tables) + pool)
    assert _kernels(eng._jit_step, params, None, tables, stream, beam,
                    S((b, w, eng._need)), S((b,), jnp.int32)) == {
        "logmel", "tds_conv", "layernorm", "hypothesis_unit"}
