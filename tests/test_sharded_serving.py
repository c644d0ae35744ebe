"""Mesh-sharded serving step: sharded-vs-unsharded parity, mesh=None
no-op parity, slot-gather step scheduling, and KernelPolicy/shard_map
composition.

Multi-device cases run in-process when the host exposes >= 2 devices
(CI's multi-device-tests job runs this file under
XLA_FLAGS=--xla_force_host_platform_device_count=8; see ci.yml) and are
skipped on a 1-device host — the slow subprocess test always exercises
them by forcing the flag itself.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.policy import KernelPolicy
from repro.launch.serve import asr_demo_engine, asr_demo_system
from repro.serving import AsrEngine, AsrProgram, EngineConfig

multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >= 2 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=N)")
multi_device4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >= 4 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=N)")


def _utts(words, n=3):
    from repro.data.pipeline import SyntheticASR
    data = SyntheticASR(words)
    return [data.utterance(i)["audio"] for i in range(n)]


# ---------------------------------------------------------------------------
# mesh=None stays the exact current path
# ---------------------------------------------------------------------------
def test_mesh_none_noop_parity():
    """EngineConfig(mesh=None) is the default and must decode exactly
    like an engine built without any mesh argument (bitwise scores)."""
    tds_cfg, words, lex, lm, params, dec_cfg = asr_demo_system()
    program = AsrProgram(tds_cfg, lex, lm, dec_cfg=dec_cfg)
    a = AsrEngine(EngineConfig(program, n_slots=2), params)
    b = AsrEngine(EngineConfig(program, n_slots=2, mesh=None), params)
    assert b.config.mesh is None
    utts = _utts(words, 2)
    for ra, rb in zip(a.serve(utts), b.serve(utts)):
        assert ra["words"].tolist() == rb["words"].tolist()
        assert ra["score"] == rb["score"]


def test_engine_config_rejects_mesh_without_model_axis():
    tds_cfg, words, lex, lm, params, dec_cfg = asr_demo_system()
    program = AsrProgram(tds_cfg, lex, lm, dec_cfg=dec_cfg)
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="model"):
        EngineConfig(program, mesh=mesh)


def test_lm_engine_rejects_mesh():
    from repro.configs import get_config
    from repro.serving import LmEngine, LmProgram

    cfg = get_config("mamba2-1.3b").tiny()
    program = LmProgram(cfg, cache_len=24, max_new=8)
    mesh = jax.make_mesh((1,), ("model",))
    with pytest.raises(NotImplementedError, match="ASR"):
        LmEngine(EngineConfig(program, mesh=mesh), params=None)


def test_mesh_1_shard_map_wrapper_matches_unsharded():
    """A 1-device ('model',) mesh exercises the whole shard_map wrapper
    (specs, gather/scatter, psum over a size-1 axis) on any host and
    must reproduce the unsharded engine bitwise — the machinery itself
    is a no-op at width 1."""
    mesh = jax.make_mesh((1,), ("model",))
    ref, words = asr_demo_engine(2)
    shd, _ = asr_demo_engine(2, mesh=mesh)
    utts = _utts(words, 2)
    for ra, rb in zip(ref.serve(utts), shd.serve(utts)):
        assert ra["words"].tolist() == rb["words"].tolist()
        assert ra["tokens"].tolist() == rb["tokens"].tolist()
        assert abs(ra["score"] - rb["score"]) < 1e-5


def test_warmed_engine_serves_under_zero_compile_budget(compile_budget):
    """A warmed engine re-serving the same workload (same slot/window
    buckets) must compile NOTHING: the gathered sub-batch step is
    shape-stable across waves, so a retrace here means a (b, w) bucket
    or readout shape silently varied."""
    engine, words = asr_demo_engine(2)
    utts = _utts(words, 2)
    first = engine.serve(utts)
    engine.serve(utts)      # wave 2 also warms the slot-reset path
    # (re-admission resets slots; wave 1 ran on fresh state and never
    # compiled reset, so only wave 3 runs with everything warmed)
    with compile_budget(0, "warmed AsrEngine.serve wave"):
        again = engine.serve(utts)
    for ra, rb in zip(first, again):
        assert ra["words"].tolist() == rb["words"].tolist()
        assert ra["tokens"].tolist() == rb["tokens"].tolist()


# ---------------------------------------------------------------------------
# slot-gather scheduling (the batched-serve regression fix)
# ---------------------------------------------------------------------------
def test_lone_active_slot_steps_at_subbatch_one():
    """One busy slot in a 4-slot pool must step at b=1, not at a masked
    b=4 (the full-pool masked step made the batched engine 1.25x SLOWER
    than sequential on ragged utterance tails)."""
    engine, words = asr_demo_engine(4)
    engine.serve(_utts(words, 1))
    assert engine.step_shapes, "no steps ran"
    assert all(b == 1 for (_, b, _) in engine.step_shapes), \
        engine.step_shapes


def test_window_bucket_maximizes_retired_windows():
    """avail=[3,3,3,5]: stepping w=4 would advance ONE slot (4 windows);
    the scheduler must take w=2 across all four slots (8 windows)."""
    engine, _ = asr_demo_engine(4)
    for s, k in enumerate((3, 3, 3, 5)):
        n = engine._need + (k - 1) * engine._spp
        engine.feed_slot(s, np.zeros((n,), np.float32))
        assert engine.slot_windows(s) == k
    assert engine._step()
    n_active, b, w = engine.step_shapes[0]
    assert (n_active, b, w) == (4, 4, 2), engine.step_shapes


def test_gathered_step_results_match_full_pool_reference():
    """Ragged per-slot feeds through the gathered sub-batch steps must
    decode every utterance exactly like a lone 1-slot engine (per-slot
    trajectories are schedule-independent)."""
    multi, words = asr_demo_engine(3)
    single, _ = asr_demo_engine(1)
    utts = _utts(words, 5)                 # 5 utts over 3 slots: reuse
    got = multi.serve(utts)
    assert {b for (_, b, _) in multi.step_shapes} != {multi.n_slots} \
        or len(utts) <= multi.n_slots      # sub-batching actually engaged
    for audio, res in zip(utts, got):
        ref = single.serve([audio])[0]
        assert res["words"].tolist() == ref["words"].tolist()
        assert res["tokens"].tolist() == ref["tokens"].tolist()
        assert abs(res["score"] - ref["score"]) < 1e-4


# ---------------------------------------------------------------------------
# KernelPolicy dispatch composes with shard_map
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_kernel_policy_modes_under_shard_map(mode):
    """Hot-path ops resolve and lower inside a shard_map body in every
    CPU mode — the sharded engine step wraps the whole kernel sequence
    in one per-device program."""
    from repro.kernels import ops

    mesh = jax.make_mesh((1,), ("model",))
    policy = KernelPolicy(mode)
    x = jnp.asarray(np.random.RandomState(0).randn(8, 32), jnp.float32)
    s = jnp.ones((32,), jnp.float32)
    b = jnp.zeros((32,), jnp.float32)

    def body(x):
        return ops.layernorm(x, s, b, policy=policy, hot=True)

    from jax.sharding import PartitionSpec as P
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                              out_specs=P(), check_vma=False))
    np.testing.assert_allclose(np.asarray(f(x)),
                               np.asarray(ops.layernorm(x, s, b,
                                                        policy=policy)),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# >= 2 device parity (in-process under the CI multi-device run)
# ---------------------------------------------------------------------------
@multi_device
def test_forward_batched_sharded_matches_unsharded_fp32():
    from repro.models import tds
    from repro.parallel import sharding as shlib
    from jax.sharding import PartitionSpec as P

    tds_cfg, words, lex, lm, params, dec_cfg = asr_demo_system()
    mesh = jax.make_mesh((2,), ("model",))
    feats = jnp.asarray(np.random.RandomState(0).randn(3, 8, 80),
                        jnp.float32)
    st = tds.init_batched_stream_state(tds_cfg, 3)
    ref, ref_st = tds.forward_batched(params, tds_cfg, feats, st)
    pspecs = shlib.tds_param_specs(tds_cfg, mesh)
    placed = shlib.place_tree(params, pspecs, mesh)

    def body(p, f, s):
        return tds.forward_batched(p, tds_cfg, f, s, axis="model")

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=(pspecs, P(), P()),
                              out_specs=(P(), P()), check_vma=False))
    got, got_st = f(placed, feats, st)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
        got_st, ref_st)


@multi_device
def test_sharded_engine_transcript_parity_d2():
    mesh = jax.make_mesh((2,), ("model",))
    ref, words = asr_demo_engine(2)
    shd, _ = asr_demo_engine(2, mesh=mesh)
    utts = _utts(words, 3)
    for ra, rb in zip(ref.serve(utts), shd.serve(utts)):
        assert ra["words"].tolist() == rb["words"].tolist()
        assert ra["tokens"].tolist() == rb["tokens"].tolist()
        assert abs(ra["score"] - rb["score"]) < 1e-3


@multi_device
def test_sharded_engine_prepared_int8_parity_d2():
    """The int8 program shards its PREPARED weights (wq on the feature
    axis, scales replicated): activation quantization runs on full
    rows, so the sharded step matches the unsharded int8 engine."""
    tds_cfg, words, lex, lm, params, dec_cfg = asr_demo_system()
    program = AsrProgram(tds_cfg, lex, lm, dec_cfg=dec_cfg,
                         use_int8=True).with_beam_width(25.0)
    mesh = jax.make_mesh((2,), ("model",))
    ref = AsrEngine(EngineConfig(program, n_slots=2), params)
    shd = AsrEngine(EngineConfig(program, n_slots=2, mesh=mesh), params)
    wq = shd._prepared["s0b0_fc1"]["wq"]
    assert wq.sharding.spec[0] == "model"     # weight shard, not a copy
    utts = _utts(words, 2)
    for ra, rb in zip(ref.serve(utts), shd.serve(utts)):
        assert ra["words"].tolist() == rb["words"].tolist()
        assert abs(ra["score"] - rb["score"]) < 1e-3


# ---------------------------------------------------------------------------
# 2D ('data','model') mesh: slot pool sharded on 'data'
# ---------------------------------------------------------------------------
def test_engine_config_rejects_unknown_mesh_axes():
    tds_cfg, words, lex, lm, params, dec_cfg = asr_demo_system()
    program = AsrProgram(tds_cfg, lex, lm, dec_cfg=dec_cfg)
    mesh = jax.make_mesh((1, 1), ("replica", "model"))
    with pytest.raises(ValueError, match="axes"):
        EngineConfig(program, mesh=mesh)


def test_mesh_1x1_2d_wrapper_matches_unsharded_bitwise():
    """A 1x1 ('data','model') mesh runs the ENTIRE 2D machinery on a
    1-device host — shard-aligned grouped assembly, -1 pad rows,
    axis_index slot localization, drop-mode scatter-back — and both
    axes are width 1, so it must reproduce the unsharded engine bitwise
    (scores included) with the same step schedule."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    ref, words = asr_demo_engine(2)
    shd, _ = asr_demo_engine(2, mesh=mesh)
    assert shd._data_axis == "data" and shd._n_data == 1
    utts = _utts(words, 3)
    for ra, rb in zip(ref.serve(utts), shd.serve(utts)):
        assert ra["words"].tolist() == rb["words"].tolist()
        assert ra["tokens"].tolist() == rb["tokens"].tolist()
        assert ra["score"] == rb["score"]
    assert ref.step_shapes == shd.step_shapes


@multi_device
def test_engine_config_rejects_indivisible_data_axis():
    """n_slots must split evenly over the data axis: each shard owns
    n_slots/n_data slots end-to-end."""
    tds_cfg, words, lex, lm, params, dec_cfg = asr_demo_system()
    program = AsrProgram(tds_cfg, lex, lm, dec_cfg=dec_cfg)
    mesh = jax.make_mesh((2, 1), ("data", "model"))
    with pytest.raises(ValueError, match="divide evenly"):
        EngineConfig(program, n_slots=3, mesh=mesh)


@multi_device
def test_assemble_batch_is_shard_aligned():
    """With a 2-wide data axis over 4 slots (2 slots/shard), eligible
    slots {0,1,3} must assemble into per-shard row blocks: shard 0's
    slots at rows [0, bloc), shard 1's at [bloc, 2*bloc), pad rows
    zero-filled with index -1 (dropped on scatter-back).  Assembly is
    non-destructive (a faulted step must be replayable on the surviving
    halves); `_retire` is what consumes the buffered samples."""
    mesh = jax.make_mesh((2, 1), ("data", "model"))
    engine, _ = asr_demo_engine(4, mesh=mesh)
    assert engine._slots_per_shard == 2
    for s in (0, 1, 3):
        engine.feed_slot(s, np.full((engine._need,), s + 1.0, np.float32))
        assert engine.slot_windows(s) == 1
    batch, idx = engine._assemble_batch([0, 1, 3], 1)
    # largest group (shard 0) has 2 slots -> bloc=2 -> b = 2*2
    assert batch.shape == (4, 1, engine._need)
    assert idx.tolist() == [0, 1, 3, -1]
    np.testing.assert_array_equal(batch[0], 1.0)
    np.testing.assert_array_equal(batch[1], 2.0)
    np.testing.assert_array_equal(batch[2], 4.0)      # slot 3 -> row bloc+0
    np.testing.assert_array_equal(batch[3], 0.0)      # pad row: zeros
    for s in (0, 1, 3):                               # NOT yet consumed
        assert engine.slot_windows(s) == 1
    engine._retire([0, 1, 3], 1)                      # commit consumes
    for s in (0, 1, 3):
        assert engine.slot_windows(s) == 0


@multi_device
def test_slot_buckets_are_per_shard_sizes():
    """Slot buckets bucket the LOCAL per-shard group size, so every
    global sub-batch b = bloc * n_data is a multiple of n_data."""
    mesh = jax.make_mesh((2, 1), ("data", "model"))
    engine, words = asr_demo_engine(4, mesh=mesh)
    assert engine._slot_buckets[-1] == engine._slots_per_shard
    engine.serve(_utts(words, 3))
    assert all(b % 2 == 0 for (_, b, _) in engine.step_shapes), \
        engine.step_shapes


@multi_device
def test_data_sharded_engine_transcript_parity_d2():
    """Data-only sharding (2x1) re-partitions identical per-slot compute
    across devices — transcripts AND scores stay bitwise."""
    mesh = jax.make_mesh((2, 1), ("data", "model"))
    ref, words = asr_demo_engine(4)
    shd, _ = asr_demo_engine(4, mesh=mesh)
    utts = _utts(words, 4)
    for ra, rb in zip(ref.serve(utts), shd.serve(utts)):
        assert ra["words"].tolist() == rb["words"].tolist()
        assert ra["tokens"].tolist() == rb["tokens"].tolist()


@multi_device4
def test_2d_mesh_engine_transcript_parity_d4():
    """The issue's acceptance case: a (2,2) mesh over 4 devices decodes
    bitwise-identical transcripts to mesh=None (scores shift within
    float tolerance from the model-axis psum reduction order)."""
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    ref, words = asr_demo_engine(4)
    shd, _ = asr_demo_engine(4, mesh=mesh)
    utts = _utts(words, 4)
    for ra, rb in zip(ref.serve(utts), shd.serve(utts)):
        assert ra["words"].tolist() == rb["words"].tolist()
        assert ra["tokens"].tolist() == rb["tokens"].tolist()
        assert abs(ra["score"] - rb["score"]) < 1e-3


@multi_device
def test_warmed_2d_step_runs_under_strict_transfer_guard():
    """Once warmed, the data-sharded step's only host<->device traffic
    is the explicit batch/idx device_put (placed with the step's
    in_specs shardings): under no_implicit_transfers(strict=True) —
    which also disallows the device-to-device reshard-on-dispatch that
    bounces through the host on CPU — a second same-shape step must
    dispatch with zero hidden per-step round-trips."""
    from repro.analysis.guards import no_implicit_transfers

    mesh = jax.make_mesh((2, 1), ("data", "model"))
    engine, _ = asr_demo_engine(4, mesh=mesh)
    assert engine._input_shardings is not None

    def feed_all():
        for s in range(4):
            engine.feed_slot(s, np.zeros((engine._need,), np.float32))

    feed_all()
    assert engine._step()       # cold: compiles, places state + params
    feed_all()
    with no_implicit_transfers(strict=True):
        assert engine._step()   # warmed same-bucket step: no transfers
    assert engine.step_shapes[0] == engine.step_shapes[1]


@multi_device
def test_overlap_psum_matches_sync_engine():
    """The latency-hiding chunked-psum FC path must decode the same
    transcripts as the sync psum reference (chunking splits the output
    columns, so only reduction order can differ)."""
    mesh = jax.make_mesh((1, 2), ("data", "model"))
    sync, words = asr_demo_engine(2, mesh=mesh)
    ovl, _ = asr_demo_engine(2, mesh=mesh, overlap_psum=True)
    utts = _utts(words, 3)
    for ra, rb in zip(sync.serve(utts), ovl.serve(utts)):
        assert ra["words"].tolist() == rb["words"].tolist()
        assert ra["tokens"].tolist() == rb["tokens"].tolist()
        assert abs(ra["score"] - rb["score"]) < 1e-3


@multi_device
def test_psum_overlap_matmul_matches_sync():
    from repro.kernels import ops
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((2,), ("model",))
    R = np.random.RandomState(0)
    x = jnp.asarray(R.randn(4, 32), jnp.float32)
    w = jnp.asarray(R.randn(32, 24), jnp.float32)

    def body(x, wloc):
        xloc = ops.shard_local_cols(x, wloc.shape[0], "model")
        sync = jax.lax.psum(xloc @ wloc, "model")
        ovl = ops.psum_overlap_matmul(xloc, wloc, "model")
        return sync, ovl

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=(P(), P("model", None)),
                              out_specs=(P(), P()), check_vma=False))
    sync, ovl = f(x, w)
    np.testing.assert_allclose(np.asarray(ovl), np.asarray(sync),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# subprocess: full parity sweep on a forced 8-device host (slow suite)
# ---------------------------------------------------------------------------
SUBPROC_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.data.pipeline import SyntheticASR
    from repro.launch.serve import asr_demo_engine, serve_mesh

    ref, words = asr_demo_engine(4)
    data = SyntheticASR(words)
    utts = [data.utterance(i)["audio"] for i in range(4)]
    want = ref.serve(utts)
    for d in (2, 4, "2x2", "2x4", "4x2"):
        shd, _ = asr_demo_engine(4, mesh=serve_mesh(d))
        got = shd.serve(utts)
        for i, (a, b) in enumerate(zip(want, got)):
            assert a["words"].tolist() == b["words"].tolist(), (d, i)
            assert a["tokens"].tolist() == b["tokens"].tolist(), (d, i)
            assert abs(a["score"] - b["score"]) < 1e-3, (d, i)
    ovl, _ = asr_demo_engine(4, mesh=serve_mesh("2x2"), overlap_psum=True)
    got = ovl.serve(utts)
    for i, (a, b) in enumerate(zip(want, got)):
        assert a["words"].tolist() == b["words"].tolist(), ("ovl", i)
        assert abs(a["score"] - b["score"]) < 1e-3, ("ovl", i)
    print("SHARDED_OK")
""")


@pytest.mark.slow
def test_sharded_serve_parity_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", SUBPROC_SHARDED], env=env,
                       capture_output=True, text=True,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "SHARDED_OK" in r.stdout, r.stdout + r.stderr[-3000:]
