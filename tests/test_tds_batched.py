"""Slot-native TDS acoustic scoring: batched-forward parity, prepared
int8 weights, and the Pallas conv/LN kernel routing (interpret vs ref)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.tds_asr import TDSConfig, TDSStage
from repro.core import features
from repro.kernels.policy import KernelPolicy
from repro.models import tds

TINY_TDS = TDSConfig(
    stages=(TDSStage(1, 3, 16, 5, 2), TDSStage(1, 4, 16, 5, 2),
            TDSStage(1, 4, 16, 5, 2)),
    sub_kernel=6, vocab_size=20)


def _warm_state(params, B, seed=2):
    """Batched stream state with NONZERO per-slot left context (each slot
    advanced through a different prior chunk)."""
    st = tds.init_batched_stream_state(TINY_TDS, B)
    warm = jax.random.normal(jax.random.PRNGKey(seed), (B, 8, 16))
    _, st = tds.forward_batched(params, TINY_TDS, warm, st)
    return st


def test_forward_batched_bitexact_vs_per_slot_forward():
    """The natively batched forward IS the per-slot forward, bit for bit:
    every slot of forward_batched equals a dedicated single-stream
    `tds.forward` call on that slot's feats + carried state (the old
    serving path vmapped exactly that per-slot function)."""
    params = tds.init_tds(jax.random.PRNGKey(0), TINY_TDS)
    B = 3
    feats = jax.random.normal(jax.random.PRNGKey(1), (B, 16, 16))
    st = _warm_state(params, B)
    logp_b, ns_b = tds.forward_batched(params, TINY_TDS, feats, st)
    for i in range(B):
        st_i = jax.tree.map(lambda a, i=i: a[i], st)
        logp_i, ns_i = tds.forward(params, TINY_TDS, feats[i], st_i)
        np.testing.assert_array_equal(np.asarray(logp_b[i]),
                                      np.asarray(logp_i))
        jax.tree.map(lambda a, b, i=i: np.testing.assert_array_equal(
            np.asarray(a[i]), np.asarray(b)), ns_b, ns_i)


def test_forward_batched_matches_vmap_forward():
    """forward_batched == jax.vmap(forward) — the literal pre-refactor
    per-slot vmap of the acoustic function."""
    params = tds.init_tds(jax.random.PRNGKey(0), TINY_TDS)
    B = 2
    feats = jax.random.normal(jax.random.PRNGKey(3), (B, 8, 16))
    st = _warm_state(params, B, seed=4)
    logp_b, _ = tds.forward_batched(params, TINY_TDS, feats, st)
    logp_v, _ = jax.vmap(
        lambda f, s: tds.forward(params, TINY_TDS, f, s))(feats, st)
    np.testing.assert_allclose(np.asarray(logp_b), np.asarray(logp_v),
                               rtol=1e-5, atol=1e-5)


def test_forward_batched_compilation_budget(compile_budget):
    """One jit entry serves repeated batched forwards: the first call
    compiles (the counter must see it), then fresh same-shape inputs
    run under a ZERO compile budget — any retrace means the batched
    forward bakes a data-dependent shape into its trace."""
    from repro.analysis.guards import count_compilations
    params = tds.init_tds(jax.random.PRNGKey(0), TINY_TDS)
    B = 2
    st = _warm_state(params, B, seed=5)
    step = jax.jit(lambda f, s: tds.forward_batched(params, TINY_TDS, f, s))
    feats = jax.random.normal(jax.random.PRNGKey(6), (B, 8, 16))
    with count_compilations() as warm:
        logp, st2 = step(feats, st)
        jax.block_until_ready(logp)
    assert warm.count >= 1, "counter missed the warmup compile"
    feats2 = jax.random.normal(jax.random.PRNGKey(7), (B, 8, 16))
    with compile_budget(0, "warmed tds.forward_batched"):
        logp2, _ = step(feats2, st2)
        jax.block_until_ready(logp2)
    assert logp2.shape == logp.shape


def test_prepared_int8_bitexact_vs_on_the_fly():
    """Pre-quantized weights (quantize_params + int8_matmul_prepared)
    produce exactly the per-call use_int8 path's output — preparation
    moves the weight quantization, it does not change it."""
    params = tds.init_tds(jax.random.PRNGKey(0), TINY_TDS)
    feats = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    prepared = tds.quantize_params(params, TINY_TDS)
    fc_specs = [s for s in tds.build_kernel_specs(TINY_TDS)
                if s.kind in ("fc", "head")]
    assert sorted(prepared) == sorted(s.name for s in fc_specs)
    a, _ = tds.forward(params, TINY_TDS, feats, use_int8=True)
    b, _ = tds.forward(params, TINY_TDS, feats, use_int8=True,
                       prepared=prepared)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_forward_interpret_kernels_match_ref():
    """The full kernel-backed forward (Pallas conv with fused
    bias+ReLU+residual epilogue, Pallas LayerNorm, under the
    interpreter) matches the pure-jnp ref dispatch on shapes that are
    no multiple of any kernel tile."""
    params = tds.init_tds(jax.random.PRNGKey(0), TINY_TDS)
    B = 2
    feats = jax.random.normal(jax.random.PRNGKey(5), (B, 24, 16))
    st = _warm_state(params, B, seed=6)
    ref, _ = tds.forward_batched(params, TINY_TDS, feats, st,
                                 kernels=KernelPolicy("ref"))
    itp, _ = tds.forward_batched(params, TINY_TDS, feats, st,
                                 kernels=KernelPolicy("interpret"))
    np.testing.assert_allclose(np.asarray(itp), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_streaming_equals_offline_through_batched_forward():
    """The PR 0 property — chunked streaming == offline, bit for bit up
    to float tolerance — must survive the batched/kernel-backed rewrite
    at B > 1 with per-slot carried context."""
    params = tds.init_tds(jax.random.PRNGKey(0), TINY_TDS)
    B, T = 2, 32
    feats = jax.random.normal(jax.random.PRNGKey(7), (B, T, 16))
    full, _ = tds.forward_batched(params, TINY_TDS, feats,
                                  tds.init_batched_stream_state(TINY_TDS, B))
    state = tds.init_batched_stream_state(TINY_TDS, B)
    outs = []
    for i in range(0, T, 8):
        o, state = tds.forward_batched(params, TINY_TDS,
                                       feats[:, i:i + 8], state)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(jnp.concatenate(outs, axis=1)),
                               rtol=1e-4, atol=1e-4)


def test_mfcc_batched_matches_per_row():
    """features.mfcc folds leading batch axes; each row equals the 1-D
    call (the engine feeds every slot's window in one batched call).

    To float tolerance, not bitwise: the mel and DCT matmuls see 3x the
    rows batched, and XLA's CPU matmul blocks a different row count
    differently, which reorders the f32 sums (~1e-5 here).  Nothing
    relies on bits across batch sizes — the engine's sub-batch bucket
    already changes with the number of eligible slots."""
    sig = jnp.asarray(np.random.RandomState(0).randn(3, 4000)
                      .astype(np.float32))
    batched = features.mfcc(sig)
    for i in range(3):
        np.testing.assert_allclose(np.asarray(batched[i]),
                                   np.asarray(features.mfcc(sig[i])),
                                   rtol=1e-5, atol=1e-4)
    fused = features.mfcc(sig, use_pallas=True, hot=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(batched),
                               rtol=1e-4, atol=1e-4)
