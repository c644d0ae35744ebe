"""Fused Pallas hypothesis unit: interpret-mode bit-for-bit parity with
the pure-jnp ref pipeline, fused-vs-legacy semantic equivalence, the
hash-sentinel collision regression, and KernelPolicy dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hypothesis as hyp
from repro.kernels import ops, ref
from repro.kernels.policy import KernelPolicy

NEG_INF = hyp.NEG_INF


def _candidates(seed, b, n, dup_rate=0.5, dead_rate=0.2):
    """Random candidate rows with forced duplicate hashes and dead
    (-inf) entries."""
    r = np.random.RandomState(seed)
    n_hash = max(1, int(n * (1.0 - dup_rate)))
    hashes = r.randint(0, n_hash, (b, n)).astype(np.int32)
    pb = (r.randn(b, n) * 3).astype(np.float32)
    pnb = (r.randn(b, n) * 3).astype(np.float32)
    dead = r.rand(b, n) < dead_rate
    pb = np.where(dead, NEG_INF, pb)
    pnb = np.where(dead, NEG_INF, pnb)
    return jnp.asarray(hashes), jnp.asarray(pb), jnp.asarray(pnb)


# ---------------------------------------------------------------------------
# kernel vs ref: bit-for-bit on CPU interpret mode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed,b,n,k,beam", [
    (0, 1, 12, 4, 5.0), (1, 3, 64, 16, 10.0), (2, 4, 200, 16, 3.0),
    (3, 2, 130, 32, 1e9),          # crosses the 128-lane pad boundary
    (4, 8, 256, 128, 25.0),        # one full 8-slot block, k = 128 lanes
    (5, 9, 150, 16, 5.0),          # slot rows padded to two 8-row blocks
    (6, 2, 128, 16, 5.0),          # rows exactly one 128-lane tile wide
    (7, 9, 256, 128, 5.0),         # two 8-row blocks, k = 128 lanes
])
def test_fused_kernel_matches_ref_bit_for_bit(seed, b, n, k, beam):
    hashes, pb, pnb = _candidates(seed, b, n)
    got = ops.hypothesis_unit(hashes, pb, pnb, k, beam,
                              policy=KernelPolicy("interpret"))
    want = ops.hypothesis_unit(hashes, pb, pnb, k, beam,
                               policy=KernelPolicy("ref"))
    for key in ("idx", "pb", "pnb", "valid"):
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]), err_msg=key)
    # ...and both match the standalone ref.py oracle
    oracle = ref.hypothesis_unit(hashes, pb, pnb, k=k, beam=beam)
    for key in ("idx", "pb", "pnb", "valid"):
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(oracle[key]), err_msg=key)


def test_fused_kernel_all_pruned_edge():
    """A row whose candidates are ALL dead selects nothing, bit-for-bit
    across interpret and ref."""
    hashes = jnp.zeros((2, 10), jnp.int32)
    dead = jnp.full((2, 10), NEG_INF, jnp.float32)
    outs = [ops.hypothesis_unit(hashes, dead, dead, 4, 2.0,
                                policy=KernelPolicy(m))
            for m in ("interpret", "ref")]
    for key in ("idx", "pb", "pnb", "valid"):
        np.testing.assert_array_equal(np.asarray(outs[0][key]),
                                      np.asarray(outs[1][key]))
    assert not np.asarray(outs[0]["valid"]).any()
    assert np.all(np.asarray(outs[0]["pb"]) == NEG_INF)


def test_fused_kernel_duplicate_hash_merges_mass():
    """All candidates share one hash: the single survivor carries the
    full channel-wise logsumexp mass."""
    r = np.random.RandomState(0)
    pb = jnp.asarray(r.randn(1, 8).astype(np.float32))
    pnb = jnp.asarray(r.randn(1, 8).astype(np.float32))
    hashes = jnp.full((1, 8), 77, jnp.int32)
    for mode in ("interpret", "ref"):
        out = ops.hypothesis_unit(hashes, pb, pnb, 4, 1e9,
                                  policy=KernelPolicy(mode))
        valid = np.asarray(out["valid"])[0]
        assert valid.tolist() == [True, False, False, False]
        want_pb = float(jax.nn.logsumexp(pb))
        want_pnb = float(jax.nn.logsumexp(pnb))
        assert abs(float(out["pb"][0, 0]) - want_pb) < 1e-4
        assert abs(float(out["pnb"][0, 0]) - want_pnb) < 1e-4


# ---------------------------------------------------------------------------
# fused step vs the legacy merge_duplicates + select pipeline
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed,n,k,beam", [(0, 30, 8, 5.0), (1, 50, 12, 2.0),
                                           (2, 6, 12, 1e9)])
def test_fused_step_matches_legacy_pipeline(seed, n, k, beam):
    """hypothesis_unit_step (fused) == merge_duplicates -> select
    (legacy) on everything except the float error of the merge order."""
    hashes, pb, pnb = _candidates(seed, 1, n)
    c = hyp.Candidates(hashes[0], pb[0], pnb[0],
                       {"node": jnp.arange(n, dtype=jnp.int32)})
    fused = hyp.hypothesis_unit_step(c, k, beam)
    legacy = hyp.select(hyp.merge_duplicates(c), k, beam)
    assert (np.asarray(fused["valid"]) == np.asarray(legacy["valid"])).all()
    v = np.asarray(fused["valid"])
    for key in ("hash", "node"):
        np.testing.assert_array_equal(np.asarray(fused[key])[v],
                                      np.asarray(legacy[key])[v])
    for key in ("pb", "pnb"):
        np.testing.assert_allclose(np.asarray(fused[key])[v],
                                   np.asarray(legacy[key])[v],
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# sentinel collision regression
# ---------------------------------------------------------------------------
def test_valid_candidate_with_sentinel_hash_survives_merge():
    """A live candidate whose 31-bit hash equals 2**31 - 1 used to be
    keyed onto the invalid-candidate sentinel and silently dropped."""
    h = jnp.asarray([2**31 - 1, 5, 2**31 - 1], jnp.int32)
    pb = jnp.asarray([-1.0, -2.0, NEG_INF], jnp.float32)
    pnb = jnp.asarray([-0.5, NEG_INF, -3.0], jnp.float32)
    c = hyp.Candidates(h, pb, pnb, {})
    m = hyp.merge_duplicates(c)
    tot = np.asarray(hyp.total_score(m.pb, m.pnb))
    live = tot > NEG_INF / 2
    assert live.sum() == 2          # both hashes survive, merged
    want = np.logaddexp(np.logaddexp(-1.0, -0.5), -3.0)
    assert abs(tot[live & (np.asarray(h) == 2**31 - 1)][0] - want) < 1e-4

    sel = hyp.hypothesis_unit_step(c, 2, 1e9)
    assert np.asarray(sel["valid"]).all()
    assert set(np.asarray(sel["hash"]).tolist()) == {2**31 - 1, 5}


def test_dead_candidates_never_merge_with_sentinel_hash():
    """Dead entries must not contribute mass to a live 2**31-1 hash."""
    h = jnp.full((6,), 2**31 - 1, jnp.int32)
    pb = jnp.asarray([-1.0] + [NEG_INF] * 5, jnp.float32)
    pnb = jnp.full((6,), NEG_INF, jnp.float32)
    sel = hyp.hypothesis_unit_step(hyp.Candidates(h, pb, pnb, {}), 3, 1e9)
    v = np.asarray(sel["valid"])
    assert v.tolist() == [True, False, False]
    assert abs(float(sel["pb"][0]) - (-1.0)) < 1e-5


# ---------------------------------------------------------------------------
# KernelPolicy dispatch
# ---------------------------------------------------------------------------
def test_kernel_policy_resolution():
    assert KernelPolicy("ref").resolve() == "ref"
    assert KernelPolicy("interpret").resolve(hot=True) == "interpret"
    auto = KernelPolicy()
    assert auto.resolve(hot=True) == ("ref" if jax.default_backend() == "cpu"
                                      else "mosaic")
    assert auto.resolve() == ("interpret" if jax.default_backend() == "cpu"
                              else "mosaic")
    with pytest.raises(ValueError):
        KernelPolicy("eager")


def test_policy_dispatch_is_consistent_across_small_kernels():
    """Every ops wrapper honors an explicit policy: ref and interpret
    agree on beam_prune (exact masking math in both)."""
    r = np.random.RandomState(0)
    s = jnp.asarray(r.randn(300).astype(np.float32) * 10)
    a = ops.beam_prune(s, 4.0, policy=KernelPolicy("ref"))
    b = ops.beam_prune(s, 4.0, policy=KernelPolicy("interpret"))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
