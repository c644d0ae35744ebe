"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs ref.py oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

R = np.random.RandomState(7)


def _arr(*shape, dtype=np.float32, scale=1.0):
    return jnp.asarray((R.randn(*shape) * scale).astype(dtype))


# ---------------------------------------------------------------------------
# int8 matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(8, 128, 128), (32, 256, 64),
                                   (100, 200, 96), (1, 1200, 600),
                                   (128, 128, 128)])
def test_int8_matmul_matches_ref(m, k, n):
    x, w = _arr(m, k), _arr(k, n)
    out = ops.int8_matmul(x, w)
    xq, xs = ops.quantize_rows(x)
    wqt, ws = ops.quantize_rows(w.T)
    expected = ref.int8_matmul(xq, wqt.T, xs, ws)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-5, atol=1e-4)


def test_int8_matmul_prepared_matches_unprepared():
    """prepare_int8_weights + int8_matmul_prepared == int8_matmul exactly
    (the prepared split moves weight quantization out of the call, it
    must not change a single bit of the result)."""
    x, w = _arr(9, 200), _arr(200, 96)
    wq, ws = ops.prepare_int8_weights(w)
    np.testing.assert_array_equal(
        np.asarray(ops.int8_matmul_prepared(x, wq, ws)),
        np.asarray(ops.int8_matmul(x, w)))


@pytest.mark.parametrize("m,k,n", [(16, 512, 256)])
def test_int8_matmul_quant_error_small(m, k, n):
    x, w = _arr(m, k), _arr(k, n)
    out = np.asarray(ops.int8_matmul(x, w))
    exact = np.asarray(x @ w)
    rel = np.abs(out - exact).max() / np.abs(exact).max()
    assert rel < 0.05, rel


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sq,skv,causal,window", [
    (64, 64, True, None), (64, 128, True, None), (32, 128, False, None),
    (128, 128, True, 48), (64, 256, True, 17),
])
def test_flash_attention(sq, skv, causal, window, dtype):
    q = _arr(2, 3, sq, 32).astype(dtype)
    k = _arr(2, 3, skv, 32).astype(dtype)
    v = _arr(2, 3, skv, 32).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=32, block_kv=32)
    expected = ref.flash_attention(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_matches_model_attention():
    """Pallas kernel vs the pure-JAX chunked attention used by the models."""
    from repro.models import layers
    B, S, H, K, D = 2, 64, 8, 4, 16
    q = _arr(B, S, H, D)
    k = _arr(B, S, K, D)
    v = _arr(B, S, K, D)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    out_jax = layers.attention_chunked(q, k, v, pos, pos, causal=True,
                                       chunk_q=16, chunk_kv=16)
    # expand GQA for the kernel
    kk = jnp.repeat(k, H // K, axis=2)
    vv = jnp.repeat(v, H // K, axis=2)
    out_pl = ops.flash_attention(q.transpose(0, 2, 1, 3),
                                 kk.transpose(0, 2, 1, 3),
                                 vv.transpose(0, 2, 1, 3),
                                 causal=True, block_q=16, block_kv=16)
    np.testing.assert_allclose(np.asarray(out_jax, np.float32),
                               np.asarray(out_pl.transpose(0, 2, 1, 3),
                                          np.float32), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("t,d", [(32, 64), (256, 80), (100, 257),
                                 (37, 80), (300, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_layernorm(t, d, dtype):
    """Includes row counts that are no multiple of the row tile (the
    kernel pads rows, which are independent, and slices the pad off)."""
    x = _arr(t, d).astype(dtype)
    s, b = _arr(d), _arr(d)
    np.testing.assert_allclose(
        np.asarray(ops.layernorm(x, s, b), np.float32),
        np.asarray(ref.layernorm(x, s, b), np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5, atol=2e-2)


def test_rmsnorm():
    x, s = _arr(128, 96), _arr(96)
    np.testing.assert_allclose(np.asarray(ops.rmsnorm(x, s)),
                               np.asarray(ref.rmsnorm(x, s)),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# logmel / beam prune / tds conv
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("t", [8, 50, 128, 300])
def test_logmel(t):
    p = jnp.abs(_arr(t, 257)) + 1e-3
    fb = jnp.abs(_arr(257, 80))
    dct = _arr(80, 40)
    np.testing.assert_allclose(np.asarray(ops.logmel(p, fb, dct)),
                               np.asarray(ref.logmel(p, fb, dct)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("t", [8, 112, 300])
def test_logmel_without_dct(t):
    """The Conformer's front end: the kernel stops at the log."""
    p = jnp.abs(_arr(t, 257)) + 1e-3
    fb = jnp.abs(_arr(257, 80))
    got = ops.logmel(p, fb)
    assert got.shape == (t, 80)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.logmel(p, fb)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,beam", [(100, 1.0), (1000, 5.0), (8448, 25.0)])
def test_beam_prune(n, beam):
    s = _arr(n, scale=10.0)
    np.testing.assert_array_equal(np.asarray(ops.beam_prune(s, beam)),
                                  np.asarray(ref.beam_prune(s, beam)))


@pytest.mark.parametrize("k,stride,t,w,cin,cout", [
    (9, 1, 32, 16, 5, 7), (9, 2, 32, 16, 5, 7), (10, 2, 64, 80, 15, 19),
    (21, 1, 64, 8, 3, 3),
    # t_out not divisible by the default bt=32 tile: the kernel used to
    # hard-assert here; now bt halves until it divides (40 -> 8, 48 -> 16)
    (9, 1, 48, 16, 5, 7), (9, 1, 40, 8, 3, 3), (5, 2, 72, 8, 3, 3),
])
def test_tds_conv(k, stride, t, w, cin, cout):
    x = _arr(k - 1 + t, w, cin)
    wgt = _arr(k, cin, cout, scale=0.3)
    b = _arr(cout)
    np.testing.assert_allclose(
        np.asarray(ops.tds_conv(x, wgt, b, stride=stride)),
        np.asarray(ref.tds_conv(x, wgt, b, stride=stride)),
        rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("batch,relu,residual", [
    (1, True, False), (3, True, True), (2, False, True), (4, False, False),
])
def test_tds_conv_batched_fused_epilogue(batch, relu, residual):
    """Slot-batched conv with the fused bias+ReLU+residual epilogue
    (interpret) vs the epilogue applied around the unbatched ref conv."""
    k, t, w, cin = 9, 24, 8, 6
    cout = cin if residual else 7
    x = _arr(batch, k - 1 + t, w, cin)
    wgt = _arr(k, cin, cout, scale=0.3)
    b = _arr(cout)
    res = _arr(batch, t, w, cout) if residual else None
    got = ops.tds_conv(x, wgt, b, relu=relu, res=res,
                       policy=ops.KernelPolicy("interpret"))
    assert got.shape == (batch, t, w, cout)
    for i in range(batch):
        want = ref.tds_conv(x[i], wgt, b)
        if relu:
            want = jnp.maximum(want, 0.0)
        if residual:
            want = want + res[i]
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# chunked relative-position attention (streaming Conformer)
# ---------------------------------------------------------------------------
def _chunk_attention_loops(q, k, v, pos, u, vb, bias, chunk):
    """The kernel's definition written query by query in numpy."""
    q, k, v, pos, u, vb, bias = (np.asarray(a, np.float64)
                                 for a in (q, k, v, pos, u, vb, bias))
    B, H, T, d = q.shape
    left = k.shape[2] - T
    out = np.zeros(q.shape)
    for t in range(T):
        c, r = divmod(t, chunk)
        keys = c * chunk + np.arange(left + chunk)
        dist = left + r - np.arange(left + chunk)        # query - key
        rows = left + chunk - 1 - dist                   # pos row of dist
        s = (np.einsum("bhd,bhnd->bhn", q[:, :, t] + u[None, :, 0],
                       k[:, :, keys])
             + np.einsum("bhd,hnd->bhn", q[:, :, t] + vb[None, :, 0],
                         pos[:, rows])) / np.sqrt(d) + bias[:, :, keys]
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[:, :, t] = np.einsum("bhn,bhnd->bhd", p, v[:, :, keys])
    return out


@pytest.mark.parametrize("b,h,w,d,chunk,left,seen", [
    (2, 2, 2, 16, 4, 8, 8), (3, 4, 4, 16, 4, 8, 0), (2, 1, 3, 32, 5, 10, 3),
    (1, 8, 1, 64, 14, 70, 70), (2, 8, 4, 64, 14, 70, 28)])
def test_chunk_attention(b, h, w, d, chunk, left, seen):
    """Kernel (interpret) and ref against the definition written out,
    with cached keys from before an utterance's start masked when fewer
    than `left` frames were seen."""
    from repro.kernels.chunk_attention import POS_ROWS
    t, s = w * chunk, left + w * chunk
    q, k, v = _arr(b, h, t, d), _arr(b, h, s, d), _arr(b, h, s, d)
    pos, u, vb = _arr(h, POS_ROWS, d), _arr(h, 1, d), _arr(h, 1, d)
    bias = jnp.where(jnp.arange(s) >= left - seen, 0.0, ref.MASK)
    bias = jnp.broadcast_to(bias, (b, 1, s))
    want = _chunk_attention_loops(q, k, v, pos, u, vb, bias, chunk)
    for mode in ("ref", "interpret"):
        got = ops.chunk_attention(q, k, v, pos, u, vb, bias, chunk=chunk,
                                  policy=ops.KernelPolicy(mode))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5)
