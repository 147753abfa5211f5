"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Shapes/dtypes swept parametrically + hypothesis property tests on the
quantizer's error bound.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.kernels.act_compress import (CODECS, compress, compressed_bytes,
                                        decompress, dequantize_rows_ref,
                                        ef_compress, quantize_rows_ref)
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.rglru import rglru_ref, rglru_scan
from repro.kernels.ssd import ssd, ssd_ref_bh
from repro.kernels.vb_scatter import (permute_rows, scatter_rows,
                                      scatter_rows_ref, vb_scatter,
                                      vb_scatter_ref)


# ------------------------------------------------------------ flash attention

@pytest.mark.parametrize("B,S,H,KV,D,win,dtype", [
    (1, 128, 2, 2, 64, 0, jnp.float32),
    (2, 256, 4, 2, 64, 0, jnp.float32),
    (1, 192, 2, 1, 128, 0, jnp.float32),       # padding path (192 % 64 != 0)
    (1, 256, 2, 1, 128, 64, jnp.float32),      # sliding window
    (1, 128, 2, 2, 64, 0, jnp.bfloat16),
])
def test_flash_vs_ref(B, S, H, KV, D, win, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    out = flash_attention(q, k, v, causal=True, window=win,
                          block_q=64, block_k=64)
    rep = H // KV
    kr = jnp.repeat(k, rep, 2) if rep > 1 else k
    vr = jnp.repeat(v, rep, 2) if rep > 1 else v
    ref = attention_ref(
        q.transpose(0, 2, 1, 3).reshape(B * H, S, D).astype(jnp.float32),
        kr.transpose(0, 2, 1, 3).reshape(B * H, S, D).astype(jnp.float32),
        vr.transpose(0, 2, 1, 3).reshape(B * H, S, D).astype(jnp.float32),
        scale=1 / math.sqrt(D), causal=True, window=win)
    ref = ref.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


# ----------------------------------------------------------------------- SSD

@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 32, 2, 16, 8, 8),
    (2, 64, 3, 32, 16, 16),
    (1, 128, 1, 64, 32, 32),
])
def test_ssd_vs_sequential_ref(B, S, H, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A_log = jax.random.normal(ks[2], (H,)) * 0.5
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y, hT = ssd(x, dt, A_log, Bm, Cm, chunk=chunk)

    A = -jnp.exp(A_log)
    dA = (dt * A).transpose(0, 2, 1).reshape(B * H, S)
    xf = (x * dt[..., None]).transpose(0, 2, 1, 3).reshape(B * H, S, P)
    Bf = jnp.broadcast_to(Bm[:, None], (B, H, S, N)).reshape(B * H, S, N)
    Cf = jnp.broadcast_to(Cm[:, None], (B, H, S, N)).reshape(B * H, S, N)
    yr, hTr = ssd_ref_bh(dA, xf, Bf, Cf)
    yr = yr.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(hT.reshape(B * H, P, N)),
                               np.asarray(hTr), atol=2e-4, rtol=2e-4)


# --------------------------------------------------------------------- RG-LRU

@pytest.mark.parametrize("B,S,W,chunk", [(1, 32, 64, 8), (2, 48, 128, 16),
                                         (1, 40, 64, 16)])
def test_rglru_vs_ref(B, S, W, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W)))
    b = jax.random.normal(ks[1], (B, S, W))
    h, hT = rglru_scan(a, b, chunk=chunk)
    hr = rglru_ref(a, b)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hr[:, -1]),
                               atol=1e-5)


# -------------------------------------------------------------- act compress

def test_quantizer_matches_ref_bitexact():
    x = jax.random.normal(jax.random.PRNGKey(3), (96, 192)) * 5
    payload = compress(x, block_rows=32)
    qr, sr = quantize_rows_ref(x)
    # scales match to 1 ulp (interpret-mode reduction order may differ);
    # quantized values may then differ by at most 1 level on ties
    np.testing.assert_allclose(np.asarray(payload["scale"]), np.asarray(sr),
                               rtol=1e-6)
    assert int(jnp.abs(payload["q"].astype(jnp.int32)
                       - qr.astype(jnp.int32)).max()) <= 1
    xr = decompress(payload, x.shape, block_rows=32)
    ref = dequantize_rows_ref(qr, sr)
    np.testing.assert_allclose(np.asarray(xr), np.asarray(ref), atol=1e-6)


def test_fp8_quantizer_matches_ref():
    x = jax.random.normal(jax.random.PRNGKey(4), (96, 192)) * 5
    payload = compress(x, codec="fp8", block_rows=32)
    assert payload["q"].dtype == jnp.float8_e4m3fn
    qr, sr = quantize_rows_ref(x, codec="fp8")
    np.testing.assert_allclose(np.asarray(payload["scale"]), np.asarray(sr),
                               rtol=1e-6)
    xr = decompress(payload, x.shape, block_rows=32)
    ref = dequantize_rows_ref(qr, sr, codec="fp8")
    # a 1-ulp scale difference moves a dequantized element by at most one
    # e4m3 quantization level of its row
    tol = np.abs(np.asarray(x)).max(axis=1, keepdims=True) / 16.0
    assert np.all(np.abs(np.asarray(xr) - np.asarray(ref)) <= tol + 1e-6)


@pytest.mark.parametrize("codec", sorted(CODECS))
def test_quantizer_wire_bytes(codec):
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 128))
    payload = compress(x, codec=codec, block_rows=32)
    # 1 B/element (both rungs are single-byte dtypes) + 4 B f32 scale/row
    assert compressed_bytes(payload) == 64 * 128 + 64 * 4


def test_compress_rejects_non_float():
    with pytest.raises(TypeError, match="floating-point"):
        compress(jnp.arange(32).reshape(4, 8))
    with pytest.raises(TypeError, match="floating-point"):
        compress(np.zeros((4, 8), bool))


def test_bf16_roundtrip_regression():
    """bf16 in / bf16 out through the int8 wire: dtype is preserved and the
    error stays within the int8 grid bound (+ bf16's own half-ulp)."""
    x = (jax.random.normal(jax.random.PRNGKey(6), (32, 64)) * 3
         ).astype(jnp.bfloat16)
    payload = compress(x, block_rows=32)
    xr = decompress(payload, x.shape, out_dtype=jnp.bfloat16, block_rows=32)
    assert xr.dtype == jnp.bfloat16
    xf = np.asarray(x, np.float32)
    bound = np.abs(xf).max(axis=1, keepdims=True) * (0.5 / 127 + 2.0 ** -8)
    assert np.all(np.abs(np.asarray(xr, np.float32) - xf) <= bound + 1e-6)


@given(codec=st.sampled_from(sorted(CODECS)),
       value=st.floats(-1e3, 1e3, allow_nan=False, width=32),
       rows=st.integers(1, 5), cols=st.integers(1, 16), sends=st.integers(1, 4))
@settings(max_examples=25, deadline=None)
@example(codec="fp8", value=3.3338291764751723e-41, rows=1, cols=1, sends=1)
@example(codec="int8", value=-1e-13, rows=2, cols=3, sends=2)
def test_ef_residual_of_constant_contracts_to_exact_zero(codec, value, rows,
                                                         cols, sends):
    """Lossless-in-the-limit, sharpest case: a constant tensor's
    error-feedback residual is *exactly* zero from the first send on (the
    scale = absmax formulation makes x/scale = ±1 and q/DENOM = ±1 exact),
    so the delivered tensor is bit-equal to the input every time."""
    x = jnp.full((rows, cols), np.float32(value))
    residual = None
    for _ in range(sends):
        _, delivered, residual = ef_compress(x, residual, codec=codec,
                                             block_rows=1)
        np.testing.assert_array_equal(np.asarray(delivered), np.asarray(x))
        assert np.all(np.asarray(residual) == 0.0)


@pytest.mark.parametrize("codec", sorted(CODECS))
def test_ef_residual_drives_mean_delivered_to_x(codec):
    """Lossless-in-the-limit on *random* data: with error feedback, the
    running mean of delivered tensors converges to x (quantization error is
    carried forward, not discarded), far below the one-shot error bound."""
    x = jnp.asarray(np.random.default_rng(7).normal(size=(16, 32)) * 5,
                    jnp.float32)
    residual, acc = None, np.zeros(x.shape, np.float32)
    for k in range(1, 65):
        _, delivered, residual = ef_compress(x, residual, codec=codec,
                                             block_rows=16)
        acc += np.asarray(delivered)
    one_shot = np.abs(np.asarray(x)).max() / (127 if codec == "int8" else 16)
    err = np.abs(acc / 64 - np.asarray(x)).max()
    assert err < one_shot / 8


# ---------------------------------------------------------------- vb_scatter

def _segmented_perm(sizes, seed):
    """Concatenated ``batch_positions`` of a ragged node split: a shuffled
    partition of 0..N-1 handed out as contiguous per-node segments — the
    exact index stream the orchestrator's reassembly sees."""
    N = sum(sizes)
    pos = np.random.default_rng(seed).permutation(N)
    segs, o = [], 0
    for k in sizes:
        segs.append(pos[o:o + k])
        o += k
    return np.concatenate(segs).astype(np.int32)


@pytest.mark.parametrize("sizes", [[13, 8, 11], [5, 1, 2], [1, 1, 14]],
                         ids=["3nodes-uneven", "1sample-node", "two-1sample"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_vb_scatter_forward_and_vjp_match_ref(sizes, dtype):
    """Forward and custom_vjp backward are *exactly* (not just ULP-) equal
    to the pure-jnp scatter oracle over ragged node splits — the kernel and
    its transpose are pure row copies, so any difference is a bug."""
    N = sum(sizes)
    r = np.random.default_rng(N * 7 + 1)
    perm = jnp.asarray(_segmented_perm(sizes, seed=N))
    x1 = jnp.asarray(r.normal(size=(N, 4, 6))).astype(dtype)
    dL = jnp.asarray(r.normal(size=(N, 3))).astype(dtype)
    dx1 = jnp.asarray(r.normal(size=(N, 4, 6))).astype(dtype)

    for got, want in zip(vb_scatter(x1, dL, dx1, perm),
                         vb_scatter_ref(x1, dL, dx1, perm)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))

    # row-dependent weights make the cotangent row-distinguishable, so a
    # transposed-with-the-wrong-index backward cannot pass
    w = jnp.arange(1, N + 1, dtype=jnp.float32)

    def make_loss(scatter_fn):
        def loss(x1, dL, dx1):
            a, b, c = scatter_fn(perm, (x1, dL, dx1))
            return (w[:, None, None] * a.astype(jnp.float32) ** 2).sum() \
                + (w[:, None] * b.astype(jnp.float32)).sum() \
                + (w[:, None, None] * c.astype(jnp.float32) ** 3).sum()
        return loss

    g_kernel = jax.jit(jax.grad(make_loss(scatter_rows),
                                argnums=(0, 1, 2)))(x1, dL, dx1)
    g_ref = jax.jit(jax.grad(make_loss(scatter_rows_ref),
                             argnums=(0, 1, 2)))(x1, dL, dx1)
    for got, want in zip(g_kernel, g_ref):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def test_vb_scatter_mixed_int_rows_ride_the_fused_pass():
    """Integer rows (tokens/targets on the production path) scatter in the
    same kernel launch; differentiation skips them via float0 cotangents."""
    N = 9
    r = np.random.default_rng(3)
    perm = jnp.asarray(_segmented_perm([4, 1, 4], seed=11))
    h1 = jnp.asarray(r.normal(size=(N, 5)).astype(np.float32))
    tok = jnp.asarray(r.integers(0, 97, (N, 4)).astype(np.int32))

    hs, ts = scatter_rows(perm, (h1, tok))
    hr, tr = scatter_rows_ref(perm, (h1, tok))
    np.testing.assert_array_equal(np.asarray(hs), np.asarray(hr))
    np.testing.assert_array_equal(np.asarray(ts), np.asarray(tr))

    def loss(h1):
        a, t = scatter_rows(perm, (h1, tok))
        return (a * t.astype(jnp.float32).sum(-1, keepdims=True)).sum()

    def loss_ref(h1):
        a, t = scatter_rows_ref(perm, (h1, tok))
        return (a * t.astype(jnp.float32).sum(-1, keepdims=True)).sum()

    np.testing.assert_array_equal(
        np.asarray(jax.jit(jax.grad(loss))(h1)),
        np.asarray(jax.jit(jax.grad(loss_ref))(h1)))


@pytest.mark.parametrize("mode", ["scatter", "gather"])
def test_permute_rows_column_blocking(mode):
    """Rows spanning several native tiles (one exact multiple, one padded
    past a tile boundary) and a narrow padded ref share one call and
    produce the same rows as the oracle in both routings."""
    N = 7
    r = np.random.default_rng(5)
    idx = jnp.asarray(r.permutation(N).astype(np.int32))
    wide = jnp.asarray(r.normal(size=(N, 2500)).astype(np.float32))
    narrow = jnp.asarray(r.normal(size=(N, 3)).astype(np.float32))
    got_w, got_n = permute_rows(idx, wide, narrow, mode=mode)
    exact = jnp.asarray(r.normal(size=(N, 2048)).astype(np.float32))
    [got_e] = permute_rows(idx, exact, mode=mode)
    want_e = (jnp.zeros_like(exact).at[idx].set(exact) if mode == "scatter"
              else exact[idx])
    np.testing.assert_array_equal(np.asarray(got_e), np.asarray(want_e))
    if mode == "scatter":
        want_w = jnp.zeros_like(wide).at[idx].set(wide)
        want_n = jnp.zeros_like(narrow).at[idx].set(narrow)
    else:
        want_w, want_n = wide[idx], narrow[idx]
    np.testing.assert_array_equal(np.asarray(got_w), np.asarray(want_w))
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))


@given(codec=st.sampled_from(sorted(CODECS)),
       rows=st.integers(1, 40), cols=st.integers(2, 64),
       scale=st.floats(1e-3, 1e3), zero_row=st.booleans())
@settings(max_examples=25, deadline=None)
def test_quantizer_error_bound(codec, rows, cols, scale, zero_row):
    """Property: per-row |x - dequant(quant(x))| <= absmax/127 for int8
    (half-ulp of the int8 grid), resp. absmax/16 for fp8 (e4m3 half-ulp is
    2^-4 relative) — the §5.2 compression is lossy but bounded.  Covers
    single-row payloads (rows=1) and all-zero rows, which must round-trip
    to exactly zero."""
    x = np.random.default_rng(rows * 100 + cols).normal(
        size=(rows, cols)).astype(np.float32) * scale
    if zero_row:
        x[rows // 2] = 0.0
    q, s = quantize_rows_ref(jnp.asarray(x), codec=codec)
    xr = np.asarray(dequantize_rows_ref(q, s, codec=codec))
    half_ulp = 0.5 / 127.0 if codec == "int8" else 1.0 / 16.0
    bound = np.abs(x).max(axis=1) * half_ulp + 1e-7
    err = np.abs(xr - x).max(axis=1)
    assert np.all(err <= bound * 1.01)
    if zero_row:
        assert np.all(xr[rows // 2] == 0.0)
