"""Pallas kernel tests: shape/dtype sweeps against pure-jnp oracles
(interpret mode on CPU), plus solver-quality checks vs coordinate descent."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import cd_solve, make_problem, objective, unique_with_counts
from repro.kernels import (
    fista_quant, quant_matmul, ref_fista, ref_quant_matmul, solve_fista_batch,
    power_iter_lipschitz,
)


# ------------------------------------------------------------ quant_matmul

@pytest.mark.parametrize("M,K,N", [(8, 32, 16), (16, 128, 128), (128, 256, 64),
                                   (5, 33, 17)])  # last one exercises padding
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_matches_ref(M, K, N, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(M, K)), dtype)
    idx = jnp.asarray(rng.integers(0, 16, (K, N)), jnp.uint8)
    cb = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    out = quant_matmul(x, idx, cb, bm=8, bn=16, bk=32, interpret=True)
    ref = ref_quant_matmul(x, idx, cb)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2  # blocked-k accumulation order
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_quant_matmul_int32_codes_large_codebook():
    rng = np.random.default_rng(1)
    C = 1000
    x = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, C, (64, 32)), jnp.int32)
    cb = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
    out = quant_matmul(x, idx, cb, bm=8, bn=16, bk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_quant_matmul(x, idx, cb)),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------ fista_quant

@pytest.mark.parametrize("B,M,T", [(1, 128, 128), (3, 256, 128), (2, 100, 128),
                                   (4, 64, 64)])
def test_fista_kernel_matches_ref(B, M, T):
    """Kernel iterates == pure-jnp FISTA iterates (same math, blocked scans)."""
    rng = np.random.default_rng(2)
    w = np.sort(rng.normal(size=(B, M)), axis=1).astype(np.float32)
    d = np.diff(w, axis=1, prepend=0.0).astype(np.float32)
    n = np.ones((B, M), np.float32)
    lam = np.full((B, M), 0.05, np.float32)
    eta = (1.0 / (power_iter_lipschitz(d, n) * 1.01)).astype(np.float32)

    padM = (-M) % T
    pad = lambda a: np.pad(a, ((0, 0), (0, padM)))
    nb = (M + padM) // T
    a_kern = fista_quant(
        jnp.asarray(pad(w).reshape(B, nb, T)), jnp.asarray(pad(d).reshape(B, nb, T)),
        jnp.asarray(pad(n).reshape(B, nb, T)), jnp.asarray(pad(lam).reshape(B, nb, T)),
        jnp.asarray(eta.reshape(B, 1, 1)), n_iters=50, block_t=T, interpret=True,
    )
    a_kern = np.asarray(a_kern).reshape(B, -1)[:, :M]
    a_ref = np.asarray(ref_fista(jnp.asarray(w), jnp.asarray(d), jnp.asarray(n),
                                 jnp.asarray(lam), jnp.asarray(eta), n_iters=50))
    np.testing.assert_allclose(a_kern, a_ref, atol=2e-4, rtol=1e-3)


def test_fista_converges_to_cd_objective():
    """Solver quality: FISTA reaches the CD (global) objective within 1%."""
    rng = np.random.default_rng(3)
    w = rng.normal(0, 1, 500).round(2)
    vals, counts, _ = unique_with_counts(w)
    prob = make_problem(vals, counts)
    m = prob.m
    d = np.asarray(prob.d)[None, :]
    wv = np.asarray(prob.w_hat)[None, :]
    n = np.ones((1, m), np.float32)
    lam = 0.05
    alpha = solve_fista_batch(wv, d, n, lam, n_iters=2000, interpret=True)
    a_cd, _ = cd_solve(prob, lam, max_sweeps=500, tol=1e-9)
    f_fista = float(objective(prob, jnp.asarray(alpha[0]), lam))
    f_cd = float(objective(prob, a_cd, lam))
    assert f_fista <= f_cd * 1.01 + 1e-4


def test_fista_batch_padding_mask():
    """Zero-weight padded tail must not leak into real coordinates."""
    rng = np.random.default_rng(4)
    m1, m2 = 60, 90
    rows_w = np.zeros((2, m2), np.float32)
    rows_d = np.zeros((2, m2), np.float32)
    rows_n = np.zeros((2, m2), np.float32)
    for i, m in enumerate((m1, m2)):
        v = np.sort(rng.normal(size=m)).astype(np.float32)
        rows_w[i, :m] = v
        rows_d[i, :m] = np.diff(v, prepend=0.0)
        rows_n[i, :m] = 1.0
    a2 = solve_fista_batch(rows_w, rows_d, rows_n, 0.05, n_iters=200, interpret=True)
    # row 0 solved alone must equal row 0 solved in the batch
    a1 = solve_fista_batch(rows_w[:1, :m1], rows_d[:1, :m1], rows_n[:1, :m1],
                           0.05, n_iters=200, interpret=True)
    np.testing.assert_allclose(a2[0, :m1], a1[0], atol=1e-4)
    assert np.all(a2[:, m2:] == 0) if a2.shape[1] > m2 else True
    assert np.all(a2[0, m1:] == 0)


# ------------------------------------------------------------ paged decode


def _paged_state(rng, *, nb, bs, Hkv, Dh, L, quantized, packed, frozen_ids=()):
    from repro.kernels import pack4

    kfp = jnp.asarray(rng.normal(size=(nb, bs, Hkv, Dh)), jnp.float32)
    vfp = jnp.asarray(rng.normal(size=(nb, bs, Hkv, Dh)), jnp.float32)
    if quantized:
        kcodes = rng.integers(0, L, (nb, bs, Hkv, Dh)).astype(np.uint8)
        vcodes = rng.integers(0, L, (nb, bs, Hkv, Dh)).astype(np.uint8)
        if packed:
            kcodes, vcodes = (np.asarray(pack4(jnp.asarray(c)))
                              for c in (kcodes, vcodes))
        kc, vc = jnp.asarray(kcodes), jnp.asarray(vcodes)
        kcb = jnp.asarray(rng.normal(size=(nb, L)), jnp.float32)
        vcb = jnp.asarray(rng.normal(size=(nb, L)), jnp.float32)
        blkq = np.zeros((nb,), np.int32)
        blkq[list(frozen_ids)] = 1
        blkq = jnp.asarray(blkq)
    else:
        kc = vc = jnp.zeros((1, 1, 1, 1), jnp.uint8)
        kcb = vcb = jnp.zeros((1, 1), jnp.float32)
        blkq = jnp.zeros((1,), jnp.int32)
    return kfp, vfp, kc, vc, kcb, vcb, blkq


@pytest.mark.parametrize("quantized,packed,softcap", [
    (True, True, None), (True, False, None), (False, True, None),
    (True, True, 30.0)])
def test_paged_decode_kernel_matches_oracle(quantized, packed, softcap):
    """Fused flash-decode == dense oracle on mixed frozen/hot pages with
    per-sequence valid lengths (incl. an idle slot parked on the null
    page)."""
    from repro.kernels import paged_decode_attention, ref_paged_decode

    rng = np.random.default_rng(0)
    nb, bs, Hkv, Dh, L, B, mb, Hq = 7, 8, 2, 16, 16, 3, 3, 4
    state = _paged_state(rng, nb=nb, bs=bs, Hkv=Hkv, Dh=Dh, L=L,
                         quantized=quantized, packed=packed,
                         frozen_ids=(1, 4, 5))
    table = jnp.asarray([[1, 2, 3], [4, 5, 6], [0, 0, 0]], jnp.int32)
    valid = jnp.asarray([3 * bs, bs + 3, 1], jnp.int32)   # full / partial / idle
    q = jnp.asarray(rng.normal(size=(B, Hq, Dh)), jnp.float32)
    out = paged_decode_attention(q, *state, table, valid, softcap=softcap,
                                 quantized=quantized, packed=packed,
                                 interpret=True)
    ref = ref_paged_decode(q, *state, table, valid, softcap=softcap,
                           quantized=quantized, packed=packed)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_paged_decode_skips_pages_past_valid():
    """Pages beyond ceil(valid/bs) must not influence the output: poison
    them with huge fp values and check against a table that never maps
    them."""
    from repro.kernels import paged_decode_attention

    rng = np.random.default_rng(1)
    nb, bs, Hkv, Dh, B, mb, Hq = 5, 8, 2, 16, 1, 3, 4
    state = list(_paged_state(rng, nb=nb, bs=bs, Hkv=Hkv, Dh=Dh, L=16,
                              quantized=False, packed=True))
    q = jnp.asarray(rng.normal(size=(B, Hq, Dh)), jnp.float32)
    valid = jnp.asarray([bs + 2], jnp.int32)              # 2 pages needed
    clean = paged_decode_attention(q, *state, jnp.asarray([[1, 2, 3]],
                                   jnp.int32), valid, interpret=True)
    poisoned = [state[0].at[4].set(1e9), state[1].at[4].set(1e9)] + state[2:]
    out = paged_decode_attention(q, *poisoned, jnp.asarray([[1, 2, 4]],
                                 jnp.int32), valid, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(clean), atol=1e-6)


def test_quantize_pages_device_quality():
    """Batched on-device kmeans_ls: codes index a sorted L-wide codebook and
    reconstruction error is small for clusterable rows."""
    from repro.kernels import quantize_pages_device

    rng = np.random.default_rng(2)
    centers = rng.normal(size=(3, 8)) * 5
    rows = (centers[:, rng.integers(0, 8, 256)]
            + rng.normal(size=(3, 256)) * 0.05).astype(np.float32)
    codes, cb = quantize_pages_device(jnp.asarray(rows), num_values=8)
    codes, cb = np.asarray(codes), np.asarray(cb)
    assert codes.shape == (3, 256) and cb.shape == (3, 8)
    assert codes.max() < 8
    assert np.all(np.diff(cb, axis=1) >= 0), "codebooks must be sorted"
    recon = np.take_along_axis(cb, codes.astype(np.int64), axis=1)
    rms = np.sqrt(((recon - rows) ** 2).mean()) / np.sqrt((rows ** 2).mean())
    assert rms < 0.05, rms


def test_quantize_pages_fista_budget_and_quality():
    """Batched FISTA lam-method page solver: per-row lambda bisection lands
    the support inside the count budget, codebooks are sorted and exactly
    L wide, and the full-row LS refit beats a crude 2-level quantizer."""
    from repro.kernels import quantize_pages_device, quantize_pages_fista

    rng = np.random.default_rng(3)
    # mixed difficulty: clusterable rows and raw gaussian rows
    centers = rng.normal(size=(2, 6)) * 4
    clustered = (centers[:, rng.integers(0, 6, 320)]
                 + rng.normal(size=(2, 320)) * 0.05)
    gauss = rng.normal(size=(2, 320))
    rows = jnp.asarray(np.concatenate([clustered, gauss]).astype(np.float32))
    L = 16
    codes, cb = quantize_pages_fista(rows, num_values=L)
    codes, cb = np.asarray(codes), np.asarray(cb)
    assert codes.shape == rows.shape and cb.shape == (4, L)
    assert codes.dtype == np.uint8 and codes.max() < L
    assert np.all(np.diff(cb, axis=1) >= -1e-5), "codebooks must be sorted"
    recon = np.take_along_axis(cb, codes.astype(np.int64), axis=1)
    err = ((recon - np.asarray(rows)) ** 2).mean(axis=1)
    # sanity floor: a 2-level (sign * mean|x|) quantizer per row
    crude = np.sign(np.asarray(rows)) * np.abs(np.asarray(rows)).mean(
        axis=1, keepdims=True)
    crude_err = ((crude - np.asarray(rows)) ** 2).mean(axis=1)
    assert np.all(err < 0.5 * crude_err), (err, crude_err)
    # within striking distance of the exact-DP kmeans_ls backend (the l1
    # path trades a little loss for the lam parameterisation)
    ck, cbk = quantize_pages_device(rows, num_values=L)
    reck = np.take_along_axis(np.asarray(cbk),
                              np.asarray(ck).astype(np.int64), axis=1)
    kerr = ((reck - np.asarray(rows)) ** 2).mean(axis=1)
    assert err.mean() < 5.0 * kerr.mean() + 1e-6, (err.mean(), kerr.mean())
