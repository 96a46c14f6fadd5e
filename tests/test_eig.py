"""`repro.eig` subsystem: recorded-rotation eigensolvers and SVD.

Oracle tests against `{np,jnp}.linalg`, staircase-packing correctness of
the tridiagonal/bidiagonal recordings, delayed-buffer flush equivalence
(bit-for-bit per backend), the rotation count of a seeded QR recording,
and the SOAP-Givens `solver="qr"` consumer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core.api import apply_rotation_sequence
from repro.core.ref import rot_sequence_numpy
from repro.core.rotations import random_sequence
from repro.eig import (DelayedRotationBuffer, bidiag_qr, bidiagonalize,
                       eigh_givens, svd_givens, tridiag_qr, tridiagonalize)


def _sym(n, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, n)).astype(dtype)
    return (X + X.T) / 2


# ------------------------------------------------------------ tridiag ----

@pytest.mark.parametrize("n", [2, 5, 33, 64])
def test_tridiagonalize_records_similarity(n):
    """Replaying the recorded staircase waves reproduces Q: Q^T H Q = T."""
    H = _sym(n, seed=n, dtype=np.float64)
    tri = tridiagonalize(H)
    Q = rot_sequence_numpy(np.eye(n), tri.cos, tri.sin)
    np.testing.assert_allclose(Q.T @ Q, np.eye(n), atol=1e-12 * n)
    T = Q.T @ H @ Q
    band = np.abs(np.subtract.outer(np.arange(n), np.arange(n))) > 1
    scale = np.abs(H).max()
    if band.any():
        assert np.abs(T[band]).max() <= 1e-12 * n * scale
    np.testing.assert_allclose(np.diagonal(T), tri.diag,
                               atol=1e-12 * n * scale)
    np.testing.assert_allclose(np.diagonal(T, 1), tri.offdiag,
                               atol=1e-12 * n * scale)


def test_tridiag_qr_eigenvalues_and_sequence():
    """QR waves diagonalize T both as scalars and as a replayed sequence."""
    n = 24
    H = _sym(n, seed=3, dtype=np.float64)
    tri = tridiagonalize(H)
    qr = tridiag_qr(tri.diag, tri.offdiag)
    assert qr.converged
    ref = np.sort(np.linalg.eigvalsh(H))
    np.testing.assert_allclose(np.sort(qr.eigenvalues), ref,
                               atol=1e-12 * n * np.abs(ref).max())
    # replay: U^T T U must be diag(eigenvalues)
    T = np.diag(tri.diag) + np.diag(tri.offdiag, 1) + np.diag(tri.offdiag, -1)
    U = rot_sequence_numpy(np.eye(n), qr.cos, qr.sin)
    np.testing.assert_allclose(U.T @ T @ U, np.diag(qr.eigenvalues),
                               atol=1e-11 * n * np.abs(ref).max())


# --------------------------------------------------------------- eigh ----

def test_eigh_qr_recording_rotation_count():
    """The rotations a seeded n=64 QR recording carries, pinned: the
    first 512 waves of the tridiagonalization and implicit-shift QR
    recordings, concatenated, hold 6746 nonzero sines in float32.  A
    change in either recording's wave layout or deflation moves it."""
    from repro.core.sequence import RotationSequence

    rng = np.random.default_rng(64)
    X = rng.standard_normal((64, 64))
    tri = tridiagonalize((X + X.T) / 2)
    qr = tridiag_qr(tri.diag, tri.offdiag)
    seq = RotationSequence(
        jnp.asarray(np.concatenate([tri.cos, qr.cos], axis=1), jnp.float32),
        jnp.asarray(np.concatenate([tri.sin, qr.sin], axis=1), jnp.float32))
    window = seq[:512]
    assert (window.n, window.k) == (64, 262)
    assert int(np.count_nonzero(np.asarray(window.sin))) == 6746


@pytest.mark.parametrize("n", [4, 33, 64])
def test_eigh_qr_oracle_f32(n):
    H = _sym(n, seed=n + 1)
    w, V = eigh_givens(jnp.asarray(H), method="qr")
    ref = np.sort(np.linalg.eigvalsh(H.astype(np.float64)))
    scale = np.abs(ref).max()
    assert np.abs(np.asarray(w) - ref).max() <= 1e-4 * scale
    Vn = np.asarray(V, np.float64)
    np.testing.assert_allclose(Vn.T @ Vn, np.eye(n), atol=1e-4)
    resid = np.abs(Vn.T @ H @ Vn - np.diag(np.asarray(w, np.float64))).max()
    assert resid <= 1e-4 * n * scale


def test_eigh_qr_oracle_f32_n256():
    """Acceptance bar: n=256 float32 within 1e-4 relative of the oracle."""
    n = 256
    H = _sym(n, seed=7)
    w, V = eigh_givens(jnp.asarray(H), method="qr")
    ref = np.sort(np.linalg.eigvalsh(H.astype(np.float64)))
    scale = np.abs(ref).max()
    assert np.abs(np.asarray(w) - ref).max() <= 1e-4 * scale
    Vn = np.asarray(V, np.float64)
    assert np.abs(Vn.T @ Vn - np.eye(n)).max() <= 1e-4
    resid = np.abs(Vn.T @ H @ Vn - np.diag(np.asarray(w, np.float64))).max()
    assert resid <= 1e-4 * scale * np.sqrt(n)


def test_eigh_qr_oracle_f64():
    """Acceptance bar: float64 within 1e-10 relative (x64 mode)."""
    with compat.enable_x64():
        n = 48
        H = _sym(n, seed=11, dtype=np.float64)
        w, V = eigh_givens(jnp.asarray(H), method="qr")
        assert w.dtype == jnp.float64 and V.dtype == jnp.float64
        ref = np.sort(np.linalg.eigvalsh(H))
        scale = np.abs(ref).max()
        assert np.abs(np.asarray(w) - ref).max() <= 1e-10 * scale
        Vn = np.asarray(V)
        assert np.abs(Vn.T @ Vn - np.eye(n)).max() <= 1e-10
        resid = np.abs(Vn.T @ H @ Vn - np.diag(np.asarray(w))).max()
        assert resid <= 1e-10 * scale


def test_eigh_jacobi_wrapper_matches_oracle():
    n = 16
    H = _sym(n, seed=5)
    w, V = eigh_givens(jnp.asarray(H), method="jacobi", cycles=8)
    ref = np.sort(np.linalg.eigvalsh(H.astype(np.float64)))
    np.testing.assert_allclose(np.asarray(w), ref, atol=1e-4 * n)
    assert np.all(np.diff(np.asarray(w)) >= -1e-6)  # sorted ascending
    Vn = np.asarray(V, np.float64)
    np.testing.assert_allclose(Vn.T @ Vn, np.eye(n), atol=1e-5 * n)


def test_eigh_methods_agree():
    H = _sym(12, seed=9)
    wq, _ = eigh_givens(jnp.asarray(H), method="qr")
    wj, _ = eigh_givens(jnp.asarray(H), method="jacobi")
    np.testing.assert_allclose(np.asarray(wq), np.asarray(wj), atol=2e-3)


def test_eigh_unknown_method_raises():
    with pytest.raises(ValueError, match="unknown eigh method"):
        eigh_givens(jnp.eye(4), method="householder")


# ---------------------------------------------------------------- svd ----

@pytest.mark.parametrize("shape", [(48, 32), (32, 48), (40, 40), (33, 20)])
def test_svd_oracle_f32(shape):
    rng = np.random.default_rng(sum(shape))
    A = rng.standard_normal(shape).astype(np.float32)
    U, s, Vt = svd_givens(jnp.asarray(A))
    k = min(shape)
    assert U.shape == (shape[0], k) and Vt.shape == (k, shape[1])
    sr = np.linalg.svd(A.astype(np.float64), compute_uv=False)
    scale = sr.max()
    assert np.abs(np.asarray(s) - sr).max() <= 1e-4 * scale
    sn = np.asarray(s)
    assert np.all(sn >= 0) and np.all(np.diff(sn) <= 1e-6)  # descending
    Un, Vn = np.asarray(U, np.float64), np.asarray(Vt, np.float64)
    np.testing.assert_allclose(Un.T @ Un, np.eye(k), atol=1e-4)
    np.testing.assert_allclose(Vn @ Vn.T, np.eye(k), atol=1e-4)
    rec = np.abs(Un @ np.diag(np.asarray(s, np.float64)) @ Vn - A).max()
    assert rec <= 1e-4 * scale


def test_svd_oracle_f64():
    with compat.enable_x64():
        rng = np.random.default_rng(2)
        A = rng.standard_normal((40, 28))
        U, s, Vt = svd_givens(jnp.asarray(A))
        sr = np.linalg.svd(A, compute_uv=False)
        scale = sr.max()
        assert np.abs(np.asarray(s) - sr).max() <= 1e-10 * scale
        rec = np.abs(np.asarray(U) @ np.diag(np.asarray(s)) @ np.asarray(Vt)
                     - A).max()
        assert rec <= 1e-10 * scale


def test_svd_full_matrices():
    rng = np.random.default_rng(4)
    A = jnp.asarray(rng.standard_normal((12, 7)), jnp.float32)
    U, s, Vt = svd_givens(A, full_matrices=True)
    assert U.shape == (12, 12)
    Un = np.asarray(U, np.float64)
    np.testing.assert_allclose(Un.T @ Un, np.eye(12), atol=1e-4)


def test_svd_exactly_zero_diagonal_entries():
    """Zero columns/rows (routine in compressed gradients) must not stall
    the implicit sweep — regression test for the d[lo]==0 stall."""
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")  # unconverged would warn -> fail
        A = jnp.asarray([[0.0, 1.0], [0.0, 1.0]], jnp.float32)
        U, s, Vt = svd_givens(A)
        np.testing.assert_allclose(np.asarray(s), [np.sqrt(2.0), 0.0],
                                   atol=1e-6)
        rec = np.asarray(U, np.float64) @ np.diag(np.asarray(s, np.float64)) \
            @ np.asarray(Vt, np.float64)
        np.testing.assert_allclose(rec, np.asarray(A), atol=1e-6)
        rng = np.random.default_rng(0)
        B = rng.standard_normal((6, 4)).astype(np.float32)
        B[:, 2] = 0.0
        _, s2, _ = svd_givens(jnp.asarray(B))
        sr = np.linalg.svd(B.astype(np.float64), compute_uv=False)
        np.testing.assert_allclose(np.asarray(s2), sr, atol=1e-5)


def test_truncated_sweep_budget_warns():
    H = _sym(12, seed=13)
    with pytest.warns(RuntimeWarning, match="sweep budget"):
        eigh_givens(jnp.asarray(H), method="qr", max_sweeps=2)


def test_bidiagonalize_records_factors():
    """Replayed left/right recordings reproduce U^T A V = B exactly."""
    rng = np.random.default_rng(6)
    m, n = 14, 9
    A = rng.standard_normal((m, n))
    bd = bidiagonalize(A)
    U = rot_sequence_numpy(np.eye(m), bd.cos_left, bd.sin_left)
    V = rot_sequence_numpy(np.eye(n), bd.cos_right, bd.sin_right)
    B = U.T @ A @ V
    ref = np.zeros((m, n))
    ref[:n, :n] = np.diag(bd.diag) + np.diag(bd.superdiag, 1)
    np.testing.assert_allclose(B, ref, atol=1e-12 * (m + n))


def test_bidiag_qr_diagonalizes():
    rng = np.random.default_rng(8)
    n = 12
    A = rng.standard_normal((n, n))
    bd = bidiagonalize(A)
    qr = bidiag_qr(bd.diag, bd.superdiag)
    assert qr.converged
    B = np.diag(bd.diag) + np.diag(bd.superdiag, 1)
    L = rot_sequence_numpy(np.eye(n), qr.cos_left, qr.sin_left)
    R = rot_sequence_numpy(np.eye(n), qr.cos_right, qr.sin_right)
    np.testing.assert_allclose(L.T @ B @ R, np.diag(qr.values),
                               atol=1e-11 * n * np.abs(bd.diag).max())


# ------------------------------------------------------ delayed buffer ----

@pytest.mark.parametrize("method", ["unoptimized", "wavefront", "blocked",
                                    "accumulated"])
def test_delayed_flush_equivalent_bitwise(method):
    """Delayed (k_delay-batched) application == eager, bit-for-bit.

    k_delay is a multiple of the band depth k_b, so chunked calls hit
    the same band boundaries as one whole-sequence call; identity
    padding of the final partial flush is an exact no-op.
    """
    rng = np.random.default_rng(0)
    n, K = 24, 40  # 40 = 2.5 flushes: exercises the padded partial flush
    M = jnp.asarray(rng.standard_normal((10, n)), jnp.float32)
    seq = random_sequence(jax.random.key(0), n, K)
    buf = DelayedRotationBuffer(M, k_delay=16, method=method)
    buf.push_sequence(np.asarray(seq.cos), np.asarray(seq.sin))
    delayed = np.asarray(buf.value)
    assert buf.flushes == 3 and buf.waves_pushed == K
    eager = np.asarray(apply_rotation_sequence(M, seq.cos, seq.sin,
                                               method=method))
    np.testing.assert_array_equal(delayed, eager)


def test_delayed_flush_auto_matches_oracle():
    rng = np.random.default_rng(1)
    n, K = 17, 23
    M = jnp.asarray(rng.standard_normal((8, n)), jnp.float32)
    seq = random_sequence(jax.random.key(2), n, K)
    buf = DelayedRotationBuffer(M, k_delay=8, method="auto")
    buf.push_sequence(np.asarray(seq.cos), np.asarray(seq.sin))
    ref = rot_sequence_numpy(np.asarray(M), np.asarray(seq.cos),
                             np.asarray(seq.sin))
    np.testing.assert_allclose(np.asarray(buf.value, np.float64), ref,
                               atol=5e-5, rtol=1e-4)


def test_delayed_buffer_validates_wave_shape():
    buf = DelayedRotationBuffer(jnp.eye(5), k_delay=4)
    with pytest.raises(ValueError, match="planes"):
        buf.push(np.ones(7), np.zeros(7))


# ----------------------------------------------------------- consumers ----

def test_soap_qr_solver_minimizes_quadratic():
    from repro.optim import SoapGivens

    opt = SoapGivens(lr=0.1, update_freq=3, solver="qr")
    target = jnp.asarray(np.random.default_rng(0).standard_normal((8, 8)),
                         jnp.float32)
    params = {"w": jnp.zeros((8, 8))}
    st = opt.init(params)

    def loss(p):
        return jnp.sum(jnp.square(p["w"] - target))

    for _ in range(60):
        g = jax.grad(loss)(params)
        params, st, _ = opt.update(g, st, params)
    assert float(loss(params)) < 0.1 * float(jnp.sum(jnp.square(target)))


def test_soap_qr_solver_rejects_jit():
    from repro.optim import SoapGivens

    opt = SoapGivens(lr=0.1, update_freq=1, solver="qr")
    params = {"w": jnp.zeros((8, 8))}
    st = opt.init(params)
    g = {"w": jnp.ones((8, 8))}
    with pytest.raises(RuntimeError, match="cannot run under jit"):
        jax.jit(lambda g, s, p: opt.update(g, s, p))(g, st, params)
