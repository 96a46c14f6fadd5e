"""Public eigensolver / SVD entry points built on recorded rotations.

``eigh_givens(A, method="qr"|"jacobi")`` and ``svd_givens(A)`` are
drop-in analogues of ``jnp.linalg.eigh`` / ``jnp.linalg.svd`` whose
eigen/singular-vector accumulation runs through the rotation-sequence
registry:

* ``method="qr"`` — tridiagonalize (:mod:`repro.eig.tridiag`), then
  implicit Wilkinson-shift QR (:mod:`repro.eig.qr_shift`).  Both stages
  *record* their rotations; the basis ``V = Q_tri . U_qr`` is obtained
  by streaming the two recordings — they share the ``(n-1, .)`` plane
  layout — through a single :class:`DelayedRotationBuffer` seeded with
  the identity.  Eigen*values* come from float64 scalar recurrences, so
  value accuracy is oracle-grade in every dtype; vector accuracy is that
  of the (blocked) application in the requested dtype.
* ``method="jacobi"`` — wraps the existing round-robin solver
  (``repro.core.jacobi``), with its recorded reflector sequence applied
  through the same ``method="auto"`` dispatch.

``svd_givens`` runs Golub-Kahan bidiagonalization + bidiagonal QR
(:mod:`repro.eig.svd`) with one delayed buffer per singular-vector side.

The ``k_delay`` knob is the paper-SS5.1 delay depth: how many recorded
waves are batched per registry-dispatched application.
"""
from __future__ import annotations

import warnings
from typing import NamedTuple, Optional

import numpy as np

from repro.core.sequence import RotationSequence

from .delayed import DelayedRotationBuffer
from .qr_shift import tridiag_qr
from .svd import bidiag_qr, bidiagonalize
from .tridiag import tridiagonalize

__all__ = ["EighResult", "SvdResult", "eigh_givens", "svd_givens"]


class EighResult(NamedTuple):
    eigenvalues: "object"   # (n,) ascending, like jnp.linalg.eigh
    eigenvectors: "object"  # (n, n); column i pairs with eigenvalue i


class SvdResult(NamedTuple):
    U: "object"   # (m, k) left singular vectors, k = min(m, n)
    s: "object"   # (k,) descending, non-negative
    Vt: "object"  # (k, n) right singular vectors, transposed


def _canonical_dtype(A):
    import jax.numpy as jnp

    return jnp.zeros((), getattr(A, "dtype", jnp.float32)).dtype


def eigh_givens(A, *, method: str = "qr", k_delay: int = 32,
                apply_method: str = "auto",
                cycles: int = 8, tol: Optional[float] = None,
                max_sweeps: Optional[int] = None) -> EighResult:
    """Symmetric eigendecomposition via recorded rotation sequences.

    Args:
      A: symmetric ``(n, n)``.
      method: ``"qr"`` (tridiagonal QR, default) or ``"jacobi"``
        (round-robin ``core.jacobi``).
      k_delay: delayed-application batch depth (waves per flush).
      apply_method: dispatch method for basis accumulation (``"auto"``
        routes through the registry cost model + plan cache).
      cycles: Jacobi cycles (``method="jacobi"`` only).
      tol / max_sweeps: QR deflation threshold and sweep budget.

    Returns:
      ``EighResult(eigenvalues, eigenvectors)`` with ascending
      eigenvalues, ``A @ V == V @ diag(w)`` up to dtype accuracy.
    """
    import jax.numpy as jnp

    n = A.shape[0]
    if A.shape != (n, n):
        raise ValueError(f"eigh_givens expects square input, got {A.shape}")
    dtype = _canonical_dtype(A)
    if n == 0:
        return EighResult(jnp.zeros((0,), dtype), jnp.zeros((0, 0), dtype))

    if method == "jacobi":
        from repro.core.jacobi import jacobi_apply_basis, jacobi_eigh

        res = jacobi_eigh(jnp.asarray(A, dtype), cycles=cycles)
        V = jacobi_apply_basis(res, method=apply_method)
        w = res.eigenvalues
        order = jnp.argsort(w)
        return EighResult(w[order].astype(dtype), V[:, order].astype(dtype))
    if method != "qr":
        raise ValueError(f"unknown eigh method {method!r}; "
                         f"one of ('qr', 'jacobi')")

    tri = tridiagonalize(np.asarray(A, np.float64))
    qr = tridiag_qr(tri.diag, tri.offdiag, tol=tol, max_sweeps=max_sweeps)
    _warn_unconverged("eigh_givens", qr.converged, qr.sweeps)
    buf = DelayedRotationBuffer(jnp.eye(n, dtype=dtype), k_delay=k_delay,
                                method=apply_method)
    # V = Q_tri @ U_qr: both recordings share the (n-1, .) plane layout,
    # so they stream through the buffer as one composed sequence
    buf.push_sequence(RotationSequence(tri.cos, tri.sin))
    buf.push_sequence(RotationSequence(qr.cos, qr.sin))
    V = buf.value
    order = np.argsort(qr.eigenvalues, kind="stable")
    w = jnp.asarray(qr.eigenvalues[order], dtype)
    return EighResult(w, V[:, jnp.asarray(order)])


def svd_givens(A, *, k_delay: int = 32, apply_method: str = "auto",
               tol: Optional[float] = None,
               max_sweeps: Optional[int] = None,
               full_matrices: bool = False) -> SvdResult:
    """Golub-Kahan SVD via recorded rotation sequences.

    Returns ``SvdResult(U, s, Vt)`` matching
    ``jnp.linalg.svd(A, full_matrices=False)`` conventions: descending
    non-negative ``s``, ``A ~= U @ diag(s) @ Vt``.  With
    ``full_matrices=True`` the trailing null-space columns of the wide
    factor are kept.
    """
    import jax.numpy as jnp

    m, n = A.shape
    dtype = _canonical_dtype(A)
    if m < n:
        r = svd_givens(jnp.asarray(A).T, k_delay=k_delay,
                       apply_method=apply_method, tol=tol, max_sweeps=max_sweeps,
                       full_matrices=full_matrices)
        return SvdResult(r.Vt.T, r.s, r.U.T)
    if n == 0:
        return SvdResult(jnp.zeros((m, 0), dtype), jnp.zeros((0,), dtype),
                         jnp.zeros((0, 0), dtype))

    bd = bidiagonalize(np.asarray(A, np.float64))
    qr = bidiag_qr(bd.diag, bd.superdiag, tol=tol, max_sweeps=max_sweeps)
    _warn_unconverged("svd_givens", qr.converged, qr.sweeps)

    # left factor: bidiag waves live on (m-1) planes, QR waves on (n-1);
    # embed the latter with identity padding below plane n-2
    buf_u = DelayedRotationBuffer(jnp.eye(m, dtype=dtype), k_delay=k_delay,
                                  method=apply_method)
    buf_u.push_sequence(RotationSequence(bd.cos_left, bd.sin_left))
    buf_u.push_sequence(RotationSequence(
        _embed_planes(qr.cos_left, m - 1, 1.0),
        _embed_planes(qr.sin_left, m - 1, 0.0)))
    U = buf_u.value
    buf_v = DelayedRotationBuffer(jnp.eye(n, dtype=dtype), k_delay=k_delay,
                                  method=apply_method)
    buf_v.push_sequence(RotationSequence(bd.cos_right, bd.sin_right))
    buf_v.push_sequence(RotationSequence(qr.cos_right, qr.sin_right))
    V = buf_v.value

    # sign fix + descending sort are column ops on the accumulated
    # factors, not rotations
    vals = qr.values
    sgn = np.where(vals < 0.0, -1.0, 1.0)
    order = np.argsort(-np.abs(vals), kind="stable")
    s = jnp.asarray(np.abs(vals)[order], dtype)
    Uk = (U[:, :n] * jnp.asarray(sgn, dtype)[None, :])[:, jnp.asarray(order)]
    Vk = V[:, jnp.asarray(order)]
    if full_matrices and m > n:
        Uk = jnp.concatenate([Uk, U[:, n:]], axis=1)
    return SvdResult(Uk, s, Vk.T)


def _warn_unconverged(who: str, converged: bool, sweeps: int) -> None:
    # values from a truncated run look plausible; make the truncation loud
    if not converged:
        warnings.warn(
            f"{who}: implicit-shift QR exhausted its sweep budget "
            f"({sweeps} sweeps) before full deflation; results are "
            f"approximate (raise max_sweeps, or check the input for "
            f"pathological structure)", RuntimeWarning, stacklevel=3)


def _embed_planes(C, planes: int, fill: float) -> np.ndarray:
    """Grow a ``(j, k)`` wave block to ``planes`` rows of no-op padding."""
    C = np.asarray(C, np.float64)
    if C.shape[0] == planes:
        return C
    out = np.full((planes, C.shape[1]), fill, np.float64)
    out[:C.shape[0], :] = C
    return out
