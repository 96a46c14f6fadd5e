"""The plain reference for a complex target: real planar rotation
sequences applied in numpy, in complex128.

Semantics: LAPACK's ``ZLASR`` with ``SIDE = 'R'``, ``PIVOT = 'V'``,
``DIRECT = 'F'`` ("applies a sequence of real plane rotations to a
complex matrix A", netlib.org/lapack).  A sequence is two real ``(n -
1, k)`` arrays ``C`` and ``S``; rotation ``(j, p)`` acts on columns
``j`` and ``j + 1`` of the complex target::

    x' = c * x + s * y
    y' = c * y - s * x

every rotation of wave ``p`` (ascending ``j``) before wave ``p + 1``.
Rows never mix, so a few rows of a target can be checked alone, and so
can ``y @ Z`` for a real row vector ``y``.

:func:`apply_waves` visits the rotations in wavefront order, ``t = j +
2 p``, as ``bench/reference.py`` does: the rotations of one ``t`` touch
disjoint column pairs, and every rotation that must come earlier has a
smaller ``t``.

It imports nothing of the program under test.
"""
from __future__ import annotations

import numpy as np


def apply_waves(Z, C, S):
    """Apply the real sequences ``(C, S)`` to the complex rows ``Z``,
    in complex128.

    ``Z`` is ``(..., r, n)``; ``C`` and ``S`` are ``(..., n - 1, k)``
    with the same leading dimensions, one sequence per leading index.
    Complex waves raise: ``ZLASR``'s rotations are real.
    """
    if np.iscomplexobj(C) or np.iscomplexobj(S):
        raise TypeError("ZLASR applies real rotations; got complex waves")
    Z = np.array(Z, dtype=np.complex128, copy=True)
    C = np.asarray(C, dtype=np.float64)
    S = np.asarray(S, dtype=np.float64)
    n = Z.shape[-1]
    J, k = C.shape[-2], C.shape[-1]
    if J != n - 1:
        raise ValueError(f"waves for {J + 1} columns, target has {n}")
    for t in range(n - 2 + 2 * (k - 1) + 1):
        p_lo = max(0, -(-(t - (n - 2)) // 2))
        p_hi = min(k - 1, t // 2)
        if p_lo > p_hi:
            continue
        p = np.arange(p_hi, p_lo - 1, -1)       # ascending j = t - 2 p
        j0, j1 = t - 2 * p_hi, t - 2 * p_lo
        cols = slice(j0, j1 + 1, 2)
        nxt = slice(j0 + 1, j1 + 2, 2)
        c = C[..., t - 2 * p, p][..., None, :]
        s = S[..., t - 2 * p, p][..., None, :]
        x = Z[..., cols].copy()
        y = Z[..., nxt]
        Z[..., cols] = c * x + s * y
        Z[..., nxt] = c * y - s * x
    return Z


def rel_gap(got, ref) -> float:
    """Largest modulus of ``got - ref`` over the largest modulus of
    ``ref``."""
    got = np.asarray(got, np.complex128)
    ref = np.asarray(ref, np.complex128)
    scale = float(np.abs(ref).max())
    return float(np.abs(got - ref).max() / scale) if scale > 0 else \
        float(np.abs(got).max())
