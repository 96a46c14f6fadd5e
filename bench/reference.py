"""The plain reference: planar rotation sequences applied in numpy.

Semantics (the paper's Algorithm 1.2, arXiv 2412.01852): a sequence is
two ``(n - 1, k)`` arrays ``C`` and ``S``; rotation ``(j, p)`` acts on
columns ``j`` and ``j + 1`` of the target, applied from the right::

    x' = c * x + s * y
    y' = c * y - s * x

in wave-major order: every rotation of wave ``p`` (ascending ``j``)
before wave ``p + 1``.  Rows never mix, so a few rows of a target can
be checked alone, and so can ``y @ A`` for a row vector ``y``.

:func:`apply_waves` visits the same rotations in wavefront order,
``t = j + 2 p``: rotations with one ``t`` touch disjoint column pairs,
and every rotation that must come earlier in wave-major order has a
smaller ``t``, so the result is the wave-major one.  That turns
``k (n - 1)`` scalar steps into ``n + 2 k - 2`` vector steps.

It imports nothing of the program under test.
"""
from __future__ import annotations

import numpy as np


def apply_waves(X, C, S):
    """Apply the sequences ``(C, S)`` to the rows ``X``, in float64.

    ``X`` is ``(..., r, n)``; ``C`` and ``S`` are ``(..., n - 1, k)``
    with the same leading dimensions, one sequence per leading index.
    """
    X = np.array(X, dtype=np.float64, copy=True)
    C = np.asarray(C, dtype=np.float64)
    S = np.asarray(S, dtype=np.float64)
    n = X.shape[-1]
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
        x = X[..., cols].copy()
        y = X[..., nxt]
        X[..., cols] = c * x + s * y
        X[..., nxt] = c * y - s * x
    return X


def rel_gap(got, ref) -> float:
    """Largest entry of ``|got - ref|`` over the largest ``|ref|``."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    scale = float(np.abs(ref).max())
    return float(np.abs(got - ref).max() / scale) if scale > 0 else \
        float(np.abs(got).max())
