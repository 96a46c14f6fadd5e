"""Reference implementations of rotation-sequence application.

``rot_sequence_numpy``        — Algorithm 1.2, pure numpy, float64 (complex128
                                for a complex target): the oracle.
``rot_sequence_unoptimized``  — Algorithm 1.2 in JAX (fori_loop), jit-able.
``rot_sequence_wavefront``    — Algorithm 1.3 (wavefront order) in JAX.

All three are mathematically identical; the wavefront version re-orders the
rotations along anti-diagonals of the ``(j, p)`` grid, which is legal because
rotations only need to respect the partial order
``(j, p) < (j+1, p)`` and ``(j+1, p) < (j, p+1)``.

Bit-stability: every path evaluates the 2x2 plane transform through
:func:`repro.core.rotations.plane_update` with the rotation/reflector
sign held as a *runtime array* — the scalar ``reflect=True`` flag is
normalized to a ``+1`` sign grid rather than a foldable scalar constant,
so the scalar-reflect and sign-grid paths compile to the same evaluation
order and agree to the last bit (the ROADMAP "bitwise-stable reflector
normalization" contract).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rotations import plane_update

__all__ = [
    "rot_sequence_numpy",
    "rot_sequence_unoptimized",
    "rot_sequence_wavefront",
    "reflector_sequence_numpy",
]


def rot_sequence_numpy(A, C, S, reflect: bool = False,
                       G=None) -> np.ndarray:
    """Algorithm 1.2 in numpy (float64 accumulate). The test oracle.

    Evaluates the canonical :func:`~repro.core.rotations.plane_update`
    order with the sign materialized per entry, exactly like the jnp
    and Pallas paths (numpy has no constant folding, so the unified
    order is bit-identical to the seed's branched formulas).  A complex
    target stays complex (complex128): the real rotations update its
    real and imaginary parts alike.
    """
    A = np.array(A, dtype=np.complex128 if np.iscomplexobj(A)
                 else np.float64, copy=True)
    C = np.asarray(C, dtype=np.float64)
    S = np.asarray(S, dtype=np.float64)
    n = A.shape[1]
    assert C.shape[0] == n - 1, (C.shape, A.shape)
    if G is None:
        G = np.full(C.shape, 1.0 if reflect else -1.0)
    else:
        G = np.asarray(G, dtype=np.float64)
    for p in range(C.shape[1]):
        for j in range(n - 1):
            c, s, g = C[j, p], S[j, p], G[j, p]
            x = A[:, j].copy()
            y = A[:, j + 1].copy()
            A[:, j], A[:, j + 1] = plane_update(x, y, c, s, g)
    return A


def reflector_sequence_numpy(A, C, S) -> np.ndarray:
    """2x2 reflector variant (paper SS8.4): ``[[c, s], [s, -c]]`` per plane."""
    return rot_sequence_numpy(A, C, S, reflect=True)


def _rot_cols(A, j, c, s, g):
    """Apply one plane transform to columns ``(j, j+1)`` of ``A``.

    Unified update ``y' = g * (s*x - c*y)``: ``g = -1`` is a rotation,
    ``g = +1`` a 2x2 reflector.  ``g`` must carry a runtime array value
    (see :func:`repro.core.rotations.plane_update`).
    """
    xy = jax.lax.dynamic_slice_in_dim(A, j, 2, axis=1)  # (m, 2)
    xn, yn = plane_update(xy[:, 0], xy[:, 1], c, s, g)
    return jax.lax.dynamic_update_slice_in_dim(
        A, jnp.stack([xn, yn], axis=1), j, axis=1
    )


def _sign_grid(C, reflect: bool, G):
    """Per-entry sign array for the signed families, or ``None``.

    Plain rotations (``G is None`` and not ``reflect``) keep the seed's
    scalar ``g = -1`` fast path — no per-plane gather, and a constant
    ``-1`` multiplicand in the ``g*(s*x - c*y)`` form is bit-identical
    to the runtime ``-1`` array (negation commutes with rounding).
    Reflector/sign paths must carry a runtime *array*: a foldable
    scalar ``+1`` is exactly the low-order-bit divergence
    :func:`~repro.core.rotations.plane_update` documents.
    """
    if G is not None:
        return G
    if reflect:
        return jnp.full(C.shape, 1.0, C.dtype)
    return None


@partial(jax.jit, static_argnames=("reflect",))
def rot_sequence_unoptimized(A, C, S, reflect: bool = False, G=None):
    """Algorithm 1.2 with ``fori_loop`` over ``p`` (outer) and ``j`` (inner)."""
    n = A.shape[1]
    k = C.shape[1]
    G = _sign_grid(C, reflect, G)
    g_rot = jnp.asarray(-1.0, A.dtype)

    def wave(p, A):
        def body(j, A):
            g = g_rot if G is None else G[j, p].astype(A.dtype)
            return _rot_cols(A, j, C[j, p].astype(A.dtype),
                             S[j, p].astype(A.dtype), g)

        return jax.lax.fori_loop(0, n - 1, body, A)

    return jax.lax.fori_loop(0, k, wave, A)


@partial(jax.jit, static_argnames=("reflect",))
def rot_sequence_wavefront(A, C, S, reflect: bool = False, G=None):
    """Algorithm 1.3: anti-diagonal (wavefront) order.

    Diagonal ``d`` applies rotations ``(j, p)`` with ``j + p = d`` in order of
    ascending ``p``.  Out-of-range entries are skipped via identity rotations
    (c=1, s=0), the same trick the blocked algorithms use for the startup and
    shutdown triangles.
    """
    n = A.shape[1]
    k = C.shape[1]
    G = _sign_grid(C, reflect, G)

    def diag(d, A):
        def body(p, A):
            j = d - p
            valid = (j >= 0) & (j <= n - 2)
            jc = jnp.clip(j, 0, n - 2)
            c = jnp.where(valid, C[jc, p], 1.0).astype(A.dtype)
            s = jnp.where(valid, S[jc, p], 0.0).astype(A.dtype)
            if G is None:
                g = jnp.asarray(-1.0, A.dtype)
            else:
                # padding must stay a no-op => rotation sign when invalid
                g = jnp.where(valid, G[jc, p],
                              jnp.asarray(-1.0, G.dtype)).astype(A.dtype)
            return _rot_cols(A, jc, c, s, g)

        return jax.lax.fori_loop(0, k, body, A)

    return jax.lax.fori_loop(0, (n - 2) + (k - 1) + 1, diag, A)
