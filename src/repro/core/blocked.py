"""Blocked wavefront application of rotation sequences (paper SS2, SS5).

The ``(j, p)`` rotation grid is tiled into *parallelograms*: bands of
``k_b`` waves x tiles of ``n_b`` anti-diagonals.  Within a band, the matrix
is swept left-to-right in column tiles; ``k_b`` partially-rotated "carry"
columns flow from each tile to the next — the TPU/VMEM analogue of the
paper's cache blocking.  The startup and shutdown triangles are handled
uniformly by identity-padding the rotation grid (instead of the paper's
special ``k_r = 1`` edge kernels).

Coordinate bookkeeping (derived once, reused by the Pallas kernels):

* diagonal index ``u = j + p``; tile ``t`` covers ``u in [t*n_b, (t+1)*n_b)``.
* tile ``t`` touches matrix columns ``[t*n_b - k_b + 1, (t+1)*n_b]``:
  ``k_b`` carry columns + ``n_b`` fresh columns.
* after tile ``t``, columns up to ``(t+1)*n_b - k_b`` are final; the last
  ``k_b`` touched columns become the next carry.
* inside a tile, wave ``p`` applies rotations at local column pairs
  ``(j_l, j_l + 1)`` for ``j_l = k_b - 1 - p + jj``, ``jj in [0, n_b)`` —
  exactly Algorithm 2.1 of the paper.
* the rotation value for ``(t, jj, p)`` is ``C[t*n_b + jj - p, p0 + p]`` —
  a *sheared* ("packed", paper SS4) view of ``C``/``S`` built on the
  device (:func:`pack_sheared`) so kernels read aligned tiles.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.rotations import plane_update

__all__ = [
    "PACK",
    "ACCUMULATE",
    "SWEEP",
    "pack_sheared",
    "apply_tile",
    "apply_band",
    "rot_sequence_blocked",
    "num_tiles",
]


# Names of an application's phases on the device (``jax.named_scope``,
# read back from the profiler trace): the per-sequence setup (packing
# the waves into tiles, accumulating tile factors) and the stream over
# the target's rows (docs/cost-model.md).
PACK = "rotseq.pack"
ACCUMULATE = "rotseq.accumulate"
SWEEP = "rotseq.sweep"


def num_tiles(n: int, n_b: int, k_b: int) -> int:
    """Number of diagonal tiles needed so every output column is emitted."""
    return math.ceil((n + k_b - 1) / n_b)


def _shear(X, fill, L: int, w: int):
    """``X`` ``(..., J, w0)`` by diagonal: ``(..., L, w)``.

    ``out[..., u, p] = X[..., u - p, p]``, and ``fill`` where ``u - p``
    falls outside ``[0, J)`` or ``p >= w0``.  Each column of ``X``, padded
    with ``fill`` to length ``R + 1``, is read back in rows of ``R``:
    column ``p`` then starts ``p`` entries later, and its first ``p``
    entries come from the fill at the end of column ``p - 1``, which is
    long enough once ``R >= J + w - 2``.  A pad and a reshape shear the
    grid: no gather.
    """
    *lead, J, w0 = X.shape
    R = max(L, J + w - 2)
    Y = jnp.pad(jnp.swapaxes(X, -1, -2),
                [(0, 0)] * (X.ndim - 2) + [(0, w - w0), (0, R + 1 - J)],
                constant_values=fill)
    Y = Y.reshape(*lead, w * (R + 1))[..., :w * R].reshape(*lead, w, R)
    return jnp.swapaxes(Y[..., :L], -1, -2)


def pack_sheared(C, S, p0: int, k_b: int, n_b: int, T: int,
                 reflect: bool = False, G=None):
    """Shear-pack waves ``[p0, p0 + k_b)`` into aligned ``(T, n_b, k_b)`` tiles.

    ``Ct[t, jj, p] = C[t*n_b + jj - p, p0 + p]`` with no-op padding outside
    the valid ``(j, wave)`` range.  Returns ``(Ct, St, Gt)``; ``Gt`` holds
    the per-entry sign of the unified update ``y' = g * (s*x - c*y)``:
    ``g = -1`` is a rotation (and the no-op padding ``c=1, s=0``), ``g = +1``
    a 2x2 reflector (paper SS8.4).  A padded *reflector* would not be a
    no-op (det = -1), hence the sign tile rather than a global flag.

    ``G``: optional per-entry sign array ``(n-1, k)`` for *mixed*
    rotation/reflector sequences (e.g. the Jacobi solver's pivots-with-
    swaps interleaved with no-op rotations); overrides ``reflect``.

    Each array's band is sheared by a pad and two reshapes
    (:func:`_shear`, which :func:`repro.core.accumulate._diagonals` also
    uses for the tiles' anti-diagonals): no element is gathered.
    """
    def pack(X, fill):
        return _shear(X[:, p0:p0 + k_b], fill, T * n_b, k_b).reshape(
            T, n_b, k_b)

    Ct = pack(C, 1)
    St = pack(S, 0)
    if G is not None:
        Gt = pack(G.astype(jnp.result_type(G, C)), -1)
    elif reflect:
        Gt = pack(jnp.ones_like(C), -1)
    else:
        Gt = jnp.full_like(Ct, -1.0)
    return Ct, St, Gt


def apply_tile(X, Ct, St, Gt):
    """Apply one parallelogram tile of rotations to ``X`` (m, k_b + n_b).

    ``Ct``/``St``/``Gt`` are one sheared tile of shape ``(n_b, k_b)``.
    Sequential wavefront order: wave ``p`` ascending, within a wave ``jj``
    ascending.  This is the jnp oracle for the Pallas kernel body.
    """
    n_b, k_b = Ct.shape

    def wave(p, X):
        def rot(jj, X):
            jl = k_b - 1 - p + jj
            c = Ct[jj, p].astype(X.dtype)
            s = St[jj, p].astype(X.dtype)
            g = Gt[jj, p].astype(X.dtype)
            xy = jax.lax.dynamic_slice_in_dim(X, jl, 2, axis=1)
            xn, yn = plane_update(xy[:, 0], xy[:, 1], c, s, g)
            return jax.lax.dynamic_update_slice_in_dim(
                X, jnp.stack([xn, yn], axis=1), jl, axis=1
            )

        return jax.lax.fori_loop(0, n_b, rot, X)

    return jax.lax.fori_loop(0, k_b, wave, X)


def _band_inputs(A, k_b: int, n_b: int, T: int):
    """Initial carry + fresh-column tiles for one band sweep over ``A``."""
    m, n = A.shape
    carry0 = jnp.concatenate(
        [jnp.zeros((m, k_b - 1), A.dtype), A[:, :1]], axis=1
    )
    # Fresh columns stream: tile t consumes columns [t*n_b + 1, (t+1)*n_b].
    fresh = jnp.pad(A[:, 1:], ((0, 0), (0, T * n_b - (n - 1))))
    return carry0, fresh


def apply_band(A, Ct, St, Gt):
    """Sweep one band of ``k_b`` waves over ``A`` via a scan with carry.

    ``Ct``/``St``/``Gt``: sheared tiles ``(T, n_b, k_b)`` from
    :func:`pack_sheared`.  Returns ``A`` with the band applied (true column
    coordinates).
    """
    T, n_b, k_b = Ct.shape
    m, n = A.shape
    carry0, fresh = _band_inputs(A, k_b, n_b, T)
    fresh_tiles = fresh.reshape(m, T, n_b).transpose(1, 0, 2)  # (T, m, n_b)

    def step(carry, xs):
        ct, st, gt, ft = xs
        X = jnp.concatenate([carry, ft], axis=1)  # (m, k_b + n_b)
        X = apply_tile(X, ct, st, gt)
        return X[:, n_b:], X[:, :n_b]

    _, out = jax.lax.scan(step, carry0, (Ct, St, Gt, fresh_tiles))
    O = out.transpose(1, 0, 2).reshape(m, T * n_b)
    # O[:, i] holds final column  i - (k_b - 1)  of A.
    return jax.lax.slice_in_dim(O, k_b - 1, k_b - 1 + n, axis=1)


@partial(jax.jit, static_argnames=("n_b", "k_b", "reflect"))
def rot_sequence_blocked(A, C, S, *, n_b: int = 64, k_b: int = 16,
                         reflect: bool = False, G=None):
    """Blocked wavefront algorithm (paper SS2 + SS5) on the host in jnp."""
    m, n = A.shape
    J, k = C.shape
    assert J == n - 1, (C.shape, A.shape)
    n_b = min(n_b, max(8, n))  # don't tile wider than the matrix
    T = num_tiles(n, n_b, k_b)
    for p0 in range(0, k, k_b):  # bands, sequential (python loop: k/k_b small)
        with jax.named_scope(PACK):
            Ct, St, Gt = pack_sheared(C, S, p0, k_b, n_b, T,
                                      reflect=reflect, G=G)
        with jax.named_scope(SWEEP):
            A = apply_band(A, Ct, St, Gt)
    return A
