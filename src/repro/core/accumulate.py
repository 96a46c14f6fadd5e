"""Accumulated (GEMM) application of rotation sequences — paper's ``rs_gemm``.

Each parallelogram tile of ``n_b`` waves x ``k_b`` rotations is accumulated
into a dense orthogonal factor ``Q_t`` of size ``w x w`` (``w = k_b + n_b``)
by applying the tile to an identity matrix; the sweep over ``A`` then
becomes a scan of ``(m, w) @ (w, w)`` matmuls.

``Q_t`` is built one anti-diagonal ``jj + p`` of the tile at a time: the
``k_b`` rotations of an anti-diagonal pair disjoint planes, and all they
depend on lies on earlier anti-diagonals, so ``n_b + k_b - 1`` steps of
``k_b`` rotations give what ``n_b * k_b`` steps of one plane give, value
for value.  The per-step rotations come from a pad-and-reshape shear of
the tile, not a gather.  On a TPU a Pallas kernel keeps each tile's
factor in VMEM for all its steps (float32); elsewhere an XLA loop runs
the same schedule over all tiles at once.  The loop's step is a few XLA
ops over the whole ``T x 2k_b x w`` window, which costs ~73 µs on a TPU
v5e at 96/96 tiles against ~0.2 µs per tile and anti-diagonal in the
kernel (PERF.md, section 5).

On CPU (the paper) this trades ~4/3 more flops for MKL GEMM throughput and
only wins for large matrices.  On TPU it is the *natural* formulation: the
MXU delivers ~50x the VPU flop rate, so paying ``2 m w^2`` MXU flops instead
of ``6 m n_b k_b`` VPU flops per tile inverts the paper's CPU conclusion.
Accumulation costs a step per anti-diagonal, whatever ``m``; the
sweep's flops grow with ``m``.

``Q_t`` is banded (columns of ``Q_t`` mix at most ``k_b`` neighbours below),
but we apply it densely: for ``n_b ~ k_b`` the band covers most of ``Q`` and
dense matmuls keep the MXU at full tilt.

In the registry's cost split (docs/cost-model.md) the factor
accumulation and tile packing are *setup* — per-sequence work, paid
once for a shared-sequence batch but ``b`` times for the serving
path's per-request buckets — while the GEMM sweep is *stream*, scaling
with the rows of ``A``.  That asymmetry is why this backend wins
batched accumulator flushes yet loses serving buckets of the same
shape to the fused kernel.

The phases carry ``jax.named_scope`` names that reach the device trace
(the ``tf_op`` of each XLA op): ``rotseq.pack`` and
``rotseq.accumulate`` are the setup, ``rotseq.sweep`` the stream.  Every
backend names its phases at their call sites, so the backends that
share these helpers (``accumulated``, ``pallas_mxu``) read alike.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import compat
from repro.kernels.factors import tile_factors_pallas
from repro.kernels.limits import LANES, SUBLANES, round_up

from .blocked import (ACCUMULATE, PACK, SWEEP, _band_inputs, _shear,
                      num_tiles, pack_sheared)
from .rotations import plane_update

__all__ = [
    "accumulate_tile_factors",
    "apply_band_accumulated",
    "rot_sequence_accumulated",
]


def _diagonals(X, fill, steps: int, lanes: int):
    """Tiles ``(T, n_b, k_b)`` by anti-diagonal: ``(T, steps, lanes)``.

    Row ``d`` holds the rotations with ``jj + p = d`` in descending wave
    order: ``D[t, d, q] = X[t, d - p, p]`` with ``p = k_b - 1 - q``, and
    the no-op value ``fill`` where ``d - p`` falls outside the tile, at
    ``d`` past the last anti-diagonal and at ``q >= k_b``: the tiles
    sheared (:func:`repro.core.blocked._shear`), no gather.
    """
    T, n_b, k_b = X.shape
    L = n_b + k_b - 1
    D = _shear(X, fill, L, k_b)[:, :, ::-1]
    return jnp.pad(D, ((0, 0), (0, steps - L), (0, lanes - k_b)),
                   constant_values=fill)


def _factors_loop(Ct, St, Gt, dtype):
    """The schedule as an XLA loop over the rows of ``Q_tᵀ``.

    Rotation ``(jj, p)`` of anti-diagonal ``d`` pairs rows ``k_b - 1 + d
    - 2p`` and the next of ``Q_tᵀ``, padded by ``k_b - 1`` rows above.
    Its ``x`` row has the parity of ``d``, so the rows are kept split by
    parity (``E`` even, ``O`` odd) and an anti-diagonal updates two
    contiguous ``k_b``-row slices in place: ``E[r:]`` and ``O[r:]`` for
    ``d = 2r``, ``O[r:]`` and ``E[r + 1:]`` for ``d = 2r + 1``.  One loop
    step does both.
    """
    T, n_b, k_b = Ct.shape
    w = k_b + n_b
    half = -(-(n_b + k_b - 1) // 2)
    rows = half + k_b  # per parity: the last step reads E to row rows - 1
    C, S, G = (_diagonals(X.astype(dtype), fill, 2 * half, k_b)
               for X, fill in ((Ct, 1), (St, 0), (Gt, -1)))
    eye = jnp.pad(jnp.eye(w, dtype=dtype),
                  ((k_b - 1, 2 * rows - (k_b - 1) - w), (0, 0)))
    # inside shard_map the tiles may be device-varying; the identity must
    # carry the same varying-manual-axes type to be a legal loop carry
    eye = compat.pvary_like(eye, Ct)
    EO = jnp.broadcast_to(eye.reshape(rows, 2, w), (T, rows, 2, w))

    def diagonal(X, Y, x0, y0, d):
        x = jax.lax.dynamic_slice_in_dim(X, x0, k_b, axis=1)
        y = jax.lax.dynamic_slice_in_dim(Y, y0, k_b, axis=1)
        c, s, g = (jax.lax.dynamic_index_in_dim(V, d, axis=1,
                                                keepdims=False)[..., None]
                   for V in (C, S, G))
        x, y = plane_update(x, y, c, s, g)
        return (jax.lax.dynamic_update_slice_in_dim(X, x, x0, axis=1),
                jax.lax.dynamic_update_slice_in_dim(Y, y, y0, axis=1))

    def step(r, EO):
        E, O = diagonal(*EO, r, r, 2 * r)
        O, E = diagonal(O, E, r, r + 1, 2 * r + 1)
        return E, O

    E, O = jax.lax.fori_loop(0, half, step, (EO[:, :, 0], EO[:, :, 1]))
    Qt = jnp.stack([E, O], axis=2).reshape(T, 2 * rows, w)
    return jnp.swapaxes(Qt[:, k_b - 1:k_b - 1 + w], 1, 2)


def _factors_kernel(Ct, St, Gt, *, interpret: bool = False):
    """The schedule as a Pallas kernel over float32 factors: the waves
    sheared by anti-diagonal, each tile's columns built split by parity
    (``kernels/factors.py``), then interleaved."""
    T, n_b, k_b = Ct.shape
    steps = round_up(n_b + k_b - 1, SUBLANES)
    lanes = round_up(k_b, LANES)
    C, S, G = (_diagonals(X.astype(jnp.float32), fill, steps, lanes)
               for X, fill in ((Ct, 1), (St, 0), (Gt, -1)))
    E, O = tile_factors_pallas(C, S, G, n_b=n_b, k_b=k_b,
                               interpret=interpret)
    Q = jnp.stack([E, O], axis=3).reshape(T, n_b + k_b, -1)
    return Q[:, :, k_b - 1:k_b - 1 + n_b + k_b]


def accumulate_tile_factors(Ct, St, Gt, *, dtype=jnp.float32):
    """Accumulate sheared tiles ``(T, n_b, k_b)`` into factors ``(T, w, w)``.

    ``X_out = X_in @ Q_t`` for each tile, so ``Q_t`` is the tile applied
    to an identity, as ``apply_tile(I)`` gives it, value for value (a
    zero's sign aside).  The rotations run one anti-diagonal ``d = jj +
    p`` of the tile at a time: every rotation that ``(jj, p)`` depends
    on lies on an earlier anti-diagonal, and those of one anti-diagonal
    pair disjoint planes, ``(k_b - 1 + d - 2p, k_b + d - 2p)`` in a
    padding of ``k_b - 1``.  That is ``n_b + k_b - 1`` steps of ``k_b``
    rotations each, where applying the tile plane by plane takes ``n_b
    * k_b`` steps of one.

    On a TPU, float32 factors come from a Pallas kernel that keeps each
    tile's factor in VMEM for all its steps; elsewhere, and for other
    dtypes, from an XLA loop of the same schedule (two anti-diagonals per
    step over all tiles at once).
    """
    if compat.is_tpu() and jnp.dtype(dtype) == jnp.float32:
        return _factors_kernel(Ct, St, Gt)
    return _factors_loop(Ct, St, Gt, dtype)


def apply_band_accumulated(A, Q, *, k_b: int):
    """Sweep one band using precomputed tile factors ``Q`` (T, w, w).

    The GEMMs run at ``Precision.HIGHEST``: at the default precision a
    TPU multiplies float32 operands in one bfloat16 pass, which falls
    far short of the float32 accuracy this backend promises.
    """
    T, w, _ = Q.shape
    n_b = w - k_b
    m, n = A.shape
    carry0, fresh = _band_inputs(A, k_b, n_b, T)
    fresh_tiles = fresh.reshape(m, T, n_b).transpose(1, 0, 2)

    def step(carry, xs):
        q, ft = xs
        X = jnp.concatenate([carry, ft], axis=1)
        X = jnp.dot(X, q.astype(X.dtype),
                    precision=jax.lax.Precision.HIGHEST)
        return X[:, n_b:], X[:, :n_b]

    _, out = jax.lax.scan(step, carry0, (Q, fresh_tiles))
    O = out.transpose(1, 0, 2).reshape(m, T * n_b)
    return jax.lax.slice_in_dim(O, k_b - 1, k_b - 1 + n, axis=1)


@partial(jax.jit, static_argnames=("n_b", "k_b", "reflect"))
def rot_sequence_accumulated(A, C, S, *, n_b: int = 128, k_b: int = 128,
                             reflect: bool = False, G=None):
    """Full ``rs_gemm``-style application: accumulate tiles, apply as GEMMs."""
    m, n = A.shape
    J, k = C.shape
    assert J == n - 1
    n_b = min(n_b, max(8, n))
    T = num_tiles(n, n_b, k_b)
    for p0 in range(0, k, k_b):
        with jax.named_scope(PACK):
            Ct, St, Gt = pack_sheared(C, S, p0, k_b, n_b, T,
                                      reflect=reflect, G=G)
        with jax.named_scope(ACCUMULATE):
            Q = accumulate_tile_factors(Ct, St, Gt, dtype=A.dtype)
        with jax.named_scope(SWEEP):
            A = apply_band_accumulated(A, Q, k_b=k_b)
    return A
