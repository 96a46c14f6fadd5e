"""Accumulated (GEMM) application of rotation sequences — paper's ``rs_gemm``.

Each parallelogram tile of ``n_b`` waves x ``k_b`` rotations is accumulated
into a dense orthogonal factor ``Q_t`` of size ``w x w`` (``w = k_b + n_b``)
by applying the tile to an identity matrix with the wavefront kernel; the
sweep over ``A`` then becomes a scan of ``(m, w) @ (w, w)`` matmuls.

On CPU (the paper) this trades ~4/3 more flops for MKL GEMM throughput and
only wins for large matrices.  On TPU it is the *natural* formulation: the
MXU delivers ~50x the VPU flop rate, so paying ``2 m w^2`` MXU flops instead
of ``6 m n_b k_b`` VPU flops per tile inverts the paper's CPU conclusion.
Accumulation cost is amortized by ``m / w``.

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

from .blocked import (ACCUMULATE, PACK, SWEEP, _band_inputs, apply_tile,
                      num_tiles, pack_sheared)

__all__ = [
    "accumulate_tile_factors",
    "apply_band_accumulated",
    "rot_sequence_accumulated",
]


def accumulate_tile_factors(Ct, St, Gt, *, dtype=jnp.float32):
    """Accumulate sheared tiles ``(T, n_b, k_b)`` into factors ``(T, w, w)``.

    ``X_out = X_in @ Q_t`` for each tile, so ``Q_t = apply_tile(I)``
    (application is linear and acts identically on every row).
    """
    T, n_b, k_b = Ct.shape
    w = k_b + n_b
    eye = jnp.eye(w, dtype=dtype)
    # inside shard_map the tiles may be device-varying; the identity must
    # carry the same varying-manual-axes type to be a legal loop carry
    eye = compat.pvary_like(eye, Ct)
    return jax.vmap(lambda c, s, g: apply_tile(eye, c, s, g))(Ct, St, Gt)


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
