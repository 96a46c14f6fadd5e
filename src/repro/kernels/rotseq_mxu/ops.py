"""Jit'd wrapper for the MXU rotation-sequence kernel."""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro import compat, obs
from repro.core.accumulate import accumulate_tile_factors
from repro.core.blocked import (ACCUMULATE, PACK, SWEEP, num_tiles,
                                pack_sheared)
from repro.kernels.limits import round_up

from .kernel import rotseq_mxu_pallas

__all__ = ["rot_sequence_mxu"]


def rot_sequence_mxu(A, C, S, *, n_b: int = 128, k_b: int = 128,
                     m_blk: int = 256, reflect: bool = False, G=None,
                     interpret: bool | None = None):
    """Apply ``(C, S)`` to ``A`` from the right via accumulated MXU tiles.

    ``interpret=None`` resolves via the compat shim: compiled on TPU,
    interpreter elsewhere.

    The host wrapper only adds obs accounting (launches, planes, modeled
    bytes per the accumulated-traffic term) around the jitted core — a
    no-op while obs is off or under tracing.
    """
    if obs.enabled() and not compat.is_tracer(A):
        m, n = A.shape
        J, k = C.shape
        itemsize = jnp.dtype(A.dtype).itemsize
        bands = max(1, math.ceil(k / max(1, k_b)))
        obs.inc("kernels.rotseq_mxu.launches")
        obs.inc("kernels.rotseq_mxu.planes_applied", J * k)
        obs.inc("kernels.rotseq_mxu.bytes_moved",
                int((2 * m * n * bands + 3 * J * k) * itemsize))
    return _rot_sequence_mxu_jit(A, C, S, n_b=n_b, k_b=k_b, m_blk=m_blk,
                                 reflect=reflect, G=G, interpret=interpret)


@partial(
    jax.jit,
    static_argnames=("n_b", "k_b", "m_blk", "reflect", "interpret"),
)
def _rot_sequence_mxu_jit(A, C, S, *, n_b: int = 128, k_b: int = 128,
                          m_blk: int = 256, reflect: bool = False,
                          G=None, interpret: bool | None = None):
    if interpret is None:
        interpret = compat.pallas_interpret_default()
    m, n = A.shape
    J, k = C.shape
    assert J == n - 1
    n_b = min(n_b, max(8, n))
    T = num_tiles(n, n_b, k_b)

    m_pad = round_up(m, m_blk)
    with jax.named_scope(SWEEP):
        Ap = jnp.pad(A, ((0, m_pad - m), (0, 0)))

    for p0 in range(0, k, k_b):
        with jax.named_scope(PACK):
            Ct, St, Gt = pack_sheared(C, S, p0, k_b, n_b, T,
                                      reflect=reflect, G=G)
        with jax.named_scope(ACCUMULATE):
            Q = accumulate_tile_factors(Ct, St, Gt, dtype=Ap.dtype)
        with jax.named_scope(SWEEP):
            init = jnp.concatenate(
                [jnp.zeros((m_pad, k_b - 1), Ap.dtype), Ap[:, :1]], axis=1
            )
            fresh = jnp.pad(Ap[:, 1:], ((0, 0), (0, T * n_b - (n - 1))))
            O = rotseq_mxu_pallas(
                fresh, Q, init, n_b=n_b, k_b=k_b,
                m_blk=min(m_blk, m_pad), interpret=interpret,
            )
            Ap = jax.lax.slice_in_dim(O, k_b - 1, k_b - 1 + n, axis=1)

    with jax.named_scope(SWEEP):
        return Ap[:m]
