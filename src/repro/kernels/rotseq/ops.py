"""Jit'd wrapper for the wavefront rotation-sequence Pallas kernel.

Handles the packing (transpose to column-major-of-rows layout, paper SS4),
identity padding, band loop over ``k_b`` waves, and unpacking.  Public entry:
:func:`rot_sequence_wave`.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro import compat, obs
from repro.core.blocked import PACK, SWEEP, num_tiles, pack_sheared
from repro.kernels.limits import round_up

from .kernel import rotseq_wave_pallas

__all__ = ["rot_sequence_wave"]


def rot_sequence_wave(A, C, S, *, n_b: int = 64, k_b: int = 16,
                      m_blk: int = 256, reflect: bool = False, G=None,
                      interpret: bool | None = None):
    """Apply the rotation sequence ``(C, S)`` to ``A`` from the right.

    Drop-in equivalent of ``repro.core.ref.rot_sequence_numpy`` computed by
    the Pallas wavefront kernel.  ``m_blk`` is clamped/padded so any ``m``
    works; on hardware use multiples of 128.  ``interpret=None`` resolves
    via the compat shim: compiled on TPU, interpreter elsewhere.

    The host wrapper only adds obs accounting (launches, planes, modeled
    bytes per the blocked-traffic term) around the jitted core — a no-op
    while obs is off or under tracing.
    """
    if obs.enabled() and not compat.is_tracer(A):
        m, n = A.shape
        J, k = C.shape
        itemsize = jnp.dtype(A.dtype).itemsize
        bands = max(1, math.ceil(k / max(1, k_b)))
        obs.inc("kernels.rotseq.launches")
        obs.inc("kernels.rotseq.planes_applied", J * k)
        obs.inc("kernels.rotseq.bytes_moved",
                int((2 * m * n * bands + 3 * J * k) * itemsize))
    return _rot_sequence_wave_jit(A, C, S, n_b=n_b, k_b=k_b, m_blk=m_blk,
                                  reflect=reflect, G=G,
                                  interpret=interpret)


@partial(
    jax.jit,
    static_argnames=("n_b", "k_b", "m_blk", "reflect", "interpret"),
)
def _rot_sequence_wave_jit(A, C, S, *, n_b: int = 64, k_b: int = 16,
                           m_blk: int = 256, reflect: bool = False,
                           G=None, interpret: bool | None = None):
    if interpret is None:
        interpret = compat.pallas_interpret_default()
    m, n = A.shape
    J, k = C.shape
    assert J == n - 1, (C.shape, A.shape)
    n_b = min(n_b, max(8, n))
    T = num_tiles(n, n_b, k_b)

    m_pad = round_up(m, m_blk)
    with jax.named_scope(SWEEP):  # packed layout (n, m_pad)
        AT = jnp.pad(A.T, ((0, 0), (0, m_pad - m)))

    for p0 in range(0, k, k_b):
        with jax.named_scope(PACK):
            Ct, St, Gt = pack_sheared(C, S, p0, k_b, n_b, T,
                                      reflect=reflect, G=G)
        with jax.named_scope(SWEEP):
            init = jnp.concatenate(
                [jnp.zeros((k_b - 1, m_pad), AT.dtype), AT[:1]], axis=0
            )
            fresh = jnp.pad(AT[1:], ((0, T * n_b - (n - 1)), (0, 0)))
            O = rotseq_wave_pallas(
                fresh, Ct, St, Gt, init,
                n_b=n_b, k_b=k_b, m_blk=min(m_blk, m_pad),
                interpret=interpret,
            )
            AT = jax.lax.slice_in_dim(O, k_b - 1, k_b - 1 + n, axis=0)

    with jax.named_scope(SWEEP):
        return AT[:, :m].T  # unpack
