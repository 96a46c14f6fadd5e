"""Pallas TPU kernel: the tile factors of the accumulated backends.

``core/accumulate.py`` builds each parallelogram tile's factor ``Q_t``
one anti-diagonal of the tile at a time and gives this kernel the waves
sheared by anti-diagonal.  One grid step holds one tile's factor in
VMEM for all ``n_b + k_b - 1`` anti-diagonals, where an XLA loop would
read and write every tile's window through memory at each step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core.rotations import plane_update
from repro.kernels.limits import LANES, round_up

__all__ = ["tile_factors_pallas"]


def _factor_kernel(c_ref, s_ref, g_ref, e_ref, o_ref, *, k_b: int,
                   half: int):
    """One tile's factor, two anti-diagonals per loop step.

    ``e_ref`` / ``o_ref`` hold the even / odd columns of ``Q_t`` padded
    by ``k_b - 1`` columns on the left: column ``2i`` of the padded
    factor is ``E[:, i]``.  Anti-diagonal ``d = 2r`` pairs ``E[:, r +
    q]`` with ``O[:, r + q]``, ``d = 2r + 1`` pairs ``O[:, r + q]`` with
    ``E[:, r + 1 + q]``.  Rolling ``E`` left by one lane before each odd
    anti-diagonal and ``O`` before each even one keeps every pair at
    lanes ``[0, k_b)``: Mosaic lowers no slice at an unaligned dynamic
    offset, but it lowers rolls and a dynamic single row of the waves.
    A column leaving at lane 0 wraps to the far end, past every window.
    """
    w, P = e_ref.shape[1:]
    lanes = c_ref.shape[2]
    row = jax.lax.broadcasted_iota(jnp.int32, (w, P), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (w, P), 1)
    one, zero = jnp.ones((), e_ref.dtype), jnp.zeros((), e_ref.dtype)
    e_ref[0] = jnp.where(2 * col - (k_b - 1) == row, one, zero)
    o_ref[0] = jnp.where(2 * col + 1 - (k_b - 1) == row, one, zero)

    def diagonal(x_ref, y_ref, d):
        c, s, g = (v[0, pl.ds(d, 1), :] for v in (c_ref, s_ref, g_ref))
        x, y = plane_update(x_ref[0, :, :lanes], y_ref[0, :, :lanes],
                            c, s, g)
        x_ref[0, :, :lanes] = x
        y_ref[0, :, :lanes] = y

    def slide(ref):
        ref[0] = pltpu.roll(ref[0], P - 1, 1)

    def step(r, carry):
        diagonal(e_ref, o_ref, 2 * r)
        slide(e_ref)
        diagonal(o_ref, e_ref, 2 * r + 1)
        slide(o_ref)
        return carry

    jax.lax.fori_loop(0, half, step, 0)
    e_ref[0] = pltpu.roll(e_ref[0], half, 1)
    o_ref[0] = pltpu.roll(o_ref[0], half, 1)


@functools.partial(jax.jit, static_argnames=("n_b", "k_b", "interpret"))
def tile_factors_pallas(C, S, G, *, n_b: int, k_b: int,
                        interpret: bool = False):
    """Even and odd columns of every tile's padded factor.

    Args:
      C, S, G: ``(T, steps, lanes)`` float32 waves by anti-diagonal:
        row ``d`` of tile ``t`` holds the cos / sin / sign of the
        rotations with ``jj + p = d``, wave ``p`` at lane ``k_b - 1 -
        p``, and the no-op ``(1, 0, -1)`` elsewhere; ``steps`` covers
        ``n_b + k_b - 1`` rounded up to even, ``lanes`` is ``k_b``
        rounded up to a lane tile.
      n_b, k_b: the tile's anti-diagonals and waves.

    Returns:
      ``(E, O)``, each ``(T, n_b + k_b, P)``: ``E[t, :, i]`` is column
      ``2i - (k_b - 1)`` of ``Q_t`` and ``O[t, :, i]`` column ``2i + 1 -
      (k_b - 1)``, zero where that is no column.
    """
    T, steps, lanes = C.shape
    w = n_b + k_b
    half = -(-(n_b + k_b - 1) // 2)
    assert steps >= 2 * half and lanes >= k_b, (C.shape, n_b, k_b)
    P = round_up(half + k_b, LANES)
    vma = frozenset().union(*(compat.varying_axes(x) for x in (C, S, G)))
    waves = pl.BlockSpec((1, steps, lanes), lambda t: (t, 0, 0))
    columns = pl.BlockSpec((1, w, P), lambda t: (t, 0, 0))
    return pl.pallas_call(
        functools.partial(_factor_kernel, k_b=k_b, half=half),
        name="rotseq_factors",
        grid=(T,),
        in_specs=[waves] * 3,
        out_specs=[columns] * 2,
        out_shape=[jax.ShapeDtypeStruct((T, w, P), jnp.float32,
                                        vma=vma)] * 2,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(C, S, G)
