"""Pallas TPU kernel: fused multi-request rotation-sequence application.

One launch serves a whole ``RotationService`` bucket: the grid runs over
``(batch, m-blocks)`` and each step rotates one ``(n, m_blk)`` slab of
one request entirely in VMEM — the packed C/S/G panel is loaded once per
batch element (reused across its m-blocks) instead of once per request
launch, and the target streams through HBM exactly once regardless of
the wave count.  This is the paper's communication argument applied
across *requests*: the bucket's batched memory pass replaces ``b``
vmap'd/looped per-request launches.

Identity padding is *skipped*, not multiplied through.  Buckets
normalize wave counts with ``pad_to`` (whole trailing waves of
``c=1, s=0`` no-ops) and ``seq.T`` packs a ``k``-wave sequence into an
``n+k-2``-wave anti-diagonal staircase that is mostly identity; both
paddings leave each wave's *live* planes in one contiguous window.  The
host computes a per-wave ``(start, count)`` window (``valid_planes``)
and the kernel loops over ``count`` planes only — ``count = 0`` waves
cost nothing.  A per-grid-step plane counter is emitted so tests can
assert the skip actually happened.

Layout matches the VPU wavefront kernel ("packing", paper SS4): targets
are transposed to ``(n, m)`` so matrix columns are sublane rows and the
row dimension ``m`` lies along TPU lanes; every plane update is a dense
``(1, m_blk)`` x scalar VPU op through the canonical
:func:`~repro.core.rotations.plane_update` evaluation order (bit-stable
against every jnp backend).

Residency: the whole ``(n, m_blk)`` slab stays in VMEM for all ``K``
waves, and the scalar-indexed C/S/G panels stay in SMEM — the registry
offers the kernel no tile on TPU when the panels exceed
``repro.kernels.limits.SMEM_PANEL_BUDGET``
(``registry.rotseq_batched_tiles``), since interpret mode would happily
run grids Mosaic could never compile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core.rotations import plane_update

__all__ = ["rotseq_batched_pallas"]


def _batched_kernel(starts_ref, counts_ref, c_ref, s_ref, g_ref, a_ref,
                    out_ref, planes_ref, *, K: int):
    """Apply all K waves to one (n, m_blk) slab, skipping dead planes.

    The slab is rotated in place in the output block, one row load and
    store per plane side (see the VPU wavefront kernel for why rows go
    through the ref).
    """
    out_ref[...] = a_ref[...]
    dtype = out_ref.dtype

    def wave(p, total):
        start = starts_ref[0, 0, p]
        count = counts_ref[0, 0, p]

        def rot(jj, _):
            j = start + jj
            c = c_ref[0, j, p].astype(dtype)
            s = s_ref[0, j, p].astype(dtype)
            g = g_ref[0, j, p].astype(dtype)
            xn, yn = plane_update(out_ref[0, pl.ds(j, 1), :],
                                  out_ref[0, pl.ds(j + 1, 1), :], c, s, g)
            out_ref[0, pl.ds(j, 1), :] = xn
            out_ref[0, pl.ds(j + 1, 1), :] = yn
            return 0

        jax.lax.fori_loop(0, count, rot, 0)
        return total + count

    planes_ref[0, 0, 0, 0] = jax.lax.fori_loop(0, K, wave, jnp.int32(0))


@functools.partial(
    jax.jit, static_argnames=("m_blk", "interpret")
)
def rotseq_batched_pallas(AT, C, S, G, starts, counts, *, m_blk: int,
                          interpret: bool = True):
    """One fused launch over a batch of packed targets.

    Args:
      AT: ``(b, n, m_pad)`` packed (transposed) targets, ``m_pad`` a
        multiple of ``m_blk``.
      C, S, G: ``(bs, n-1, K)`` wave stacks — ``bs = b`` for per-request
        sequences or ``bs = 1`` for one shared sequence.  ``G`` is the
        per-entry sign of the unified update (``-1`` rotation, ``+1``
        reflector), always materialized.
      starts, counts: ``(bs, K)`` int32 — first live plane and number of
        contiguous live planes per wave; ``count = 0`` skips the wave.
      m_blk: lanes of the target per grid step.

    Returns:
      ``(out, planes)``: the rotated ``(b, n, m_pad)`` stack and an
      ``(b, R)`` int32 count of planes actually processed per grid step
      (the plane-skip witness; ``R = m_pad // m_blk``).
    """
    b, n, m_pad = AT.shape
    bs, J, K = C.shape
    assert J == n - 1, (C.shape, AT.shape)
    assert bs in (1, b), (bs, b)
    assert m_pad % m_blk == 0, (m_pad, m_blk)
    R = m_pad // m_blk
    grid = (b, R)

    if bs == 1:
        panel_ix = lambda ib, i: (0, 0, 0)
    else:
        panel_ix = lambda ib, i: (ib, 0, 0)

    # Every block's last two dimensions equal the array's own (Mosaic's
    # block rule): the (bs, K) windows ride as (bs, 1, K) and the plane
    # counter as (b, R, 1, 1).
    panel_spec = pl.BlockSpec((1, J, K), panel_ix,
                              memory_space=pltpu.SMEM)
    window_spec = pl.BlockSpec((1, 1, K), panel_ix,
                               memory_space=pltpu.SMEM)
    # inside shard_map the outputs vary over every mesh axis an input
    # does (shard_map checks it); outside, the set is empty
    vma = frozenset().union(
        *(compat.varying_axes(x) for x in (AT, C, S, G, starts, counts)))
    kernel = functools.partial(_batched_kernel, K=K)
    out, planes = pl.pallas_call(
        kernel,
        name="rotseq_batched",
        grid=grid,
        in_specs=[
            window_spec,
            window_spec,
            panel_spec,
            panel_spec,
            panel_spec,
            pl.BlockSpec((1, n, m_blk), lambda ib, i: (ib, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, n, m_blk), lambda ib, i: (ib, 0, i)),
            pl.BlockSpec((1, 1, 1, 1), lambda ib, i: (ib, i, 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, m_pad), AT.dtype, vma=vma),
            jax.ShapeDtypeStruct((b, R, 1, 1), jnp.int32, vma=vma),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(starts[:, None], counts[:, None], C, S, G, AT)
    return out, planes.reshape(b, R)
