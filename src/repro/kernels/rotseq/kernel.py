"""Pallas TPU kernel: wavefront application of rotation sequences (VPU path).

Faithful TPU adaptation of the paper's register-reuse kernel (SS3).  The
paper pins ``m_r`` rows x ``k_r + 1`` columns of ``A`` in AVX registers and
streams waves of rotations through them; here a ``(k_b + n_b, m_blk)`` block
of the *packed* (transposed) matrix is pinned in VMEM and ``k_b`` waves of
rotations stream through it.  The ``k_b`` trailing columns carry over to the
next grid step in a VMEM scratch buffer — they never round-trip to HBM,
which is exactly the paper's fused-rotation reuse argument one level up the
memory hierarchy.

Layout ("packing", paper SS4): the kernel operates on ``AT`` of shape
``(n_cols, m)`` so that matrix *columns* are rows of vregs — the row
dimension ``m`` lies along TPU lanes and every rotation is a dense
``(1, m_blk)`` x scalar VPU op.  The caller transposes once (the packing
cost; negligible for ``k >> 1``) or keeps the operand packed across calls
(paper's ``rs_kernel_v2``).

Grid: ``(num_row_blocks, T)`` with the tile dimension ``T`` innermost and
sequential ("arbitrary" semantics): the carry scratch persists across ``t``
and is re-initialized at ``t == 0``.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core.rotations import plane_update

__all__ = ["rotseq_wave_pallas"]


def _wave_kernel(ct_ref, st_ref, gt_ref, init_ref, fresh_ref, out_ref,
                 x_ref, *, n_b: int, k_b: int):
    """One parallelogram tile: k_b waves over X = [carry; fresh] (w, m_blk).

    ``x_ref`` is the VMEM slab ``X``; its first ``k_b`` rows persist across
    ``t`` as the carry.  Each plane loads and stores its two rows through
    the ref one row at a time: Mosaic lowers a dynamic single-row slice
    of a ref, but neither a ``dynamic_slice`` of a value nor a two-row
    window at an offset it cannot prove sublane-aligned.
    """
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        x_ref[:k_b, :] = init_ref[...]

    x_ref[k_b:, :] = fresh_ref[...]
    dtype = x_ref.dtype

    def wave(p, _):
        def rot(jj, _):
            jl = k_b - 1 - p + jj
            c = ct_ref[0, jj, p].astype(dtype)
            s = st_ref[0, jj, p].astype(dtype)
            g = gt_ref[0, jj, p].astype(dtype)
            xn, yn = plane_update(x_ref[pl.ds(jl, 1), :],
                                  x_ref[pl.ds(jl + 1, 1), :], c, s, g)
            x_ref[pl.ds(jl, 1), :] = xn
            x_ref[pl.ds(jl + 1, 1), :] = yn
            return 0

        return jax.lax.fori_loop(0, n_b, rot, 0)

    jax.lax.fori_loop(0, k_b, wave, 0)
    out_ref[...] = x_ref[:n_b, :]
    x_ref[:k_b, :] = x_ref[n_b:, :]


@functools.partial(
    jax.jit,
    static_argnames=("n_b", "k_b", "m_blk", "interpret"),
)
def rotseq_wave_pallas(ATfresh, Ct, St, Gt, init, *, n_b: int, k_b: int,
                       m_blk: int, interpret: bool = True):
    """Apply one band of ``k_b`` waves to the packed operand.

    Args:
      ATfresh: ``(T * n_b, m)`` — fresh column stream, packed layout
        (``ATfresh[i] = A[:, i + 1]`` zero-padded; see ``core.blocked``).
      Ct, St, Gt: ``(T, n_b, k_b)`` sheared rotation tiles (no-op padded;
        ``Gt`` is the rotation/reflector sign, see ``pack_sheared``).
      init: ``(k_b, m)`` initial carry (``[0...0, A[:, 0]]``).
      n_b, k_b: tile diagonals / band waves (k_b = paper's ``k_b``,
        n_b plays the role of the paper's L1 block ``n_b``).
      m_blk: rows of ``A`` per grid step (lane dimension; multiple of 128
        on hardware).

    Returns:
      ``(T * n_b, m)`` output stream ``O`` with
      ``O[i] = A_final[:, i - (k_b - 1)]``.
    """
    U, m = ATfresh.shape
    T = U // n_b
    assert U == T * n_b, (U, n_b)
    assert m % m_blk == 0, (m, m_blk)
    R = m // m_blk
    grid = (R, T)

    kernel = functools.partial(_wave_kernel, n_b=n_b, k_b=k_b)
    cs_spec = pl.BlockSpec((1, n_b, k_b), lambda i, t: (t, 0, 0),
                           memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        name="rotseq_wave",
        grid=grid,
        in_specs=[
            cs_spec,
            cs_spec,
            cs_spec,
            pl.BlockSpec((k_b, m_blk), lambda i, t: (0, i)),
            pl.BlockSpec((n_b, m_blk), lambda i, t: (t, i)),
        ],
        out_specs=pl.BlockSpec((n_b, m_blk), lambda i, t: (t, i)),
        out_shape=jax.ShapeDtypeStruct((T * n_b, m), ATfresh.dtype),
        scratch_shapes=[pltpu.VMEM((k_b + n_b, m_blk), ATfresh.dtype)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(Ct, St, Gt, init, ATfresh)
