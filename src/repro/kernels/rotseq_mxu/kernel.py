"""Pallas TPU kernel: accumulated (MXU) application of rotation sequences.

The beyond-paper TPU formulation of ``rs_gemm`` (paper SS8): parallelogram
tiles of rotations are pre-accumulated into ``(w, w)`` orthogonal factors
(``w = k_b + n_b``), and this kernel sweeps the matrix through them with a
carry, turning the whole rotation band into a chain of MXU matmuls::

    X_t   = [carry_t | fresh_t]          # (m_blk, w)
    Y_t   = X_t @ Q_t                    # MXU
    out_t = Y_t[:, :n_b];  carry_{t+1} = Y_t[:, n_b:]

The carry column block stays in VMEM between grid steps — the same
communication-avoidance as the VPU kernel, but at MXU flop rates.  With
``n_b = k_b`` the factor is dense and only 4/3 extra flops are paid
relative to the direct method (on a unit ~50x faster than the VPU).

Natural (row-major) layout: ``m`` on sublanes, columns on lanes; all matmul
dims are multiples of 128 when ``n_b = k_b = 128``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat

__all__ = ["rotseq_mxu_pallas"]


def _mxu_kernel(q_ref, init_ref, fresh_ref, out_ref, carry_ref,
                *, n_b: int, k_b: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        carry_ref[...] = init_ref[...]

    x = jnp.concatenate([carry_ref[...], fresh_ref[...]], axis=1)
    # float32 at HIGHEST: at its default precision Mosaic multiplies
    # float32 operands in bfloat16 passes (a 5e-3 relative error at the
    # paper's k=180 on a v5e), far from the float32 accuracy the GEMM
    # family promises; bfloat16 operands take the default
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else None)
    y = jnp.dot(x, q_ref[0], preferred_element_type=jnp.float32,
                precision=precision)
    y = y.astype(x.dtype)
    out_ref[...] = y[:, :n_b]
    carry_ref[...] = y[:, n_b:]


@functools.partial(
    jax.jit, static_argnames=("n_b", "k_b", "m_blk", "interpret")
)
def rotseq_mxu_pallas(fresh, Q, init, *, n_b: int, k_b: int, m_blk: int,
                      interpret: bool = True):
    """Sweep one band using tile factors ``Q`` (T, w, w).

    Args:
      fresh: ``(m, T * n_b)`` fresh-column stream (natural layout,
        ``fresh[:, i] = A[:, i + 1]`` zero-padded).
      Q: ``(T, w, w)`` accumulated tile factors, ``w = k_b + n_b``.
      init: ``(m, k_b)`` initial carry.

    Returns:
      ``(m, T * n_b)`` output stream ``O``, ``O[:, i] = A_final[:, i - k_b + 1]``.
    """
    m, U = fresh.shape
    T, w, _ = Q.shape
    assert w == n_b + k_b and U == T * n_b
    assert m % m_blk == 0
    grid = (m // m_blk, T)

    kernel = functools.partial(_mxu_kernel, n_b=n_b, k_b=k_b)
    return pl.pallas_call(
        kernel,
        name="rotseq_mxu",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, w, w), lambda i, t: (t, 0, 0)),
            pl.BlockSpec((m_blk, k_b), lambda i, t: (i, 0)),
            pl.BlockSpec((m_blk, n_b), lambda i, t: (i, t)),
        ],
        out_specs=pl.BlockSpec((m_blk, n_b), lambda i, t: (i, t)),
        out_shape=jax.ShapeDtypeStruct((m, T * n_b), fresh.dtype),
        scratch_shapes=[pltpu.VMEM((m_blk, k_b), fresh.dtype)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(Q, init, fresh)
