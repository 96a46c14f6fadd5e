"""Pallas TPU kernel: fused RoPE application to q and k.

One grid step rotates a ``(blk, heads * head_dim)`` tile of both q and k
while the cos/sin tables stay resident in VMEM — q and k never round-trip
to HBM between their (identical-plane) rotations, the same fused-rotation
reuse argument as the paper's SS1.3 applied to the two operands that share
rotation values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rope_pallas"]


def _rope_kernel(cos_ref, sin_ref, q_ref, k_ref, qo_ref, ko_ref,
                 *, heads_q: int, heads_k: int, head_dim: int):
    half = head_dim // 2
    c = cos_ref[...]
    s = sin_ref[...]

    def rot(x_ref, o_ref, heads):
        blk = x_ref.shape[0]
        x = x_ref[...].reshape(blk, heads, head_dim)
        x1 = x[..., :half]
        x2 = x[..., half:]
        cc = c[:, None, :]
        ss = s[:, None, :]
        # RoPE's half-split convention rotates (x1, x2) with its own
        # sign layout; it is not part of the rotation-sequence bitwise
        # contract, so the canonical plane_update does not apply here.
        # repro-lint: disable-next=RA301
        out = jnp.concatenate([x1 * cc - x2 * ss, x1 * ss + x2 * cc],
                              axis=-1)
        o_ref[...] = out.reshape(blk, heads * head_dim)

    rot(q_ref, qo_ref, heads_q)
    rot(k_ref, ko_ref, heads_k)


@functools.partial(
    jax.jit, static_argnames=("heads_q", "heads_k", "head_dim", "blk",
                              "interpret")
)
def rope_pallas(q, k, cos, sin, *, heads_q: int, heads_k: int,
                head_dim: int, blk: int = 256, interpret: bool = True):
    """Fused RoPE for ``q`` (S, Hq*D) and ``k`` (S, Hk*D); tables (S, D/2)."""
    S = q.shape[0]
    assert S % blk == 0, (S, blk)
    grid = (S // blk,)
    half = head_dim // 2

    kernel = functools.partial(
        _rope_kernel, heads_q=heads_q, heads_k=heads_k, head_dim=head_dim
    )
    return pl.pallas_call(
        kernel,
        name="rope",
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk, half), lambda i: (i, 0)),
            pl.BlockSpec((blk, half), lambda i: (i, 0)),
            pl.BlockSpec((blk, heads_q * head_dim), lambda i: (i, 0)),
            pl.BlockSpec((blk, heads_k * head_dim), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((blk, heads_q * head_dim), lambda i: (i, 0)),
            pl.BlockSpec((blk, heads_k * head_dim), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
        ],
        interpret=interpret,
    )(cos, sin, q, k)
