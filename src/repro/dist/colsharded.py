"""Column-sharded (CAQR-style panel) application of rotation sequences.

Each device owns a contiguous column slab of the target.  A band of
``k_b`` waves — one *panel* in the communication-avoiding sense of
Demmel–Grigori–Hoemmen–Langou (CAQR, arXiv 0809.2407) — must sweep
left-to-right across devices, so bands are *pipelined*: at superstep
``s`` device ``d`` processes band ``s - d``, and boundary planes are
exchanged **once per panel**, not once per wave, via three small
``collective_permute`` halos:

  - the ``(m_loc, k_b)`` partially-rotated **carry** columns (rightward),
  - one column of pre-band state (leftward) so the sweep can consume its
    right-neighbour's first column,
  - the ``(m_loc, k_b - 1)`` **realign** block (leftward), because the
    band sweep emits finalized columns shifted by ``k_b - 1``.

Per superstep each device communicates ``O(m_loc * k_b)`` elements
versus the ``O(m_loc * n_loc)`` it computes on — communication-efficient
in the same ``k_b / n_b`` sense as the paper's cache analysis (SS1.2),
with ICI links playing the role of the memory bus.  Pipeline
utilization is ``B / (B + D - 1)`` for ``B`` bands over ``D`` devices;
idle devices run no-op (identity-rotation) tiles so the program stays
SPMD-uniform.

This module is the drift-coordinate pipeline formerly hosted in
``repro.core.distributed`` (now a thin compat wrapper); the row-sharded
and batched fused paths live in :mod:`repro.dist.plan`.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.accumulate import accumulate_tile_factors
from repro.core.blocked import apply_tile
from repro.core.sequence import RotationSequence

__all__ = [
    "rot_sequence_column_sharded",
    "rot_sequence_column_sharded_padded",
    "column_sharded_comm_bytes",
]


def _require_sequence(seq, mesh, who: str):
    """Typed arguments only: the raw ``(A, C, S, mesh)`` positional form
    was removed after its deprecation release (PR 10)."""
    if not isinstance(seq, RotationSequence):
        raise TypeError(
            f"{who}(A, seq, mesh, ...) requires a RotationSequence; the "
            f"deprecated raw-array form {who}(A, C, S, mesh) was removed "
            f"— wrap the waves: RotationSequence(C, S)")
    if mesh is None:
        raise TypeError(f"{who}() missing required argument: 'mesh'")
    return seq, mesh


def _pack_local(C, S, c0, k_b, n_b, T_tot, p0):
    """Sheared tiles for one band over a device-local diagonal range.

    ``c0`` may be a traced device offset; gathers handle it.  Returns
    ``(T_tot, n_b, k_b)`` tiles covering diagonals ``[c0, c0 + T_tot*n_b)``.
    """
    J, k = C.shape
    u = c0 + jnp.arange(T_tot * n_b)
    p = jnp.arange(k_b)
    jg = u[:, None] - p[None, :]
    pg = p0 + p
    valid = (jg >= 0) & (jg < J) & (pg < k)[None, :]
    jc = jnp.clip(jg, 0, J - 1)
    pc = jnp.clip(pg, 0, k - 1)
    Ct = jnp.where(valid, C[jc, pc], 1.0).astype(C.dtype)
    St = jnp.where(valid, S[jc, pc], 0.0).astype(S.dtype)
    Gt = jnp.full_like(Ct, -1.0)
    shape = (T_tot, n_b, k_b)
    return Ct.reshape(shape), St.reshape(shape), Gt.reshape(shape)


def _sweep(X0carry, fresh_tiles, Ct, St, Gt, use_mxu: bool):
    """Scan tiles with carry; returns (final_carry, out_tiles)."""
    if use_mxu:
        Q = accumulate_tile_factors(Ct, St, Gt, dtype=X0carry.dtype)

        def step(carry, xs):
            q, ft = xs
            X = jnp.concatenate([carry, ft], axis=1)
            X = jnp.dot(X, q, preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST).astype(X.dtype)
            n_b = ft.shape[1]
            return X[:, n_b:], X[:, :n_b]

        return jax.lax.scan(step, X0carry, (Q, fresh_tiles))

    def step(carry, xs):
        ct, st, gt, ft = xs
        X = jnp.concatenate([carry, ft], axis=1)
        X = apply_tile(X, ct, st, gt)
        n_b = ft.shape[1]
        return X[:, n_b:], X[:, :n_b]

    return jax.lax.scan(step, X0carry, (Ct, St, Gt, fresh_tiles))


def rot_sequence_column_sharded(A, seq, mesh=None, *,
                                col_axis: str = "model",
                                n_b: int = 64, k_b: int = 16,
                                row_axes=(), method: str = "blocked"):
    """Column-sharded pipelined application of a :class:`RotationSequence`.

    Drift-coordinate scheme: each band's sweep emits its output shifted
    right by ``delta = k_b - 1`` state columns (the wavefront's natural
    output offset), so after band ``pb`` the device state holds matrix
    column ``i - pb*delta`` at state index ``i``.  Content drifts through
    right padding and is sliced back once at the end — no per-band
    realignment collective is needed.

    Each superstep is split in two phases so the pipeline needs only a
    one-column look-ahead halo: every device first applies *tile 0* of its
    current band, permutes that tile's first output column leftward (the
    right-neighbour value the *previous*-band device needs for its last
    tile), then sweeps its remaining tiles.

    Padding requirements (see :func:`rot_sequence_column_sharded_padded`
    for the public wrapper): global width ``W = D * n_loc`` with
    ``n_loc = T_loc * n_b``, ``T_loc >= 2`` and ``W >= n + B * (k_b - 1)``.
    """
    seq, mesh = _require_sequence(seq, mesh, "rot_sequence_column_sharded")
    C, S = seq.cos, seq.sin
    if seq.sign is not None or seq.reflect:
        raise ValueError(
            "column-sharded pipeline supports plain rotation sequences "
            "only (no per-entry signs / reflectors)")
    m, W = A.shape
    J, k = C.shape
    D = mesh.shape[col_axis]
    assert W % D == 0, (W, D)
    n_loc = W // D
    assert n_loc % n_b == 0, (n_loc, n_b)
    T_loc = n_loc // n_b
    assert T_loc >= 2, "need n_loc >= 2 * n_b for the split superstep"
    delta = k_b - 1
    B = math.ceil(k / k_b)
    assert W >= (J + 1) + B * delta, "insufficient drift padding"
    use_mxu = method == "accumulated"

    def device_fn(A_loc, C_full, S_full):
        d = jax.lax.axis_index(col_axis)
        D_ = D
        m_loc = A_loc.shape[0]
        right = [(i, (i + 1) % D_) for i in range(D_)]
        left = [(i, (i - 1) % D_) for i in range(D_)]

        def superstep(s, state):
            A_cur, carry_recv = state
            pb = s - d
            active = (pb >= 0) & (pb < B)
            pb_c = jnp.clip(pb, 0, B - 1)

            # rotations for this device's diagonal range, in drifted state
            # coordinates: state index i <-> matrix column i - pb*delta
            c0 = d * n_loc - pb_c * delta
            Ct, St, Gt = _pack_local(
                C_full, S_full, c0, k_b, n_b, T_loc, pb_c * k_b
            )
            Ct = jnp.where(active, Ct, jnp.ones_like(Ct))
            St = jnp.where(active, St, jnp.zeros_like(St))

            synth = jnp.concatenate(
                [jnp.zeros((m_loc, k_b - 1), A_loc.dtype), A_cur[:, :1]],
                axis=1,
            )
            carry_in = jnp.where(d == 0, synth, carry_recv)

            # --- phase 1: tile 0 (consumes only own fresh columns) ---
            fresh_own = A_cur[:, 1:]  # n_loc - 1 columns
            carry1, out0 = _sweep(
                carry_in, fresh_own[:, :n_b][None, :, :],
                Ct[:1], St[:1], Gt[:1], use_mxu)
            out0 = out0[0]  # (m_loc, n_b)

            # --- phase 2: halo = neighbour's tile-0 first output column
            # (post-its-band state), or its untouched slab head if the
            # neighbour is idle this superstep ---
            send = jnp.where(active, out0[:, :1], A_cur[:, :1])
            halo = jax.lax.ppermute(send, col_axis, left)
            halo = jnp.where(d == D_ - 1, jnp.zeros_like(halo), halo)

            # --- phase 3: remaining T_loc - 1 tiles ---
            fresh_rest = jnp.concatenate(
                [fresh_own[:, n_b:], halo], axis=1)
            rest_tiles = fresh_rest.reshape(
                m_loc, T_loc - 1, n_b).transpose(1, 0, 2)
            carry_out, out_rest = _sweep(
                carry1, rest_tiles, Ct[1:], St[1:], Gt[1:], use_mxu)
            O = jnp.concatenate(
                [out0[None], out_rest], axis=0
            ).transpose(1, 0, 2).reshape(m_loc, n_loc)

            A_new = jnp.where(active, O, A_cur)
            carry_next = jax.lax.ppermute(carry_out, col_axis, right)
            return (A_new, carry_next)

        carry0 = jnp.zeros((m_loc, k_b), A_loc.dtype)
        # match the varying-manual-axes type of the slab (plus the pipe
        # axis the ppermute varies over) so the fori carry types agree;
        # identity on JAX versions without vma tracking (repro.compat)
        carry0 = compat.pvary_like(carry0, A_loc, extra=(col_axis,))
        A_fin, _ = jax.lax.fori_loop(
            0, B + D_ - 1, superstep, (A_loc, carry0)
        )
        return A_fin

    row_spec = row_axes if row_axes else None
    fn = compat.shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(P(row_spec, col_axis), P(None, None), P(None, None)),
        out_specs=P(row_spec, col_axis),
    )
    return fn(A, C, S)


def rot_sequence_column_sharded_padded(A, seq, mesh=None, *,
                                       col_axis: str = "model",
                                       n_b: int = 64, k_b: int = 16,
                                       row_axes=(),
                                       method: str = "blocked"):
    """Public wrapper: pads ``A`` for drift + divisibility, slices back."""
    seq, mesh = _require_sequence(seq, mesh,
                                  "rot_sequence_column_sharded_padded")
    m, n = A.shape
    J, k = seq.shape
    assert J == n - 1
    D = mesh.shape[col_axis]
    delta = k_b - 1
    B = math.ceil(k / k_b)
    # choose n_loc: multiple of n_b, >= 2*n_b, and D*n_loc >= n + B*delta
    need = n + B * delta
    n_loc = max(2 * n_b, n_b * math.ceil(need / (D * n_b)))
    W = D * n_loc
    Ap = jnp.pad(A, ((0, 0), (0, W - n)))
    out = rot_sequence_column_sharded(
        Ap, seq, mesh, col_axis=col_axis, n_b=n_b, k_b=k_b,
        row_axes=row_axes, method=method,
    )
    return jax.lax.slice_in_dim(out, B * delta, B * delta + n, axis=1)


def _live_waves(sequence: RotationSequence) -> int:
    """Count of waves holding at least one live (non-identity) plane.

    Mirrors the fused kernel's liveness rule: an entry is dead iff it is
    the exact identity *rotation* ``(c, s, g) = (1, 0, -1)`` — padded
    reflectors are live (det -1), so sign-carrying entries are dead only
    where the sign marks a rotation.
    """
    import numpy as np

    C = np.asarray(sequence.cos)
    S = np.asarray(sequence.sin)
    if sequence.sign is not None:
        G = np.asarray(sequence.sign)
    else:
        fill = 1.0 if sequence.reflect else -1.0
        G = np.full_like(C, fill)
    live = ~((C == 1.0) & (S == 0.0) & (G < 0))
    return int(np.count_nonzero(live.any(axis=0)))


def column_sharded_comm_bytes(m_loc: int, n: int, k: int, D: int,
                              n_b: int, k_b: int, itemsize: int = 4, *,
                              sequence: Optional[RotationSequence] = None,
                              live_planes: Optional[int] = None) -> dict:
    """Analytic per-device communication volume of the pipelined algorithm
    vs an all-gather baseline — the distributed analogue of paper SS1.2.

    Identity padding is exchange-free: a band whose ``k_b`` waves are
    all identity sweeps nothing across the boundary, so only *live*
    bands are priced.  Pass ``sequence`` to count live waves exactly
    (the fused kernel's per-wave ``valid_planes`` liveness rule —
    ``pad_to`` tails and ``seq.T`` staircases price far below the dense
    ``(n-1, k)`` grid), or ``live_planes`` (the static
    ``RotationSequence.k_live`` bound) to model a ``pad_to`` tail of
    ``ceil(live_planes / (n-1))`` leading live waves.  With neither,
    every band is assumed live (the dense grid — the historical
    behaviour, which overstated boundary traffic for padded sequences).

    Returns ``{"pipelined", "allgather", "ratio", "bands",
    "live_bands"}`` (bytes; ``ratio = allgather / pipelined``).
    """
    J = max(1, n - 1)
    B = math.ceil(k / k_b)
    if sequence is not None:
        if sequence.shape != (n - 1, k):
            raise ValueError(
                f"sequence shape {sequence.shape} != waves ({n - 1}, {k})")
        waves = _live_waves(sequence)
    elif live_planes is not None:
        waves = min(k, math.ceil(max(0, int(live_planes)) / J))
    else:
        waves = k
    # pad_to tails / staircase fills trail the live region, so live
    # waves occupy leading bands; a mid-grid dead band still permutes
    # its (cheap) identity halos in the real schedule, but contributes
    # no boundary *planes* — the quantity this model prices.
    live_bands = min(B, math.ceil(waves / k_b))
    supersteps = live_bands + D - 1
    per_step = m_loc * (1 + k_b + (k_b - 1)) * itemsize
    pipelined = supersteps * per_step
    # gather full row-panel once per live band
    allgather = live_bands * m_loc * n * itemsize
    return {"pipelined": pipelined, "allgather": allgather,
            "ratio": allgather / max(pipelined, 1),
            "bands": B, "live_bands": live_bands}
