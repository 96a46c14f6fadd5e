"""Jit'd wrapper for the fused multi-request rotation kernel.

Handles target packing (transpose + lane padding), sign materialization
(the bit-stable runtime sign grid — see ``core.rotations.plane_update``),
and the live-plane window computation that lets the kernel *skip*
identity padding (``pad_to`` waves, ``seq.T`` staircases) instead of
multiplying it through.  Public entry: :func:`rot_sequence_batched`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat, obs
from repro.core.blocked import SWEEP
from repro.kernels.limits import clamp_m_blk, round_up

from .kernel import rotseq_batched_pallas

__all__ = ["rot_sequence_batched", "wave_windows", "count_live_planes"]


def wave_windows(C, S, G):
    """Per-wave live-plane windows ``(starts, counts)`` of shape (bs, K).

    A plane is *dead* (exactly skippable) iff it is the identity
    rotation ``c = 1, s = 0, g = -1`` — a padded 2x2 *reflector* with
    the same cos/sin is ``diag(1, -1)``, not the identity, so the sign
    participates in the test.  Each wave's live planes are reduced to
    their contiguous hull ``[start, start + count)``: interior dead
    planes (rare; only in hand-built sequences) are applied as exact
    no-ops, while the hull bounds skip the ``pad_to`` tails and the
    ``seq.T`` staircase triangles that dominate padded workloads.

    Skipping is exact for finite targets free of ``-0.0`` entries:
    backends that multiply an identity plane through compute ``0*x``
    terms, which a NaN/inf target column turns into NaN and a ``-0.0``
    entry normalizes to ``+0.0`` — the skip leaves such values
    untouched instead.  Non-finite and negative-zero targets are
    therefore outside the bitwise bucketed==per-request contract.
    """
    live = ~((C == 1) & (S == 0) & (G < 0))          # (bs, J, K)
    any_live = live.any(axis=1)                       # (bs, K)
    first = jnp.argmax(live, axis=1).astype(jnp.int32)
    last = (live.shape[1] - 1
            - jnp.argmax(live[:, ::-1, :], axis=1)).astype(jnp.int32)
    starts = jnp.where(any_live, first, 0)
    counts = jnp.where(any_live, last - first + 1, 0)
    return starts.astype(jnp.int32), counts.astype(jnp.int32)


def count_live_planes(seq) -> int:
    """Concrete hull-plane count of one RotationSequence (test helper).

    Derived from :func:`wave_windows` itself so the plane-skip witness
    tests always assert against the kernel's actual liveness rule.
    """
    C = jnp.asarray(seq.cos)[None]
    S = jnp.asarray(seq.sin)[None]
    if seq.sign is not None:
        G = jnp.asarray(seq.sign)[None]
    else:
        G = jnp.full(C.shape, 1.0 if seq.reflect else -1.0, C.dtype)
    _, counts = wave_windows(C, S, G)
    return int(counts.sum())


def rot_sequence_batched(A, C, S, *, reflect: bool = False, G=None,
                         m_blk: int = 256, interpret: bool | None = None,
                         return_planes: bool = False):
    """Apply shared or per-request wave stacks to a batch of targets.

    One Pallas launch per call — the fused serving path.

    Args:
      A: targets ``(b, m, n)``, or a single ``(m, n)`` target.
      C, S: waves — shared ``(n-1, K)`` (every target gets the same
        sequence) or stacked ``(b, n-1, K)`` (per-request sequences).
      G: optional per-entry signs, matching ``C``'s shape; ``reflect``
        marks an all-reflector stack when ``G`` is ``None``.
      m_blk: target rows (lanes) per grid step.
      return_planes: also return the kernel's per-grid-step processed
        plane counts (the identity-skip witness used by tests).

    Returns:
      The rotated targets with ``A``'s shape (and the ``(b, R)`` int32
      plane counts when ``return_planes``).

    This host wrapper only adds obs accounting (launches, planes
    applied vs skipped, modeled bytes moved) around the jitted core —
    a no-op while obs is off or under tracing.
    """
    if obs.enabled() and not any(
            compat.is_tracer(x) for x in (A, C, S, G) if x is not None):
        _record_launch(A, C, S, G, reflect)
    return _rot_sequence_batched_jit(
        A, C, S, reflect=reflect, G=G, m_blk=m_blk, interpret=interpret,
        return_planes=return_planes)


def _record_launch(A, C, S, G, reflect: bool) -> None:
    # accounting runs on every obs-enabled launch of the serving hot
    # path, so the liveness hull is computed host-side in numpy: the
    # jnp formulation dispatches a dozen traced ops and syncs on
    # ``counts.sum()``, which costs more than the kernel itself at
    # serving batch sizes.  Same boolean rule as :func:`wave_windows`.
    b = int(A.shape[0]) if A.ndim == 3 else 1
    Cb = np.asarray(C)
    if Cb.ndim == 2:
        Cb = Cb[None]
    Sb = np.asarray(S).reshape(Cb.shape)
    if G is None:
        # reflect: g = +1 everywhere, so no plane passes the identity
        # test; plain: g = -1 everywhere, the test reduces to cos/sin
        live = np.ones(Cb.shape, bool) if reflect \
            else (Cb != 1) | (Sb != 0)
    else:
        Gb = np.asarray(G).reshape(Cb.shape)
        live = ~((Cb == 1) & (Sb == 0) & (Gb < 0))
    bs, J, K = Cb.shape
    any_live = live.any(axis=1)                       # (bs, K)
    first = live.argmax(axis=1)
    last = J - 1 - live[:, ::-1, :].argmax(axis=1)
    counts = np.where(any_live, last - first + 1, 0)
    # hull planes each target actually executes; shared waves (bs=1)
    # replay the same windows on every target
    applied = int(counts.sum()) * (b // bs)
    total = J * K * b
    itemsize = jnp.dtype(A.dtype).itemsize
    m = int(A.shape[-2]) if A.ndim == 3 else int(A.shape[0])
    n = int(A.shape[-1])
    moved = (2 * b * m * n + 3 * bs * J * K) * itemsize
    obs.inc("kernels.rotseq_batched.launches")
    obs.inc("kernels.rotseq_batched.planes_applied", applied)
    obs.inc("kernels.rotseq_batched.planes_skipped", total - applied)
    obs.inc("kernels.rotseq_batched.bytes_moved", int(moved))


@partial(
    jax.jit,
    static_argnames=("m_blk", "reflect", "interpret", "return_planes"),
)
def _rot_sequence_batched_jit(A, C, S, *, reflect: bool = False, G=None,
                              m_blk: int = 256,
                              interpret: bool | None = None,
                              return_planes: bool = False):
    if interpret is None:
        interpret = compat.pallas_interpret_default()
    # the whole launch, packing included, is the stream over the rows
    with jax.named_scope(SWEEP):
        single = A.ndim == 2
        if single:
            A = A[None]
        b, m, n = A.shape
        if C.ndim == 2:
            C = C[None]
            S = S[None]
            if G is not None:
                G = G[None]
        bs, J, K = C.shape
        assert J == n - 1, (C.shape, A.shape)
        assert bs in (1, b), (C.shape, A.shape)
        if G is None:
            G = jnp.full(C.shape, 1.0 if reflect else -1.0, C.dtype)
        starts, counts = wave_windows(C, S, G)

        # never tile (and pad) wider than the target: small serve-bucket
        # rows would otherwise pay m_blk lanes of identity work per plane
        # (multiples of 8 keep sublane alignment; use 128+ on hardware)
        m_blk = clamp_m_blk(m, m_blk)
        m_pad = round_up(m, m_blk)
        AT = jnp.pad(jnp.swapaxes(A, 1, 2),
                     ((0, 0), (0, 0), (0, m_pad - m)))
        out, planes = rotseq_batched_pallas(
            AT, C, S, G, starts, counts,
            m_blk=m_blk, interpret=interpret,
        )
        out = jnp.swapaxes(out[:, :, :m], 1, 2)
        if single:
            out = out[0]
        if return_planes:
            return out, planes
        return out
