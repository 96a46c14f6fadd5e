"""Backend registration + the raw-array compatibility entry point.

The idiomatic API lives in :mod:`repro.core.sequence`: build a
:class:`~repro.core.sequence.RotationSequence`, resolve it once with
``seq.plan(like=A)``, and apply the frozen plan many times.
``apply_rotation_sequence(A, C, S, method=...)`` below is the thin
back-compat wrapper over that machinery for callers still holding loose
``C``/``S`` arrays; ``method`` one of:

  ``unoptimized``   Algorithm 1.2 (paper baseline, jnp)
  ``wavefront``     Algorithm 1.3 (jnp)
  ``blocked``       blocked wavefront, host jnp (paper SS2/SS5)
  ``accumulated``   rs_gemm analogue: tile factors + GEMM sweeps
  ``pallas_wave``   Pallas VPU wavefront kernel (packed layout)
  ``pallas_mxu``    Pallas MXU accumulated kernel
  ``rotseq_batched`` fused multi-request Pallas kernel (one launch per
                    batch, identity planes skipped)
  ``auto``          registry cost model picks backend + (n_b, k_b, m_blk)
                    from problem shape/dtype/platform, memoized in
                    process per problem.

Each backend is registered below with a capability record (dtypes,
platforms, per-entry-sign support, shard_map compatibility, Pallas
requirements) and a cost model from the paper's SS6 memory-operation
analysis.  Explicit ``n_b``/``k_b``/``m_blk`` arguments always override
the planned tiles.

Deprecation policy: the raw-array kwargs that duplicate
``RotationSequence`` state (``G=`` per-entry signs) warn with
``DeprecationWarning`` and will be removed once external callers have
migrated; plain ``(A, C, S)`` positional calls remain supported as the
compatibility surface.  Internal ``src/repro`` code must construct
``RotationSequence`` objects instead — analyzer rule RA201 and the
``pytest.ini`` DeprecationWarning-to-error filter (scoped to warnings
originating from ``repro.*`` frames) enforce it.
"""
from __future__ import annotations

import warnings

from repro.core import registry
from repro.core.registry import BackendSpec, Capability, select_plan
from repro.core.sequence import RotationSequence

from .accumulate import rot_sequence_accumulated
from .blocked import rot_sequence_blocked
from .ref import rot_sequence_unoptimized, rot_sequence_wavefront

__all__ = ["apply_rotation_sequence", "METHODS", "select_plan"]


# --------------------------------------------------------------------------
# backend registration
# --------------------------------------------------------------------------

def _run_unoptimized(A, C, S, *, reflect=False, G=None, **kw):
    return rot_sequence_unoptimized(A, C, S, reflect=reflect, G=G)


def _run_wavefront(A, C, S, *, reflect=False, G=None, **kw):
    return rot_sequence_wavefront(A, C, S, reflect=reflect, G=G)


def _run_blocked(A, C, S, *, n_b=64, k_b=16, reflect=False, G=None, **kw):
    return rot_sequence_blocked(A, C, S, n_b=n_b, k_b=k_b, reflect=reflect,
                                G=G)


def _run_accumulated(A, C, S, *, n_b=64, k_b=16, reflect=False, G=None,
                     **kw):
    return rot_sequence_accumulated(A, C, S, n_b=n_b, k_b=k_b,
                                    reflect=reflect, G=G)


def _run_pallas_wave(A, C, S, *, n_b=64, k_b=16, reflect=False, G=None,
                     **kw):
    from repro.kernels.rotseq.ops import rot_sequence_wave
    return rot_sequence_wave(A, C, S, n_b=n_b, k_b=k_b, reflect=reflect,
                             G=G, **kw)


def _run_pallas_mxu(A, C, S, *, n_b=64, k_b=16, reflect=False, G=None,
                    **kw):
    from repro.kernels.rotseq_mxu.ops import rot_sequence_mxu
    return rot_sequence_mxu(A, C, S, n_b=n_b, k_b=k_b, reflect=reflect,
                            G=G, **kw)


def _run_rotseq_batched(A, C, S, *, m_blk=256, reflect=False, G=None,
                        n_b=None, k_b=None, **kw):
    # n_b/k_b are accepted (and ignored) so seed tile defaults from
    # named-method planning don't trip the fused kernel, which tiles
    # only over lanes (whole n stays VMEM-resident).
    from repro.kernels.rotseq_batched.ops import rot_sequence_batched
    return rot_sequence_batched(A, C, S, m_blk=m_blk, reflect=reflect,
                                G=G, **kw)


registry.register(BackendSpec(
    name="unoptimized",
    fn=_run_unoptimized,
    capability=Capability(supports_signs=False, supports_sharding=True),
    cost=registry.cost_unoptimized,
    candidates=registry.no_tiles,
    doc="Algorithm 1.2 reference: one rotation at a time, no blocking.",
))

registry.register(BackendSpec(
    name="wavefront",
    fn=_run_wavefront,
    capability=Capability(supports_signs=False, supports_sharding=True),
    cost=registry.cost_wavefront,
    candidates=registry.no_tiles,
    doc="Algorithm 1.3 wavefront order, unblocked.",
))

registry.register(BackendSpec(
    name="blocked",
    fn=_run_blocked,
    capability=Capability(supports_sharding=True, tile_min=(2, 1)),
    cost=registry.cost_blocked,
    candidates=registry.blocked_tiles,
    doc="Blocked wavefront (paper SS2/SS5), jnp scan over tiles.",
))

registry.register(BackendSpec(
    name="accumulated",
    fn=_run_accumulated,
    capability=Capability(supports_sharding=True, tile_min=(2, 1)),
    cost=registry.cost_accumulated,
    candidates=registry.accumulated_tiles,
    doc="rs_gemm analogue: accumulate tile factors, sweep as GEMMs.",
))

# Pallas kernels pad m to m_blk internally, so a shared-sequence batch
# still flattens to (b*m, n); per-request wave batches fall back to a
# per-element loop (supports_vmap=False) rather than vmapping pallas_call.
# Mosaic has no complex type: the kernels take real targets only.
registry.register(BackendSpec(
    name="pallas_wave",
    fn=_run_pallas_wave,
    capability=Capability(dtypes=registry.REAL_DTYPES, platforms=("tpu",),
                          tile_min=(2, 1),
                          needs_pallas=True, supports_vmap=False),
    cost=registry.cost_pallas_wave,
    candidates=registry.pallas_wave_tiles,
    doc="Pallas TPU VPU wavefront kernel (packed layout, VMEM carry).",
))

registry.register(BackendSpec(
    name="pallas_mxu",
    fn=_run_pallas_mxu,
    capability=Capability(dtypes=registry.REAL_DTYPES, platforms=("tpu",),
                          tile_min=(2, 1),
                          needs_pallas=True, supports_vmap=False),
    cost=registry.cost_pallas_mxu,
    candidates=registry.pallas_mxu_tiles,
    doc="Pallas TPU MXU accumulated kernel.",
))

# The fused multi-request kernel: one launch per serve bucket, grid over
# (batch, m-blocks), per-wave valid_planes windows skipping pad_to /
# seq.T identity padding.  batch_via="fused" makes apply_batched hand it
# the whole (b, m, n) stack (shared or per-request waves) in one call;
# per-request vmap/loop stays available as the fallback capability on
# every other backend.  supports_sharding: the launch is pure per-shard
# work (rows are independent under column-pair rotations), so
# repro.dist runs exactly one of these launches per shard_map shard.
registry.register(BackendSpec(
    name="rotseq_batched",
    fn=_run_rotseq_batched,
    capability=Capability(dtypes=registry.REAL_DTYPES, platforms=("tpu",),
                          tile_min=(2, 1), needs_pallas=True,
                          supports_vmap=False, supports_sharding=True,
                          batch_via="fused"),
    cost=registry.cost_rotseq_batched,
    candidates=registry.rotseq_batched_tiles,
    doc="Fused multi-request Pallas kernel (one launch per bucket, "
        "identity planes skipped).",
))

METHODS = registry.registered_methods()


# --------------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------------

def apply_rotation_sequence(A, C, S, *, method: str = "accumulated",
                            n_b: int | None = None, k_b: int | None = None,
                            reflect: bool = False, G=None, **kw):
    """Apply the rotation sequence ``(C, S)`` to ``A`` from the right.

    Back-compat wrapper: wraps the loose arrays in a
    :class:`~repro.core.sequence.RotationSequence` and executes one
    freshly resolved :class:`~repro.core.sequence.SequencePlan`.
    ``method="auto"`` consults the registry (capability filter, SS6 cost
    model, per-problem plan memo); a named
    ``method`` keeps the seed behaviour (tiled backends default to
    ``n_b=64, k_b=16``).  Empty sequences (``n < 2`` or ``k < 1``) are
    the identity under *every* method.

    Prefer the typed API for new code — especially for repeated
    applications, where ``seq.plan(like=A)`` amortizes dispatch:

    ======================================  ==================================
    raw-array call                          RotationSequence API
    ======================================  ==================================
    ``apply_rotation_sequence(A, C, S)``    ``seq.apply(A)``
    ``..., G=G)``                           ``RotationSequence(C, S, sign=G)``
    ``..., reflect=True)``                  ``RotationSequence(C, S, reflect=True)``
    ``..., method=..., n_b=..., k_b=...)``  ``seq.plan(like=A, method=..., ...)``
    per-call dispatch                       ``plan.apply(A)`` (plan once)
    ======================================  ==================================

    Autodiff note: this wrapper calls the planned backend *directly*, so
    it keeps the seed's native JAX differentiation semantics — including
    gradients w.r.t. ``C``/``S`` through the pure-jnp backends.  The
    typed ``plan.apply`` instead uses the transposed-sequence
    ``custom_vjp`` (exact and cheap w.r.t. ``A``; the sequence is a
    constant there — see :mod:`repro.core.sequence`).
    """
    if G is not None:
        warnings.warn(
            "apply_rotation_sequence(G=...) with a raw per-entry sign "
            "array is deprecated; construct "
            "RotationSequence(C, S, sign=G) and use seq.apply / "
            "seq.plan(...).apply instead",
            DeprecationWarning, stacklevel=2)
    seq = RotationSequence(C, S, G, reflect)
    platform = kw.pop("platform", None)
    sharded = kw.pop("sharded", False)
    plan = seq.plan(like=A, method=method,
                    platform=platform, sharded=sharded,
                    n_b=n_b, k_b=k_b, **kw)
    return plan.apply_direct(A)
