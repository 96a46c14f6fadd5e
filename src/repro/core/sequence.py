"""First-class rotation sequences: plan-once / apply-many.

The paper's central object is not a matrix but a *sequence of planar
rotations* — recorded once in the packed ``(n-1, K)`` C/S wave layout,
then applied many times with blocked or accumulated kernels.  This
module makes that object a real type:

* :class:`RotationSequence` — a frozen dataclass holding ``cos``/``sin``
  waves, an optional per-entry ``sign`` array (mixed rotation/reflector
  sequences, paper SS8.4), and a ``reflect`` flag.  It is registered as
  a JAX **pytree**, so sequences pass through ``jit``/``vmap``/``grad``
  and ``shard_map`` like any array.  Constructors
  (:meth:`~RotationSequence.from_waves`,
  :meth:`~RotationSequence.from_pairs`,
  :meth:`~RotationSequence.identity`) validate the wave layout and can
  repair ``c^2 + s^2 = 1`` drift.

* **Composition semantics** — ``seq.T`` is the exact inverse (reversed
  waves, transposed planes), ``seq1 @ seq2`` concatenates along ``K``
  ("apply seq1, then seq2"), ``seq[i:j]`` slices waves, and
  :meth:`~RotationSequence.pad_to` identity-pads to a target ``K`` so
  repeated applications present plan-cache-stable shapes.

* **Two-phase execution** — ``plan = seq.plan(like=A)`` resolves the
  backend registry *once* (capability filter + SS6 cost model + plan
  memo) into a frozen :class:`SequencePlan`;
  ``plan.apply(A)`` then calls the chosen backend directly, with no
  registry lookup on the hot path.  ``seq.apply(A)`` is the one-shot
  convenience composing both.

* **Autodiff** — application is linear in ``A``, so its VJP is exactly
  one application of the *transposed* sequence: ``custom_vjp`` on the
  planned apply makes ``jax.grad`` work through any backend (including
  Pallas kernels) at the cost of one extra sequence application — no
  unrolled rotation tape.  The sequence itself is treated as a constant
  (its cotangents are symbolically zero); differentiate the *recording*
  step instead if you need angle gradients.

Transpose math: one plane transform is ``M(c, s, g) = [[c, g s], [s,
-g c]]`` acting on columns ``(j, j+1)`` (``g = -1`` rotation, ``g = +1``
reflector).  ``M^T = M(c, g s, g)`` on the *same* column pair, so the
inverse applies the per-plane transposes in reversed total order; that
order re-packs into the wave-major layout as an anti-diagonal staircase
(see :attr:`RotationSequence.T`), the same pipelining trick the eig
recorders use for their descending elimination sweeps.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat, obs
from repro.core import registry

__all__ = ["RotationSequence", "SequencePlan", "PLAN_DICT_FORMAT",
           "planned_apply", "planned_apply_batched", "planned_run",
           "stack_request_waves"]


# sign value of the unified update ``y' = g * (s x - c y)``
_ROT = -1.0      # plain rotation (identity padding is a no-op)
_REFL = 1.0      # 2x2 reflector (paper SS8.4)

# relative drift of c^2 + s^2 (in ulps of the wave dtype) above which
# from_waves(normalize="auto") renormalizes an entry (exact pairs pass
# through bit-for-bit)
_DRIFT_ULPS = 64


def _ensure_backends() -> None:
    """Planning needs the backend registry populated (api.py does it)."""
    import repro.core.api  # noqa: F401  (import side effect: registration)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class RotationSequence:
    """A sequence of ``(n-1) * k`` planar rotations in the paper's layout.

    ``cos``/``sin`` have shape ``(n-1, k)``: entry ``(j, p)`` acts on
    columns ``(j, j+1)`` of the target during wave ``p`` (wave-major
    order, ascending ``j`` within a wave).  ``sign`` is an optional
    per-entry array mixing rotations (``-1``) and 2x2 reflectors
    (``+1``); ``reflect=True`` marks an all-reflector sequence without
    materializing the array.

    ``k_live`` is an optional *static* upper bound on the number of
    non-identity planes in the grid (``None`` = unknown, assume dense).
    Identity-padding constructors maintain it — ``pad_to`` preserves the
    pre-padding bound, ``seq.T`` carries the original plane count
    through the anti-diagonal staircase, ``identity`` is 0 — so the
    planner can route padded/staircase sequences to plane-skipping
    backends (``rotseq_batched``) whose cost scales with live planes
    rather than the padded grid.

    Registered as a JAX pytree: ``cos``/``sin``/``sign`` are children,
    ``reflect`` and ``k_live`` are static aux data.
    """

    cos: Any
    sin: Any
    sign: Any = None
    reflect: bool = False
    k_live: Optional[int] = None

    def __post_init__(self):
        # complex Givens rotations need a conjugate-aware plane update,
        # which no applier has: refuse them rather than apply a
        # non-unitary transform (real waves on a complex target are fine)
        for x in (self.cos, self.sin):
            if getattr(getattr(x, "dtype", None), "kind", None) == "c":
                raise TypeError(
                    f"complex Givens rotations are not supported: the "
                    f"waves are {x.dtype}; cos and sin must be real "
                    f"(they may be applied to a complex target)")

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.cos, self.sin, self.sign), (self.reflect, self.k_live)

    @classmethod
    def tree_unflatten(cls, aux, children):
        cos, sin, sign = children
        reflect = aux[0]
        k_live = aux[1] if len(aux) > 1 else None
        return cls(cos, sin, sign, reflect, k_live)

    # -- shape / dtype -----------------------------------------------------
    @property
    def n(self) -> int:
        """Width of a compatible target matrix (``planes + 1``)."""
        return self.cos.shape[0] + 1

    @property
    def k(self) -> int:
        """Number of waves."""
        return self.cos.shape[1]

    @property
    def shape(self) -> Tuple[int, int]:
        return tuple(self.cos.shape)

    @property
    def dtype(self):
        return self.cos.dtype

    def __repr__(self) -> str:
        return (f"RotationSequence(n={self.n}, k={self.k}, "
                f"dtype={getattr(self.cos, 'dtype', '?')}, "
                f"sign={'per-entry' if self.sign is not None else None}, "
                f"reflect={self.reflect})")

    # -- constructors ------------------------------------------------------
    @classmethod
    def from_waves(cls, cos, sin, sign=None, *, reflect: bool = False,
                   normalize: str | bool = "auto") -> "RotationSequence":
        """Build from ``(n-1, k)`` wave arrays, validating the layout.

        ``normalize``: ``"auto"`` (default) renormalizes only entries
        whose ``c^2 + s^2`` drifts from 1 by more than ~64 ulp — exact
        pairs pass through bit-for-bit; ``True`` always divides by
        ``hypot(c, s)``; ``False`` stores the arrays untouched.
        """
        cos = jnp.asarray(cos)
        sin = jnp.asarray(sin)
        if cos.ndim != 2:
            raise ValueError(f"waves must be 2D (n-1, k), got {cos.shape}")
        if cos.shape != sin.shape:
            raise ValueError(
                f"cos/sin shape mismatch: {cos.shape} vs {sin.shape}")
        if sign is not None:
            sign = jnp.asarray(sign)
            if sign.shape != cos.shape:
                raise ValueError(
                    f"sign shape {sign.shape} != wave shape {cos.shape}")
        if normalize == "auto":
            r2 = cos * cos + sin * sin
            tol = _DRIFT_ULPS * jnp.finfo(
                r2.dtype if jnp.issubdtype(r2.dtype, jnp.floating)
                else jnp.float32).eps
            drift = jnp.abs(r2 - 1.0) > jnp.asarray(tol, r2.dtype)
            r = jnp.sqrt(jnp.where(r2 > 0, r2, 1.0))
            # a (0, 0) pair has no direction to rescale: repair it to the
            # identity rotation, like normalize=True does
            cos = jnp.where(drift, jnp.where(r2 > 0, cos / r, 1.0), cos)
            sin = jnp.where(drift, jnp.where(r2 > 0, sin / r, 0.0), sin)
        elif normalize:
            r = jnp.hypot(cos, sin)
            safe = r > 0
            rs = jnp.where(safe, r, 1.0)
            cos = jnp.where(safe, cos / rs, 1.0)
            sin = jnp.where(safe, sin / rs, 0.0)
        return cls(cos, sin, sign, reflect)

    @classmethod
    def from_pairs(cls, waves, *, reflect: bool = False) -> "RotationSequence":
        """Build from an iterable of per-wave columns.

        Each element is ``(c, s)`` or ``(c, s, g)`` with 1D arrays of a
        common length ``n-1``; waves are stacked along ``K`` in order.
        ``g`` columns may be ``None`` (all-rotation wave); if any wave
        carries signs the missing ones are filled with rotations.
        """
        waves = list(waves)
        if not waves:
            raise ValueError("from_pairs needs at least one wave; use "
                             "RotationSequence.identity for an empty one")
        cs, ss, gs = [], [], []
        for w in waves:
            c, s, g = (*w, None) if len(w) == 2 else w
            c = jnp.asarray(c).reshape(-1)
            s = jnp.asarray(s).reshape(-1)
            cs.append(c)
            ss.append(s)
            gs.append(None if g is None else jnp.asarray(g).reshape(-1))
        planes = cs[0].shape[0]
        for c, s in zip(cs, ss):
            if c.shape[0] != planes or s.shape[0] != planes:
                raise ValueError(
                    f"inconsistent wave lengths: {c.shape[0]} vs {planes}")
        sign = None
        if any(g is not None for g in gs):
            fill = jnp.full((planes,), _REFL if reflect else _ROT,
                            cs[0].dtype)
            sign = jnp.stack([fill if g is None else g for g in gs], axis=1)
        return cls.from_waves(jnp.stack(cs, axis=1), jnp.stack(ss, axis=1),
                              sign, reflect=reflect, normalize=False)

    @classmethod
    def identity(cls, n: int, k: int, dtype=jnp.float32) -> "RotationSequence":
        """``k`` identity waves on ``n`` columns (exact no-op)."""
        return cls(jnp.ones((n - 1, k), dtype), jnp.zeros((n - 1, k), dtype),
                   k_live=0)

    # -- composition -------------------------------------------------------
    @property
    def T(self) -> "RotationSequence":
        """The inverse sequence: ``seq.T.apply(seq.apply(A)) == A`` in
        exact arithmetic.

        ``Q^T`` is the product of per-plane transposes (``M(c, s, g)^T =
        M(c, g s, g)`` — each staying on its own column pair ``(j,
        j+1)``) in *reversed* total order.  Reversed wave-major order
        means descending ``j`` within descending ``p``, which re-packs
        into the wave-major layout as an anti-diagonal staircase: the
        rotation from ``(j, p)`` lands in wave ``q = (n-2-j) +
        (k-1-p)``, giving an ``(n-1, n+k-2)`` grid with identity
        padding off the staircase (``seq.T.T`` therefore applies the
        same transform as ``seq``, identity-padded wider).

        The result carries ``k_live``: the staircase holds exactly the
        original ``(n-1) * k`` planes (or the original bound if one was
        already known), so plane-skipping backends apply it at the cost
        of the *original* sequence, not the padded grid.
        """
        c_t, s_t, g_t, refl_t = _transpose_waves(
            self.cos, self.sin, self.sign, self.reflect)
        J, k = self.cos.shape
        live = self.k_live if self.k_live is not None else J * k
        return RotationSequence(c_t, s_t, g_t, refl_t, k_live=live)

    def __matmul__(self, other: "RotationSequence") -> "RotationSequence":
        """Concatenate along ``K``: applying ``seq1 @ seq2`` equals
        applying ``seq1`` then ``seq2`` (``A @ Q1 @ Q2``)."""
        if not isinstance(other, RotationSequence):
            return NotImplemented
        if self.cos.shape[0] != other.cos.shape[0]:
            raise ValueError(
                f"cannot compose sequences on {self.n} and {other.n} columns")
        cos = jnp.concatenate([self.cos, other.cos], axis=1)
        sin = jnp.concatenate([self.sin, other.sin], axis=1)
        live = None
        if self.k_live is not None and other.k_live is not None:
            live = self.k_live + other.k_live
        if (self.sign is None and other.sign is None
                and self.reflect == other.reflect):
            return RotationSequence(cos, sin, None, self.reflect,
                                    k_live=live)
        return RotationSequence(
            cos, sin,
            jnp.concatenate([self._sign_array(), other._sign_array()],
                            axis=1),
            False, k_live=live)

    def __getitem__(self, idx) -> "RotationSequence":
        """Wave slicing: ``seq[i:j]`` keeps waves ``i..j-1``."""
        if not isinstance(idx, slice):
            raise TypeError(
                "RotationSequence supports wave *slices* only (seq[i:j]); "
                "a single wave is seq[p:p+1]")
        return RotationSequence(
            self.cos[:, idx], self.sin[:, idx],
            None if self.sign is None else self.sign[:, idx], self.reflect)

    def pad_to(self, k_target: int) -> "RotationSequence":
        """Identity-pad to ``k_target`` waves (plan-cache-stable shapes).

        Padding waves are exact no-op *rotations*.  A plain (unsigned)
        sequence stays plain — padding into a signed serve bucket must
        not materialize a dense sign grid; the batch-stacking step
        broadcasts an implicit-identity sign lazily when (and only
        when) a genuinely sign-carrying batch needs one.  An
        all-reflector sequence is the exception and materializes its
        ``sign`` array (a padded reflector would not be a no-op — det
        is -1).  The pre-padding live-plane bound is preserved so the
        planner can skip the padding it just added.
        """
        pad = k_target - self.k
        if pad < 0:
            raise ValueError(f"cannot pad {self.k} waves down to {k_target}")
        if pad == 0:
            return self
        planes = self.cos.shape[0]
        live = self.k_live if self.k_live is not None else planes * self.k
        cos = jnp.concatenate(
            [self.cos, jnp.ones((planes, pad), self.cos.dtype)], axis=1)
        sin = jnp.concatenate(
            [self.sin, jnp.zeros((planes, pad), self.sin.dtype)], axis=1)
        if self.sign is None and not self.reflect:
            return RotationSequence(cos, sin, None, False, k_live=live)
        sign = jnp.concatenate(
            [self._sign_array(),
             jnp.full((planes, pad), _ROT, self.cos.dtype)], axis=1)
        return RotationSequence(cos, sin, sign, False, k_live=live)

    def _sign_array(self):
        """Per-entry sign array (``reflect`` folded in), built on demand.

        Implicit signs materialize *here*, not at admission: queued
        sequences keep ``sign=None`` and only the consumer that
        genuinely needs a grid (batch stacking of a sign-carrying
        bucket, transposition of a reflector) pays for one — and only
        at that moment.  (Under eager execution the broadcast still
        commits a device buffer; the saving is that implicit sequences
        sitting in queues or pad slots never do.)
        """
        if self.sign is not None:
            return self.sign
        return jnp.broadcast_to(
            jnp.asarray(_REFL if self.reflect else _ROT, self.cos.dtype),
            self.cos.shape)

    def with_signs(self) -> "RotationSequence":
        """Per-entry-sign normal form: ``sign`` materialized, ``reflect``
        folded in — for callers that need every sequence in a batch to
        present the same pytree structure.  (The serving path no longer
        calls this at admission: plain sequences stay implicit in the
        bucket queue and are sign-broadcast at stack time.)"""
        if self.sign is not None:
            return self
        return RotationSequence(self.cos, self.sin, self._sign_array(),
                                False, k_live=self.k_live)

    # -- serialization -----------------------------------------------------
    def to_dict(self) -> dict:
        """JSON-serializable dict (wave arrays as nested lists).

        Intended for small recorded sequences (request replay); large
        waves belong in array checkpoints.
        """
        import numpy as np

        return {
            "cos": np.asarray(self.cos).tolist(),
            "sin": np.asarray(self.sin).tolist(),
            "sign": None if self.sign is None
            else np.asarray(self.sign).tolist(),
            "reflect": bool(self.reflect),
            "dtype": str(self.dtype),
            "k_live": self.k_live,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "RotationSequence":
        """Inverse of :meth:`to_dict` (waves pass through bit-for-bit)."""
        import numpy as np

        dtype = jnp.dtype(d.get("dtype", "float32"))
        cos = jnp.asarray(np.asarray(d["cos"], dtype))
        sin = jnp.asarray(np.asarray(d["sin"], dtype))
        sign = d.get("sign")
        if sign is not None:
            sign = jnp.asarray(np.asarray(sign, dtype))
        seq = cls.from_waves(cos, sin, sign,
                             reflect=bool(d.get("reflect", False)),
                             normalize=False)
        k_live = d.get("k_live")
        if k_live is not None:
            seq = dataclasses.replace(seq, k_live=int(k_live))
        return seq

    # -- execution ---------------------------------------------------------
    def plan(self, like=None, *, m: Optional[int] = None,
             method: str = "auto",
             platform: Optional[str] = None, sharded: bool = False,
             batch: Optional[int] = None, shared_sequence: bool = True,
             n_b: Optional[int] = None, k_b: Optional[int] = None,
             **kw) -> "SequencePlan":
        """Resolve the registry once into a frozen :class:`SequencePlan`.

        ``like`` (an array or ShapeDtypeStruct) supplies the target row
        count and dtype; ``m`` overrides the row count.  A 3D ``like``
        (``(b, m, n)``, a batched target for :meth:`SequencePlan.
        apply_batched`) supplies the batch count too; ``batch``
        overrides it.  ``shared_sequence=False`` declares the batch
        *per-request* — each target will carry its own sequence via
        ``apply_batched(A, sequences=...)`` — which prices per-sequence
        setup × b and can plan onto a different backend than the same
        batch sharing one sequence (docs/cost-model.md).
        ``method="auto"`` runs capability filtering + the
        SS6 cost model through the per-problem plan memo — batch-aware,
        so a batch-64 bucket can plan onto a
        different backend than a single request; a named method keeps
        the seed defaults (``n_b=64, k_b=16`` for tiled backends; on the
        chip a Pallas kernel takes its cheapest compilable tile, see
        ``registry.pinned_tiles``).
        Explicit ``n_b``/``k_b`` always override the planned tiles.
        """
        _ensure_backends()
        # imported here: accumulate's kernel imports this module
        from repro.core.accumulate import record_factor_schedule

        like_shape = getattr(like, "shape", None)
        if like_shape is not None and len(like_shape) == 3:
            if batch is None:
                batch = like_shape[0]
            if m is None:
                m = like_shape[1]
        if m is None:
            m = like_shape[0] if like_shape is not None else max(self.n, 1)
        batch = 1 if batch is None else max(1, int(batch))
        dtype = getattr(like, "dtype", None) or self.dtype
        target = str(jnp.dtype(dtype))
        n, k = self.n, self.k
        if method != "auto":
            # validate the method name + sign capability even when the
            # sequence is empty, so typos never silently "succeed"
            spec = registry.get_backend(method)  # raises on unknown
            if self.sign is not None and not spec.capability.supports_signs:
                raise ValueError(
                    f"method {method!r} does not support per-entry signs; "
                    f"use a blocked-family backend")
            if target in registry.COMPLEX_DTYPES \
                    and target not in spec.capability.dtypes:
                raise ValueError(
                    f"method {method!r} cannot apply to a {target} "
                    f"target; one of {spec.capability.dtypes}")
        if n < 2 or k < 1 or m < 1:
            return SequencePlan(self, _IDENTITY, (), None)

        if method == "auto":
            with obs.span("plan", m=m, n=n, k=k, batch=batch,
                          dtype=target) as sp:
                plan = registry.select_plan(
                    m, n, k, dtype=dtype, platform=platform,
                    signs=self.sign is not None, sharded=sharded,
                    batch=batch, shared_sequence=shared_sequence,
                    live_planes=self.k_live)
                sp.set(method=plan.method)
            planned = plan.kwargs()
            if n_b is not None:
                planned["n_b"] = n_b
            if k_b is not None:
                planned["k_b"] = k_b
            planned.update(kw)
            record_factor_schedule(plan.method, planned, n, k, dtype)
            return SequencePlan(self, plan.method,
                                tuple(sorted(planned.items())), plan)

        tiles = registry.pinned_tiles(method, registry.Problem(
            m=m, n=n, k=k, dtype=str(jnp.dtype(dtype)),
            platform=platform or compat.default_platform(),
            signs=self.sign is not None, batch=batch,
            shared_sequence=shared_sequence, live_planes=self.k_live))
        if n_b is not None:
            tiles["n_b"] = n_b
        if k_b is not None:
            tiles["k_b"] = k_b
        planned = {**tiles, **kw}
        record_factor_schedule(method, planned, n, k, dtype)
        return SequencePlan(self, method, tuple(sorted(planned.items())),
                            None)

    def apply(self, A, *, method: str = "auto", **kw):
        """One-shot convenience: ``seq.plan(like=A, ...).apply(A)``.

        For repeated applications at a fixed shape, hold the plan — that
        is the whole point of the two-phase API.
        """
        return self.plan(like=A, method=method, **kw).apply(A)


# sentinel backend name for degenerate (zero-rotation) plans
_IDENTITY = "identity"


@dataclasses.dataclass(frozen=True, eq=False)
class SequencePlan:
    """A frozen dispatch decision bound to one :class:`RotationSequence`.

    ``apply(A)`` calls the resolved backend directly — no registry
    lookup, no plan-cache probe — and is differentiable w.r.t. ``A``
    (``custom_vjp``: the cotangent is one application of the transposed
    sequence).  Rebind the same decision to fresh waves of the same
    shape with :meth:`rebind` (the delayed-buffer hot path).
    """

    sequence: RotationSequence
    method: str
    kwargs: Tuple[Tuple[str, Any], ...]
    plan: Optional[registry.Plan] = None

    def __repr__(self) -> str:
        return (f"SequencePlan(method={self.method!r}, "
                f"kwargs={dict(self.kwargs)}, seq={self.sequence!r})")

    def apply(self, A):
        """Apply the planned sequence: ``A <- A @ Q`` on the hot path.

        Differentiable w.r.t. ``A`` through every backend via the
        transposed-sequence ``custom_vjp``; the sequence arrays are
        treated as constants (zero cotangents).  Use
        :meth:`apply_direct` for the backend's native JAX autodiff.

        Backward-pass cost: ``seq.T`` re-packs ``k`` waves into an
        ``n + k - 2``-wave staircase, so one VJP costs roughly
        ``(n + k) / k`` forward applications — cheap for wide recordings
        (``k >~ n``), noticeable for small ``k``; prefer
        :meth:`apply_direct` for grad-heavy small-``k`` jnp workloads
        (a padding-free transpose kernel is a ROADMAP item).
        """
        self._check_target(A)
        if self.method == _IDENTITY:
            return A
        seq = self.sequence
        if compat.is_tracer(A):
            return _apply_planned(self.method, self.kwargs, seq.reflect,
                                  A, seq.cos, seq.sin, seq.sign)
        with obs.span("apply", method=self.method, m=int(A.shape[0]),
                      n=int(A.shape[1]), dtype=str(A.dtype)):
            out = _apply_planned(self.method, self.kwargs, seq.reflect,
                                 A, seq.cos, seq.sin, seq.sign)
        self._count_apply(A)
        return out

    __call__ = apply

    def apply_direct(self, A):
        """Apply via the backend with no ``custom_vjp`` wrapping.

        Differentiation (where the backend supports it — the pure-jnp
        family) goes through the actual computation, so gradients
        w.r.t. the wave arrays are exact rather than symbolically zero.
        The compat wrapper ``apply_rotation_sequence`` uses this path to
        preserve the seed's autodiff semantics.
        """
        self._check_target(A)
        if self.method == _IDENTITY:
            return A
        seq = self.sequence
        if compat.is_tracer(A):
            return _run_backend(self.method, self.kwargs, seq.reflect,
                                A, seq.cos, seq.sin, seq.sign)
        with obs.span("apply", method=self.method, direct=True,
                      dtype=str(A.dtype)):
            out = _run_backend(self.method, self.kwargs, seq.reflect,
                               A, seq.cos, seq.sin, seq.sign)
        self._count_apply(A)
        return out

    def apply_batched(self, A, sequences=None, *, direct: bool = False):
        """Apply to a batch of targets ``A`` of shape ``(b, m, n)``.

        With ``sequences=None`` the plan's own sequence is applied to
        every batch element.  Rotations act row-wise, so most backends
        execute the *flattened* ``(b*m, n)`` problem — bit-identical to
        ``b`` separate :meth:`apply` calls; backends whose capability
        says ``batch_via="vmap"`` are mapped over the leading axis
        instead.

        With ``sequences`` (an iterable of ``b`` :class:`RotationSequence`
        objects of the plan's wave shape) each batch element gets its
        own waves — the serving path's shape-bucketed execution.
        Backends whose capability says ``batch_via="fused"`` (the
        ``rotseq_batched`` kernel) take the whole stack in **one
        launch**; otherwise the backend is ``jax.vmap``-ed over
        ``(A, cos, sin[, sign])`` where its capability allows
        (bit-identical to per-request application for the pure-jnp
        backends) and looped per element as the last resort.

        Sign structure: when the plan's sequence carries per-entry
        signs, batch members may be plain rotation sequences — their
        implicit-identity sign is broadcast at stack time, never
        materialized per request (bucket admission keeps queues
        implicit).  A signed member under an unsigned plan still
        raises, since the planned backend was not capability-checked
        for signs.

        Autodiff mirrors the single-target pair :meth:`apply` /
        :meth:`apply_direct` uniformly across every strategy:
        ``direct=False`` (default) differentiates w.r.t. ``A`` through
        the transposed-sequence ``custom_vjp`` (wave cotangents are
        symbolic zeros); ``direct=True`` calls the backend with its
        native JAX autodiff semantics.
        """
        A = jnp.asarray(A)
        if A.ndim != 3:
            raise ValueError(
                f"apply_batched expects A of shape (b, m, n); "
                f"got {A.shape} — use apply() for a single target")
        if self.method == _IDENTITY:
            return A
        if compat.is_tracer(A):
            return self._apply_batched_impl(A, sequences, direct)
        with obs.span("apply_batched", method=self.method,
                      batch=int(A.shape[0]), m=int(A.shape[1]),
                      n=int(A.shape[2]), dtype=str(A.dtype)):
            out = self._apply_batched_impl(A, sequences, direct)
        self._count_apply(A)
        return out

    def _apply_batched_impl(self, A, sequences, direct: bool):
        seq = self.sequence
        b, m, n = A.shape
        if n != seq.n:
            raise ValueError(
                f"plan built for n={seq.n} targets; got A.shape={A.shape}")
        run = _run_backend if direct else _apply_planned
        run_fused = _run_backend if direct else _apply_planned_batched
        cap = registry.get_backend(self.method).capability
        if sequences is None:
            if cap.batch_via == "fused":
                return run_fused(self.method, self.kwargs, seq.reflect,
                                 A, seq.cos, seq.sin, seq.sign)
            if cap.batch_via == "flatten":
                out = run(self.method, self.kwargs, seq.reflect,
                          A.reshape(b * m, n), seq.cos, seq.sin, seq.sign)
                return out.reshape(b, m, n)
            return jax.vmap(
                lambda Ai: run(self.method, self.kwargs, seq.reflect,
                               Ai, seq.cos, seq.sin, seq.sign))(A)

        seqs = list(sequences)
        if len(seqs) != b:
            raise ValueError(
                f"{len(seqs)} sequences for a batch of {b} targets")
        plan_signed = seq.sign is not None
        for s in seqs:
            if not isinstance(s, RotationSequence):
                raise TypeError(f"expected RotationSequence, got {type(s)}")
            if tuple(s.shape) != tuple(seq.shape):
                raise ValueError(
                    f"sequence shape {s.shape} != plan shape {seq.shape}; "
                    f"pad_to a bucket-stable wave count first")
            if plan_signed:
                continue  # any structure coerces to the sign grid below
            if s.sign is not None or s.reflect != seq.reflect:
                raise ValueError(
                    "mixed sign/reflect structure in one batch; plan the "
                    "bucket on a sign-carrying representative (or "
                    "normalize with RotationSequence.with_signs()) first")
        C, S, G = _stack_waves(seqs, plan_signed)
        if cap.batch_via == "fused":
            return run_fused(self.method, self.kwargs, seq.reflect,
                             A, C, S, G)
        if cap.supports_vmap:
            in_axes = (0, 0, 0, None if G is None else 0)
            return jax.vmap(
                lambda Ai, Ci, Si, Gi: run(
                    self.method, self.kwargs, seq.reflect, Ai, Ci, Si, Gi),
                in_axes=in_axes)(A, C, S, G)
        return jnp.stack([
            run(self.method, self.kwargs, seq.reflect,
                A[i], C[i], S[i], None if G is None else G[i])
            for i in range(b)])

    def _check_target(self, A):
        if self.method == _IDENTITY:
            return
        if A.ndim != 2 or A.shape[1] != self.sequence.n:
            raise ValueError(
                f"plan built for n={self.sequence.n} targets; "
                f"got A.shape={A.shape}")

    def _count_apply(self, A) -> None:
        """Count one concrete application, in all and per backend, and
        in ``accumulate.complex_views`` where the GEMM sweep ran on a
        complex target's real view."""
        if obs.enabled():
            obs.inc("sequence.applies")
            obs.inc(f"sequence.applies.{self.method}")
            if self.method == "accumulated" and jnp.iscomplexobj(A):
                obs.inc("accumulate.complex_views")

    def rebind(self, sequence: RotationSequence) -> "SequencePlan":
        """Bind this (method, tiles) decision to a new same-shape sequence."""
        old = self.sequence
        if sequence.shape != old.shape:
            raise ValueError(
                f"rebind needs matching wave shape {old.shape}; "
                f"got {sequence.shape}")
        if (sequence.sign is None) != (old.sign is None) and \
                self.method != _IDENTITY:
            spec = registry.get_backend(self.method)
            if sequence.sign is not None and \
                    not spec.capability.supports_signs:
                raise ValueError(
                    f"plan method {self.method!r} cannot carry per-entry "
                    f"signs; re-plan the sign-carrying sequence")
        return dataclasses.replace(self, sequence=sequence)

    # -- serialization -----------------------------------------------------
    def to_dict(self) -> dict:
        """Serialize the *dispatch decision* (not the waves) to JSON.

        The dict captures everything another process needs to skip
        planning: backend method, resolved kwargs, the registry
        :class:`~repro.core.registry.Plan` record, and the wave
        shape/dtype/sign signature the decision was made for.  It is
        keyed to the running JAX version; :meth:`from_dict` rejects
        stale or mismatched entries.
        """
        seq = self.sequence
        d = {
            "format": PLAN_DICT_FORMAT,
            "jax": registry._jax_version_str(),
            "method": self.method,
            "kwargs": dict(self.kwargs),
            "shape": list(seq.shape),
            "dtype": str(seq.dtype),
            "signed": seq.sign is not None,
            "reflect": bool(seq.reflect),
        }
        if self.plan is not None:
            d["plan"] = {"method": self.plan.method, "n_b": self.plan.n_b,
                         "k_b": self.plan.k_b, "m_blk": self.plan.m_blk,
                         "est_seconds": self.plan.est_seconds,
                         "source": self.plan.source}
        return d

    @classmethod
    def from_dict(cls, d: dict, sequence: RotationSequence) -> "SequencePlan":
        """Rebuild a frozen plan from :meth:`to_dict`, bound to ``sequence``.

        Raises ``ValueError`` when the entry is unusable: unknown
        format, different JAX version, wave-shape/dtype/sign mismatch
        with ``sequence``, or a backend that is no longer registered.
        Callers should treat the error as a miss and re-plan.
        """
        _ensure_backends()
        if d.get("format") != PLAN_DICT_FORMAT:
            raise ValueError(
                f"unsupported SequencePlan dict format {d.get('format')!r}")
        jax_now = registry._jax_version_str()
        if d.get("jax") != jax_now:
            raise ValueError(
                f"plan serialized under JAX {d.get('jax')!r}; running "
                f"{jax_now} — re-plan")
        if tuple(d.get("shape", ())) != tuple(sequence.shape):
            raise ValueError(
                f"plan serialized for wave shape {d.get('shape')}; "
                f"sequence has {sequence.shape}")
        if d.get("signed", False) != (sequence.sign is not None) \
                or d.get("reflect", False) != bool(sequence.reflect):
            raise ValueError(
                "plan serialized for a different sign/reflect structure")
        if d.get("dtype") != str(sequence.dtype):
            raise ValueError(
                f"plan serialized for dtype {d.get('dtype')!r}; "
                f"sequence is {sequence.dtype}")
        method = d["method"]
        if method != _IDENTITY:
            spec = registry.get_backend(method)  # raises on unknown
            if sequence.sign is not None \
                    and not spec.capability.supports_signs:
                raise ValueError(
                    f"serialized method {method!r} cannot carry signs")
        kwargs = tuple(sorted(d.get("kwargs", {}).items()))
        plan = None
        pd = d.get("plan")
        if pd is not None:
            plan = registry.Plan(
                method=str(pd.get("method", method)), n_b=pd.get("n_b"),
                k_b=pd.get("k_b"), m_blk=pd.get("m_blk"),
                est_seconds=float(pd.get("est_seconds", 0.0)),
                source=str(pd.get("source", "model")))
        return cls(sequence, method, kwargs, plan)


# JSON format version of SequencePlan.to_dict (bump on layout change)
PLAN_DICT_FORMAT = 1


# --------------------------------------------------------------------------
# planned application with a transposed-sequence VJP
# --------------------------------------------------------------------------

def _transpose_waves(cos, sin, sign, reflect: bool):
    """Anti-diagonal staircase repack of one ``(n-1, k)`` wave grid.

    The pure-function core of :attr:`RotationSequence.T` (vmapped by
    the batched VJP over per-request stacks).  Returns
    ``(c_t, s_t, g_t, reflect_t)`` where ``g_t`` is ``None`` for plain
    rotation inputs and a materialized sign grid otherwise (identity
    padding off the staircase must stay a rotation no-op).
    """
    J, k = cos.shape
    if sign is None:
        s_signed = sin if reflect else -sin
    else:
        s_signed = jnp.where(sign > 0, sin, -sin)
    j = jnp.arange(J)[:, None]
    q = jnp.arange(J + k - 1)[None, :]
    p_idx = (J - 1 - j) + (k - 1) - q
    valid = (p_idx >= 0) & (p_idx < k)
    pc = jnp.clip(p_idx, 0, k - 1)
    jb = jnp.broadcast_to(j, pc.shape)
    c_t = jnp.where(valid, cos[jb, pc], jnp.ones((), cos.dtype))
    s_t = jnp.where(valid, s_signed[jb, pc], jnp.zeros((), sin.dtype))
    g_t = None
    if sign is not None:
        g_t = jnp.where(valid, sign[jb, pc], jnp.asarray(_ROT, sign.dtype))
    elif reflect:
        # identity padding must stay a rotation no-op (a padded
        # reflector has det -1), so materialize the sign grid
        g_t = jnp.where(valid, jnp.asarray(_REFL, cos.dtype),
                        jnp.asarray(_ROT, cos.dtype))
    return c_t, s_t, g_t, (False if g_t is not None else reflect)


def _stack_waves(seqs, plan_signed: bool):
    """Stack per-request waves into ``(b, planes, k)`` batch arrays.

    On the concrete (serving) path the stack happens in **numpy** — one
    memcpy per array instead of one traced ``jnp.stack`` op over ``b``
    operands, which dominates the per-batch host time at serving batch
    sizes.  The bytes are identical either way (stacking reorders
    storage, never values), so the streamed-vs-synchronous bitwise
    contract is untouched; the batch arrays convert to device buffers
    once at the backend call boundary.  Traced leaves (a transformed
    caller) keep the ``jnp.stack`` path.
    """
    leaves = [x for s in seqs for x in (s.cos, s.sin, s.sign)
              if x is not None]
    if any(compat.is_tracer(x) for x in leaves):
        C = jnp.stack([s.cos for s in seqs])
        S = jnp.stack([s.sin for s in seqs])
        G = None if not plan_signed \
            else jnp.stack([s._sign_array() for s in seqs])
        return C, S, G
    C = np.stack([np.asarray(s.cos) for s in seqs])
    S = np.stack([np.asarray(s.sin) for s in seqs])
    G = None if not plan_signed \
        else np.stack([np.asarray(s._sign_array()) for s in seqs])
    return C, S, G


def _run_backend(method: str, kwargs: Tuple[Tuple[str, Any], ...],
                 reflect: bool, A, C, S, G):
    spec = registry.get_backend(method)
    return spec.fn(A, C, S, reflect=reflect, G=G, **dict(kwargs))


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _apply_planned(method, kwargs, reflect, A, C, S, G):
    return _run_backend(method, kwargs, reflect, A, C, S, G)


def _apply_planned_fwd(method, kwargs, reflect, A, C, S, G):
    out = _run_backend(method, kwargs, reflect, A, C, S, G)
    return out, (C, S, G)


def _apply_planned_bwd(method, kwargs, reflect, residuals, dY):
    C, S, G = residuals
    seq_t = RotationSequence(C, S, G, reflect).T
    bwd_method, bwd_kwargs = method, kwargs
    if seq_t.sign is not None and \
            not registry.get_backend(method).capability.supports_signs:
        # transposing an all-reflector sequence materializes a mixed
        # sign grid; route the cotangent through the blocked family
        bwd_method, bwd_kwargs = "blocked", tuple(
            (key, val) for key, val in kwargs if key in ("n_b", "k_b"))
    dA = _run_backend(bwd_method, bwd_kwargs, seq_t.reflect,
                      dY, seq_t.cos, seq_t.sin, seq_t.sign)
    # The sequence is a constant of the application (symbolic-zero
    # cotangents): exact angle gradients would need the rotation tape.
    return (dA, jnp.zeros_like(C), jnp.zeros_like(S),
            None if G is None else jnp.zeros_like(G))


_apply_planned.defvjp(_apply_planned_fwd, _apply_planned_bwd)


# --------------------------------------------------------------------------
# fused batched application (batch_via="fused" backends) with the same
# transposed-sequence VJP semantics as the per-target path
# --------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _apply_planned_batched(method, kwargs, reflect, A, C, S, G):
    return _run_backend(method, kwargs, reflect, A, C, S, G)


def _apply_planned_batched_fwd(method, kwargs, reflect, A, C, S, G):
    out = _run_backend(method, kwargs, reflect, A, C, S, G)
    return out, (C, S, G)


def _apply_planned_batched_bwd(method, kwargs, reflect, residuals, dY):
    C, S, G = residuals
    if C.ndim == 2:
        c_t, s_t, g_t, refl_t = _transpose_waves(C, S, G, reflect)
    elif G is None and not reflect:
        # plain rotation stacks transpose to plain rotation staircases
        tw = lambda c, s: _transpose_waves(c, s, None, False)[:2]
        c_t, s_t = jax.vmap(tw)(C, S)
        g_t, refl_t = None, False
    else:
        # sign-carrying (or all-reflector) stacks materialize the
        # transposed sign grid per request; g_t presence is static in
        # (G, reflect), so the vmap output structure is uniform
        if G is None:
            tw = lambda c, s: _transpose_waves(c, s, None, True)[:3]
            c_t, s_t, g_t = jax.vmap(tw)(C, S)
        else:
            tw = lambda c, s, g: _transpose_waves(c, s, g, reflect)[:3]
            c_t, s_t, g_t = jax.vmap(tw)(C, S, G)
        refl_t = False
    # fused backends declare supports_signs (capability-checked at
    # registration); no blocked reroute is needed here, unlike the
    # reflect-through-unblocked case in _apply_planned_bwd
    dA = _run_backend(method, kwargs, refl_t, dY, c_t, s_t, g_t)
    return (dA, jnp.zeros_like(C), jnp.zeros_like(S),
            None if G is None else jnp.zeros_like(G))


_apply_planned_batched.defvjp(_apply_planned_batched_fwd,
                              _apply_planned_batched_bwd)


# --------------------------------------------------------------------------
# shard-local execution hooks (repro.dist)
# --------------------------------------------------------------------------
#
# ``repro.dist`` executes shard-local work through the exact same
# planned ``custom_vjp`` pair as the single-device paths — called from
# *inside* ``shard_map``, so gradients flow shard-locally into the
# transposed-sequence VJP with zero extra collectives (rotations act on
# column pairs; row shards differentiate independently).  These are the
# sanctioned planned-execution entry points for the dist layer, which
# never imports kernel modules directly (analyzer rule RA206).

def planned_apply(method, kwargs, reflect, A, C, S, G):
    """Planned single-target application (``custom_vjp`` w.r.t. ``A``).

    ``method``/``kwargs``/``reflect`` are the static fields of a
    resolved :class:`SequencePlan`; ``A`` is a ``(m, n)`` target and
    ``C``/``S``/``G`` the ``(n-1, k)`` wave arrays (``G`` may be
    ``None``).
    """
    return _apply_planned(method, kwargs, reflect, A, C, S, G)


def planned_apply_batched(method, kwargs, reflect, A, C, S, G):
    """Planned fused batched application (``custom_vjp`` w.r.t. ``A``).

    ``A`` is ``(b, m, n)``; waves are shared ``(n-1, k)`` or stacked
    ``(b, n-1, k)`` per-request grids (see :func:`stack_request_waves`).
    """
    return _apply_planned_batched(method, kwargs, reflect, A, C, S, G)


def planned_run(method, kwargs, reflect, A, C, S, G):
    """Planned application with the backend's *native* autodiff.

    The shard-local analogue of :meth:`SequencePlan.apply_direct` — no
    ``custom_vjp`` wrapping, so gradients w.r.t. the wave arrays go
    through the actual computation where the backend supports it.
    """
    return _run_backend(method, kwargs, reflect, A, C, S, G)


def stack_request_waves(seqs, plan_signed: bool):
    """Stack ``b`` per-request sequences into ``(b, n-1, k)`` wave arrays.

    The public face of the serving path's stacker for out-of-package
    batched executors (``repro.dist``): numpy memcpy on the concrete
    path, ``jnp.stack`` under tracing, implicit-identity signs
    broadcast only when ``plan_signed``.
    """
    return _stack_waves(seqs, plan_signed)
