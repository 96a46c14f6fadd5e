"""Delayed application of recorded rotation waves (paper SS5.1).

The eigensolvers in this package *generate* rotations one scalar at a
time (bulge chasing is inherently sequential) but *apply* them to the
eigen/singular-vector accumulators in bulk: a
:class:`DelayedRotationBuffer` holds the accumulator matrix and queues
recorded waves until ``k_delay`` of them are pending, then flushes the
whole batch as one :class:`~repro.core.sequence.RotationSequence`
through a **cached** :class:`~repro.core.sequence.SequencePlan` — the
registry (capability filter + cost model + plan memo) is consulted on
the *first* flush only; every later flush
rebinds the frozen plan to the fresh waves and calls the chosen backend
directly.  This converts the accumulation flops from ``K`` rank-2
column updates into ``K / k_delay`` blocked/accumulated (or Pallas)
applications — the paper's "delayed sequences of rotations" use case —
and makes plan-once/apply-many the structural invariant rather than a
cache accident.

Partial final batches are identity-padded
(:meth:`~repro.core.sequence.RotationSequence.pad_to`; ``c=1, s=0`` is
an *exact* no-op, the same trick the blocked appliers use for wavefront
triangles) so every flush presents the same ``(n-1, k_delay)`` problem
shape and reuses the same frozen plan.
"""
from __future__ import annotations

import numpy as np

from repro import obs

__all__ = ["DelayedRotationBuffer"]


class DelayedRotationBuffer:
    """Accumulate ``M <- M @ G_wave`` lazily, flushing every ``k_delay``.

    Args:
      M: initial accumulator ``(m, n)`` (e.g. an identity basis), or a
        *batched* accumulator ``(b, m, n)`` — ``b`` independent bases
        sharing every pushed wave, flushed in one batched application
        (:meth:`~repro.core.sequence.SequencePlan.apply_batched`; exact
        per slice, since rotations act row-wise).
      k_delay: waves buffered per flush (the SS5.1 delay depth).
      method: dispatch method for the flush; ``"auto"`` consults the
        registry cost model + plan cache (once — see ``plan``).
      mesh: optional ``jax.sharding.Mesh`` — flushes resolve a
        row-sharded :class:`~repro.dist.ShardedSequencePlan` via
        :func:`repro.dist.plan_sharded` instead of a replicated
        ``SequencePlan`` (distributed eigenvector accumulation; the
        comm-extended cost model arbitrates sharded vs replicated under
        ``method="auto"``).
      row_axes: mesh axes the accumulator's rows shard over (with
        ``mesh``; default ``("data",)``).
      apply_kw: extra plan kwargs (e.g. explicit ``n_b``/``k_b``
        overrides) forwarded to ``RotationSequence.plan``.
    """

    def __init__(self, M, *, k_delay: int = 32, method: str = "auto",
                 pad_flush: bool = True,
                 mesh=None, row_axes=("data",), **apply_kw):
        import jax.numpy as jnp

        if k_delay < 1:
            raise ValueError(f"k_delay must be >= 1, got {k_delay}")
        self._M = jnp.asarray(M)
        if self._M.ndim not in (2, 3):
            raise ValueError(
                f"accumulator must be 2D (m, n) or batched 3D (b, m, n), "
                f"got {self._M.shape}")
        self.k_delay = int(k_delay)
        self.method = method
        self.mesh = mesh
        self.row_axes = tuple(row_axes)
        self.pad_flush = bool(pad_flush)
        self.apply_kw = dict(apply_kw)
        self.planes = self._M.shape[-1] - 1
        self.flushes = 0
        self.waves_pushed = 0
        self._c: list = []
        self._s: list = []
        self._g: list = []  # per-wave sign columns; None = all-rotation
        # frozen SequencePlan per flush signature (k_padded, signs) —
        # resolved once, rebound to fresh waves on every later flush
        self._plans: dict = {}

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (f"DelayedRotationBuffer(shape={tuple(self._M.shape)}, "
                f"pending={len(self._c)}/{self.k_delay}, "
                f"flushes={self.flushes}, method={self.method!r})")

    @property
    def pending(self) -> int:
        return len(self._c)

    def push(self, c, s, g=None) -> "DelayedRotationBuffer":
        """Queue one wave (``(n-1,)`` cos/sin, optional sign column)."""
        c = np.asarray(c, np.float64).reshape(-1)
        s = np.asarray(s, np.float64).reshape(-1)
        if c.shape[0] != self.planes or s.shape[0] != self.planes:
            raise ValueError(
                f"wave has {c.shape[0]} planes; accumulator with "
                f"{self._M.shape[-1]} columns needs {self.planes}")
        self._c.append(c)
        self._s.append(s)
        self._g.append(None if g is None
                       else np.asarray(g, np.float64).reshape(-1))
        self.waves_pushed += 1
        if len(self._c) >= self.k_delay:
            self.flush()
        return self

    def push_sequence(self, seq, S=None, G=None) -> "DelayedRotationBuffer":
        """Queue every wave of a :class:`RotationSequence` in order.

        The legacy raw-array form ``push_sequence(C, S[, G])`` is still
        accepted but deprecated — wrap the waves in a
        ``RotationSequence`` instead.
        """
        from repro.core.sequence import RotationSequence

        if not isinstance(seq, RotationSequence):
            import warnings

            warnings.warn(
                "push_sequence(C, S) with raw wave arrays is deprecated; "
                "push a RotationSequence instead",
                DeprecationWarning, stacklevel=2)
            seq = RotationSequence(np.asarray(seq), np.asarray(S),
                                   None if G is None else np.asarray(G))
        C = np.asarray(seq.cos)
        S_ = np.asarray(seq.sin)
        G_ = None if seq.sign is None else np.asarray(seq.sign)
        if G_ is None and seq.reflect:
            G_ = np.ones(C.shape, np.float64)
        for p in range(C.shape[1]):
            self.push(C[:, p], S_[:, p], None if G_ is None else G_[:, p])
        return self

    def _pending_sequence(self):
        """Pending waves as one RotationSequence, identity-padded to the
        flush shape (``(n-1, k_delay)``) when ``pad_flush`` is on."""
        from repro.core.sequence import RotationSequence

        k = len(self._c)
        C = np.stack(self._c, 1)
        S = np.stack(self._s, 1)
        G = None
        if any(g is not None for g in self._g):
            G = np.full((self.planes, k), -1.0, np.float64)
            for p, g in enumerate(self._g):
                if g is not None:
                    G[:, p] = g
        dt = self._M.dtype
        seq = RotationSequence(C.astype(dt), S.astype(dt),
                               None if G is None else G.astype(dt))
        if self.pad_flush and k < self.k_delay:
            seq = seq.pad_to(self.k_delay)
        return seq

    def flush(self):
        """Apply all pending waves through the cached frozen plan."""
        if self._c:
            waves = len(self._c)
            with obs.span("flush", waves=waves, planes=self.planes):
                seq = self._pending_sequence()
                plan_key = (seq.k, seq.sign is not None)
                plan = self._plans.get(plan_key)
                if plan is None:
                    # a batched accumulator applies ONE pending sequence
                    # to every basis in the (b, m, n) stack — a
                    # shared-sequence batch (explicit, so the registry
                    # amortizes per-sequence setup instead of pricing it
                    # per basis like a serving bucket)
                    if self.mesh is not None:
                        from repro import dist

                        plan = dist.plan_sharded(
                            seq, like=self._M, mesh=self.mesh,
                            row_axes=self.row_axes, method=self.method,
                            shared_sequence=True, **self.apply_kw)
                    else:
                        plan = seq.plan(like=self._M, method=self.method,
                                        shared_sequence=True,
                                        **self.apply_kw)
                    self._plans[plan_key] = plan
                else:
                    with obs.span("rebind"):
                        plan = plan.rebind(seq)
                # host-driven accumulation is never differentiated
                # through; the direct paths skip the custom_vjp wrapper
                # (and keep the backend's native autodiff semantics if
                # anyone ever does).  A batched accumulator flushes all
                # b bases through one batched application of the same
                # frozen plan.
                if self._M.ndim == 3:
                    self._M = plan.apply_batched(self._M, direct=True)
                elif self.mesh is not None:
                    # ShardedSequencePlan spells direct as a kwarg
                    self._M = plan.apply(self._M, direct=True)
                else:
                    self._M = plan.apply_direct(self._M)
                self._c.clear()
                self._s.clear()
                self._g.clear()
                self.flushes += 1
            obs.inc("eig.flushes")
            obs.observe("eig.waves_per_flush", waves, unit="waves")
        return self._M

    @property
    def value(self):
        """Flush any pending waves and return the accumulator."""
        return self.flush()
