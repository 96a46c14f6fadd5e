"""Batched rotation-application serving: plan once, apply many, at scale.

The paper's amortization argument — pack many rotations per memory pass
so the cost of touching ``A`` is paid once — extends across *requests*:
independent ``(sequence, target)`` problems of the same shape can share
one dispatch decision and one batched memory pass.  Ballard, Demmel &
Dumitriu make the system-scale version of this point for eigenproblems:
batching independent instances through one communication schedule is how
you approach the machine's bandwidth lower bound.

:class:`RotationService` is the serving-shaped realization (the async
continuous-batching engine in :mod:`repro.serve.stream` layers request
queues, deadlines, and double-buffered dispatch on top of the same
buckets):

* **shape-bucketed admission** — ``submit(seq, A)`` drops each request
  into a bucket keyed by ``(m, n, dtype, k_pad, signed)``.  Wave counts
  are :meth:`~repro.core.sequence.RotationSequence.pad_to`-normalized to
  the bucket's ``k_pad`` (next power of two — identity padding is an
  exact, bitwise no-op) so every drain presents one plan-cache-stable
  problem shape.
* **one frozen plan per bucket** — the first drain of a bucket resolves
  the registry exactly once (``seq.plan(like=..., batch=slots)``, so the
  cost model prices the *batched* problem: a batch-64 bucket can
  legitimately land on a different backend than a single request; a
  second service in the same process finds the plan in the registry's
  memo); every later drain rebinds the frozen
  :class:`~repro.core.sequence.SequencePlan` and calls the backend
  directly via :meth:`~repro.core.sequence.SequencePlan.apply_batched`.
* **slot padding + per-request unpadding** — partial drains are padded
  to the bucket's ``slots`` with identity requests (zero targets,
  identity waves) so the jitted batched computation sees one stable
  shape; results are sliced back out per ticket.  Queued sequences keep
  their sign structure *implicit* (no dense grid per request or pad
  slot); stacking broadcasts identity signs only for genuinely
  sign-carrying batches.
* **fused bucket execution** — when the bucket plan lands on a
  ``batch_via="fused"`` backend (the ``rotseq_batched`` kernel —
  ``method="auto"`` picks it on TPU, or pass
  ``method="rotseq_batched"``), the whole drain executes in **one**
  Pallas launch gridded over ``(batch, m-blocks)``, with per-wave
  ``valid_planes`` windows skipping the ``pad_to`` identity waves
  instead of multiplying them through; per-request vmap/loop execution
  stays as the fallback capability on every other backend.

Bitwise contract: per-request and bucketed execution are bit-identical
for plain-rotation sequences on every rotation-family backend
(``unoptimized`` / ``wavefront`` / ``blocked`` / ``rotseq_batched``),
for per-entry-sign sequences on the sign-capable family (``blocked``
and the fused kernel — the backends signed dispatch can reach), and —
new with the bit-stable reflector normalization — for **all-reflector**
sequences across the two: every path evaluates the canonical
``core.rotations.plane_update`` order with runtime sign arrays, so the
sign-grid normalization a signed bucket performs matches the scalar
``reflect`` path a lone request takes, to the last bit.  Only the
``accumulated``/MXU family (which reassociates rotations into GEMMs)
agrees to dtype accuracy rather than bitwise.  The contract assumes
finite targets without ``-0.0`` entries: the fused kernel's
identity-plane skipping leaves NaN/inf/-0.0 values untouched where a
multiplied-through ``0*x`` would poison or sign-normalize them.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from repro import obs

__all__ = ["RotationService", "BucketKey", "synthetic_stream"]

# canonical mixed-shape demo workload (>= 3 buckets by construction),
# shared by `repro.launch.serve --rotations` and the serving tests so
# their bucket counts track one definition
DEMO_SHAPES = ((16, 32, 8), (32, 32, 8), (16, 64, 12))


def synthetic_stream(n_requests: int, *, shapes=DEMO_SHAPES, seed: int = 0):
    """Seeded mixed-shape ``(sequence, target)`` request stream."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.rotations import random_sequence

    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_requests):
        m, n, k = shapes[i % len(shapes)]
        A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
        out.append((random_sequence(jax.random.key(seed + i), n, k), A))
    return out


def _next_pow2(x: int) -> int:
    return 1 << max(0, (max(1, x) - 1).bit_length())


# ``str(dtype)`` walks numpy's dtype-name machinery — measurable on the
# per-request admission path, so bucket keys use a memoized lookup
_DTYPE_NAMES: Dict = {}


def _dtype_name(dt) -> str:
    name = _DTYPE_NAMES.get(dt)
    if name is None:
        name = _DTYPE_NAMES[dt] = str(dt)
    return name


@dataclasses.dataclass(frozen=True)
class BucketKey:
    """Shape/dtype class of one admission bucket.

    Both the target dtype and the *wave* dtype participate: stacking
    float32 and float64 waves in one bucket would silently promote the
    whole batch and break the bitwise per-request contract.
    """
    m: int
    n: int
    dtype: str
    k_pad: int
    signed: bool
    wave_dtype: str


@dataclasses.dataclass
class _Pending:
    ticket: int
    seq: "object"   # pad_to/sign-normalized RotationSequence
    A: "object"
    # admission timestamp (obs.timing.now) — populated only while obs
    # is enabled, feeding the admit→drain latency histogram; None keeps
    # the disabled path allocation-identical
    admit_t: Optional[float] = None


class RotationService:
    """Shape-bucketed, batched rotation-application service.

    Args:
      slots: per-bucket batch capacity.  Admission auto-drains a bucket
        the moment it fills (fixed-slot semantics); partial
        drains are padded to ``slots`` with identity requests so the
        batched computation keeps one stable shape.
      method: dispatch method for bucket plans (``"auto"`` prices the
        *batched* problem through the registry cost model).
      pad_waves: normalize each request's wave count to the bucket's
        next-power-of-two ``k_pad`` (exact identity padding).  With
        ``False``, the raw wave count becomes part of the bucket key.
      min_k_pad: floor for ``k_pad`` (avoids one bucket per tiny k).
      mesh: optional ``jax.sharding.Mesh`` — bucket plans resolve
        through :func:`repro.dist.plan_sharded` (row-sharded batched
        drains; ``method="auto"`` arbitrates sharded vs replicated via
        the comm-extended cost model).
      row_axes: mesh axes bucket targets' rows shard over (with
        ``mesh``; default ``("data",)``).
      plan_kw: extra kwargs forwarded to ``RotationSequence.plan`` when
        a bucket is first resolved (e.g. explicit ``n_b``/``k_b``).
    """

    def __init__(self, *, slots: int = 8, method: str = "auto",
                 pad_waves: bool = True, min_k_pad: int = 4,
                 mesh=None, row_axes=("data",), **plan_kw):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        self.slots = int(slots)
        self.method = method
        self.pad_waves = bool(pad_waves)
        self.min_k_pad = int(min_k_pad)
        self.mesh = mesh
        self.row_axes = tuple(row_axes)
        self.plan_kw = dict(plan_kw)
        self._queues: Dict[BucketKey, List[_Pending]] = {}
        self._plans: Dict[BucketKey, "object"] = {}   # frozen SequencePlan
        self._results: Dict[int, "object"] = {}
        self._next_ticket = 0
        # "requests" counts *real* admissions only; "slots_executed" is
        # total batch slots run (real + identity pad) — keeping the two
        # separate is what stops pad slots inflating req/s accounting
        self.stats = {"requests": 0, "batches": 0, "plans_resolved": 0,
                      "padded_slots": 0, "padded_waves": 0,
                      "slots_executed": 0}

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        pending = sum(len(q) for q in self._queues.values())
        return (f"RotationService(slots={self.slots}, "
                f"buckets={len(self._queues)}, pending={pending}, "
                f"plans={len(self._plans)})")

    # -- admission ---------------------------------------------------------
    def _bucket_key(self, seq, A) -> BucketKey:
        m, n = A.shape
        if seq.n != n:
            raise ValueError(
                f"sequence on {seq.n} columns cannot serve a target with "
                f"{n} columns")
        k_pad = max(self.min_k_pad, _next_pow2(seq.k)) if self.pad_waves \
            else seq.k
        signed = seq.sign is not None or bool(seq.reflect)
        return BucketKey(m=int(m), n=int(n), dtype=_dtype_name(A.dtype),
                         k_pad=int(k_pad), signed=signed,
                         wave_dtype=_dtype_name(seq.dtype))

    def _normalize(self, seq, key: BucketKey):
        """pad_to the bucket wave count; sign structure stays implicit.

        Queued sequences keep their own sign representation — a plain
        (unsigned) sequence padded into a signed bucket is *not*
        materialized into a dense sign grid at admission
        (``pad_to`` keeps identity padding implicit; only genuine
        reflector sequences carry grids).  Batch stacking broadcasts
        implicit-identity signs lazily at drain time.
        """
        if seq.k < key.k_pad:
            self.stats["padded_waves"] += key.k_pad - seq.k
            seq = self._pad_concrete(seq, key.k_pad)
        return seq

    @staticmethod
    def _pad_concrete(seq, k_target: int):
        """Host-side identity padding for concrete unsigned sequences.

        ``pad_to`` issues traced concatenations per request — at serving
        volume (every admitted request of a padded bucket) that per-op
        dispatch dominates the batch period, so plain concrete
        sequences pad in numpy instead: identity waves are exact
        constants (``cos=1.0``, ``sin=0.0``), so the padded bytes are
        identical to ``pad_to``'s and the streamed-vs-sync bitwise
        contract is untouched.  Sign-carrying / reflector / traced
        sequences keep the canonical ``pad_to`` path (reflector padding
        must materialize a sign grid — see ``pad_to``).
        """
        from repro.core.sequence import RotationSequence

        from repro.compat import is_tracer

        if (seq.sign is not None or seq.reflect
                or is_tracer(seq.cos) or is_tracer(seq.sin)):
            return seq.pad_to(k_target)
        pad = k_target - seq.k
        planes = seq.cos.shape[0]
        live = seq.k_live if seq.k_live is not None else planes * seq.k
        cos = np.asarray(seq.cos)
        sin = np.asarray(seq.sin)
        cos = np.concatenate(
            [cos, np.ones((planes, pad), cos.dtype)], axis=1)
        sin = np.concatenate(
            [sin, np.zeros((planes, pad), sin.dtype)], axis=1)
        return RotationSequence(cos, sin, None, False, k_live=live)

    def submit(self, seq, A) -> int:
        """Admit one request; returns a ticket for :meth:`result`.

        A full bucket drains immediately (slot semantics); otherwise the
        request waits for :meth:`drain` / :meth:`result`.
        """
        import jax.numpy as jnp

        A = jnp.asarray(A)
        if A.ndim != 2:
            raise ValueError(f"targets must be 2D (m, n); got {A.shape}")
        with obs.span("admit"):
            key = self._bucket_key(seq, A)
            ticket = self._next_ticket
            self._next_ticket += 1
            self.stats["requests"] += 1
            obs.inc("serve.requests")
            admit_t = obs.timing.now() if obs.enabled() else None
            queue = self._queues.setdefault(key, [])
            queue.append(_Pending(ticket, self._normalize(seq, key), A,
                                  admit_t))
            obs.gauge("serve.queue_depth",
                      sum(len(q) for q in self._queues.values()))
        if len(queue) >= self.slots:
            self._drain_bucket(key)
        return ticket

    def apply_many(self, pairs) -> list:
        """Convenience: submit ``(seq, A)`` pairs, drain, return results
        in submission order."""
        tickets = [self.submit(seq, A) for seq, A in pairs]
        self.drain()
        return [self.result(t) for t in tickets]

    # -- execution ---------------------------------------------------------
    def drain(self) -> None:
        """Execute every non-empty bucket (partial batches padded)."""
        for key in list(self._queues):
            if self._queues[key]:
                self._drain_bucket(key)

    def result(self, ticket: int):
        """Return (and forget) one request's rotated target, draining
        its bucket if still pending."""
        if ticket not in self._results:
            self.drain()
        if ticket not in self._results:
            raise KeyError(f"unknown or already-collected ticket {ticket}")
        return self._results.pop(ticket)

    def _bucket_plan(self, key: BucketKey, rep_seq, like):
        """The bucket's frozen plan, resolved exactly once."""
        plan = self._plans.get(key)
        if plan is not None:
            return plan
        if self.mesh is not None:
            from repro import dist

            plan = dist.plan_sharded(rep_seq, like=like, mesh=self.mesh,
                                     row_axes=self.row_axes,
                                     method=self.method,
                                     shared_sequence=False,
                                     **self.plan_kw)
        else:
            # shared_sequence=False: a bucket batch carries one distinct
            # sequence per slot, so the registry prices per-sequence
            # setup × slots — the correction that lets method="auto"
            # avoid setup-heavy backends on serving traffic
            plan = rep_seq.plan(like=like, method=self.method,
                                batch=self.slots, shared_sequence=False,
                                **self.plan_kw)
        self.stats["plans_resolved"] += 1
        obs.inc("serve.plans_resolved")
        self._plans[key] = plan
        return plan

    def assemble_batch(self, key: BucketKey, seqs: list, targets: list):
        """Stack one bucket batch into the plan-cache-stable shape.

        Slot-pads ``seqs``/``targets`` (already ``_normalize``-d to the
        bucket's ``k_pad``) to ``self.slots`` with identity requests
        (zero targets, identity waves — implicit-identity signs even in
        signed buckets: the stack step broadcasts them, no dense grid
        per pad slot) and picks the planning representative.  Returns
        ``(seqs, A, rep, pad)`` where ``A`` is the ``(slots, m, n)``
        target stack.  Shared verbatim by the synchronous drain and the
        :mod:`repro.serve.stream` dispatcher — running one code path is
        what makes streamed results bit-equal to synchronous drains.
        """
        import jax.numpy as jnp

        from repro.core.sequence import RotationSequence

        if not seqs or len(seqs) > self.slots:
            raise ValueError(
                f"batch of {len(seqs)} requests for slots={self.slots}")
        pad = self.slots - len(seqs)
        if pad:  # identity requests keep the jitted shape slot-stable
            self.stats["padded_slots"] += pad
            ident = RotationSequence.identity(key.n, key.k_pad,
                                              dtype=seqs[0].dtype)
            zero = jnp.zeros((key.m, key.n), targets[0].dtype)
            seqs = seqs + [ident] * pad
            targets = targets + [zero] * pad
        # concrete targets stack host-side (one memcpy; same bytes) —
        # a traced jnp.stack over ``slots`` operands costs milliseconds
        # of pure dispatch at serving batch sizes
        from repro.compat import is_tracer
        if any(is_tracer(t) for t in targets):
            A = jnp.stack(targets)
        else:
            A = np.stack([np.asarray(t) for t in targets])
        # the planning representative carries the bucket's signature: a
        # signed bucket plans on a sign-carrying sequence even when the
        # first queued request is implicit
        rep = seqs[0].with_signs() if key.signed else seqs[0]
        return seqs, A, rep, pad

    def execute_batch(self, key: BucketKey, seqs: list, targets: list):
        """Plan (exactly once per bucket) and run one assembled batch.

        Returns ``(out, pad)`` — ``out`` is the ``(slots, m, n)`` result
        stack (slice ``out[i]`` per request; pad slots are garbage) and
        ``pad`` the identity-slot count.  Does *not* block on the device
        result: ``out`` is an asynchronously-dispatched value, which is
        what lets the stream dispatcher overlap the next batch's
        assembly with this batch's device execution.
        """
        n_live = len(seqs)
        seqs, A, rep, pad = self.assemble_batch(key, seqs, targets)
        plan = self._bucket_plan(key, rep, A)
        out = plan.apply_batched(A, sequences=seqs)
        self.stats["batches"] += 1
        self.stats["slots_executed"] += self.slots
        if obs.enabled():
            obs.inc("serve.batches")
            obs.inc("serve.slots_executed", self.slots)
            obs.inc("serve.pad_slots", pad)
            obs.gauge("serve.bucket_fill_ratio", n_live / self.slots)
            obs.gauge("serve.pad_slot_fraction",
                      self.stats["padded_slots"]
                      / max(1, self.stats["slots_executed"]))
        return out, pad

    def bucket_plan_estimate(self, key: BucketKey) -> Optional[float]:
        """§6-modeled seconds for one batched drain of ``key``'s bucket.

        ``None`` until the bucket has been planned (the stream engine's
        age-based close policy falls back to its floor target then).
        """
        plan = self._plans.get(key)
        if plan is None or plan.plan is None:
            return None
        est = float(plan.plan.est_seconds)
        return est if est > 0 else None

    def _drain_bucket(self, key: BucketKey) -> None:
        queue = self._queues.get(key, [])
        if not queue:
            return
        with obs.span("drain", m=key.m, n=key.n, k_pad=key.k_pad) as sp:
            batch, self._queues[key] = (queue[: self.slots],
                                        queue[self.slots:])
            out, pad = self.execute_batch(key, [p.seq for p in batch],
                                          [p.A for p in batch])
            sp.set(requests=len(batch), pad_slots=pad)
            if obs.enabled():
                done_t = obs.timing.now()
                for p in batch:
                    if p.admit_t is not None:
                        obs.observe("serve.request_latency_seconds",
                                    done_t - p.admit_t)
            for i, p in enumerate(batch):  # per-request unpadding
                self._results[p.ticket] = out[i]
            obs.gauge("serve.queue_depth",
                      sum(len(q) for q in self._queues.values()))
        if self._queues[key]:
            self._drain_bucket(key)
