"""repro.serve.stream — async continuous-batching rotation serving.

:class:`StreamEngine` puts two daemon threads (scheduler + dispatcher)
around a depth-1 handoff queue on top of
:class:`~repro.serve.rotations.RotationService`'s shape buckets:
``submit()`` admits requests without touching JAX, buckets close on an
adaptive size-or-age policy priced by the §6 cost model, and closed
batches execute through the exact synchronous batch path — so streamed
results are bit-equal to a synchronous drain while host assembly
double-buffers against device execution.  Buckets are per-request
batches (one sequence per slot), so their plans are priced with
``shared_sequence=False`` — the serving-aware cost model that lets
``method="auto"`` run streaming workloads unpinned.  Backpressure
(``block``/``fail``/``shed``), deadlines, and every counter are
explicit; analyzer rule RA204 confines thread/queue primitives to this
module.

The full design — bucket lifecycle, plan reuse, backpressure and
deadline semantics, close policy — is documented in
``docs/serving.md``; ``docs/architecture.md`` places this module in the
registry → sequence → serve → stream layer diagram, and
``docs/cost-model.md`` derives the per-request bucket pricing.
"""
from __future__ import annotations

import math
import queue
import threading
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.serve.rotations import BucketKey, RotationService

__all__ = ["StreamEngine", "StreamTicket", "Backpressure",
           "DeadlineExceeded", "EngineClosed"]


class Backpressure(RuntimeError):
    """The global pending budget is full and the policy rejects."""


class DeadlineExceeded(RuntimeError):
    """The request was shed because its deadline passed while queued."""


class EngineClosed(RuntimeError):
    """The engine stopped before this request could be served."""


# serializes lazy Event creation across racing result() waiters; held
# for pointer reads/stores only, never while waiting
_TICKET_EVENT_LOCK = threading.Lock()


class StreamTicket:
    """Future-like handle for one streamed request.

    ``result()`` blocks until the dispatcher fulfills (or fails) the
    ticket and returns the rotated target — an asynchronously-dispatched
    JAX value; materialize with ``jax.block_until_ready`` if you need
    the wall-clock cost on your thread.
    """

    __slots__ = ("key", "seq", "A", "admit_t", "deadline_t",
                 "_event", "_done", "_value", "_error")

    def __init__(self, key: BucketKey, seq, A, admit_t: float,
                 deadline_t: Optional[float]):
        self.key = key
        self.seq = seq
        self.A = A
        self.admit_t = admit_t
        self.deadline_t = deadline_t
        # the Event is lazy: allocating one per admitted request costs
        # more than the rest of the admission path combined, and a
        # caller that polls done() / collects after close never waits.
        # result() materializes it on first use; the CPython-atomic
        # attribute stores below keep the handoff safe (see _fulfill).
        self._event: Optional[threading.Event] = None
        self._done = False
        self._value = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._done

    def result(self, timeout: Optional[float] = None):
        """The rotated target (blocks until fulfilled).

        Raises :class:`DeadlineExceeded` if the request was shed,
        :class:`EngineClosed` if the engine stopped without draining,
        ``TimeoutError`` if ``timeout`` elapses first.
        """
        if not self._done:
            ev = self._event
            if ev is None:
                with _TICKET_EVENT_LOCK:  # one event even with racing waiters
                    ev = self._event
                    if ev is None:
                        ev = self._event = threading.Event()
            # re-check after publishing the event: a fulfill that raced
            # the store above either saw the event (and set it) or
            # finished first (then _done is already visible)
            if not self._done and not ev.wait(timeout):
                raise TimeoutError(
                    "streamed result not ready within timeout")
        if self._error is not None:
            raise self._error
        return self._value

    # -- dispatcher/scheduler side ----------------------------------------
    def _fulfill(self, value) -> None:
        self._value = value
        self.seq = self.A = None  # drop request payload references
        self._done = True
        ev = self._event  # read after _done is visible (GIL ordering)
        if ev is not None:
            ev.set()

    def _fail(self, error: BaseException) -> None:
        self._error = error
        self.seq = self.A = None
        self._done = True
        ev = self._event
        if ev is not None:
            ev.set()


# one closed batch on its way to the dispatcher
_Batch = Tuple[BucketKey, List[StreamTicket], str]


class StreamEngine:
    """Async continuous-batching engine over ``RotationService`` buckets.

    Args:
      service: the bucket/plan substrate to execute through.  ``None``
        builds a private ``RotationService(slots=slots, **service_kw)``.
        Whatever is passed must not be driven synchronously while the
        engine runs — the dispatcher thread owns its plan/stat state.
      slots: per-bucket batch capacity (ignored when ``service`` given).
      max_pending: bounded global budget of queued-but-undispatched
        requests; ``submit()`` applies ``backpressure`` once it is full.
      backpressure: ``"block"`` | ``"fail"`` | ``"shed"`` (see module
        docstring).
      age_factor: age-close target = ``age_factor`` × the bucket plan's
        §6-modeled batch seconds (a bucket whose batch costs t to run
        is worth holding open ~``age_factor``·t for better fill).
      min_age_s / max_age_s: clamp for the age target; ``min_age_s`` is
        also the cold-bucket target before the first plan resolution.
      max_burst: cap on consecutive batch closes one bucket gets per
        round-robin visit.
      start: spawn the scheduler/dispatcher threads immediately
        (``False`` lets tests exercise admission policies inertly).
      service_kw: forwarded to the private ``RotationService`` (e.g.
        ``method=...``, ``pad_waves=False``, or ``mesh=``/``row_axes=``
        for row-sharded bucket execution via :mod:`repro.dist`).
    """

    def __init__(self, service: Optional[RotationService] = None, *,
                 slots: int = 8, max_pending: int = 256,
                 backpressure: str = "block", age_factor: float = 8.0,
                 min_age_s: float = 0.002, max_age_s: float = 0.25,
                 max_burst: int = 4, start: bool = True, **service_kw):
        if backpressure not in ("block", "fail", "shed"):
            raise ValueError(f"unknown backpressure policy {backpressure!r}")
        if max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        if service is not None and service_kw:
            raise ValueError("pass service_kw only without an explicit "
                             "service")
        self.service = service if service is not None \
            else RotationService(slots=slots, **service_kw)
        self.slots = self.service.slots
        self.max_pending = int(max_pending)
        self.backpressure = backpressure
        self.age_factor = float(age_factor)
        self.min_age_s = float(min_age_s)
        self.max_age_s = float(max_age_s)
        self.max_burst = max(1, int(max_burst))

        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)   # scheduler wakeups
        self._space = threading.Condition(self._lock)  # budget waiters
        self._buckets: Dict[BucketKey, Deque[StreamTicket]] = {}
        self._ring: List[BucketKey] = []   # round-robin visit order
        self._ring_idx = 0
        self._bursts: Dict[BucketKey, int] = {}  # consecutive closes/visit
        self._pending = 0
        self._closing = False
        self._stopped = threading.Event()
        # depth-1 handoff: at most one closed batch waits while the
        # dispatcher executes the previous one — the double buffer
        self._handoff: "queue.Queue[Optional[_Batch]]" = queue.Queue(1)
        self.stats = {"submitted": 0, "completed": 0, "shed": 0,
                      "rejected": 0, "closes_size": 0, "closes_age": 0,
                      "closes_drain": 0}
        self._scheduler: Optional[threading.Thread] = None
        self._dispatcher: Optional[threading.Thread] = None
        if start:
            self.start()

    # ------------------------------------------------------------ admission
    def submit(self, seq, A, *, deadline_s: Optional[float] = None
               ) -> StreamTicket:
        """Admit one request; returns a :class:`StreamTicket`.

        ``deadline_s`` is a relative latency budget: under the
        ``"shed"`` policy a request whose deadline passes while still
        queued may be dropped (its ticket raises
        :class:`DeadlineExceeded`) to make room for new admissions.
        """
        if not hasattr(A, "ndim"):  # lists/tuples; arrays pass untouched
            import jax.numpy as jnp

            A = jnp.asarray(A)
        if A.ndim != 2:
            raise ValueError(f"targets must be 2D (m, n); got {A.shape}")
        key = self.service._bucket_key(seq, A)
        now = obs.timing.now()
        ticket = StreamTicket(key, seq, A, now,
                              None if deadline_s is None
                              else now + float(deadline_s))
        with self._lock:
            if self._closing:
                raise EngineClosed("submit() after close()")
            while self._pending >= self.max_pending:
                if self.backpressure == "shed":
                    self._shed_expired_locked()
                    if self._pending < self.max_pending:
                        break
                if self.backpressure in ("fail", "shed"):
                    self.stats["rejected"] += 1
                    obs.inc("serve.stream.rejected")
                    raise Backpressure(
                        f"{self._pending} pending >= budget "
                        f"{self.max_pending} (policy={self.backpressure})")
                obs.inc("serve.stream.block_waits")  # block: wait for room
                self._space.wait()
                if self._closing:
                    raise EngineClosed("engine closed while blocked on "
                                       "the pending budget")
            q = self._buckets.get(key)
            if q is None:
                q = self._buckets[key] = deque()
                self._ring.append(key)
            q.append(ticket)
            self._pending += 1
            self.stats["submitted"] += 1
            obs.inc("serve.stream.submitted")
            # wake the scheduler only on a state change it can act on —
            # the bucket crossing the size threshold, or its first
            # pending request (arms the age timer).  Notifying every
            # submit makes admission and the scheduler ping-pong the
            # lock, and that contention caps the sustainable admit rate
            # (the pending gauge moves to close/shed time for the same
            # reason).
            if len(q) >= self.slots or len(q) == 1:
                self._wake.notify()
        return ticket

    def _shed_expired_locked(self) -> int:
        """Drop queued requests whose deadline has passed; returns count."""
        now = obs.timing.now()
        shed = 0
        for q in self._buckets.values():
            kept = [t for t in q
                    if t.deadline_t is None or t.deadline_t > now]
            if len(kept) != len(q):
                for t in q:
                    if t.deadline_t is not None and t.deadline_t <= now:
                        t._fail(DeadlineExceeded(
                            f"deadline passed while queued "
                            f"(budget {t.deadline_t - t.admit_t:.4f}s)"))
                        shed += 1
                q.clear()
                q.extend(kept)
        if shed:
            self._pending -= shed
            self.stats["shed"] += shed
            obs.inc("serve.stream.shed", shed)
            obs.gauge("serve.stream.pending", self._pending)
            self._space.notify_all()
        return shed

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "StreamEngine":
        if self._scheduler is not None:
            return self
        self._scheduler = threading.Thread(
            target=self._scheduler_loop, name="repro-stream-scheduler",
            daemon=True)
        self._dispatcher = threading.Thread(
            target=self._dispatcher_loop, name="repro-stream-dispatcher",
            daemon=True)
        self._scheduler.start()
        self._dispatcher.start()
        return self

    def close(self, drain: bool = True) -> None:
        """Stop the engine.

        ``drain=True`` (graceful) flushes every queued request through
        the normal batch path before the threads exit; ``drain=False``
        fails still-queued tickets with :class:`EngineClosed`.
        Idempotent.
        """
        with self._lock:
            if self._closing and self._stopped.is_set():
                return
            self._closing = True
            if not drain:
                for q in self._buckets.values():
                    for t in q:
                        t._fail(EngineClosed("engine closed without drain"))
                        self._pending -= 1
                    q.clear()
            self._wake.notify_all()
            self._space.notify_all()
        if self._scheduler is None:
            # never started: nothing to join, but honour drain semantics
            self._drain_inline()
            self._stopped.set()
            return
        self._scheduler.join()
        self._dispatcher.join()
        self._stopped.set()

    def _drain_inline(self) -> None:
        """close(drain=True) on a never-started engine: flush in-thread."""
        while True:
            batch = self._close_one_locked_wrapper()
            if batch is None:
                return
            self._execute(batch)

    def _close_one_locked_wrapper(self) -> Optional[_Batch]:
        with self._lock:
            return self._close_next_locked(draining=True)

    def __enter__(self) -> "StreamEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close(drain=True)

    # ------------------------------------------------------- close policy
    def _age_target(self, key: BucketKey) -> float:
        """Per-bucket hold-open budget: §6-modeled batch seconds scaled.

        Reads the frozen bucket plan's ``est_seconds`` through the
        service; before the first resolution the floor applies (close a
        cold bucket fast so the plan exists for every later decision).
        """
        est = self.service.bucket_plan_estimate(key)
        if est is None:
            return self.min_age_s
        return min(self.max_age_s, max(self.min_age_s,
                                       self.age_factor * est))

    def _ready_locked(self, now: float, draining: bool
                      ) -> Optional[Tuple[BucketKey, str]]:
        """First ready bucket in weighted-round-robin order, with why."""
        n = len(self._ring)
        for off in range(n):
            key = self._ring[(self._ring_idx + off) % n]
            q = self._buckets.get(key)
            if not q:
                continue
            if len(q) >= self.slots:
                return key, "size"
            if now - q[0].admit_t >= self._age_target(key):
                return key, "age"
            if draining:
                return key, "drain"
        return None

    def _next_wake_locked(self, now: float) -> Optional[float]:
        """Seconds until the earliest age-close fires (None: no pending)."""
        horizon = None
        for key, q in self._buckets.items():
            if not q:
                continue
            due = q[0].admit_t + self._age_target(key) - now
            if horizon is None or due < horizon:
                horizon = due
        return None if horizon is None else max(horizon, 0.0)

    def _close_next_locked(self, draining: bool = False
                           ) -> Optional[_Batch]:
        """Pop the next batch to dispatch, or None if nothing is ready."""
        if not self._ring:
            return None
        now = obs.timing.now()
        ready = self._ready_locked(now, draining)
        if ready is None:
            return None
        key, reason = ready
        q = self._buckets[key]
        tickets = [q.popleft() for _ in range(min(self.slots, len(q)))]
        self._pending -= len(tickets)
        # weighted round-robin: a still-hot bucket keeps the ring head
        # for up to max_burst consecutive closes, then yields
        idx = self._ring.index(key)
        weight = min(self.max_burst, int(math.ceil(len(q) / self.slots)))
        if weight < 1 or self._bursts.get(key, 0) + 1 >= self.max_burst:
            self._ring_idx = (idx + 1) % len(self._ring)
            self._bursts[key] = 0
        else:
            self._ring_idx = idx
            self._bursts[key] = self._bursts.get(key, 0) + 1
        self.stats[f"closes_{reason}"] += 1
        obs.inc(f"serve.stream.closes_{reason}")
        obs.gauge("serve.stream.pending", self._pending)
        self._space.notify_all()
        return key, tickets, reason

    # ------------------------------------------------------------- threads
    def _scheduler_loop(self) -> None:
        while True:
            with self._lock:
                while True:
                    now = obs.timing.now()
                    if self._closing:
                        batch = self._close_next_locked(draining=True)
                        break
                    batch = self._close_next_locked()
                    if batch is not None:
                        break
                    self._wake.wait(self._next_wake_locked(now))
                if batch is None and self._closing:
                    done = True
                else:
                    done = False
            if done:
                break
            if batch is not None:
                # wave-normalize outside the lock: submit stays cheap,
                # and only this thread mutates stats["padded_waves"]
                key, tickets, reason = batch
                for t in tickets:
                    t.seq = self.service._normalize(t.seq, key)
                # depth-1 queue: blocks only while a previous batch is
                # already assembled AND another is executing
                self._handoff.put(batch)
        self._handoff.put(None)  # dispatcher shutdown sentinel

    def _dispatcher_loop(self) -> None:
        while True:
            item = self._handoff.get()
            if item is None:
                return
            self._execute(item)

    def _execute(self, item: _Batch) -> None:
        key, tickets, reason = item
        with obs.span("stream.dispatch", m=key.m, n=key.n,
                      k_pad=key.k_pad) as sp:
            try:
                out, pad = self.service.execute_batch(
                    key, [t.seq for t in tickets],
                    [t.A for t in tickets])
            except BaseException as e:  # fail tickets, never hang callers
                for t in tickets:
                    t._fail(e)
                return
            sp.set(requests=len(tickets), pad_slots=pad, close=reason)
            # one host materialization for the whole batch: per-request
            # results are zero-copy row views, where slicing the device
            # array would pay one gather dispatch per slot.  This blocks
            # on the in-flight batch only — the admission path and the
            # scheduler's next-batch assembly keep running (the double
            # buffer), and tickets resolve to device-complete values.
            host = np.asarray(out)
            done_t = obs.timing.now()
            record = obs.enabled()
            for i, t in enumerate(tickets):  # per-request unpadding
                if record:
                    obs.observe("serve.request_latency_seconds",
                                done_t - t.admit_t)
                t._fulfill(host[i])
            self.stats["completed"] += len(tickets)
            obs.inc("serve.stream.completed", len(tickets))
