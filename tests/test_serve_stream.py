"""repro.serve.stream: the async continuous-batching engine.

The acceptance contract: streamed results are **bit-identical** to
synchronous ``RotationService`` drains (plain/signed/reflector, mixed
shapes) because both run the same ``assemble_batch``/``execute_batch``
code path; each bucket is planned exactly once (and a second engine in
the process finds its plans in the registry's memo); the close policy
fires on size *or* age; the
backpressure policies block / fail / shed as selected; weighted
round-robin keeps a cold bucket from starving behind a hot one; and a
graceful shutdown drains every queued request.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.registry import clear_plan_cache, plan_cache_stats
from repro.core.rotations import random_sequence
from repro.core.sequence import RotationSequence
from repro.serve import (Backpressure, DeadlineExceeded, EngineClosed,
                         RotationService, StreamEngine)
from repro.serve.rotations import synthetic_stream

TIMEOUT = 60.0  # generous per-result bound: CI interpret mode is slow


@pytest.fixture(autouse=True)
def _clean():
    clear_plan_cache()
    obs.reset()
    yield
    obs.reset()
    clear_plan_cache()


def _run_stream(engine, requests, **submit_kw):
    tickets = [engine.submit(seq, A, **submit_kw) for seq, A in requests]
    engine.close(drain=True)
    return [t.result(timeout=TIMEOUT) for t in tickets]


# ------------------------------------------------- bitwise acceptance ----

def test_stream_bitwise_equals_sync_mixed_shapes():
    """Streamed == synchronous RotationService, bit for bit, across the
    canonical mixed-shape stream (odd count: partial buckets drain)."""
    requests = synthetic_stream(14, seed=5)
    refs = RotationService(slots=4).apply_many(requests)
    eng = StreamEngine(slots=4)
    outs = _run_stream(eng, requests)
    for ref, out in zip(refs, outs):
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert eng.stats["completed"] == 14


def test_stream_bitwise_signed_and_reflector():
    """Sign-carrying and all-reflector sequences stream bit-identically
    to per-request application (the PR 5 bit-stable normalization)."""
    rng = np.random.default_rng(7)
    m, n, k = 16, 24, 8
    requests = []
    for i in range(9):
        A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
        seq = random_sequence(jax.random.key(i), n, k)
        if i % 3 == 1:
            sign = jnp.where(
                jax.random.bernoulli(jax.random.key(100 + i), 0.5,
                                     seq.cos.shape), 1.0, -1.0)
            seq = RotationSequence(seq.cos, seq.sin, sign)
        elif i % 3 == 2:
            seq = RotationSequence(seq.cos, seq.sin, None, True)
        requests.append((seq, A))
    refs = [seq.plan(like=A).apply(A) for seq, A in requests]
    sync = RotationService(slots=4).apply_many(requests)
    outs = _run_stream(StreamEngine(slots=4), requests)
    for ref, s, out in zip(refs, sync, outs):
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(out))


# ----------------------------------------------------- close policies ----

def test_age_close_fires_on_partial_bucket():
    """A partial bucket must not wait for slots to fill: the age policy
    closes it once the oldest request exceeds the bucket target."""
    requests = synthetic_stream(3, shapes=((16, 32, 8),), seed=1)
    eng = StreamEngine(slots=8, min_age_s=0.001)
    tickets = [eng.submit(seq, A) for seq, A in requests]
    # no close(): the age policy alone must complete the requests
    for t in tickets:
        t.result(timeout=TIMEOUT)
    assert eng.stats["closes_age"] >= 1
    assert eng.stats["closes_size"] == 0
    assert eng.service.stats["padded_slots"] >= 5  # 3 real + 5 identity
    eng.close()


def test_age_target_scales_with_cost_model():
    """The per-bucket age target derives from the §6-modeled batch
    seconds once the bucket is planned, clamped to [min, max]."""
    requests = synthetic_stream(8, shapes=((16, 32, 8),), seed=2)
    eng = StreamEngine(slots=8, start=False,
                       min_age_s=0.004, max_age_s=0.2, age_factor=8.0)
    key = eng.service._bucket_key(*requests[0])
    assert eng._age_target(key) == eng.min_age_s  # unplanned: floor
    for seq, A in requests:
        eng.submit(seq, A)
    eng.close(drain=True)  # inline drain resolves the bucket plan
    est = eng.service.bucket_plan_estimate(key)
    assert est is not None and est > 0
    assert eng._age_target(key) == min(
        eng.max_age_s, max(eng.min_age_s, eng.age_factor * est))


def test_weighted_round_robin_serves_cold_bucket():
    """Deterministic WRR check on the scheduler policy itself: with a
    hot bucket (3 batches queued) and a cold full bucket, the cold
    bucket is served within ``max_burst`` consecutive hot closes."""
    eng = StreamEngine(slots=4, start=False, max_burst=2)
    hot = synthetic_stream(12, shapes=((16, 32, 8),), seed=3)
    cold = synthetic_stream(4, shapes=((16, 64, 12),), seed=4)
    for seq, A in hot + cold:
        eng.submit(seq, A)
    order = []
    for _ in range(4):
        with eng._lock:
            key, tickets, reason = eng._close_next_locked()
        order.append((key.n, reason))
    ns = [n for n, _ in order]
    assert ns[0] == 32                       # hot leads (admission order)
    assert 64 in ns[:3]                      # cold served within the burst
    assert all(r == "size" for _, r in order)


def test_fairness_hot_and_cold_end_to_end():
    """A single cold request completes (age close + WRR) while a hot
    bucket keeps the engine saturated — no starvation, no shedding."""
    eng = StreamEngine(slots=4, min_age_s=0.001)
    hot = synthetic_stream(32, shapes=((16, 32, 8),), seed=6)
    (cold_seq, cold_A), = synthetic_stream(1, shapes=((16, 64, 12),),
                                           seed=7)
    hot_tickets = [eng.submit(seq, A) for seq, A in hot[:16]]
    cold_ticket = eng.submit(cold_seq, cold_A)
    hot_tickets += [eng.submit(seq, A) for seq, A in hot[16:]]
    cold_out = cold_ticket.result(timeout=TIMEOUT)
    eng.close(drain=True)
    ref = cold_seq.plan(like=cold_A).apply(cold_A)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(cold_out))
    assert all(t.result(timeout=TIMEOUT) is not None for t in hot_tickets)
    assert eng.stats["shed"] == 0
    assert eng.stats["completed"] == 33


# ------------------------------------------------ backpressure policies ----

def test_backpressure_fail_policy_rejects():
    eng = StreamEngine(slots=4, start=False, max_pending=2,
                       backpressure="fail")
    requests = synthetic_stream(3, shapes=((8, 16, 4),), seed=8)
    eng.submit(*requests[0])
    eng.submit(*requests[1])
    with obs.override(True):
        with pytest.raises(Backpressure):
            eng.submit(*requests[2])
        assert obs.snapshot()["counters"]["serve.stream.rejected"] == 1
    assert eng.stats["rejected"] == 1
    eng.close(drain=True)  # the two admitted requests still drain


def test_backpressure_shed_policy_drops_expired():
    """Under pressure the shed policy fails queued past-deadline tickets
    (DeadlineExceeded) to admit new work; unexpired requests survive."""
    eng = StreamEngine(slots=4, start=False, max_pending=3,
                       backpressure="shed")
    requests = synthetic_stream(5, shapes=((8, 16, 4),), seed=9)
    doomed = [eng.submit(*requests[i], deadline_s=0.0) for i in range(2)]
    keeper = eng.submit(*requests[2])  # no deadline: never shed
    with obs.override(True):
        admitted = eng.submit(*requests[3])  # sheds both expired tickets
        assert obs.snapshot()["counters"]["serve.stream.shed"] == 2
    for t in doomed:
        with pytest.raises(DeadlineExceeded):
            t.result(timeout=1.0)
    assert eng.stats["shed"] == 2
    # budget full again with unsheddable requests -> Backpressure
    eng.submit(*requests[4])
    with pytest.raises(Backpressure):
        eng.submit(*requests[0])
    eng.close(drain=True)
    for t in (keeper, admitted):
        assert t.result(timeout=TIMEOUT) is not None


def test_backpressure_block_policy_waits_for_room():
    """submit() under the block policy stalls until the scheduler frees
    budget — every request is eventually admitted and served."""
    eng = StreamEngine(slots=2, max_pending=2,
                       backpressure="block", min_age_s=0.001)
    requests = synthetic_stream(7, shapes=((8, 16, 4),), seed=10)
    outs = _run_stream(eng, requests)
    assert len(outs) == 7
    assert eng.stats["submitted"] == 7
    assert eng.stats["completed"] == 7
    assert eng.stats["rejected"] == eng.stats["shed"] == 0


# ------------------------------------------------------------ lifecycle ----

def test_graceful_shutdown_drains_everything():
    """close(drain=True) flushes every queued request — including
    partial buckets — through the normal batch path."""
    requests = synthetic_stream(11, seed=11)  # 3 buckets, none full
    eng = StreamEngine(slots=8, min_age_s=5.0,
                       max_age_s=10.0)  # age close effectively off
    tickets = [eng.submit(seq, A) for seq, A in requests]
    eng.close(drain=True)
    assert all(t.done() for t in tickets)
    refs = RotationService(slots=8).apply_many(requests)
    for ref, t in zip(refs, tickets):
        np.testing.assert_array_equal(np.asarray(ref),
                                      np.asarray(t.result()))
    assert eng.stats["closes_drain"] >= 3


def test_close_without_drain_fails_pending_tickets():
    eng = StreamEngine(slots=8, start=False)
    tickets = [eng.submit(seq, A)
               for seq, A in synthetic_stream(3, shapes=((8, 16, 4),))]
    eng.close(drain=False)
    for t in tickets:
        with pytest.raises(EngineClosed):
            t.result(timeout=1.0)
    with pytest.raises(EngineClosed):
        eng.submit(*synthetic_stream(1, shapes=((8, 16, 4),))[0])


def test_context_manager_drains_on_exit():
    requests = synthetic_stream(5, shapes=((16, 32, 8),), seed=12)
    with StreamEngine(slots=4) as eng:
        tickets = [eng.submit(seq, A) for seq, A in requests]
    assert all(t.done() for t in tickets)


# ------------------------------------------- plan discipline + metrics ----

def test_plans_resolved_exactly_once_per_bucket():
    """Many batches per bucket, one registry resolution per bucket —
    asserted through the same obs counters the artifacts export."""
    requests = synthetic_stream(24, seed=13)  # 3 buckets x 8 requests
    misses0 = plan_cache_stats()["misses"]
    with obs.override(True):
        obs.reset()
        eng = StreamEngine(slots=4)
        outs = _run_stream(eng, requests)
        snap = obs.snapshot()
    c = snap["counters"]
    assert len(outs) == 24
    assert c["serve.stream.submitted"] == 24
    assert c["serve.stream.completed"] == 24
    assert c["serve.plans_resolved"] == 3
    assert c["serve.batches"] == 6          # 8 requests / 4 slots, x3
    assert plan_cache_stats()["misses"] - misses0 == 3
    lat = snap["histograms"]["serve.request_latency_seconds"]
    assert lat["count"] == 24
    assert lat["p99"] >= lat["p50"] > 0


def test_stream_warm_start_zero_resolutions():
    """A second engine in the same process finds every bucket plan in
    the registry's memo: zero registry resolutions, identical bits."""
    clear_plan_cache()
    requests = synthetic_stream(12, seed=14)
    cold = StreamEngine(slots=4)
    outs = _run_stream(cold, requests)
    assert cold.service.stats["plans_resolved"] == 3

    with obs.override(True):
        obs.reset()
        warm = StreamEngine(slots=4)
        outs2 = _run_stream(warm, requests)
        counters = obs.snapshot()["counters"]
    assert counters.get("registry.plan_cache.misses", 0) == 0
    assert counters.get("registry.plan_cache.hits", 0) == 3
    for a, b in zip(outs, outs2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    obs.reset()
    clear_plan_cache()


def test_engine_rejects_bad_arguments():
    with pytest.raises(ValueError, match="backpressure"):
        StreamEngine(backpressure="drop", start=False)
    with pytest.raises(ValueError, match="max_pending"):
        StreamEngine(max_pending=0, start=False)
    svc = RotationService(slots=2)
    with pytest.raises(ValueError, match="service_kw"):
        StreamEngine(svc, pad_waves=False, start=False)
    eng = StreamEngine(svc, start=False)
    with pytest.raises(ValueError, match="2D"):
        eng.submit(random_sequence(jax.random.key(0), 16, 4),
                   jnp.zeros((2, 8, 16)))
