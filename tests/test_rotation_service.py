"""Batched plan-once/apply-many serving: SequencePlan.apply_batched,
plan serialization, the shape-bucketed RotationService, and the
batch-aware cost model."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.registry import clear_plan_cache, plan_cache_stats, select_plan
from repro.core.rotations import random_sequence
from repro.core.sequence import RotationSequence, SequencePlan
from repro.serve import RotationService


def _stream(n_requests=24, seed=0, shapes=None):
    """Mixed-shape request stream covering >= 3 buckets."""
    from repro.serve.rotations import DEMO_SHAPES, synthetic_stream

    return synthetic_stream(n_requests, seed=seed,
                            shapes=shapes or DEMO_SHAPES)


# ------------------------------------------------ apply_batched (core) ----

@pytest.mark.parametrize("method,kw", [
    ("unoptimized", {}), ("wavefront", {}),
    ("blocked", dict(n_b=8, k_b=4)), ("accumulated", dict(n_b=8, k_b=4)),
])
def test_apply_batched_shared_sequence_bitwise(method, kw):
    """One sequence, batched targets: flatten/vmap must equal b separate
    applies bit-for-bit (rotations act row-wise)."""
    rng = np.random.default_rng(1)
    b, m, n, k = 4, 8, 12, 6
    A = jnp.asarray(rng.standard_normal((b, m, n)), jnp.float32)
    seq = random_sequence(jax.random.key(0), n, k)
    plan = seq.plan(like=A, method=method, **kw)
    out = plan.apply_batched(A)
    ref = jnp.stack([plan.apply(A[i]) for i in range(b)])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_apply_batched_per_request_sequences_bitwise():
    """Each batch element with its own waves == per-request application."""
    rng = np.random.default_rng(2)
    b, m, n, k = 6, 8, 12, 6
    A = jnp.asarray(rng.standard_normal((b, m, n)), jnp.float32)
    seqs = [random_sequence(jax.random.key(i), n, k) for i in range(b)]
    plan = seqs[0].plan(like=A, method="blocked", n_b=8, k_b=4)
    out = plan.apply_batched(A, sequences=seqs)
    ref = jnp.stack([
        s.plan(like=A[i], method="blocked", n_b=8, k_b=4).apply(A[i])
        for i, s in enumerate(seqs)])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_apply_batched_grad_through_flatten():
    rng = np.random.default_rng(3)
    b, m, n, k = 3, 5, 9, 4
    A = jnp.asarray(rng.standard_normal((b, m, n)), jnp.float32)
    seq = random_sequence(jax.random.key(0), n, k)
    plan = seq.plan(like=A, method="blocked", n_b=8, k_b=4)
    g = jax.grad(lambda x: (plan.apply_batched(x) ** 2).sum())(A)
    eps = 1e-3
    d = jnp.zeros_like(A).at[1, 2, 3].set(eps)
    f = lambda x: float((plan.apply_batched(x) ** 2).sum())
    fd = (f(A + d) - f(A - d)) / (2 * eps)
    assert abs(fd - float(g[1, 2, 3])) < 5e-2 * max(1.0, abs(fd))


def test_apply_batched_validation():
    seq = random_sequence(jax.random.key(0), 8, 4)
    A3 = jnp.zeros((2, 5, 8))
    plan = seq.plan(like=A3)
    with pytest.raises(ValueError, match=r"\(b, m, n\)"):
        plan.apply_batched(jnp.zeros((5, 8)))
    with pytest.raises(ValueError, match="sequences for a batch"):
        plan.apply_batched(A3, sequences=[seq])
    with pytest.raises(ValueError, match="pad_to"):
        plan.apply_batched(
            A3, sequences=[seq, random_sequence(jax.random.key(1), 8, 6)])
    with pytest.raises(ValueError, match="sign/reflect"):
        plan.apply_batched(A3, sequences=[seq, seq.with_signs()])


# ------------------------------------------------ batch-aware planning ----

def test_cost_model_is_batch_aware():
    """Shared-sequence batches amortize the accumulated path's Q_t setup,
    so auto can pick a different backend at batch 64 than at batch 1."""
    clear_plan_cache()
    p1 = select_plan(4, 256, 256, platform="cpu")
    p64 = select_plan(4, 256, 256, platform="cpu", batch=64)
    assert p1.method in ("blocked", "wavefront", "unoptimized")
    assert p64.method == "accumulated"
    # distinct cache keys: batch-64 entry must not shadow batch-1
    before = plan_cache_stats()
    assert select_plan(4, 256, 256, platform="cpu") == p1
    assert select_plan(4, 256, 256, platform="cpu", batch=64) == p64
    after = plan_cache_stats()
    assert after["hits"] == before["hits"] + 2
    assert after["misses"] == before["misses"]
    clear_plan_cache()


def test_plan_accepts_batched_like():
    seq = random_sequence(jax.random.key(0), 16, 4)
    A = jnp.zeros((8, 5, 16))
    plan = seq.plan(like=A)  # 3D like: batch and m inferred
    out = plan.apply_batched(A)
    assert out.shape == A.shape


# --------------------------------------------------- plan serialization ----

def test_sequence_plan_dict_roundtrip_bitwise():
    rng = np.random.default_rng(4)
    m, n, k = 12, 16, 8
    A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    seq = random_sequence(jax.random.key(0), n, k)
    plan = seq.plan(like=A)
    d = json.loads(json.dumps(plan.to_dict()))  # through real JSON
    plan2 = SequencePlan.from_dict(d, seq)
    assert plan2.method == plan.method
    assert dict(plan2.kwargs) == dict(plan.kwargs)
    np.testing.assert_array_equal(np.asarray(plan2.apply(A)),
                                  np.asarray(plan.apply(A)))


@pytest.mark.parametrize("method", ["auto", "blocked"])
def test_plan_dict_round_trip_keeps_the_plan_record(method):
    """A serialized plan comes back with the registry record it was
    frozen with (``None`` for a pinned method), not a relabelled one."""
    seq = random_sequence(jax.random.key(1), 24, 6)
    plan = seq.plan(m=16, method=method)
    assert (plan.plan is None) == (method != "auto")
    d = json.loads(json.dumps(plan.to_dict()))
    back = SequencePlan.from_dict(d, seq)
    assert back.plan == plan.plan
    assert (back.method, back.kwargs) == (plan.method, plan.kwargs)


def test_sequence_plan_from_dict_rejects_stale_and_mismatched():
    seq = random_sequence(jax.random.key(0), 16, 8)
    plan = seq.plan(m=8)
    d = plan.to_dict()
    stale = dict(d, jax="0.0.1")
    with pytest.raises(ValueError, match="JAX"):
        SequencePlan.from_dict(stale, seq)
    with pytest.raises(ValueError, match="wave shape"):
        SequencePlan.from_dict(d, seq.pad_to(12))
    with pytest.raises(ValueError, match="sign/reflect"):
        SequencePlan.from_dict(d, seq.with_signs())
    with pytest.raises(ValueError, match="format"):
        SequencePlan.from_dict(dict(d, format=99), seq)
    with pytest.raises(ValueError, match="unknown method"):
        SequencePlan.from_dict(dict(d, method="gone"), seq)


def test_rotation_sequence_dict_roundtrip():
    seq = random_sequence(jax.random.key(5), 10, 3).with_signs()
    d = json.loads(json.dumps(seq.to_dict()))
    back = RotationSequence.from_dict(d)
    np.testing.assert_array_equal(np.asarray(back.cos), np.asarray(seq.cos))
    np.testing.assert_array_equal(np.asarray(back.sin), np.asarray(seq.sin))
    np.testing.assert_array_equal(np.asarray(back.sign),
                                  np.asarray(seq.sign))


# -------------------------------------------------------- the service ----

def test_service_bitwise_and_one_plan_per_bucket():
    """Acceptance: mixed-shape stream (3 buckets, batch 8) bit-identical
    to per-request seq.plan(like=A).apply(A), exactly one registry
    resolution per bucket."""
    clear_plan_cache()
    requests = _stream(24)
    refs = [seq.plan(like=A).apply(A) for seq, A in requests]

    misses0 = plan_cache_stats()["misses"]
    svc = RotationService(slots=8)
    outs = svc.apply_many(requests)
    assert plan_cache_stats()["misses"] - misses0 == 3  # one per bucket
    assert svc.stats["plans_resolved"] == 3
    assert svc.stats["batches"] == 3
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    # steady state: later passes rebind the frozen plans, zero new
    # registry work
    misses1 = plan_cache_stats()["misses"]
    outs2 = svc.apply_many(requests)
    assert plan_cache_stats()["misses"] == misses1
    assert svc.stats["plans_resolved"] == 3
    for out, ref in zip(outs2, refs):
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    clear_plan_cache()


def test_service_partial_batch_pads_slots():
    clear_plan_cache()
    requests = _stream(5, shapes=((16, 32, 8),))  # one bucket, 5 < slots
    refs = [seq.plan(like=A).apply(A) for seq, A in requests]
    svc = RotationService(slots=8)
    outs = svc.apply_many(requests)
    assert svc.stats["padded_slots"] == 3
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    clear_plan_cache()


def test_service_signed_and_reflect_requests():
    """Sign-carrying and reflector sequences bucket separately from plain
    rotations; every request — including all-reflector ones — stays
    **bit-identical** to per-request application: the bit-stable
    reflector normalization makes the bucket's sign-grid execution equal
    the scalar ``reflect=True`` path a lone request takes, to the last
    bit."""
    clear_plan_cache()
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
    svc = RotationService(slots=4)
    outs = svc.apply_many(requests)
    # plain bucket + signed bucket (sign-carrying and reflect share the
    # signed bucket; their structures stay implicit until stacking)
    assert svc.stats["plans_resolved"] == 2
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    clear_plan_cache()


def test_service_admission_keeps_signs_implicit():
    """Regression (pad_to/admission memory): padding a plain or
    reflector sequence into a bucket must not materialize dense sign
    grids per queued request — plain stays ``sign=None`` after
    ``pad_to``, reflector requests only materialize at genuine-reflector
    padding, and identity slot-pads stay implicit."""
    clear_plan_cache()
    svc = RotationService(slots=8)
    rng = np.random.default_rng(9)
    A = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    plain = random_sequence(jax.random.key(0), 16, 5)
    svc.submit(plain, A)
    refl = RotationSequence(plain.cos, plain.sin, None, True)
    svc.submit(refl, A)
    queued = [p.seq for q in svc._queues.values() for p in q]
    plain_q = [s for s in queued if not s.reflect and s.sign is None]
    assert plain_q, "plain request must stay implicit (sign=None)"
    # pad_to on a plain sequence keeps the sign implicit and records
    # the live-plane bound the planner skips padding with
    padded = plain.pad_to(8)
    assert padded.sign is None and padded.k_live == 15 * 5
    # the on-demand sign grid is correct when a consumer does need it
    # (the sequence itself stays implicit)
    bcast = padded._sign_array()
    assert bcast.shape == padded.cos.shape
    assert bool((np.asarray(bcast) == -1.0).all())
    assert padded.sign is None
    # genuine reflector padding still materializes (padded reflectors
    # are not no-ops)
    assert refl.pad_to(8).sign is not None
    clear_plan_cache()


def test_service_fused_bucket_execution_bitwise():
    """Bucket drains through the fused one-launch backend must equal
    per-request auto dispatch bit-for-bit (rotation + signed families,
    partial buckets included)."""
    clear_plan_cache()
    requests = _stream(10)  # 3 buckets, partial drains
    refs = [seq.plan(like=A).apply(A) for seq, A in requests]
    svc = RotationService(slots=4, method="rotseq_batched")
    outs = svc.apply_many(requests)
    assert svc.stats["padded_slots"] > 0  # partial buckets exercised
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    clear_plan_cache()


def test_service_wave_padding_buckets_by_pow2():
    clear_plan_cache()
    svc = RotationService(slots=8)
    rng = np.random.default_rng(8)
    A = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    t1 = svc.submit(random_sequence(jax.random.key(0), 16, 5), A)
    t2 = svc.submit(random_sequence(jax.random.key(1), 16, 7), A)
    svc.drain()
    # k=5 and k=7 share the k_pad=8 bucket
    assert svc.stats["plans_resolved"] == 1
    assert svc.stats["padded_waves"] == (8 - 5) + (8 - 7)
    svc.result(t1), svc.result(t2)
    with pytest.raises(KeyError):
        svc.result(t1)  # results are collected exactly once
    clear_plan_cache()


def test_service_warm_restart_zero_resolutions():
    """A second service in the same process resolves no new plan for
    buckets the registry has already planned: it finds them in the
    in-process memo, and reproduces the first service's bits."""
    from repro import obs

    clear_plan_cache()
    requests = _stream(24)
    svc = RotationService(slots=8)
    outs = svc.apply_many(requests)
    assert svc.stats["plans_resolved"] == 3
    misses0 = plan_cache_stats()["misses"]
    assert misses0 == 3

    # Counted through the obs metrics, not by poking service internals.
    warm = RotationService(slots=8)
    with obs.override(True):
        obs.reset()
        outs2 = warm.apply_many(requests)
        counters = obs.snapshot()["counters"]
    # each bucket is still frozen once per service, but through the memo
    assert counters.get("serve.plans_resolved", 0) == 3
    assert counters.get("registry.plan_cache.hits", 0) == 3
    assert counters.get("registry.plan_cache.misses", 0) == 0
    assert plan_cache_stats()["misses"] == misses0
    for key, plan in warm._plans.items():
        assert plan.plan is svc._plans[key].plan
    obs.reset()
    for a, b in zip(outs, outs2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    clear_plan_cache()


def test_service_rejects_bad_requests():
    svc = RotationService(slots=2)
    seq = random_sequence(jax.random.key(0), 16, 4)
    with pytest.raises(ValueError, match="columns"):
        svc.submit(seq, jnp.zeros((4, 8)))
    with pytest.raises(ValueError, match="2D"):
        svc.submit(seq, jnp.zeros((2, 4, 16)))
    with pytest.raises(ValueError, match="slots"):
        RotationService(slots=0)


# ------------------------------------------- batched delayed buffer ----

def test_delayed_buffer_batched_accumulator_matches_slices():
    from repro.eig.delayed import DelayedRotationBuffer

    rng = np.random.default_rng(9)
    b, m, n = 3, 8, 10
    M = jnp.asarray(rng.standard_normal((b, m, n)), jnp.float32)
    buf = DelayedRotationBuffer(M, k_delay=4)
    slices = [DelayedRotationBuffer(M[i], k_delay=4) for i in range(b)]
    for _ in range(7):  # forces one full flush + one padded flush
        th = rng.standard_normal(n - 1)
        buf.push(np.cos(th), np.sin(th))
        for s in slices:
            s.push(np.cos(th), np.sin(th))
    out = buf.value
    assert buf.flushes == 2
    for i, s in enumerate(slices):
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(s.value))


def test_delayed_buffer_rejects_wrong_rank():
    from repro.eig.delayed import DelayedRotationBuffer

    with pytest.raises(ValueError, match="accumulator"):
        DelayedRotationBuffer(jnp.zeros((2, 3, 4, 5)))
