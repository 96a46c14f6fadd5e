"""Serving-aware cost model: per-request vs shared-sequence batches.

The acceptance bucket throughout is the serving benchmark's
(m=16, n=32) shape with requests recorded at k_req=5 and padded to
k_pad=8, so ``live_planes = 31*5 = 155`` of ``planes_total = 31*8 =
248``.  The expected backend flips are the ones docs/cost-model.md
derives; they are pure cost-model arithmetic (no JAX dispatch), so the
assertions are exact, not statistical.
"""
import pytest

from repro.core import registry
from repro.core.registry import (Plan, Problem, clear_plan_cache,
                                 cost_components, select_plan)

M, N, K_PAD, LIVE = 16, 32, 8, 155


def _bucket_plan(batch, shared, platform):
    return select_plan(M, N, K_PAD, dtype="float32", platform=platform,
                       batch=batch, shared_sequence=shared,
                       live_planes=LIVE)


# --------------------------------------------------------------- the flip
def test_batch_one_ignores_the_flag():
    clear_plan_cache()
    a = _bucket_plan(1, True, "tpu")
    clear_plan_cache()
    b = _bucket_plan(1, False, "tpu")
    assert a == b  # normalized to the shared (legacy) key and plan


@pytest.mark.parametrize("batch", [8, 64])
def test_per_request_bucket_flips_to_fused_on_tpu(batch):
    clear_plan_cache()
    shared = _bucket_plan(batch, True, "tpu")
    per_req = _bucket_plan(batch, False, "tpu")
    assert per_req.method == "rotseq_batched", per_req
    assert shared.method != "rotseq_batched", shared


def test_per_request_bucket_never_plans_accumulated_on_cpu():
    clear_plan_cache()
    shared = _bucket_plan(64, True, "cpu")
    per_req = _bucket_plan(64, False, "cpu")
    # shared amortizes the Q_t build and wins on the GEMM path; paying
    # it 64x prices accumulated out entirely for the per-request twin
    assert shared.method == "accumulated", shared
    assert per_req.method != "accumulated", per_req


# --------------------------------------------------- components arithmetic
def _prob(shared):
    return Problem(m=M, n=N, k=K_PAD, dtype="float32", platform="cpu",
                   batch=64, shared_sequence=shared, live_planes=LIVE)


@pytest.mark.parametrize("method,plan", [
    ("accumulated", Plan("accumulated", n_b=32, k_b=8)),
    ("blocked", Plan("blocked", n_b=32, k_b=8)),
    ("rotseq_batched", Plan("rotseq_batched", m_blk=16)),
    ("wavefront", Plan("wavefront")),
])
def test_split_sums_to_totals(method, plan):
    c = cost_components(method, _prob(False), plan)
    assert c["flops"] == c["setup"]["flops"] + c["stream"]["flops"]
    assert c["bytes"] == c["setup"]["bytes"] + c["stream"]["bytes"]


def test_per_request_setup_scales_with_batch():
    plan = Plan("accumulated", n_b=32, k_b=8)
    shared = cost_components("accumulated", _prob(True), plan)
    per_req = cost_components("accumulated", _prob(False), plan)
    # setup x64, stream identical
    assert per_req["setup"]["flops"] == 64 * shared["setup"]["flops"]
    assert per_req["setup"]["bytes"] == 64 * shared["setup"]["bytes"]
    assert per_req["stream"] == shared["stream"]


def test_fused_kernel_price_is_ownership_flat():
    plan = Plan("rotseq_batched", m_blk=16)
    shared = cost_components("rotseq_batched", _prob(True), plan)
    per_req = cost_components("rotseq_batched", _prob(False), plan)
    # the kernel re-reads the panel per batch element either way
    assert shared == per_req


def test_modeled_prediction_cliff_is_at_least_5x():
    # the serve/prediction_cliff bench row, as a unit test: penalty-free
    # setup+stream attribution, accumulated vs fused at batch 64
    acc = cost_components("accumulated", _prob(False),
                          Plan("accumulated", n_b=32, k_b=8))
    fused = cost_components("rotseq_batched", _prob(False),
                            Plan("rotseq_batched", m_blk=16))
    acc_s = acc["setup"]["seconds"] + acc["stream"]["seconds"]
    fused_s = fused["setup"]["seconds"] + fused["stream"]["seconds"]
    assert acc_s / fused_s >= 5.0


# ------------------------------------------------------------- cache keys
def test_per_request_key_is_distinct_and_round_trips():
    clear_plan_cache()
    shared = _bucket_plan(64, True, "tpu")
    per_req = _bucket_plan(64, False, "tpu")
    keys = [k for k in registry._PLAN_CACHE
            if k[:3] == (M, N, K_PAD) and "per_req" in k]
    assert len(keys) == 1
    (pkey,) = keys
    assert registry._PLAN_CACHE[pkey] == per_req
    assert per_req != shared
    # the same problem maps to the same key, and select_plan finds it
    # as a hit, not a re-resolution
    prob = Problem(m=M, n=N, k=K_PAD, dtype="float32", platform="tpu",
                   batch=64, shared_sequence=False, live_planes=LIVE)
    assert registry._plan_key(prob) == pkey
    hits0 = registry.plan_cache_stats()["hits"]
    assert _bucket_plan(64, False, "tpu") is per_req
    assert registry.plan_cache_stats()["hits"] == hits0 + 1


def test_batch_one_shares_the_legacy_key():
    clear_plan_cache()
    _bucket_plan(1, False, "tpu")
    assert all("per_req" not in k for k in registry._PLAN_CACHE)


# ------------------------------------------------------------ memo keys
def test_interpolation_never_crosses_the_ownership_class():
    """A per-request bucket and its shared-sequence twin never share a
    memo entry: planting another backend under one key leaves the
    other's plan the model's."""
    def _plant(batch, shared, method):
        prob = Problem(m=M, n=N, k=K_PAD, dtype="float32", platform="tpu",
                       batch=batch, shared_sequence=shared,
                       live_planes=LIVE)
        registry._PLAN_CACHE[registry._plan_key(prob)] = Plan(
            method=method, est_seconds=1e-6)

    clear_plan_cache()
    model_shared = _bucket_plan(64, True, "tpu")
    model_per_req = _bucket_plan(64, False, "tpu")

    clear_plan_cache()
    _plant(64, False, "unoptimized")
    assert _bucket_plan(64, True, "tpu") == model_shared

    clear_plan_cache()
    _plant(64, True, "unoptimized")
    assert _bucket_plan(64, False, "tpu") == model_per_req
    clear_plan_cache()
