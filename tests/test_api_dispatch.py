"""Backend-registry dispatch: capability records, cost-model plans,
the in-process plan memo, and oracle agreement of every registered
method."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apply_rotation_sequence, random_sequence
from repro.core import registry
from repro.core.ref import rot_sequence_numpy
from repro.core.registry import (clear_plan_cache, eligible_backends,
                                 get_backend, plan_cache_stats, select_plan,
                                 Problem)
from repro.configs import ARCHS, get_config
from repro.configs.rotseq_paper import CONFIG as ROTSEQ_CFG

EXPECTED = {"unoptimized", "wavefront", "blocked", "accumulated",
            "pallas_wave", "pallas_mxu", "rotseq_batched"}

# shared case grid for oracle agreement
CASES = [(5, 8, 3), (12, 17, 6), (9, 33, 4)]


def _problem(m, n, k, seed=0):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((m, n)).astype(np.float32)
    seq = random_sequence(jax.random.key(seed + 1), n, k)
    return A, seq


def test_all_backends_registered():
    assert set(registry.registered_methods()) == EXPECTED


@pytest.mark.parametrize("m,n,k", CASES)
@pytest.mark.parametrize("method", sorted(EXPECTED))
def test_registered_methods_agree_with_oracle(method, m, n, k):
    A, seq = _problem(m, n, k, seed=m + n + k)
    ref = rot_sequence_numpy(A, seq.cos, seq.sin)
    kw = dict(n_b=8, k_b=4)
    if method.startswith("pallas"):
        kw["m_blk"] = 8
    out = apply_rotation_sequence(jnp.array(A), seq.cos, seq.sin,
                                  method=method, **kw)
    np.testing.assert_allclose(np.asarray(out, np.float64), ref,
                               atol=5e-5, rtol=1e-4)


def test_unknown_method_raises():
    A, seq = _problem(4, 6, 2)
    with pytest.raises(ValueError, match="unknown method"):
        apply_rotation_sequence(jnp.array(A), seq.cos, seq.sin,
                                method="does_not_exist")
    with pytest.raises(ValueError, match="unknown method"):
        get_backend("also_missing")


def test_capability_records():
    for name in ("pallas_wave", "pallas_mxu"):
        cap = get_backend(name).capability
        assert cap.needs_pallas and cap.platforms == ("tpu",)
    for name in ("unoptimized", "wavefront"):
        cap = get_backend(name).capability
        assert not cap.supports_signs
    for name in ("blocked", "accumulated"):
        cap = get_backend(name).capability
        assert cap.supports_signs and cap.supports_sharding


def test_signs_filter_eligibility():
    p = Problem(m=8, n=16, k=4, signs=True, platform="cpu")
    names = {s.name for s in eligible_backends(p)}
    assert "unoptimized" not in names and "wavefront" not in names
    assert {"blocked", "accumulated"} <= names


def test_signs_rejected_on_unblocked_methods():
    A, seq = _problem(4, 6, 2)
    G = jnp.full(seq.cos.shape, -1.0)
    for method in ("unoptimized", "wavefront"):
        with pytest.raises(ValueError, match="per-entry signs"):
            apply_rotation_sequence(jnp.array(A), seq.cos, seq.sin,
                                    method=method, G=G)


def test_sharded_plans_exclude_non_shardable_backends():
    """Even on TPU, sharded auto-plans must stay shard_map-traceable."""
    clear_plan_cache()
    for (m, n, k) in [(8, 32, 4), (1024, 4096, 64)]:
        plan = select_plan(m, n, k, platform="tpu", sharded=True)
        assert get_backend(plan.method).capability.supports_sharding, plan
        assert not plan.method.startswith("pallas"), plan


def test_degenerate_shapes_are_identity_under_auto():
    plan = select_plan(4, 1, 3)  # n=1: zero rotation sites
    assert plan.method in registry.registered_methods()
    A, _ = _problem(4, 2, 1)
    out = apply_rotation_sequence(jnp.array(A[:, :1]),
                                  jnp.zeros((0, 1)), jnp.zeros((0, 1)),
                                  method="auto")
    np.testing.assert_array_equal(np.asarray(out), A[:, :1])


def test_float16_eligible_for_auto():
    p = Problem(m=8, n=16, k=4, dtype="float16", platform="cpu")
    assert eligible_backends(p), "float16 must have eligible backends"


def test_auto_plan_for_all_configs():
    """method='auto' must produce a valid, capability-legal plan for the
    paper workload config and every LM architecture config."""
    clear_plan_cache()
    shapes = [(n, n, ROTSEQ_CFG.k) for n in ROTSEQ_CFG.sizes]
    # SOAP-Givens-style basis application on each arch's d_model
    shapes += [(get_config(a).d_model, get_config(a).d_model, 16)
               for a in ARCHS]
    for platform in ("cpu", "gpu", "tpu"):
        for (m, n, k) in shapes:
            plan = select_plan(m, n, k, platform=platform)
            assert plan.method in registry.registered_methods()
            spec = get_backend(plan.method)
            assert platform in spec.capability.platforms
            if platform != "tpu":
                assert not plan.method.startswith("pallas"), plan
            if plan.n_b is not None:
                assert plan.n_b >= 1 and plan.k_b >= 1


def test_plan_cache_hits_on_second_call():
    clear_plan_cache()
    p1 = select_plan(64, 256, 12, platform="cpu")
    before = plan_cache_stats()
    p2 = select_plan(64, 256, 12, platform="cpu")
    after = plan_cache_stats()
    assert p1 == p2
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]


# problems of every memo-key class: dense, live-annotated, shared and
# per-request batches, sharded over two and four devices
_KEY_CLASSES = [
    dict(m=64, n=256, k=12),
    dict(m=64, n=256, k=12, live_planes=255 * 3),
    dict(m=16, n=32, k=8, batch=64),
    dict(m=16, n=32, k=8, batch=64, shared_sequence=False),
    dict(m=4096, n=512, k=64, sharded=True, devices=2),
    dict(m=4096, n=512, k=64, sharded=True, devices=4),
]


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_auto_plan_is_order_independent(platform):
    """Which problems were planned before, and in what order, never
    changes a plan: each is the model's for its own problem."""
    clear_plan_cache()
    forward = [select_plan(platform=platform, **p) for p in _KEY_CLASSES]
    clear_plan_cache()
    backward = [select_plan(platform=platform, **p)
                for p in reversed(_KEY_CLASSES)][::-1]
    clear_plan_cache()
    alone = []
    for p in _KEY_CLASSES:
        alone.append(select_plan(platform=platform, **p))
        clear_plan_cache()
    assert forward == backward == alone
    assert all(pl.source == "model" for pl in forward)


def test_auto_matches_oracle():
    A, seq = _problem(10, 24, 5, seed=7)
    ref = rot_sequence_numpy(A, seq.cos, seq.sin)
    out = apply_rotation_sequence(jnp.array(A), seq.cos, seq.sin,
                                  method="auto")
    np.testing.assert_allclose(np.asarray(out, np.float64), ref,
                               atol=5e-5, rtol=1e-4)


def test_auto_with_signs_uses_sign_capable_backend():
    """G-carrying problems must dispatch to a blocked-family backend."""
    m, n, k = 6, 12, 4
    A, seq = _problem(m, n, k, seed=3)
    G = jnp.where(jax.random.bernoulli(jax.random.key(4), 0.5,
                                       seq.cos.shape), 1.0, -1.0)
    out = apply_rotation_sequence(jnp.array(A), seq.cos, seq.sin,
                                  method="auto", G=G)
    # oracle: elementwise unified update
    Anp = np.array(A, np.float64)
    C = np.asarray(seq.cos, np.float64)
    S = np.asarray(seq.sin, np.float64)
    Gn = np.asarray(G, np.float64)
    for p in range(k):
        for j in range(n - 1):
            x, y = Anp[:, j].copy(), Anp[:, j + 1].copy()
            Anp[:, j] = C[j, p] * x + S[j, p] * y
            Anp[:, j + 1] = Gn[j, p] * (S[j, p] * x - C[j, p] * y)
    np.testing.assert_allclose(np.asarray(out, np.float64), Anp,
                               atol=5e-5, rtol=1e-4)


def test_explicit_tiles_override_auto_plan():
    A, seq = _problem(9, 20, 4, seed=11)
    ref = rot_sequence_numpy(A, seq.cos, seq.sin)
    out = apply_rotation_sequence(jnp.array(A), seq.cos, seq.sin,
                                  method="auto", n_b=8, k_b=2)
    np.testing.assert_allclose(np.asarray(out, np.float64), ref,
                               atol=5e-5, rtol=1e-4)
