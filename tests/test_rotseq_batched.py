"""Fused multi-request kernel: one launch per bucket, bitwise parity
with per-request execution, identity-plane skipping (pad_to tails and
seq.T staircases), registry routing, and plan serialization."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import registry
from repro.core.registry import clear_plan_cache, select_plan
from repro.core.rotations import random_sequence
from repro.core.sequence import RotationSequence, SequencePlan
from repro.kernels.rotseq_batched.ops import (count_live_planes,
                                              rot_sequence_batched)
from repro.kernels.rotseq_batched.ref import rot_sequence_batched_ref


def _per_request_ref(A, seqs, method="blocked", **kw):
    """The fused contract's oracle: b separate planned applications."""
    return jnp.stack([
        s.plan(like=A[i], method=method, **kw).apply(A[i])
        for i, s in enumerate(seqs)])


# ------------------------------------------------------ bitwise parity ----

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("oracle", ["blocked", "unoptimized"])
def test_fused_per_request_bitwise(dtype, oracle):
    """Per-request wave stacks in one launch == b per-request applies,
    bit-for-bit, on the rotation family."""
    rng = np.random.default_rng(0)
    b, m, n, k = 5, 12, 20, 8
    A = jnp.asarray(rng.standard_normal((b, m, n)), dtype)
    seqs = [random_sequence(jax.random.key(i), n, k, dtype=dtype)
            for i in range(b)]
    plan = seqs[0].plan(like=A, method="rotseq_batched")
    out = plan.apply_batched(A, sequences=seqs)
    ref = _per_request_ref(A, seqs, method=oracle)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_fused_shared_sequence_bitwise():
    rng = np.random.default_rng(1)
    b, m, n, k = 4, 16, 32, 8
    A = jnp.asarray(rng.standard_normal((b, m, n)), jnp.float32)
    seq = random_sequence(jax.random.key(0), n, k)
    plan = seq.plan(like=A, method="rotseq_batched")
    out = plan.apply_batched(A)
    ref = _per_request_ref(A, [seq] * b)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_fused_sign_families_bitwise():
    """Per-entry-sign and all-reflector stacks (incl. mixed batches under
    a sign-carrying plan) stay bit-identical to the per-request loop."""
    rng = np.random.default_rng(2)
    b, m, n, k = 4, 8, 16, 4
    A = jnp.asarray(rng.standard_normal((b, m, n)), jnp.float32)
    base = [random_sequence(jax.random.key(i), n, k) for i in range(b)]
    sgn = jnp.where(rng.random((n - 1, k)) < 0.5, 1.0, -1.0)
    seqs = [
        RotationSequence(base[0].cos, base[0].sin, sgn.astype(jnp.float32)),
        RotationSequence(base[1].cos, base[1].sin, None, True),  # reflector
        base[2],                                                 # plain
        RotationSequence.identity(n, k),                         # slot pad
    ]
    plan = seqs[0].plan(like=A, method="rotseq_batched")
    out = plan.apply_batched(A, sequences=seqs)
    ref = _per_request_ref(A, seqs)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_fused_staircase_and_padded_bitwise():
    """seq.T staircases and pad_to'd sequences — the identity-heavy
    inputs the plane-skip exists for — stay exact."""
    rng = np.random.default_rng(3)
    b, m, n, k = 4, 8, 24, 6
    A = jnp.asarray(rng.standard_normal((b, m, n)), jnp.float32)
    stair = [random_sequence(jax.random.key(i), n, k).T for i in range(b)]
    plan = stair[0].plan(like=A, method="rotseq_batched")
    out = plan.apply_batched(A, sequences=stair)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(_per_request_ref(A, stair)))

    padded = [random_sequence(jax.random.key(10 + i), n, 3).pad_to(8)
              for i in range(b)]
    plan2 = padded[0].plan(like=A, method="rotseq_batched")
    out2 = plan2.apply_batched(A, sequences=padded)
    np.testing.assert_array_equal(np.asarray(out2),
                                  np.asarray(_per_request_ref(A, padded)))


def test_fused_f64_bitwise():
    with compat.enable_x64():
        rng = np.random.default_rng(4)
        b, m, n, k = 3, 8, 12, 4
        A = jnp.asarray(rng.standard_normal((b, m, n)), jnp.float64)
        seqs = [random_sequence(jax.random.key(i), n, k, dtype=jnp.float64)
                for i in range(b)]
        plan = seqs[0].plan(like=A, method="rotseq_batched")
        out = plan.apply_batched(A, sequences=seqs)
        assert out.dtype == jnp.float64
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(_per_request_ref(A, seqs)))


# --------------------------------------------------- plane skipping ----

def test_fused_skips_identity_planes():
    """Acceptance: the kernel processes exactly the live-plane hull —
    pad_to tails and staircase triangles are skipped, not applied."""
    rng = np.random.default_rng(5)
    b, m, n, k_orig, k_pad = 3, 8, 16, 3, 8
    A = jnp.asarray(rng.standard_normal((b, m, n)), jnp.float32)
    seqs = [random_sequence(jax.random.key(i), n, k_orig).pad_to(k_pad)
            for i in range(b)]
    C = jnp.stack([s.cos for s in seqs])
    S = jnp.stack([s.sin for s in seqs])
    out, planes = rot_sequence_batched(A, C, S, m_blk=8, return_planes=True)
    planes = np.asarray(planes)
    total = (n - 1) * k_pad
    for i, s in enumerate(seqs):
        live = count_live_planes(s)
        assert live <= (n - 1) * k_orig < total
        # every m-block of request i reports exactly its live planes
        assert (planes[i] == live).all(), (i, planes[i], live)

    # the seq.T staircase: n+k-2 waves, but only the original planes live
    t = random_sequence(jax.random.key(9), n, k_orig).T
    out_t, planes_t = rot_sequence_batched(A, t.cos, t.sin, m_blk=8,
                                           return_planes=True)
    assert t.k == n + k_orig - 2
    live_t = count_live_planes(t)
    assert live_t == (n - 1) * k_orig  # == t.k_live
    assert t.k_live == live_t
    assert (np.asarray(planes_t) == live_t).all()
    assert live_t < (n - 1) * t.k  # strictly fewer than the padded grid

    # an all-identity stack processes zero planes
    ident = RotationSequence.identity(n, k_pad)
    out_i, planes_i = rot_sequence_batched(A, ident.cos, ident.sin,
                                           m_blk=8, return_planes=True)
    assert (np.asarray(planes_i) == 0).all()
    np.testing.assert_array_equal(np.asarray(out_i), np.asarray(A))


def test_padded_reflector_planes_stay_live():
    """A c=1, s=0 *reflector* is diag(1, -1), not the identity — the
    skip test must key on the sign."""
    n, k = 8, 2
    C = jnp.ones((n - 1, k), jnp.float32)
    S = jnp.zeros((n - 1, k), jnp.float32)
    A = jnp.asarray(np.random.default_rng(0).standard_normal((2, 4, n)),
                    jnp.float32)
    out, planes = rot_sequence_batched(A, C, S, reflect=True, m_blk=8,
                                       return_planes=True)
    assert (np.asarray(planes) == (n - 1) * k).all()
    ref = rot_sequence_batched_ref(A, C, S, reflect=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ------------------------------------------------ k_live propagation ----

def test_k_live_static_propagation():
    seq = random_sequence(jax.random.key(0), 16, 4)
    J = 15
    assert seq.k_live is None
    assert seq.T.k_live == J * 4
    assert seq.pad_to(8).k_live == J * 4
    assert seq.pad_to(8).T.k_live == J * 4
    assert RotationSequence.identity(16, 4).k_live == 0
    both = seq.pad_to(8) @ seq.pad_to(8)
    assert both.k_live == 2 * J * 4
    assert seq.with_signs().k_live is None
    # pytree round-trip preserves the static aux
    leaves, treedef = jax.tree_util.tree_flatten(seq.T)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.k_live == seq.T.k_live
    # serialization carries it
    d = json.loads(json.dumps(seq.T.to_dict()))
    assert RotationSequence.from_dict(d).k_live == seq.T.k_live


def test_registry_routes_staircase_to_fused_on_tpu():
    """seq.T planning: the live-plane-aware cost model sends thin
    staircases to the plane-skipping kernel on TPU while dense grids of
    the same padded shape stay on the GEMM family — and staircases
    whose C/S/G panels exceed the kernel's SMEM budget are kept off it
    (interpret mode would run them; Mosaic could not compile them)."""
    clear_plan_cache()
    thin = select_plan(4096, 96, 102, platform="tpu",
                       live_planes=95 * 8)
    dense = select_plan(4096, 96, 102, platform="tpu")
    assert thin.method == "rotseq_batched"
    assert dense.method != "rotseq_batched"
    # distinct cache keys: the live-plane entry must not shadow dense
    assert select_plan(4096, 96, 102, platform="tpu").method == \
        dense.method
    # (255, 263) panels are ~800KB of SMEM — never routed on TPU
    big = select_plan(4096, 256, 263, platform="tpu",
                      live_planes=255 * 8)
    assert big.method != "rotseq_batched"
    clear_plan_cache()


def test_interpolation_respects_liveness_class():
    """A measured plane-skipping plan keyed with a live-plane count must
    not transfer at distance 0 to the dense grid of the same shape (and
    vice versa) — liveness is part of the interpolation class; nearby
    live-annotated problems may still borrow it."""
    clear_plan_cache()
    p_live = registry.Problem(m=4096, n=96, k=102, platform="tpu",
                              live_planes=95 * 8)
    key = registry._plan_key(p_live)
    registry._PLAN_CACHE[key] = registry.Plan(
        "rotseq_batched", m_blk=256, est_seconds=1e-5, source="measured")
    dense = select_plan(4096, 96, 102, platform="tpu")
    assert dense.method != "rotseq_batched"
    near = select_plan(4096, 96, 102, platform="tpu",
                       live_planes=95 * 10)
    assert near.method == "rotseq_batched"
    assert near.source == "interpolated"
    clear_plan_cache()


# ------------------------------------------------------- autodiff ----

def test_fused_grad_matches_blocked_bitwise():
    """The fused custom_vjp (transposed-stack cotangent) must equal the
    per-target transposed-sequence VJP of the jnp family exactly."""
    rng = np.random.default_rng(6)
    b, m, n, k = 4, 8, 12, 4
    A = jnp.asarray(rng.standard_normal((b, m, n)), jnp.float32)
    shared = random_sequence(jax.random.key(0), n, k)
    plan_f = shared.plan(like=A, method="rotseq_batched")
    plan_b = shared.plan(like=A, method="blocked", n_b=8, k_b=4)
    loss = lambda p: lambda x: (p.apply_batched(x) ** 2).sum()
    g_f = jax.grad(loss(plan_f))(A)
    g_b = jax.grad(loss(plan_b))(A)
    np.testing.assert_array_equal(np.asarray(g_f), np.asarray(g_b))

    # per-request stacks (incl. a signed member under a signed plan)
    sgn = jnp.where(rng.random((n - 1, k)) < 0.5, 1.0, -1.0)
    seqs = [RotationSequence(shared.cos, shared.sin,
                             sgn.astype(jnp.float32)),
            random_sequence(jax.random.key(1), n, k),
            random_sequence(jax.random.key(2), n, k),
            RotationSequence.identity(n, k)]
    plan_fs = seqs[0].plan(like=A, method="rotseq_batched")
    g_fs = jax.grad(
        lambda x: (plan_fs.apply_batched(x, sequences=seqs) ** 2).sum())(A)
    refs = jnp.stack([
        jax.grad(lambda x: (s.plan(
            like=A[i], method="blocked", n_b=8, k_b=4).apply(x) ** 2).sum())
        (A[i]) for i, s in enumerate(seqs)])
    np.testing.assert_array_equal(np.asarray(g_fs), np.asarray(refs))


# ------------------------------------------------- plan round-trip ----

def test_fused_plan_dict_roundtrip():
    """SequencePlan dicts for plans that selected the fused backend
    round-trip through real JSON and reproduce bucket outputs exactly."""
    rng = np.random.default_rng(7)
    b, m, n, k = 3, 8, 16, 4
    A = jnp.asarray(rng.standard_normal((b, m, n)), jnp.float32)
    seqs = [random_sequence(jax.random.key(i), n, k) for i in range(b)]
    plan = seqs[0].plan(like=A, method="rotseq_batched")
    d = json.loads(json.dumps(plan.to_dict()))
    assert d["method"] == "rotseq_batched"
    plan2 = SequencePlan.from_dict(d, seqs[0])
    assert plan2.method == "rotseq_batched"
    np.testing.assert_array_equal(
        np.asarray(plan2.apply_batched(A, sequences=seqs)),
        np.asarray(plan.apply_batched(A, sequences=seqs)))


def test_fused_capability_record():
    spec = registry.get_backend("rotseq_batched")
    assert spec.capability.batch_via == "fused"
    assert spec.capability.supports_signs
    assert spec.capability.needs_pallas and spec.capability.interpret_ok
    # cost model scales with live planes
    p_dense = registry.Problem(m=4096, n=96, k=102, platform="tpu")
    p_live = registry.Problem(m=4096, n=96, k=102, platform="tpu",
                              live_planes=95 * 8)
    plan = registry.Plan("rotseq_batched", m_blk=256)
    assert spec.cost(p_live, plan) < spec.cost(p_dense, plan)
    # and offers no tile where the panels exceed the chip's SMEM (the
    # kernel could not compile there)
    p_big = registry.Problem(m=4096, n=256, k=263, platform="tpu",
                             live_planes=255 * 8)
    assert registry.rotseq_batched_tiles(p_live)
    assert registry.rotseq_batched_tiles(p_big) == []
