"""Real rotation sequences on complex64 targets.

The eigenvector update of a complex Hermitian eigensolver (LAPACK
``ZSTEQR`` through ``ZLASR``): real waves applied to a complex Z.  The
jnp backends and ``accumulated`` take complex64 targets and agree with
the complex128 oracle; ``accumulated`` sweeps the target's real view, so
no dot of its program has a complex operand; the planned VJP is the
transposed sequence applied to the cotangent; complex waves are
refused; ``auto`` prices a complex target as its real view and never
plans a Pallas kernel for it.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import registry
from repro.core.ref import rot_sequence_numpy
from repro.core.rotations import random_sequence, sequence_to_dense
from repro.core.sequence import RotationSequence

EPS32 = float(np.finfo(np.float32).eps)


def tol(k: int) -> float:
    """Relative to the largest entry of the result.  Each of the ``k``
    waves touches an entry through at most two plane updates, each off
    by a few float32 ulps; the GEMM path adds the rounding of its
    float32 factors and of sums over a tile, which stays within the
    same order.  ``16 k eps`` leaves that room, and is still ~1e4 times
    below what a lost imaginary part or a conjugate would give."""
    return 16 * k * EPS32


def _target(rng, m, n):
    return (rng.standard_normal((m, n))
            + 1j * rng.standard_normal((m, n))).astype(np.complex64)


def _sequence(kind, n, k, seed):
    seq = random_sequence(jax.random.key(seed), n, k)
    if kind == "plain":
        return seq
    rng = np.random.default_rng(seed)
    sign = jnp.asarray(rng.choice([-1.0, 1.0], (n - 1, k)), jnp.float32)
    return RotationSequence(seq.cos, seq.sin, sign)


def _oracle(Z, seq):
    return rot_sequence_numpy(Z, seq.cos, seq.sin,
                              G=None if seq.sign is None else seq.sign)


def _rel(got, want):
    got = np.asarray(got, np.complex128)
    return float(np.abs(got - want).max() / np.abs(want).max())


# (m, n, k, tiles): n not a multiple of n_b, k over two bands of k_b,
# and one case with the planner's own tiles
CASES = [(12, 50, 13, {"n_b": 16, "k_b": 8}),
         (9, 37, 5, {"n_b": 8, "k_b": 4}),
         (16, 70, 20, {}),
         (5, 9, 3, {"n_b": 8, "k_b": 2})]


@pytest.mark.parametrize("kind", ["plain", "signed"])
@pytest.mark.parametrize("method", ["auto", "accumulated", "blocked"])
@pytest.mark.parametrize("m,n,k,tiles", CASES)
def test_complex_targets_match_the_oracle(method, kind, m, n, k, tiles):
    rng = np.random.default_rng(m * n + k)
    Z = _target(rng, m, n)
    seq = _sequence(kind, n, k, seed=m + n + k)
    plan = seq.plan(like=Z, method=method,
                    **({} if method == "auto" else tiles))
    got = plan.apply(jnp.asarray(Z))
    assert got.dtype == jnp.complex64
    want = _oracle(Z, seq)
    assert want.dtype == np.complex128
    assert _rel(got, want) < tol(k)


@pytest.mark.parametrize("method", ["auto", "accumulated", "blocked"])
def test_complex_vjp_is_the_cotangent_times_q_transposed(method):
    """``Z -> Z Q`` with a real ``Q`` is linear, and JAX's cotangent of
    it is ``ct Qᵀ``, with no conjugate: the planned ``custom_vjp``
    applies the transposed sequence to the complex cotangent."""
    rng = np.random.default_rng(7)
    m, n, k = 6, 21, 5
    Z = jnp.asarray(_target(rng, m, n))
    ct = _target(rng, m, n)
    seq = random_sequence(jax.random.key(11), n, k)
    plan = seq.plan(like=Z, method=method,
                    **({} if method == "auto" else {"n_b": 8, "k_b": 4}))
    _, vjp = jax.vjp(plan.apply, Z)
    (got,) = vjp(jnp.asarray(ct))
    want = ct.astype(np.complex128) @ sequence_to_dense(seq).T
    assert got.dtype == jnp.complex64
    assert _rel(got, want) < tol(k)


def _dots(text: str):
    return [line for line in text.splitlines()
            if re.search(r"stablehlo\.(dot_general|dot)\b", line)]


@pytest.mark.parametrize("m,n,k,tiles", [(16, 70, 20, {"n_b": 16,
                                                        "k_b": 8}),
                                         (32, 130, 32, {"n_b": 64,
                                                        "k_b": 32})])
def test_the_lowered_complex_application_has_no_complex_dot(m, n, k,
                                                             tiles):
    """The sweep's GEMMs run on the real view: every dot of the lowered
    program is real, and there is one per band."""
    Z = jax.ShapeDtypeStruct((m, n), jnp.complex64)
    W = jax.ShapeDtypeStruct((n - 1, k), jnp.float32)
    plan = RotationSequence(W, W).plan(like=Z, method="accumulated",
                                       **tiles)

    def flush(Z, C, S):
        return plan.rebind(RotationSequence(C, S)).apply(Z)

    text = jax.jit(flush).lower(Z, W, W).as_text()
    dots = _dots(text)
    assert len(dots) == -(-k // tiles["k_b"]), dots
    assert not [d for d in dots if "complex" in d], dots


@pytest.mark.parametrize("make", [
    lambda c, s: RotationSequence(c, s),
    lambda c, s: RotationSequence.from_waves(c, s),
    lambda c, s: RotationSequence.from_pairs([(c[:, 0], s[:, 0])]),
], ids=["constructor", "from_waves", "from_pairs"])
def test_complex_waves_are_refused(make):
    c = jnp.ones((4, 2), jnp.complex64)
    s = jnp.zeros((4, 2), jnp.complex64)
    with pytest.raises(TypeError, match="complex Givens rotations"):
        make(c, s)


@pytest.mark.parametrize("method", ["pallas_wave", "pallas_mxu",
                                    "rotseq_batched"])
def test_pallas_backends_refuse_complex_targets(method):
    """Mosaic has no complex type: a pinned kernel refuses a complex
    target at plan time, and its capability lists real dtypes only."""
    cap = registry.get_backend(method).capability
    assert cap.dtypes == registry.REAL_DTYPES
    seq = random_sequence(jax.random.key(0), 16, 4)
    with pytest.raises(ValueError, match="complex64"):
        seq.plan(like=jax.ShapeDtypeStruct((8, 16), jnp.complex64),
                 method=method)


# (m, n, k): the cell's flush (16384² Z, k_delay = 32), the chip
# smoke's, a paper size and a serving-sized target
V5E_SHAPES = [(16384, 16384, 32), (1024, 1024, 32), (3840, 3840, 180),
              (256, 128, 32)]


@pytest.mark.parametrize("m,n,k", V5E_SHAPES)
def test_auto_prices_a_complex_target_as_its_real_view(m, n, k):
    """For a described v5e, ``auto`` never plans a Pallas kernel for a
    complex64 target, and prices it as the real view of ``2m`` rows at 4
    bytes an item; at the cell's 16384² and k = 32 it plans the
    ``accumulated`` 64/32 tiles the cell logs."""
    registry.clear_plan_cache()
    plan = registry.select_plan(m, n, k, dtype="complex64", platform="tpu")
    registry.clear_plan_cache()
    assert not registry.get_backend(plan.method).capability.needs_pallas
    cplx = registry.Problem(m=m, n=n, k=k, dtype="complex64",
                            platform="tpu")
    real = registry.Problem(m=2 * m, n=n, k=k, platform="tpu")
    assert (cplx.m_total, cplx.itemsize) == (real.m_total, real.itemsize)
    pl = registry.Plan(plan.method, n_b=plan.n_b, k_b=plan.k_b)
    assert registry.get_backend(plan.method).cost(cplx, pl) == \
        registry.get_backend(plan.method).cost(real, pl)
    if (m, n, k) == (16384, 16384, 32):
        assert (plan.method, plan.n_b, plan.k_b) == ("accumulated", 64, 32)


@pytest.mark.parametrize("dtype,want", [("complex64", jnp.float32),
                                        ("float32", jnp.float32),
                                        ("bfloat16", jnp.bfloat16)])
def test_tile_factors_take_the_real_dtype(dtype, want):
    """A complex64 target's factors are float32 (the dtype the factor
    kernel builds on a TPU); a real target's are its own dtype."""
    from repro.core.accumulate import factor_dtype

    assert factor_dtype(dtype) == jnp.dtype(want)
