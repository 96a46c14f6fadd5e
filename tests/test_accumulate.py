"""Tile factors accumulated one anti-diagonal per step equal the tile
applied plane by plane to an identity, both as the XLA loop and as the
Pallas kernel (interpreted here), and each runs one loop of
``ceil((n_b + k_b - 1) / 2)`` steps, two anti-diagonals each.  The
sheared tiles they start from are packed without a gather, and equal
the gathered ones bit for bit."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import accumulate
from repro.core.accumulate import accumulate_tile_factors
from repro.core.blocked import apply_tile, num_tiles, pack_sheared

SHAPES = [(3, 16, 16), (2, 8, 4), (4, 24, 8), (2, 8, 1), (3, 5, 9),
          (2, 64, 64)]
SCHEDULES = {
    "loop": accumulate_tile_factors,  # what every platform but a TPU runs
    "kernel": partial(accumulate._factors_kernel, interpret=True),
}


@jax.jit
def _plane_by_plane(Ct, St, Gt):
    """The formulation the schedule replaced: ``apply_tile`` on ``I``."""
    n_b, k_b = Ct.shape[1:]
    eye = jnp.eye(n_b + k_b, dtype=jnp.float32)
    return jax.vmap(lambda c, s, g: apply_tile(eye, c, s, g))(Ct, St, Gt)


def _bands(T, n_b, k_b, signs):
    """Sheared tiles of two bands of ``2 k_b - 1`` random waves over a
    target of ``T n_b`` columns: the second band is padded with no-ops,
    and tiles cross the grid's edges."""
    n, k = T * n_b, 2 * k_b - 1
    rng = np.random.default_rng(T * 1000 + n_b * 10 + k_b)
    theta = rng.uniform(0.0, 2.0 * np.pi, (n - 1, k))
    C = jnp.asarray(np.cos(theta), jnp.float32)
    S = jnp.asarray(np.sin(theta), jnp.float32)
    kw = {"rotations": {}, "reflectors": {"reflect": True},
          "mixed": {"G": jnp.asarray(rng.choice([-1.0, 1.0], (n - 1, k)),
                                     jnp.float32)}}[signs]
    return [pack_sheared(C, S, p0, k_b, n_b, T, **kw)
            for p0 in range(0, k, k_b)]


@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
@pytest.mark.parametrize("signs", ["rotations", "reflectors", "mixed"])
@pytest.mark.parametrize("T,n_b,k_b", SHAPES)
def test_factors_equal_plane_by_plane(T, n_b, k_b, signs, schedule):
    run = jax.jit(SCHEDULES[schedule])
    for Ct, St, Gt in _bands(T, n_b, k_b, signs):
        got = run(Ct, St, Gt)
        want = _plane_by_plane(Ct, St, Gt)
        assert got.shape == (T, n_b + k_b, n_b + k_b)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _eqns(jaxpr, depth=0):
    """``(loop depth, equation)`` for every equation of ``jaxpr`` and of
    the jaxprs inside it (loop bodies, calls, a Pallas kernel's body)."""
    for eqn in jaxpr.eqns:
        yield depth, eqn
        inner = depth + (eqn.primitive.name in ("scan", "while"))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub, inner)


def _loops(jaxpr):
    """``(depth, trip count)`` of every loop in ``jaxpr``, nested too."""
    return [(d, e.params.get("length")) for d, e in _eqns(jaxpr)
            if e.primitive.name in ("scan", "while")]


@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
@pytest.mark.parametrize("T,n_b,k_b", [(41, 96, 96), (16, 64, 64),
                                       (3, 5, 9), (2, 8, 1)])
def test_one_loop_of_half_the_anti_diagonals(T, n_b, k_b, schedule):
    tile = jax.ShapeDtypeStruct((T, n_b, k_b), jnp.float32)
    jaxpr = jax.make_jaxpr(SCHEDULES[schedule])(tile, tile, tile)
    steps = -(-(n_b + k_b - 1) // 2)
    assert _loops(jaxpr.jaxpr) == [(0, steps)]
    assert 2 * steps - (n_b + k_b - 1) in (0, 1)
    # the plane-by-plane formulation nests a plane loop in a wave loop
    old = jax.make_jaxpr(_plane_by_plane)(tile, tile, tile)
    assert sorted(_loops(old.jaxpr)) == [(0, k_b), (1, n_b)]


@pytest.mark.parametrize("on_tpu,dtype,path", [
    (False, jnp.float32, "loop"), (True, jnp.float32, "kernel"),
    (True, jnp.bfloat16, "loop")])
def test_tpu_float32_takes_the_kernel(monkeypatch, on_tpu, dtype, path):
    """The kernel serves float32 on a TPU; every other case the loop."""
    monkeypatch.setattr(compat, "is_tpu", lambda: on_tpu)
    tile = jax.ShapeDtypeStruct((2, 8, 4), jnp.float32)
    jaxpr = jax.make_jaxpr(partial(accumulate_tile_factors, dtype=dtype))(
        tile, tile, tile)
    kernels = [e for _, e in _eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    assert len(kernels) == (path == "kernel")
    assert jaxpr.out_avals[0].dtype == dtype


def _gathered(C, S, p0, k_b, n_b, T, reflect=False, G=None):
    """``pack_sheared`` as one gather per element: the oracle."""
    J, k = C.shape
    u = jnp.arange(T * n_b)
    p = jnp.arange(k_b)
    jg = u[:, None] - p[None, :]
    pg = p0 + p
    valid = (jg >= 0) & (jg < J) & (pg < k)[None, :]
    jc = jnp.clip(jg, 0, J - 1)
    pc = jnp.minimum(pg, k - 1)
    Ct = jnp.where(valid, C[jc, pc], jnp.ones((), C.dtype))
    St = jnp.where(valid, S[jc, pc], jnp.zeros((), S.dtype))
    if G is not None:
        Gt = jnp.where(valid, G[jc, pc], -jnp.ones((), C.dtype))
    elif reflect:
        Gt = jnp.where(valid, jnp.ones((), C.dtype), -jnp.ones((), C.dtype))
    else:
        Gt = jnp.full_like(Ct, -1.0)
    return tuple(X.reshape(T, n_b, k_b) for X in (Ct, St, Gt))


def _waves(n, k, dtype, signs):
    rng = np.random.default_rng(n * 1000 + k)
    C, S, G = (jnp.asarray(X, dtype) for X in (
        rng.standard_normal((n - 1, k)), rng.standard_normal((n - 1, k)),
        rng.choice([-1.0, 1.0], (n - 1, k))))
    kw = {"rotations": {}, "reflectors": {"reflect": True},
          "mixed": {"G": G}}[signs]
    return C, S, kw


PACK_SIGNS = ["rotations", "reflectors", "mixed"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("signs", PACK_SIGNS)
@pytest.mark.parametrize("n,k,n_b,k_b", [
    (300, 180, 96, 96),  # n3840's tiles; the second band is partial
    (200, 180, 64, 64),  # n960's: three bands, the last partial
    (150, 32, 64, 32),   # evacc-4chip's: one band
    (50, 40, 8, 16),     # a partial last band of narrow tiles
    (50, 10, 16, 16),    # k < k_b
    (5, 4, 8, 4),        # n < n_b
    (2, 3, 8, 16),       # one plane
])
def test_pack_equals_the_gather(n, k, n_b, k_b, signs, dtype):
    C, S, kw = _waves(n, k, dtype, signs)
    T = num_tiles(n, n_b, k_b)
    for p0 in range(0, k, k_b):
        got = pack_sheared(C, S, p0, k_b, n_b, T, **kw)
        want = _gathered(C, S, p0, k_b, n_b, T, **kw)
        for g, w in zip(got, want):
            assert g.shape == w.shape == (T, n_b, k_b)
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("signs", PACK_SIGNS)
def test_pack_gathers_nothing(signs):
    """At n3840's shape, in every sign mode: no ``gather`` anywhere in
    the packing program (the oracle's has them, so the walk sees one)."""
    n, k, n_b, k_b = 3840, 180, 96, 96
    C, S, kw = _waves(n, k, jnp.float32, signs)
    T = num_tiles(n, n_b, k_b)

    def prims(fn):
        pack = partial(fn, p0=k_b, k_b=k_b, n_b=n_b, T=T, **kw)
        return {e.primitive.name
                for _, e in _eqns(jax.make_jaxpr(pack)(C, S).jaxpr)}

    assert "gather" not in prims(pack_sheared)
    assert "gather" in prims(_gathered)
