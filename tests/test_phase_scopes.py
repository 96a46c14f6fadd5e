"""The backends name their phases on the device: ``jax.named_scope``
``rotseq.pack`` / ``rotseq.accumulate`` / ``rotseq.sweep`` at the call
sites of each phase reach the name stack of every op (the ``tf_op`` of a
profiler trace), and change no output."""
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.accumulate import (accumulate_tile_factors,
                                   apply_band_accumulated,
                                   rot_sequence_accumulated)
from repro.core.blocked import (ACCUMULATE, PACK, SWEEP, num_tiles,
                                pack_sheared, rot_sequence_blocked)
from repro.core.rotations import random_sequence
from repro.kernels.rotseq.ops import _rot_sequence_wave_jit
from repro.kernels.rotseq_batched.ops import _rot_sequence_batched_jit
from repro.kernels.rotseq_mxu.ops import _rot_sequence_mxu_jit

M, N, K = 16, 32, 8


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((M, N)), jnp.float32)
    seq = random_sequence(jax.random.key(3), N, K)
    return A, seq.cos, seq.sin


@partial(jax.jit, static_argnames=("n_b", "k_b"))
def _accumulated_unscoped(A, C, S, *, n_b, k_b):
    """``rot_sequence_accumulated`` as it was before it named its phases."""
    n = A.shape[1]
    T = num_tiles(n, n_b, k_b)
    for p0 in range(0, C.shape[1], k_b):
        Ct, St, Gt = pack_sheared(C, S, p0, k_b, n_b, T)
        Q = accumulate_tile_factors(Ct, St, Gt, dtype=A.dtype)
        A = apply_band_accumulated(A, Q, k_b=k_b)
    return A


def test_scopes_leave_accumulated_bit_identical(problem):
    A, C, S = problem
    got = rot_sequence_accumulated(A, C, S, n_b=8, k_b=4)
    want = _accumulated_unscoped(A, C, S, n_b=8, k_b=4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _phases(fn, *args, **kw) -> set:
    """The ``rotseq.`` scopes in the lowered program's op locations."""
    text = fn.lower(*args, **kw).as_text(debug_info=True)
    return set(re.findall(r"rotseq\.[a-z_]+(?=/)", text))


@pytest.mark.parametrize("name,fn,kw,phases", [
    ("accumulated", rot_sequence_accumulated, dict(n_b=8, k_b=4),
     {PACK, ACCUMULATE, SWEEP}),
    ("blocked", rot_sequence_blocked, dict(n_b=8, k_b=4), {PACK, SWEEP}),
    ("pallas_mxu", _rot_sequence_mxu_jit,
     dict(n_b=8, k_b=8, m_blk=8, interpret=True), {PACK, ACCUMULATE, SWEEP}),
    ("pallas_wave", _rot_sequence_wave_jit,
     dict(n_b=8, k_b=4, m_blk=8, interpret=True), {PACK, SWEEP}),
    ("rotseq_batched", _rot_sequence_batched_jit,
     dict(m_blk=8, interpret=True), {SWEEP}),
])
def test_each_backend_names_its_phases(problem, name, fn, kw, phases):
    A, C, S = problem
    assert _phases(fn, A, C, S, **kw) == phases, name
