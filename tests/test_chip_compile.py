"""Compile the rotation-sequence kernels for a TPU v5e chip.

The chip is described, not attached: JAX's TPU compiler builds each
kernel at the widths ``chip_smoke.py`` runs (the paper's k=180 waves on
m=n=3840 and 960, serving buckets of 64 requests, the eigenvector
flush), for every tile the TPU registry offers, and the four-chip
eigenvector cell's flush (k=32 on 16384 x 65536 shards of V).
Interpret-mode tests cannot see what Mosaic refuses; these can.
Nothing runs.  The program
asks ``compat.is_tpu()`` where it takes a kernel only a TPU runs (the
factor accumulation's); the ``on_tpu`` fixture answers as a chip would.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports every test module.
"""
import pytest

import jax
import jax.numpy as jnp

from repro import compat, dist
from repro.core import registry
from repro.core.accumulate import _factors_kernel, rot_sequence_accumulated
from repro.core.blocked import num_tiles
from repro.core.sequence import RotationSequence
from repro.dist import rot_sequence_column_sharded_padded
from repro.kernels.limits import SMEM_PANEL_BUDGET, smem_panel_bytes
from repro.kernels.rotseq.ops import rot_sequence_wave
from repro.kernels.rotseq_batched.ops import rot_sequence_batched
from repro.kernels.rotseq_mxu.ops import rot_sequence_mxu

# (m, n, k) of single-target applications: the paper experiment at the
# smoke's two sizes and one eigenvector flush (n=1024, k_delay=32)
SINGLE = [(3840, 3840, 180), (960, 960, 180), (1024, 1024, 32)]
# (b, m, n, k) serving buckets: 64 slots, per-request waves
BUCKETS = [(64, 256, 128, 32), (64, 512, 128, 16), (64, 1024, 256, 32),
           (64, 256, 128, 4)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(compat, "is_tpu", lambda: True)


def _compile(fn, sharding, *shapes, dtype=jnp.float32):
    compiled = jax.jit(fn).lower(*(
        jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
        for s in shapes)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


@pytest.mark.parametrize("m,n,k", SINGLE)
@pytest.mark.parametrize("method,dtype", [("pallas_wave", "float32"),
                                          ("pallas_mxu", "float32"),
                                          ("pallas_mxu", "bfloat16")])
def test_offered_tiles_compile(one_chip, on_tpu, method, dtype, m, n, k):
    """Every tile the TPU registry offers for the shape compiles."""
    problem = registry.Problem(m=m, n=n, k=k, dtype=dtype, platform="tpu")
    plans = registry.get_backend(method).candidates(problem)
    assert plans, f"{method} offers no tile at {(m, n, k)}"
    run = rot_sequence_wave if method == "pallas_wave" else rot_sequence_mxu
    for plan in plans:
        kw = plan.kwargs()
        _compile(lambda A, C, S: run(A, C, S, interpret=False, **kw),
                 one_chip, (m, n), (n - 1, k), (n - 1, k),
                 dtype=jnp.dtype(dtype))


@pytest.mark.parametrize("m,n,k", SINGLE)
def test_factor_kernel_compiles(one_chip, on_tpu, m, n, k):
    """The factor kernel compiles for every tile the TPU registry offers
    the ``accumulated`` backend, and the application the benchmark's
    plan runs (96/96 at 3840, 64/64 at 960) holds it."""
    problem = registry.Problem(m=m, n=n, k=k, platform="tpu")
    plans = registry.accumulated_tiles(problem)
    assert plans
    for plan in plans:
        tile = (num_tiles(n, plan.n_b, plan.k_b), plan.n_b, plan.k_b)
        _compile(_factors_kernel, one_chip, tile, tile, tile)
    if (m, n) in ((3840, 3840), (960, 960)):
        b = 96 if n == 3840 else 64
        text = _compile(lambda A, C, S: rot_sequence_accumulated(
                            A, C, S, n_b=b, k_b=b),
                        one_chip, (m, n), (n - 1, k), (n - 1, k)).as_text()
        assert text.count("rotseq_factors") >= -(-k // b)


def test_column_sharded_factor_kernel_compiles(topo, on_tpu):
    """Inside ``shard_map`` on a 2x2 mesh, the column-sharded pipeline's
    GEMM sweep accumulates its factors with the kernel."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = compat.make_mesh((2, 2), ("data", "model"),
                            devices=topo.devices)
    m, n, k = 256, 1024, 64
    shapes = [((m, n), P("data", None)), ((n - 1, k), P(None, None)),
              ((n - 1, k), P(None, None))]
    args = [jax.ShapeDtypeStruct(s, jnp.float32,
                                 sharding=NamedSharding(mesh, spec))
            for s, spec in shapes]
    text = jax.jit(lambda A, C, S: rot_sequence_column_sharded_padded(
        A, RotationSequence(C, S), mesh, col_axis="model", n_b=64, k_b=32,
        row_axes=("data",), method="accumulated")).lower(
            *args).compile().as_text()
    assert "rotseq_factors" in text


@pytest.mark.parametrize("b,m,n,k", BUCKETS)
@pytest.mark.parametrize("waves", ["shared", "per_request"])
def test_fused_bucket_compiles(one_chip, waves, b, m, n, k):
    """The fused kernel compiles for a serving bucket at every offered
    lane tile, with one shared or 64 per-request wave stacks."""
    shared = waves == "shared"
    problem = registry.Problem(m=m, n=n, k=k, platform="tpu", batch=b,
                               shared_sequence=shared)
    plans = registry.rotseq_batched_tiles(problem)
    assert plans
    cs = (n - 1, k) if shared else (b, n - 1, k)
    for plan in plans:
        _compile(lambda A, C, S: rot_sequence_batched(
                     A, C, S, interpret=False, m_blk=plan.m_blk),
                 one_chip, (b, m, n), cs, cs)


def test_filtered_tiles_are_refused(one_chip):
    """What the TPU registry filters out, the chip's compiler refuses:
    a 64-lane MXU fresh-column block, fused-kernel panels past the SMEM
    budget, and a bfloat16 slab addressed one row at a time.  If Mosaic
    ever accepts them, the filters can go."""
    m = n = 960
    k = 180
    bf16 = registry.Problem(m=m, n=n, k=k, dtype="bfloat16", platform="tpu")
    assert registry.pallas_wave_tiles(bf16) == []
    with pytest.raises(Exception, match="multiple of 8"):
        _compile(lambda A, C, S: rot_sequence_wave(
                     A, C, S, interpret=False, n_b=64, k_b=16, m_blk=256),
                 one_chip, (m, n), (n - 1, k), (n - 1, k),
                 dtype=jnp.bfloat16)

    offered = registry.pallas_mxu_tiles(
        registry.Problem(m=m, n=n, k=k, platform="tpu"))
    assert all(p.n_b % 128 == 0 for p in offered)
    with pytest.raises(Exception, match="divisible by 8 and 128"):
        _compile(lambda A, C, S: rot_sequence_mxu(
                     A, C, S, interpret=False, n_b=64, k_b=64, m_blk=256),
                 one_chip, (m, n), (n - 1, k), (n - 1, k))

    b, m, n, k = 4, 256, 344, 32
    assert smem_panel_bytes(n - 1, k) > SMEM_PANEL_BUDGET
    assert registry.rotseq_batched_tiles(registry.Problem(
        m=m, n=n, k=k, platform="tpu", batch=b,
        shared_sequence=False)) == []
    with pytest.raises(Exception, match="smem"):
        _compile(lambda A, C, S: rot_sequence_batched(
                     A, C, S, interpret=False, m_blk=256),
                 one_chip, (b, m, n), (b, n - 1, k), (b, n - 1, k))


# the four-chip eigenvector cell: V is 65536 x 65536, k = 32 per flush
EVACC = (65536, 65536, 32)


def _evacc_plan():
    m, n, k = EVACC
    registry.clear_plan_cache()
    plan = registry.select_plan(m, n, k, platform="tpu", sharded=True,
                                devices=4)
    registry.clear_plan_cache()
    return plan


def test_eigenvector_shard_compiles(one_chip, on_tpu):
    """One chip's part of a flush, as the planner tiles it: the
    16384 x 65536 shard swept whole, the factors from the kernel."""
    plan = _evacc_plan()
    assert (plan.method, plan.n_b, plan.k_b) == ("accumulated", 64, 32)
    m, n, k = EVACC
    kw = plan.kwargs()
    assert kw == {"n_b": 64, "k_b": 32}
    text = _compile(lambda A, C, S: rot_sequence_accumulated(A, C, S, **kw),
                    one_chip, (m // 4, n), (n - 1, k), (n - 1, k)).as_text()
    assert "rotseq_factors" in text


def test_eigenvector_flush_fits_a_chip_in_place(topo, on_tpu):
    """The whole flush on a 2x2 mesh, as the cell runs it: the donated
    shard is the output's buffer, and what a chip holds (its shard and
    the sweep's temporaries, about two shards) fits its 16 GB.  Without
    donation the output is a fifth shard-sized buffer, and no chip holds
    the flush."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    m, n, k = EVACC
    mesh = compat.make_mesh((4,), ("data",), devices=topo.devices)
    V = jax.ShapeDtypeStruct((m, n), jnp.float32,
                             sharding=NamedSharding(mesh, P("data", None)))
    W = jax.ShapeDtypeStruct((n - 1, k), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, None)))
    seq = RotationSequence(W, W)
    plan = dist.plan_sharded(seq, like=V, mesh=mesh, method="auto",
                             platform="tpu")
    assert plan.execute_sharded and plan.method == "accumulated"

    def memory(donate):
        return plan._build_2d(False, donate).lower(V, seq).compile() \
            .memory_analysis()

    shard = m // 4 * n * 4
    ma = memory(True)
    assert ma.alias_size_in_bytes == ma.output_size_in_bytes == shard
    held = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert ma.temp_size_in_bytes > 1.9 * shard
    assert held < 16e9, held
    ma = memory(False)
    assert ma.alias_size_in_bytes == 0
    assert ma.argument_size_in_bytes + ma.output_size_in_bytes + \
        ma.temp_size_in_bytes > 16e9
