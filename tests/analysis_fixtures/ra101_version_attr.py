# repro-lint: fixture-as=src/repro/parallel/bad_shims.py
"""RA101 fixture: version-sensitive JAX API outside compat.py.

Every spelling here moved, was renamed or changed its defaults between
jax 0.4 and 0.9; all must route through repro.compat.  The aliased forms are the ones
the old compat-gate grep could not see.
"""
import jax
from jax.experimental import shard_map as _smap_mod  # expect: RA101
from jax.experimental.pallas import tpu as pltpu


def bad_direct(f, mesh, specs):
    return jax.shard_map(f, mesh=mesh, in_specs=specs)  # expect: RA101


def bad_aliased(f, mesh, specs):
    return _smap_mod.shard_map(f, mesh=mesh)  # expect: RA101


def bad_typeof(x):
    return jax.typeof(x)  # expect: RA101


def bad_pvary(x):
    return jax.lax.pvary(x, "i")  # expect: RA101


def bad_params():
    return pltpu.CompilerParams(dimension_semantics=())  # expect: RA101


def bad_mesh():
    return jax.make_mesh((2,), ("data",))  # expect: RA101
