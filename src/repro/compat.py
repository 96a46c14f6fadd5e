"""The JAX surface this repo depends on, in one module.

The repo runs one JAX line: 0.9.0 (jaxlib 0.9.0, libtpu 0.0.34).  The
lookups below moved or changed meaning between JAX lines, so they live
here and nowhere else (analyzer rule RA101): the next upgrade edits
this file only.

* :func:`shard_map`, :func:`varying_axes` / :func:`pvary` /
  :func:`pvary_like` — manual sharding and the varying-manual-axes
  ("vma") types that loop carries inside ``shard_map`` must agree on.
* :func:`make_mesh` — the one mesh constructor.  ``jax.make_mesh``
  defaults to Explicit axes on 0.9, under which ``jit`` needs
  ``jax.set_mesh`` and ``with_sharding_constraint`` rejects the axes;
  every mesh here states Auto axes instead.
* :func:`tpu_compiler_params` — ``pltpu.CompilerParams``.
* :func:`default_platform` / :func:`is_tpu` / :func:`devices` /
  :func:`device_kind` — what is attached, for the registry's
  eligibility, interpret mode and peak rates.
* :func:`enable_x64`, :func:`enable_compile_cache` — process config.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Iterable, Optional, Sequence

import jax
from jax.sharding import AxisType

__all__ = [
    "JAX_VERSION",
    "shard_map",
    "make_mesh",
    "varying_axes",
    "pvary",
    "pvary_like",
    "tpu_compiler_params",
    "default_platform",
    "devices",
    "device_kind",
    "is_tpu",
    "is_tracer",
    "pallas_interpret_default",
    "enable_x64",
    "x64_enabled",
    "enable_compile_cache",
]

JAX_VERSION: tuple = tuple(int(v) for v in jax.__version__.split(".")[:3])


# --------------------------------------------------------------------------
# sharding
# --------------------------------------------------------------------------

def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` over ``mesh``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence[Any]] = None):
    """A device mesh whose axes are all ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def varying_axes(x: Any) -> tuple:
    """Mesh axes ``x`` is device-varying over inside ``shard_map``."""
    return tuple(jax.typeof(x).vma)


def pvary(x, axes: Sequence[str]):
    """Cast ``x`` to be device-varying over ``axes``."""
    axes = tuple(axes)
    return jax.lax.pcast(x, axes, to="varying") if axes else x


def pvary_like(x, ref: Any, extra: Iterable[str] = ()):
    """Match ``x``'s varying-axes type to ``ref`` (plus ``extra`` axes).

    The canonical use is making a freshly created constant (identity
    matrix, zero carry) a legal ``scan``/``fori_loop`` carry alongside
    device-varying operands inside ``shard_map``.
    """
    want = set(varying_axes(ref)) | set(extra)
    need = tuple(sorted(want - set(varying_axes(x))))
    return pvary(x, need) if need else x


# --------------------------------------------------------------------------
# Pallas TPU compiler params
# --------------------------------------------------------------------------

def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams(**kwargs)``."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


# --------------------------------------------------------------------------
# platform detection
# --------------------------------------------------------------------------

def default_platform() -> str:
    """Lowercase default backend platform: ``cpu`` / ``gpu`` / ``tpu``."""
    return jax.default_backend().lower()


def devices() -> list:
    """The attached devices of the default backend."""
    return jax.devices()


@functools.lru_cache(maxsize=None)
def device_kind() -> str:
    """``device_kind`` of the first attached device (``"TPU v5 lite"``)."""
    return devices()[0].device_kind


def is_tpu() -> bool:
    return default_platform() == "tpu"


def is_tracer(x: Any) -> bool:
    """Whether ``x`` is an abstract tracer (inside jit/vmap/grad).

    Host-side instrumentation (repro.obs spans and counters) must be a
    no-op under tracing — a traced call dispatches nothing — so every
    instrumented seam guards with this.
    """
    return isinstance(x, jax.core.Tracer)


def pallas_interpret_default() -> bool:
    """Interpret-mode default for Pallas calls: compiled only on TPU."""
    return not is_tpu()


# --------------------------------------------------------------------------
# process configuration
# --------------------------------------------------------------------------

def x64_enabled() -> bool:
    """Whether jnp currently keeps float64 inputs at 64-bit precision."""
    return bool(jax.config.read("jax_enable_x64"))


def enable_x64(enabled: bool = True):
    """Context manager scoping 64-bit mode (float64 eigen/SVD paths)."""
    return jax.enable_x64(enabled)


# <repo>/.jax_cache: one fixed path inside the checkout (git-ignored),
# so a later process in the same checkout finds what this one compiled
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is configured; otherwise the cache goes to
    ``<repo>/.jax_cache``.  Every compile is cached: the kernels compile
    in a second or two, under JAX's default one-second threshold.
    Called by the entry points (``chip_smoke.py``, ``python -m
    repro.launch.serve``), never on import.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
