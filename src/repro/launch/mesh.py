"""Production mesh construction.

``make_production_mesh`` is a function (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``xla_force_host_platform_device_count`` before first jax init.
"""
from __future__ import annotations

from repro import compat

__all__ = ["make_production_mesh", "make_rules_for_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_rules_for_mesh(mesh, *, seq_parallel: bool = False):
    """AxisRules bound to a mesh (drops the "pod" axis on single-pod)."""
    from repro.parallel.sharding import AxisRules

    names = set(mesh.axis_names)
    data_axes = tuple(a for a in ("pod", "data") if a in names)
    rules = {
        "batch": data_axes,
        "heads": "model",
        "kv_heads": "model",
        "ff": "model",
        "vocab": "model",
        "experts": "model",
        "seq": "model" if seq_parallel else None,
        "embed": None,
    }
    return AxisRules(
        rules=rules,
        fsdp_axes=data_axes,
        mesh_shape={a: int(s) for a, s in
                    zip(mesh.axis_names, mesh.devices.shape)},
    )
