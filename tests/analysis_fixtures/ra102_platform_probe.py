# repro-lint: fixture-as=examples/bad_probe.py
"""RA102 fixture: platform probed outside compat.py."""
import jax


def which_backend():
    return jax.default_backend()  # expect: RA102


def how_many():
    return len(jax.devices())  # expect: RA102
