"""Paper Fig. 7: parallel speedup over row shards (paper SS7 + SS8.3).

Runs the row-sharded application on 1/2/4/8 devices (the paper
parallelizes over ``i_b`` row blocks with OpenMP; we shard rows over the
mesh).  Also reports the column-sharded pipeline (no CPU analogue in the
paper) with its analytic communication ratio.  On TPU every mesh is
built in this process from the attached chips; elsewhere each device
count runs in a child pinned to ``JAX_PLATFORMS=cpu`` with that many
forced host devices.
"""
import os
import subprocess
import sys

from benchmarks.common import emit

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _measure(D, devices=None):
    """``(row_seconds, col_seconds, comm_ratio)`` on a D-device mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import compat
    from repro.core.rotations import random_sequence
    from repro.dist import (column_sharded_comm_bytes,
                            rot_sequence_column_sharded_padded,
                            rot_sequence_row_sharded)
    from repro.obs import timing

    mesh = compat.make_mesh((D,), ("data",), devices=devices)
    m, n, k = 2048, 512, 64
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    seq = random_sequence(jax.random.key(0), n, k)

    def timed(fn):
        jax.block_until_ready(fn())
        ts = []
        for _ in range(3):
            t0 = timing.now()
            jax.block_until_ready(fn())
            ts.append(timing.now() - t0)
        return sorted(ts)[1]

    row = timed(lambda: rot_sequence_row_sharded(
        A, seq, mesh, row_axes=("data",), n_b=64, k_b=16,
        method="accumulated"))
    mesh2 = compat.make_mesh((1, D), ("data", "model"), devices=devices)
    col = timed(lambda: rot_sequence_column_sharded_padded(
        A, seq, mesh2, col_axis="model", n_b=32, k_b=16,
        row_axes=(), method="accumulated"))
    comm = column_sharded_comm_bytes(m, n, k, D, 32, 16)
    return row, col, comm["ratio"]


def _measure_on_host(D):
    """:func:`_measure` in a child with ``D`` forced CPU host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={D}")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), _ROOT, env.get("PYTHONPATH", "")])
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_fig7", "--child", str(D)],
        capture_output=True, text=True, timeout=600, env=env, cwd=_ROOT)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT")]
    if not line:
        raise RuntimeError(f"fig7 child D={D} failed (rc {r.returncode}):"
                           f"\n{r.stdout}\n{r.stderr}")
    return tuple(map(float, line[0].split()[1:]))


def run():
    from repro import compat

    on_tpu = compat.is_tpu()
    devs = compat.devices() if on_tpu else None
    base = None
    for D in (1, 2, 4, 8):
        if on_tpu and D > len(devs):
            break
        row_t, col_t, ratio = (_measure(D, devs[:D]) if on_tpu
                               else _measure_on_host(D))
        if D == 1:
            base = row_t
        emit(f"fig7/row_sharded/D{D}", row_t,
             f"speedup_{base/row_t:.2f}x")
        emit(f"fig7/col_pipeline/D{D}", col_t,
             f"comm_ratio_vs_allgather_{ratio:.0f}x")


if __name__ == "__main__":
    if sys.argv[1:2] == ["--child"]:
        print("RESULT", *_measure(int(sys.argv[2])))
    else:
        run()
