"""Distributed-plan suite: sharded-fused vs replicated bucket execution.

Times a batch bucket applied through a row-sharded
:class:`repro.dist.ShardedSequencePlan` (one planned launch per shard
under ``shard_map``) against the replicated
``SequencePlan.apply_batched`` path.  On TPU it runs in this process
over every attached chip; elsewhere it runs on a forced 8-device CPU
host mesh in a subprocess (``XLA_FLAGS=--xla_force_host_platform_
device_count`` must be set before JAX initializes, and the child is
pinned to ``JAX_PLATFORMS=cpu``).  Alongside the measured speedup
the suite reports the comm-extended §6 cost model's view of the same
problem — modeled inter-device bytes and the sharded-vs-replicated
crossover ratio — as deterministic warn-only context rows, so model
retunes surface in the BENCH artifacts without gating unrelated PRs.

Gating rows (``compare_baseline.SPEC``):

* ``dist/sharded_vs_replicated:speedup`` — replicated/sharded wall
  time; the abs_floor encodes "sharded execution stays in its
  performance class on a CPU CI host".
* ``:launches_per_shard`` (count) — exactly one planned launch per
  shard, the PR 10 acceptance invariant.
* ``:parity`` (count) — sharded output bit-identical to replicated.
"""
import os
import subprocess
import sys

from benchmarks.common import emit

HOST_DEVICES = 8
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _measure(D, b, m, n, k, devices=None):
    """``(sh, rep, launches, comm, parity, sh_s, rep_s)`` on a D-mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import compat, dist, obs
    from repro.core.rotations import random_sequence
    from repro.obs import timing

    mesh = compat.make_mesh((D,), ("data",), devices=devices)
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((b, m, n)), jnp.float32)
    seq = random_sequence(jax.random.key(0), n, k)

    plan_sh = dist.plan_sharded(seq, like=A, mesh=mesh, method="blocked")
    plan_rep = seq.plan(like=A, method="blocked", shared_sequence=True)

    def timed(fn):
        jax.block_until_ready(fn())
        ts = []
        for _ in range(5):
            t0 = timing.now()
            jax.block_until_ready(fn())
            ts.append(timing.now() - t0)
        return sorted(ts)[len(ts) // 2]

    sh = timed(lambda: plan_sh.apply_batched(A, direct=True))
    rep = timed(lambda: plan_rep.apply_batched(A, direct=True))

    obs.set_enabled(True)
    obs.reset()
    out = plan_sh.apply_batched(A)
    snap = obs.snapshot()
    obs.set_enabled(False)
    launches = snap["gauges"].get("dist.launches_per_shard", 0.0)
    comm = snap["counters"].get("dist.comm_bytes", 0)
    parity = int(bool(jnp.array_equal(out, plan_rep.apply_batched(A))))
    sh_s, rep_s = dist.modeled_crossover(m, n, k, devices=D, batch=b,
                                         shared_sequence=True)
    return sh, rep, launches, comm, parity, sh_s, rep_s


def _child_main(argv) -> None:
    print("RESULT", *_measure(*map(int, argv)))


def _measure_on_host(D, b, m, n, k):
    """:func:`_measure` in a child with ``D`` forced CPU host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={D}")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), _ROOT, env.get("PYTHONPATH", "")])
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_dist", "--child",
         *map(str, (D, b, m, n, k))],
        capture_output=True, text=True, timeout=900, env=env, cwd=_ROOT)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT")]
    if not line:
        raise RuntimeError(f"dist child failed (rc {r.returncode}):\n"
                           f"{r.stdout}\n{r.stderr}")
    return tuple(map(float, line[0].split()[1:]))


def run(quick: bool = False) -> None:
    from repro import compat

    b, m, n, k = (8, 256, 64, 16) if quick else (64, 512, 128, 32)
    if compat.is_tpu():
        devs = compat.devices()
        D = len(devs)
        res = _measure(D, b, m, n, k, devices=devs)
    else:
        D = HOST_DEVICES
        res = _measure_on_host(D, b, m, n, k)
    sh, rep, launches, comm, parity, sh_s, rep_s = res
    speedup = rep / sh if sh > 0 else 0.0
    emit("dist/sharded_vs_replicated", sh,
         f"speedup_{speedup:.2f}x_D{D}",
         metrics={"speedup": speedup, "parity": parity,
                  "launches_per_shard": launches})
    # deterministic cost-model context: modeled wire traffic for the
    # dispatch above, and how far the model says the sharded plan is
    # from the replicated one at this shape (ratio > 1: sharded wins)
    emit("dist/comm_model", 0.0,
         f"{comm:.0f}B_ratio_{rep_s / sh_s:.2f}",
         metrics={"comm_bytes": comm,
                  "modeled_crossover_ratio": rep_s / sh_s})


def main() -> None:
    """Standalone CLI used by CI: ``bench_dist.py --quick --json PATH``."""
    import argparse

    from benchmarks import common

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small bucket (CI artifact/regression run)")
    ap.add_argument("--json", default=None, metavar="PATH")
    args = ap.parse_args()

    common.reset_results()
    print("name,us_per_call,derived")
    run(quick=args.quick)
    if args.json:
        common.write_json(args.json, meta={"quick": args.quick})


if __name__ == "__main__":
    if sys.argv[1:2] == ["--child"]:
        _child_main(sys.argv[2:])
    else:
        main()
