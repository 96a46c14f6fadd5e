"""Closed loop of in-place applications to a row-sharded target.

The back-transformation of a dense symmetric eigensolver, as ScaLAPACK's
``PDSTEQR2`` makes it: the eigenvector matrix V is distributed by rows,
and each flush of delayed QR sweeps applies its rotations to every row
in place.  Rows never mix, so the shards exchange nothing.

Traffic parameters (``bench/traffic/<name>.json``): ``m``, ``n`` (the
target), ``shards`` (the rows split over that many chips, one mesh
axis), ``in_flight`` (flushes enqueued ahead of the oldest unfinished
one), ``ring`` (distinct sequences made at set-up and taken in turn),
``check_rows`` (a multiple of ``shards``: as many rows drawn from the
seed in each shard), ``check_apps``, ``limits`` and ``trace_seconds``,
as in ``closed_apply``.  The configuration gives ``k``, ``dtype`` and
``method``.

The program's normal path does the work: the sequence is planned once
with ``dist.plan_sharded(seq, like=V, mesh=..., method=...)``, and every
flush rebinds that plan to the ring's next waves and applies it with
``donate=True``, chained on the previous result.  V is made on the mesh
from the seed, so no chip ever holds it whole; the waves are made on
the chips and replicated.  Before the window the run fails with a clear
message where the plan is not sharded or cannot apply in place.

Check: after every flush a probe reads, from each shard, its checked
rows and its partial projection ``y_s @ V_s`` for the shard's part of a
row vector ``y`` drawn from the seed.  The host sums the partials in
float64, so the probe adds no collective.  Each probe is copied to the
host as it completes.  The comparison with the reference is
``closed_apply``'s (``check``), on the rows and the summed projection,
run in one process per wave set of the ring.
"""
from __future__ import annotations

import collections
import inspect
import io
import math
import os
import subprocess
import sys
import time

import numpy as np

from bench import work
from bench.drivers import closed_apply

PROBE_PROGRAM = "jit_bench_probe"
AXIS = "data"


def _make_waves(key, n, k, ring, dtype):
    import jax
    import jax.numpy as jnp

    theta = jax.random.uniform(key, (ring, n - 1, k), jnp.float32,
                               0.0, 2.0 * math.pi)
    return jnp.cos(theta).astype(dtype), jnp.sin(theta).astype(dtype)


def _make_target(key, m, n, dtype):
    import jax
    import jax.numpy as jnp

    return jax.random.normal(key, (m, n), jnp.float32).astype(dtype)


def make_inputs(seed: int, mesh, m: int, n: int, k: int, ring: int, dtype,
                check_rows: int):
    """From the seed: the ring's waves (replicated on every chip), a
    function that makes the target on the mesh, rows sharded, and what
    the check reads: per shard, the local indices of its checked rows,
    and the projection vector ``y``, sharded as the rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    shards = mesh.shape[AXIS]
    if m % shards or check_rows % shards:
        raise ValueError(f"m={m} and check_rows={check_rows} must divide "
                         f"over {shards} shards")
    rng = np.random.default_rng(seed)
    key = jax.random.key(int(rng.integers(0, 2**31 - 1)))
    ka, kw = jax.random.split(key)
    replicated = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(AXIS, None))
    C, S = jax.jit(_make_waves, static_argnums=(1, 2, 3, 4),
                   out_shardings=(replicated, replicated))(
        kw, n, k, ring, dtype)

    def target():
        return jax.jit(_make_target, static_argnums=(1, 2, 3),
                       out_shardings=rows)(ka, m, n, dtype)

    m_loc, per = m // shards, check_rows // shards
    local = np.stack([np.sort(rng.choice(m_loc, per, replace=False))
                      for _ in range(shards)]).astype(np.int32)
    y = rng.standard_normal(m).astype(np.float32)
    return (C, S, target, jax.device_put(local, rows),
            jax.device_put(y, NamedSharding(mesh, P(AXIS))))


def bench_probe(x, y, rows):
    """One shard's checked rows and its partial projection ``y @ x``.

    Each row is a dynamic slice: a gather of rows makes XLA copy the
    whole shard first (4.29 GB, 12.8 ms a flush on a v5e)."""
    import jax
    import jax.numpy as jnp

    picked = [jax.lax.dynamic_slice_in_dim(x, rows[0, i], 1, axis=0)
              for i in range(rows.shape[1])]
    part = jnp.dot(y, x.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    return jnp.concatenate([r.astype(jnp.float32) for r in picked]
                           + [part[None]], axis=0)


def make_probe(mesh):
    import jax
    from jax.sharding import PartitionSpec as P

    from repro import compat

    return jax.jit(compat.shard_map(
        bench_probe, mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS), P(AXIS, None)),
        out_specs=P(AXIS, None)))


def assemble(probe: np.ndarray, shards: int) -> np.ndarray:
    """A probe's host copy ``(shards * (r + 1), n)`` as ``closed_apply``
    reads one: the ``shards * r`` checked rows, then the projection, the
    shards' partials summed in float64."""
    parts = np.asarray(probe).reshape(shards, -1, probe.shape[-1])
    rows = parts[:, :-1].reshape(-1, probe.shape[-1]).astype(np.float64)
    proj = parts[:, -1].astype(np.float64).sum(axis=0)
    return np.concatenate([rows, proj[None]], axis=0)


# one wave set's share of the check, in a process of its own: the
# flushes' probes in (input, output) pairs and the waves come on
# standard input, the gaps go to standard output
_CHECK_WORKER = """
import io, sys
import numpy as np
sys.path.insert(0, sys.argv[1])
from bench.drivers import closed_apply
d = np.load(io.BytesIO(sys.stdin.buffer.read()))
gaps = closed_apply.check(d["pairs"], d["C"], d["S"], 1,
                          2 * np.arange(len(d["pairs"]) // 2))
out = io.BytesIO()
np.save(out, gaps)
sys.stdout.buffer.write(out.getvalue())
"""


def check_by_waves(got: np.ndarray, C: np.ndarray, S: np.ndarray,
                   ring: int, apps: np.ndarray) -> np.ndarray:
    """``closed_apply.check``, one process per wave set of the ring.

    The float64 reference over 65536-wide rows is ~2.4e9 rotations for
    128 flushes, some 40 s in one process.  Each process checks the
    flushes of one wave set, given their input and output probes in
    pairs, so the gaps are those ``check`` gives in one process.
    """
    # the checkout that holds this bench/, so the workers import its
    # reference whatever the current directory holds
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(closed_apply.__file__))))
    procs, gaps = [], np.empty(len(apps))
    for q in range(ring):
        sel = np.flatnonzero(apps % ring == q)
        if not len(sel):
            continue
        a = apps[sel]
        buf = io.BytesIO()
        np.savez(buf, pairs=np.stack([got[a], got[a + 1]], axis=1).reshape(
            -1, *got.shape[1:]), C=C[q:q + 1], S=S[q:q + 1])
        proc = subprocess.Popen([sys.executable, "-c", _CHECK_WORKER, root],
                                stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE)
        proc.stdin.write(buf.getvalue())
        proc.stdin.close()
        procs.append((sel, proc))
    for sel, proc in procs:
        out = proc.stdout.read()
        if proc.wait() != 0:
            raise RuntimeError(f"the reference check failed: exit code "
                               f"{proc.returncode}")
        gaps[sel] = np.load(io.BytesIO(out))
    return gaps


def plan_in_place(seq, like, mesh, method: str):
    """The configuration's sharded plan; raises where the program would
    replicate the target or cannot apply it in place."""
    from repro import dist

    plan = dist.plan_sharded(seq, like=like, mesh=mesh, method=method)
    if not plan.execute_sharded:
        raise RuntimeError(
            f"the plan replicates the {like.shape} target on every chip "
            f"({plan!r}); this cell runs the row-sharded plan only")
    if "donate" not in inspect.signature(plan.apply).parameters:
        raise RuntimeError(
            "ShardedSequencePlan.apply takes no donate=: this program "
            "cannot update the target in place")
    return plan


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from repro import compat
    from repro.core.sequence import RotationSequence

    conf, traffic = ctx.cell.config, ctx.cell.traffic
    m, n, k = int(traffic["m"]), int(traffic["n"]), int(conf["k"])
    shards = int(traffic["shards"])
    ring, in_flight = int(traffic["ring"]), int(traffic["in_flight"])
    dtype = jnp.dtype(ctx.dtype or conf["dtype"])
    mesh = compat.make_mesh((shards,), (AXIS,),
                            devices=ctx.devices[:shards])
    C, S, target, rows_d, y_d = make_inputs(
        ctx.seed, mesh, m, n, k, ring, dtype, int(traffic["check_rows"]))
    seqs = [RotationSequence(C[i], S[i]) for i in range(ring)]
    C_h = np.asarray(C.astype(jnp.float32), np.float64)
    S_h = np.asarray(S.astype(jnp.float32), np.float64)
    del C, S
    # the configuration's plan, made on the stated dtype; the control
    # runs the same backend and tiles in its lower precision
    plan = plan_in_place(seqs[0], jax.ShapeDtypeStruct((m, n),
                                                       conf["dtype"]),
                         mesh, conf["method"])
    if dtype != jnp.dtype(conf["dtype"]):
        from repro import dist

        plan = dist.plan_sharded(seqs[0], like=jax.ShapeDtypeStruct(
            (m, n), dtype), mesh=mesh, method=plan.method,
            **dict(plan.kwargs))
    plans = [plan.rebind(s) for s in seqs]
    kwargs = dict(plan.kwargs)
    print(f"[apply] plan {plan.method} {kwargs}; execute_sharded "
          f"{plan.execute_sharded}, {plan.devices} shards of "
          f"{m // shards} x {n}",
          file=sys.stderr, flush=True)
    probe = make_probe(mesh)
    # set-up: the target on the mesh, then two flushes of the chain (the
    # ring's last waves, so the window starts at its first) and their
    # probes: every program the window runs, compiled and run once; the
    # initial target goes to the first flush
    X = target()
    for i in range(ring - 2, ring):
        X = plans[i % ring].apply(X, donate=True)
        probe(X, y_d, rows_d)
    got = [assemble(np.asarray(probe(X, y_d, rows_d)), shards)]

    done_t = []
    pending = collections.deque()
    seconds = ctx.window_seconds(traffic)
    with ctx.window() as box:
        t0 = time.perf_counter()
        box_t0_unix = time.time()
        i = 0
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation("bench.enqueue"):
                X = plans[i % ring].apply(X, donate=True)
                p = probe(X, y_d, rows_d)
                p.copy_to_host_async()
                pending.append(p)
            i += 1
            if len(pending) >= in_flight:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    got.append(assemble(np.asarray(pending.popleft()),
                                        shards))
                done_t.append(time.perf_counter())
        with jax.profiler.TraceAnnotation("bench.wait"):
            while pending:
                got.append(assemble(np.asarray(pending.popleft()), shards))
                done_t.append(time.perf_counter())
    launched = i
    memory_peak = ctx.memory_peak()
    limit_bytes = max((d.memory_stats() or {}).get("bytes_limit", 0)
                      for d in ctx.devices[:shards])
    # free the program's state before the reference runs
    del X, seqs, plans, plan
    got = np.stack(got)

    in_window = [t for t in done_t if t - t0 <= seconds]
    rate = work.flops(m, n, k) * len(in_window) / (in_window[-1] - t0) \
        / 1e9 if in_window else float("nan")
    least, bound = least_per_chip(conf, traffic, ctx.peaks, dtype.itemsize)
    print(f"[apply] {launched} flushes; least time {least:.6e} s each per "
          f"chip, {bound}-bound; fullest chip's memory peak "
          f"{memory_peak} of {limit_bytes} bytes", file=sys.stderr,
          flush=True)
    print(f"[apply] {closed_apply.describe_intervals(t0, in_window)}",
          file=sys.stderr, flush=True)

    t_check = time.perf_counter()
    apps = closed_apply.pick_checked(launched, int(traffic["check_apps"]),
                                     ctx.seed)
    gaps = check_by_waves(got, C_h, S_h, ring, apps)
    check_s = time.perf_counter() - t_check
    limit = float(traffic["limits"]["gap"])
    failed = int(np.sum(~(gaps <= limit)))
    return {
        "t0_unix": box_t0_unix,
        "compiles_in_window": box["compiles"],
        "trace_dir": box.get("trace_dir"),
        "memory_peak_bytes": int(memory_peak),
        "check_s": check_s,
        "attempted": launched,
        "failed": failed,
        "e2e": {"rot_gflops": rate},
        "checks": {"gap": (float(gaps.max()), limit)},
        "layer": {"least_s": least * launched,
                  "exclude_programs": PROBE_PROGRAM},
    }


def least_per_chip(conf: dict, traffic: dict, peaks: dict,
                   itemsize: int = 4):
    """``(seconds, bound)`` of one flush's work on one chip: the
    algorithm's flops and bytes from the shapes over the shards, at one
    chip's peaks.  The trace's busy time is a mean over the chips, so
    this is what it compares with."""
    m, n, k = int(traffic["m"]), int(traffic["n"]), int(conf["k"])
    shards = int(traffic["shards"])
    return work.least_seconds(work.flops(m, n, k) / shards,
                              work.bytes_moved(m, n, k, itemsize) / shards,
                              peaks)
