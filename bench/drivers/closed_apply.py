"""Closed loop of rotation-sequence applications chained on one target.

Traffic parameters (``bench/traffic/<name>.json``): ``m``, ``n`` (the
target), ``in_flight`` (applications enqueued ahead of the oldest
unfinished one), ``ring`` (distinct sequences made at set-up and taken
in turn, as every flush of an eigensolver brings new rotations),
``check_rows``, ``check_apps`` (applications compared, drawn from the
seed), ``limits`` and ``trace_seconds`` (the window of a traced run,
short enough for the device's trace buffer).  The configuration
gives ``k``,
``dtype`` and ``method``.

Each application goes through the program's normal path: the sequence
is planned once (``seq.plan(like=A, method=...)``) and every
application rebinds that plan to the next waves of the ring, as the
delayed-rotation buffer's flushes do.  The waves are arguments of the
compiled program, never constants.

Check: after every application a probe reads, from the timed output,
``check_rows`` rows drawn from the seed and the projection ``y @ A``
for a row vector ``y`` drawn from the seed.  Rows never mix under the
rotations, so once the window has closed the reference applies each
application's waves to the probe of its input (the previous output)
and compares.  The projection sees every entry of the target, so an
answer altered anywhere, or rows left out, shows in it.
"""
from __future__ import annotations

import collections
import math
import sys
import time

import numpy as np

from bench import reference, work

PROBE_PROGRAM = "jit_bench_probe"


def _make_data(key, m, n, k, ring, dtype):
    import jax
    import jax.numpy as jnp

    ka, kw = jax.random.split(key)
    A = jax.random.normal(ka, (m, n), jnp.float32).astype(dtype)
    theta = jax.random.uniform(kw, (ring, n - 1, k), jnp.float32,
                               0.0, 2.0 * math.pi)
    return A, jnp.cos(theta).astype(dtype), jnp.sin(theta).astype(dtype)


def make_inputs(seed: int, m: int, n: int, k: int, ring: int, dtype,
                check_rows: int):
    """From the seed: the target and the ring's waves, made on the device
    in one compiled call, and the rows and projection the check reads."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    key = jax.random.key(int(rng.integers(0, 2**31 - 1)))
    A, C, S = jax.jit(_make_data, static_argnums=(1, 2, 3, 4, 5))(
        key, m, n, k, ring, dtype)
    rows = np.sort(rng.choice(m, check_rows, replace=False))
    y = jnp.asarray(rng.standard_normal(m), jnp.float32)
    return A, C, S, rows, y


def bench_probe(X, y, rows):
    import jax
    import jax.numpy as jnp

    X = X.astype(jnp.float32)
    proj = jnp.dot(y, X, precision=jax.lax.Precision.HIGHEST)
    return jnp.concatenate([X[rows], proj[None]], axis=0)


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.sequence import RotationSequence

    conf, traffic = ctx.cell.config, ctx.cell.traffic
    m, n, k = int(traffic["m"]), int(traffic["n"]), int(conf["k"])
    ring, in_flight = int(traffic["ring"]), int(traffic["in_flight"])
    dtype = jnp.dtype(ctx.dtype or conf["dtype"])
    A, C, S, rows, y = make_inputs(ctx.seed, m, n, k, ring, dtype,
                                   int(traffic["check_rows"]))
    seqs = [RotationSequence(C[i], S[i]) for i in range(ring)]
    # the configuration's plan, made on the stated dtype; the control
    # runs the same backend and tiles in its lower precision
    plan = seqs[0].plan(like=jax.ShapeDtypeStruct((m, n), conf["dtype"]),
                        method=conf["method"])
    if dtype != jnp.dtype(conf["dtype"]):
        plan = seqs[0].plan(like=A, method=plan.method,
                            **dict(plan.kwargs))
    plans = [plan.rebind(s) for s in seqs]
    method, kwargs = plan.method, dict(plan.kwargs)
    rows_d = jnp.asarray(rows, jnp.int32)
    probe = jax.jit(bench_probe)
    # set-up: every program the window runs, compiled and run once
    probe0 = probe(A, y, rows_d)
    warm = A
    for i in range(min(ring, 2)):
        warm = plans[i].apply(warm)
        probe(warm, y, rows_d)
    jax.block_until_ready(warm)
    del warm

    done_t, probes = [], []
    pending = collections.deque()
    X = A
    seconds = ctx.window_seconds(traffic)
    with ctx.window() as box:
        t0 = time.perf_counter()
        box_t0_unix = time.time()
        i = 0
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation("bench.enqueue"):
                X = plans[i % ring].apply(X)
                pending.append(probe(X, y, rows_d))
            i += 1
            if len(pending) >= in_flight:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    probes.append(jax.block_until_ready(pending.popleft()))
                done_t.append(time.perf_counter())
        with jax.profiler.TraceAnnotation("bench.wait"):
            while pending:
                probes.append(jax.block_until_ready(pending.popleft()))
                done_t.append(time.perf_counter())
    launched = i
    memory_peak = ctx.memory_peak()
    # free the program's state before the reference runs
    C_h = np.asarray(C.astype(jnp.float32), np.float64)
    S_h = np.asarray(S.astype(jnp.float32), np.float64)
    got = np.stack([np.asarray(p, np.float64) for p in [probe0] + probes])
    del X, A, C, S, seqs, plans, plan, probes, probe0

    in_window = [t for t in done_t if t - t0 <= seconds]
    fl = work.flops(m, n, k)
    rate = fl * len(in_window) / (in_window[-1] - t0) / 1e9 \
        if in_window else float("nan")
    least, bound = least_per_application(conf, traffic, ctx.peaks,
                                         dtype.itemsize)
    print(f"[apply] plan {method} {kwargs}; {launched} applications; "
          f"least time {least:.6e} s each, {bound}-bound", file=sys.stderr,
          flush=True)
    print(f"[apply] {describe_intervals(t0, in_window)}", file=sys.stderr,
          flush=True)

    t_check = time.perf_counter()
    apps = pick_checked(launched, int(traffic["check_apps"]), ctx.seed)
    gaps = check(got, C_h, S_h, ring, apps)
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


def least_per_application(conf: dict, traffic: dict, peaks: dict,
                          itemsize: int = 4):
    """``(seconds, bound)`` of one application's work at the peaks: the
    algorithm's flops and bytes from the shapes, whatever backend the
    configuration's ``method`` picks."""
    m, n, k = int(traffic["m"]), int(traffic["n"]), int(conf["k"])
    return work.least_seconds(work.flops(m, n, k),
                              work.bytes_moved(m, n, k, itemsize), peaks)


def describe_intervals(t0: float, done: list) -> str:
    """One line on the times between successive completions in the
    window (the first from its start): their quantiles in ms, how many
    exceed the median by a tenth, and where the three longest fell.  A
    run that completes fewer applications shows here whether the time
    was lost in one stall or spread over every application."""
    if not done:
        return "no completion in the window"
    iv = np.diff(np.concatenate([[t0], done])) * 1e3
    q = np.quantile(iv, [0.0, 0.1, 0.5, 0.9, 1.0])
    slow = int(np.sum(iv > 1.1 * q[2]))
    at = sorted((float(done[i] - t0) for i in np.argsort(iv)[-3:]))
    return (f"intervals ms min/p10/p50/p90/max "
            f"{'/'.join(f'{x:.2f}' for x in q)}; {slow} of {len(iv)} over "
            f"1.1x the median; longest three end at "
            f"{', '.join(f'{t:.2f}' for t in at)} s")


def pick_checked(launched: int, most: int, seed: int) -> np.ndarray:
    """The applications compared with the reference: all of them, or
    ``most`` drawn from the seed, the last one always among them."""
    if launched <= most:
        return np.arange(launched)
    rng = np.random.default_rng([seed, 4])
    picked = rng.choice(launched - 1, most - 1, replace=False)
    return np.sort(np.append(picked, launched - 1))


def check(got: np.ndarray, C: np.ndarray, S: np.ndarray, ring: int,
          apps: np.ndarray) -> np.ndarray:
    """For each application in ``apps``, the larger of the rows' and the
    projection's relative gap to the reference applied to the probe of
    its input.

    ``got[i]`` is the probe of the output of application ``i - 1``
    (``got[0]``: the initial target); application ``i`` used the waves
    ``i % ring``.  The applications go through the reference at once,
    grouped by their waves.
    """
    groups = [apps[apps % ring == q] for q in range(ring)]
    per = max(len(g) for g in groups)
    idx = np.stack([np.resize(g, per) if len(g) else np.zeros(per, int)
                    for g in groups])
    ref = reference.apply_waves(got[idx], C[:, None], S[:, None])
    gaps = np.empty(len(apps))
    pos = {int(i): j for j, i in enumerate(apps)}
    for q, g in enumerate(groups):
        for j, i in enumerate(g):
            out, want = got[i + 1], ref[q, j]
            gaps[pos[int(i)]] = max(
                reference.rel_gap(out[:-1], want[:-1]),
                reference.rel_gap(out[-1:], want[-1:]))
    return gaps
