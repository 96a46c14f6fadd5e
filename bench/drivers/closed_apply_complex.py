"""Closed loop of real rotation sequences applied to a complex target.

The eigenvector back-transformation of LAPACK's complex Hermitian
eigensolver: ``ZSTEQR`` (``COMPZ = 'V'``) applies each implicit QL/QR
sweep's real rotations to the complex eigenvector matrix Z in place,
through ``ZLASR('R', 'V', ...)``.

Traffic parameters (``bench/traffic/<name>.json``): ``m``, ``n`` (Z),
``in_flight``, ``ring``, ``check_rows``, ``check_apps``, ``limits`` and
``trace_seconds``, as in ``closed_apply``.  The configuration gives
``k``, ``dtype`` (Z's, complex), ``waves`` (the rotations' real dtype)
and ``method``.

The program's normal path does the work: the sequence is planned once
on Z (``seq.plan(like=Z, method=...)``) and every flush rebinds that
plan to the ring's next waves, chained on the previous result.  Z and
the ring are made on the chip from the seed in one compiled call.  With
the control's lower precision (``bench/control.py``) the same backend
and tiles run on Z's real view in that precision: the real parts
stacked above the imaginary ones, a ``(2m, n)`` real target.

Check: after every flush a probe reads, from the timed output,
``check_rows`` complex rows drawn from the seed and the projection ``y
@ Z`` for a real row vector ``y`` drawn from the seed, as real and
imaginary parts; each probe is copied to the host as it completes.
Once the window has closed, the complex128 reference
(``bench/reference_complex.py``) applies each checked flush's waves to
the probe of its input, and the gap is the relative modulus of the
difference.  The probe of the initial Z is itself checked against the
same rows and projection computed on the host from a copy of Z, so a
probe that loses a part fails there.

In a traced run the driver also reads, before it returns, the device
seconds of the program's phases (``bench/xspace.py``) for the per-layer
metric ``sweep_roofline.apply``.
"""
from __future__ import annotations

import collections
import math
import sys
import time

import numpy as np

from bench import reference_complex, work, xspace
from bench.drivers import closed_apply

PROBE_PROGRAM = "jit_bench_probe"


def flops(m: int, n: int, k: int) -> float:
    """A real rotation on a complex column pair: 6 flops per row on the
    real parts and 6 on the imaginary ones."""
    return 12.0 * m * (n - 1) * k


def bytes_moved(m: int, n: int, k: int, itemsize: int = 8,
                wave_itemsize: int = 4) -> float:
    """Z read once and written once, the real cosines and sines once."""
    return float(2 * m * n * itemsize + 2 * (n - 1) * k * wave_itemsize)


def least_per_flush(conf: dict, traffic: dict, peaks: dict):
    """``(seconds, bound)`` of one flush's work at the chip's peaks."""
    import jax.numpy as jnp

    m, n, k = int(traffic["m"]), int(traffic["n"]), int(conf["k"])
    return work.least_seconds(
        flops(m, n, k),
        bytes_moved(m, n, k, jnp.dtype(conf["dtype"]).itemsize,
                    jnp.dtype(conf["waves"]).itemsize), peaks)


def _make_data(key, m, n, k, ring, waves):
    import jax
    import jax.numpy as jnp

    kr, ki, kw = jax.random.split(key, 3)
    Z = jax.lax.complex(jax.random.normal(kr, (m, n), jnp.float32),
                        jax.random.normal(ki, (m, n), jnp.float32))
    theta = jax.random.uniform(kw, (ring, n - 1, k), jnp.float32,
                               0.0, 2.0 * math.pi)
    return Z, jnp.cos(theta).astype(waves), jnp.sin(theta).astype(waves)


def make_inputs(seed: int, m: int, n: int, k: int, ring: int, waves,
                check_rows: int):
    """From the seed: Z (complex64) and the ring's real waves, made on
    the device in one compiled call, and the rows and projection vector
    the check reads."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    key = jax.random.key(int(rng.integers(0, 2**31 - 1)))
    Z, C, S = jax.jit(_make_data, static_argnums=(1, 2, 3, 4, 5))(
        key, m, n, k, ring, waves)
    rows = np.sort(rng.choice(m, check_rows, replace=False))
    y = jnp.asarray(rng.standard_normal(m), jnp.float32)
    return Z, C, S, rows, y


def bench_probe(X, y, rows):
    """``(2, r + 1, n)`` float32: the checked rows and ``y @ Z``, real
    parts first, then imaginary parts.  ``X`` is Z, or the control's
    real view ``(2m, n)``, read as its two halves.  Each row is a
    dynamic slice, so no copy of Z is gathered."""
    import jax
    import jax.numpy as jnp

    m = y.shape[0]
    if jnp.iscomplexobj(X):
        parts = (X.real, X.imag)
    else:
        parts = (X[:m], X[m:])
    out = []
    for part in parts:
        part = part.astype(jnp.float32)
        picked = [jax.lax.dynamic_slice_in_dim(part, rows[i], 1, axis=0)
                  for i in range(rows.shape[0])]
        proj = jnp.dot(y, part, precision=jax.lax.Precision.HIGHEST)
        out.append(jnp.concatenate(picked + [proj[None]], axis=0))
    return jnp.stack(out)


def as_complex(probe) -> np.ndarray:
    """A probe's host copy as complex128 ``(r + 1, n)``."""
    p = np.asarray(probe, np.float64)
    return p[0] + 1j * p[1]


def host_probe(X, y: np.ndarray, rows: np.ndarray,
               block: int = 1024) -> np.ndarray:
    """What ``bench_probe`` reads from ``X``, computed on the host in
    float64 from a copy of ``X``, as complex128 ``(r + 1, n)``.

    The probe of the initial Z is checked against it, so that a probe
    that loses a part cannot pass by losing it on both sides of every
    flush."""
    X = np.asarray(X)
    m = y.shape[0]
    if np.iscomplexobj(X):
        def rows_of(lo, hi):
            return X[lo:hi].astype(np.complex128)
    else:
        def rows_of(lo, hi):
            return X[lo:hi].astype(np.float64) + \
                1j * X[m + lo:m + hi].astype(np.float64)
    proj = sum(np.asarray(y[lo:lo + block], np.float64)
               @ rows_of(lo, min(m, lo + block))
               for lo in range(0, m, block))
    picked = [rows_of(int(r), int(r) + 1) for r in rows]
    return np.concatenate(picked + [proj[None]])


def check(got: np.ndarray, C: np.ndarray, S: np.ndarray, ring: int,
          apps: np.ndarray) -> np.ndarray:
    """For each flush in ``apps``, the larger of the rows' and the
    projection's relative gap (in complex modulus) to the reference
    applied to the probe of its input.

    ``got[i]`` is the probe of the output of flush ``i - 1`` (``got[0]``:
    the initial Z); flush ``i`` used the waves ``i % ring``.  The flushes
    go through the reference at once, grouped by their waves.
    """
    groups = [apps[apps % ring == q] for q in range(ring)]
    per = max(len(g) for g in groups)
    idx = np.stack([np.resize(g, per) if len(g) else np.zeros(per, int)
                    for g in groups])
    ref = reference_complex.apply_waves(got[idx], C[:, None], S[:, None])
    gaps = np.empty(len(apps))
    pos = {int(i): j for j, i in enumerate(apps)}
    for q, g in enumerate(groups):
        for j, i in enumerate(g):
            out, want = got[i + 1], ref[q, j]
            gaps[pos[int(i)]] = max(
                reference_complex.rel_gap(out[:-1], want[:-1]),
                reference_complex.rel_gap(out[-1:], want[-1:]))
    return gaps


def phase_seconds(trace_dir) -> dict:
    """Device seconds per phase of the trace under ``trace_dir``
    (``bench/xspace.py``); empty where the trace holds no device plane,
    as on the CPU."""
    if not trace_dir:
        return {}
    try:
        return xspace.reduce_file(xspace.find(trace_dir))
    except (FileNotFoundError, ValueError) as e:
        print(f"[apply] no phases in the trace: {e}", file=sys.stderr,
              flush=True)
        return {}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.sequence import RotationSequence

    conf, traffic = ctx.cell.config, ctx.cell.traffic
    m, n, k = int(traffic["m"]), int(traffic["n"]), int(conf["k"])
    ring, in_flight = int(traffic["ring"]), int(traffic["in_flight"])
    waves = jnp.dtype(conf["waves"])
    W = jax.ShapeDtypeStruct((n - 1, k), waves)
    # the configuration's plan, made on the stated dtype before any
    # data: a program that cannot plan a complex target fails here
    plan = RotationSequence(W, W).plan(
        like=jax.ShapeDtypeStruct((m, n), conf["dtype"]),
        method=conf["method"])
    Z, C, S, rows, y = make_inputs(ctx.seed, m, n, k, ring, waves,
                                   int(traffic["check_rows"]))
    if ctx.dtype is not None:
        # the control: the same backend and tiles on Z's real view
        low = jnp.dtype(ctx.dtype)
        Z = jnp.concatenate([Z.real, Z.imag], axis=0).astype(low)
        C, S = C.astype(low), S.astype(low)
    seqs = [RotationSequence(C[i], S[i]) for i in range(ring)]
    method, kwargs = plan.method, dict(plan.kwargs)
    plan = seqs[0].plan(like=Z, method=method, **kwargs)
    plans = [plan.rebind(s) for s in seqs]
    C_h = np.asarray(C.astype(jnp.float32), np.float64)
    S_h = np.asarray(S.astype(jnp.float32), np.float64)
    del C, S
    rows_d = jnp.asarray(rows, jnp.int32)
    probe = jax.jit(bench_probe)
    # set-up: the anchor, then every program the window runs, compiled
    # and run once
    anchor = host_probe(Z, np.asarray(y), rows)
    got = [as_complex(probe(Z, y, rows_d))]
    warm = Z
    for i in range(min(ring, 2)):
        warm = plans[i].apply(warm)
        probe(warm, y, rows_d)
    jax.block_until_ready(warm)
    del warm

    done_t = []
    pending = collections.deque()
    X = Z
    del Z
    seconds = ctx.window_seconds(traffic)
    with ctx.window() as box:
        t0 = time.perf_counter()
        box_t0_unix = time.time()
        i = 0
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation("bench.enqueue"):
                X = plans[i % ring].apply(X)
                p = probe(X, y, rows_d)
                p.copy_to_host_async()
                pending.append(p)
            i += 1
            if len(pending) >= in_flight:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    got.append(as_complex(pending.popleft()))
                done_t.append(time.perf_counter())
        with jax.profiler.TraceAnnotation("bench.wait"):
            while pending:
                got.append(as_complex(pending.popleft()))
                done_t.append(time.perf_counter())
    launched = i
    memory_peak = ctx.memory_peak()
    phases = phase_seconds(box.get("trace_dir"))
    # free the program's state before the reference runs
    del X, seqs, plans, plan
    got = np.stack(got)

    in_window = [t for t in done_t if t - t0 <= seconds]
    rate = flops(m, n, k) * len(in_window) / (in_window[-1] - t0) / 1e9 \
        if in_window else float("nan")
    least, bound = least_per_flush(conf, traffic, ctx.peaks)
    target = conf["dtype"] if ctx.dtype is None else \
        f"the {ctx.dtype} real view (control)"
    print(f"[apply] plan {method} {kwargs} on {target}; "
          f"{launched} flushes; least time {least:.6e} s each, "
          f"{bound}-bound; memory peak {memory_peak} bytes",
          file=sys.stderr, flush=True)
    print(f"[apply] {closed_apply.describe_intervals(t0, in_window)}",
          file=sys.stderr, flush=True)
    if phases:
        print(f"[apply] device seconds per phase {phases}",
              file=sys.stderr, flush=True)

    t_check = time.perf_counter()
    apps = closed_apply.pick_checked(launched, int(traffic["check_apps"]),
                                     ctx.seed)
    gaps = check(got, C_h, S_h, ring, apps)
    first = max(reference_complex.rel_gap(got[0][:-1], anchor[:-1]),
                reference_complex.rel_gap(got[0][-1:], anchor[-1:]))
    check_s = time.perf_counter() - t_check
    limit = float(traffic["limits"]["gap"])
    failed = int(np.sum(~(gaps <= limit))) + int(not first <= limit)
    return {
        "t0_unix": box_t0_unix,
        "compiles_in_window": box["compiles"],
        "trace_dir": box.get("trace_dir"),
        "memory_peak_bytes": int(memory_peak),
        "check_s": check_s,
        "attempted": launched,
        "failed": failed,
        "e2e": {"rot_gflops": rate},
        "checks": {"gap": (float(gaps.max()), limit),
                   "probe": (first, limit)},
        "layer": {"least_s": least * launched,
                  "exclude_programs": PROBE_PROGRAM,
                  "phases": phases},
    }
