"""Run the rotation-sequence library end to end on a TPU and check it.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # the sharded path, 4 chips

One chip: the paper's experiment (k=180 waves on a 3840 x 3840 float32
target) through ``method="auto"`` and the two Pallas kernels pinned, a
``StreamEngine`` serving a few hundred requests over four buckets, and
an n=1024 symmetric eigendecomposition whose eigenvectors accumulate
through planned device applications.  ``--four-chips`` runs only the
``repro.dist`` paths on a 4-device mesh: a row-sharded batched bucket
and the column-panel pipeline, each against its replicated result.

Every phase prints its lines as ``[phase] ...``; compile and steady
call times are set-up and smoke numbers, not a benchmark.  A failed
check raises, so the script exits non-zero and prints no result.  The
last line of standard output is one JSON object naming the device.
Without a TPU (``JAX_PLATFORMS=cpu``) or outside this checkout it
fails.  All data is made from ``--seed``.
"""
import argparse
import importlib.metadata
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# Written tolerances, float32.  Applying k waves touches every entry
# with 2k plane updates, each adding O(eps) relative error, so results
# of any backend must agree with the jnp ``blocked`` backend and with
# the float64 oracle to 2*k*eps of the largest entry.
EPS32 = float(np.finfo(np.float32).eps)


def apply_tol(k: int) -> float:
    return 2 * k * EPS32


# eigendecomposition at n=1024 (~4000 accumulated waves): max entry of
# V^T V - I, and of H V - V diag(w) over max |H|
EIG_TOL = 1e-4

PAPER_N = 3840      # configs/rotseq_paper.py, the largest size
ORACLE_N = 960      # the size checked against the float64 oracle
SERVE_SLOTS = 64
# (m, n, k) of the serving mix: three dense buckets and one of short
# updates, whose planes the fused kernel skips past k_pad
SERVE_SHAPES = ((256, 128, 32), (512, 128, 16), (1024, 256, 32),
                (256, 128, 4))
SERVE_REQUESTS = 320
EIG_N = 1024
# --four-chips: (b, m, n, k) of a shared-sequence bucket planned with
# method="auto" and of a per-request serving bucket on the fused kernel,
# and (m, n, k) of the column-panel pipeline
FOUR_SHARED = (64, 1024, 512, 64)
FOUR_PER_REQUEST = (64, 1024, 256, 32)
FOUR_COLUMN = (2048, 1024, 64)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def rel_err(x, ref) -> float:
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.abs(x - ref).max() / np.abs(ref).max())


def check(phase: str, what: str, err: float, tol: float) -> None:
    log(phase, f"{what}: {err:.3e} (tol {tol:.3e})")
    if not err <= tol:  # NaN fails too
        raise SystemExit(f"[{phase}] {what}: error {err} exceeds {tol}")


def report_bitwise(phase: str, what: str, x, ref) -> None:
    """The CPU bitwise contract, reported (not enforced) on the chip."""
    same = bool(np.array_equal(np.asarray(x), np.asarray(ref)))
    log(phase, f"{what}: bitwise {'equal' if same else 'DIFFERENT'}")


def compile_and_time(fn, *args, reps: int = 3):
    """``(out, compiled, compile_s, steady_s)``: AOT compile, then the
    median of ``reps`` calls after one warm call."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    out = jax.block_until_ready(compiled(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        ts.append(time.perf_counter() - t0)
    return out, compiled, compile_s, sorted(ts)[len(ts) // 2]


def first_and_steady(fn, *args, reps: int = 3):
    """``(out, first_s, steady_s)`` of ``jax.jit(fn)`` called directly:
    the first call includes its compile."""
    import jax

    f = jax.jit(fn)
    t0 = time.perf_counter()
    out = jax.block_until_ready(f(*args))
    first_s = time.perf_counter() - t0
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t0)
    return out, first_s, sorted(ts)[len(ts) // 2]


def require_kernel(phase: str, what: str, compiled) -> None:
    if "tpu_custom_call" not in compiled.as_text():
        raise SystemExit(f"[{phase}] {what}: no compiled Mosaic kernel "
                         f"(tpu_custom_call) in the program")
    log(phase, f"{what}: compiled Mosaic kernel present (tpu_custom_call)")


# --------------------------------------------------------------- phases

def device_phase(four_chips: bool):
    import jax

    devs = jax.devices()
    d = devs[0]
    log("device", f"platform={d.platform} kind={d.device_kind!r} "
                  f"count={len(devs)} jax={jax.__version__} "
                  f"jaxlib={importlib.metadata.version('jaxlib')} "
                  f"libtpu={importlib.metadata.version('libtpu')}")
    if d.platform != "tpu":
        raise SystemExit(f"[device] no TPU: JAX found {d.platform!r}")
    if four_chips and len(devs) != 4:
        raise SystemExit(f"[device] --four-chips needs 4 chips, found "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def paper_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs.rotseq_paper import CONFIG
    from repro.core import registry
    from repro.core.ref import rot_sequence_numpy
    from repro.core.rotations import random_sequence

    k = CONFIG.k
    tol = apply_tol(k)
    for n in (PAPER_N, ORACLE_N):
        phase = f"paper n={n}"
        key = jax.random.key(seed * 7919 + n)
        A = jax.random.normal(key, (n, n), jnp.float32)
        seq = random_sequence(jax.random.fold_in(key, 1), n, k)
        log(phase, f"A {A.shape} float32 ({A.nbytes / 1e6:.0f} MB), "
                   f"k={k} waves")
        ref_plan = seq.plan(like=A, method="blocked")
        ref, _, c_s, s_s = compile_and_time(ref_plan.apply, A)
        log(phase, f"reference blocked {dict(ref_plan.kwargs)}: compile "
                   f"{c_s:.2f} s")
        log(phase, f"reference blocked: steady {s_s * 1e3:.2f} ms/call")
        oracle = None
        if n == ORACLE_N:
            oracle = rot_sequence_numpy(np.asarray(A), np.asarray(seq.cos),
                                        np.asarray(seq.sin))
            check(phase, "blocked vs float64 oracle, max rel err",
                  rel_err(ref, oracle),
                  tol)
        for method in ("auto", "pallas_wave", "pallas_mxu"):
            plan = seq.plan(like=A, method=method)
            source = plan.plan.source if plan.plan is not None else "pinned"
            what = f"{method} -> {plan.method}"
            log(phase, f"plan {what} tiles={dict(plan.kwargs)} "
                       f"source={source}")
            out, compiled, c_s, s_s = compile_and_time(plan.apply, A)
            if registry.get_backend(plan.method).capability.needs_pallas:
                require_kernel(phase, what, compiled)
            log(phase, f"{what}: compile {c_s:.2f} s")
            log(phase, f"{what}: steady {s_s * 1e3:.2f} ms/call")
            check(phase, f"{what} vs blocked on device, max rel err",
                  rel_err(out, ref), tol)
            report_bitwise(phase, f"{what} vs blocked", out, ref)
            if oracle is not None:
                check(phase, f"{what} vs float64 oracle, max rel err",
                      rel_err(out, oracle), tol)


def serving_phase(seed: int) -> None:
    import jax

    from repro.core.ref import rot_sequence_numpy
    from repro.serve import RotationService, StreamEngine
    from repro.serve.rotations import synthetic_stream

    phase = "serving"
    requests = synthetic_stream(SERVE_REQUESTS, shapes=SERVE_SHAPES,
                                seed=seed)
    log(phase, f"{len(requests)} requests over shapes (m, n, k) "
               f"{list(SERVE_SHAPES)}, slots={SERVE_SLOTS}")
    t0 = time.perf_counter()
    with StreamEngine(slots=SERVE_SLOTS, store=False) as eng:
        tickets = [eng.submit(seq, A) for seq, A in requests]
        outs = [t.result(timeout=900.0) for t in tickets]
    wall = time.perf_counter() - t0
    s = eng.stats
    log(phase, f"streamed {s['completed']} requests in {wall:.2f} s, "
               f"compiles included (closes size={s['closes_size']} "
               f"age={s['closes_age']} drain={s['closes_drain']})")
    if s["completed"] != len(requests):
        raise SystemExit(f"[{phase}] {s['completed']} of {len(requests)} "
                         f"requests completed")

    plans = eng.service._plans
    for key, plan in plans.items():
        log(phase, f"bucket (m={key.m}, n={key.n}, k_pad={key.k_pad}) -> "
                   f"{plan.method} tiles={dict(plan.kwargs)}")
    if len(plans) < 3:
        raise SystemExit(f"[{phase}] only {len(plans)} buckets")

    sync = RotationService(slots=SERVE_SLOTS, store=False)
    refs = sync.apply_many(requests)
    same = all(np.array_equal(np.asarray(r), np.asarray(o))
               for r, o in zip(refs, outs))
    if not same:
        raise SystemExit(f"[{phase}] streamed results differ from the "
                         f"synchronous RotationService drain")
    log(phase, "streamed results bit-equal to the synchronous drain")

    # accuracy: the first requests of every bucket against the oracle
    seen = {}
    for (seq, A), out in zip(requests, outs):
        key = (A.shape, seq.k)
        if seen.get(key, 0) >= 4:
            continue
        seen[key] = seen.get(key, 0) + 1
        ref = rot_sequence_numpy(np.asarray(A), np.asarray(seq.cos),
                                 np.asarray(seq.sin))
        check(phase, f"request {A.shape} k={seq.k} vs float64 oracle, "
                     f"max rel err",
              rel_err(out, ref), apply_tol(seq.k))

    fused = [(key, plan) for key, plan in plans.items()
             if plan.method == "rotseq_batched"]
    if not fused:
        raise SystemExit(f"[{phase}] no bucket planned onto the fused "
                         f"rotseq_batched kernel")
    key, plan = fused[0]
    batch = [(seq, A) for seq, A in requests
             if A.shape == (key.m, key.n) and seq.k == key.k_pad]
    batch = batch[:SERVE_SLOTS]
    if len(batch) < SERVE_SLOTS:
        raise SystemExit(f"[{phase}] fewer than {SERVE_SLOTS} unpadded "
                         f"requests in the fused bucket")
    seqs = [seq for seq, _ in batch]
    A = jax.numpy.stack([A for _, A in batch])
    out, compiled, c_s, s_s = compile_and_time(
        lambda x: plan.apply_batched(x, sequences=seqs), A)
    what = f"fused bucket (m={key.m}, n={key.n}, k={key.k_pad})"
    require_kernel(phase, what, compiled)
    log(phase, f"{what}: compile {c_s:.2f} s")
    log(phase, f"{what}: steady {s_s * 1e3:.2f} ms/batch of {SERVE_SLOTS}")
    per_req = np.stack([np.asarray(seq.plan(like=a, method="blocked")
                                   .apply(a)) for seq, a in batch])
    report_bitwise(phase, f"{what} vs per-request blocked", out, per_req)


def eig_phase(seed: int) -> None:
    import jax.numpy as jnp

    from repro import obs
    from repro.eig import eigh_givens

    phase = f"eig n={EIG_N}"
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((EIG_N, EIG_N))
    H = ((X + X.T) / 2).astype(np.float32)
    obs.set_enabled(True)
    obs.reset()
    t0 = time.perf_counter()
    w, V = eigh_givens(jnp.asarray(H), method="qr")
    V = np.asarray(V, np.float64)
    wall = time.perf_counter() - t0
    snap = obs.snapshot()
    obs.set_enabled(False)
    flushes = snap["counters"].get("eig.flushes", 0)
    prefix = "sequence.applies."
    backends = sorted((name[len(prefix):], int(count))
                      for name, count in snap["counters"].items()
                      if name.startswith(prefix))
    log(phase, f"eigh_givens(method='qr'): {wall:.2f} s wall, host "
               f"reduction and compiles included; {flushes:.0f} flushes "
               f"through {backends}")
    if not backends:
        raise SystemExit(f"[{phase}] no planned device application")
    w = np.asarray(w, np.float64)
    H64 = H.astype(np.float64)
    check(phase, "max |V^T V - I|",
          float(np.abs(V.T @ V - np.eye(EIG_N)).max()), EIG_TOL)
    check(phase, "max |H V - V diag(w)| / max |H|",
          float(np.abs(H64 @ V - V * w).max() / np.abs(H64).max()),
          EIG_TOL)


def four_chip_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro import compat, dist, obs
    from repro.core.rotations import random_sequence

    phase = "four-chips"
    mesh = compat.make_mesh((4,), ("data",))
    key = jax.random.key(seed)

    def sharded_vs_replicated(what, seq, A, sequences=None, **plan_kw):
        sh = dist.plan_sharded(seq, like=A, mesh=mesh, **plan_kw)
        log(phase, f"{what}: plan {sh.method} tiles={dict(sh.kwargs)} "
                   f"sharded={sh.execute_sharded} devices={sh.devices}")
        rep = seq.plan(like=A, method=sh.method,
                       shared_sequence=sequences is None, **dict(sh.kwargs))
        run_sh = lambda x: sh.apply_batched(x, sequences=sequences)
        if sh.method == "rotseq_batched":
            require_kernel(phase, what,
                           jax.jit(run_sh).lower(A).compile())
        out, first_s, s_s = first_and_steady(run_sh, A)
        log(phase, f"{what}: sharded first call {first_s:.2f} s "
                   f"(compile included)")
        log(phase, f"{what}: sharded steady {s_s * 1e3:.2f} ms/call")
        ref, first_s, s_s = first_and_steady(
            lambda x: rep.apply_batched(x, sequences=sequences), A)
        log(phase, f"{what}: replicated steady {s_s * 1e3:.2f} ms/call")
        obs.set_enabled(True)
        obs.reset()
        sh.apply_batched(A, sequences=sequences)
        launches = obs.snapshot()["gauges"].get("dist.launches_per_shard")
        obs.set_enabled(False)
        log(phase, f"{what}: launches_per_shard={launches}")
        if sh.execute_sharded and launches != 1:
            raise SystemExit(f"[{phase}] {what}: {launches} launches per "
                             f"shard")
        k = seq.k
        check(phase, f"{what}: sharded vs replicated, max rel err",
              rel_err(out, ref), apply_tol(k))
        report_bitwise(phase, f"{what}: sharded vs replicated", out, ref)

    # a shared-sequence bucket through method="auto" (the planner
    # arbitrates sharded against replicated) ...
    b, m, n, k = FOUR_SHARED
    A = jax.random.normal(key, (b, m, n), jnp.float32)
    seq = random_sequence(jax.random.fold_in(key, 1), n, k)
    sharded_vs_replicated(f"shared bucket b={b} m={m} n={n} k={k} auto",
                          seq, A, method="auto")
    # ... and a serving bucket of per-request sequences through the
    # fused kernel, one launch per shard
    b, m, n, k = FOUR_PER_REQUEST
    A = jax.random.normal(jax.random.fold_in(key, 2), (b, m, n),
                          jnp.float32)
    seqs = [random_sequence(jax.random.fold_in(key, 100 + i), n, k)
            for i in range(b)]
    sharded_vs_replicated(
        f"per-request bucket b={b} m={m} n={n} k={k} rotseq_batched",
        seqs[0], A, sequences=seqs, method="rotseq_batched",
        shared_sequence=False)

    # the column-panel (CAQR-style) pipeline against its replicated run
    mesh2 = compat.make_mesh((1, 4), ("data", "model"))
    m, n, k = FOUR_COLUMN
    A = jax.random.normal(jax.random.fold_in(key, 3), (m, n), jnp.float32)
    seq = random_sequence(jax.random.fold_in(key, 4), n, k)
    col = dist.plan_sharded(seq, like=A, mesh=mesh2, partition="column",
                            col_axis="model", method="blocked")
    what = f"column panels m={m} n={n} k={k} {col.method}"
    log(phase, f"{what}: tiles={dict(col.kwargs)} devices={col.devices}")
    out, first_s, s_s = first_and_steady(col.apply, A)
    log(phase, f"{what}: first call {first_s:.2f} s (compile included)")
    log(phase, f"{what}: steady {s_s * 1e3:.2f} ms/call")
    rep = seq.plan(like=A, method=col.method, **dict(col.kwargs))
    ref = jax.block_until_ready(rep.apply(A))
    check(phase, f"{what}: column-sharded vs replicated, max rel err",
          rel_err(out, ref), apply_tol(k))
    report_bitwise(phase, f"{what}: column-sharded vs replicated", out, ref)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a 4-chip host")
    args = ap.parse_args()

    # measured or persisted plans from another run must not steer this one
    os.environ["REPRO_PLAN_CACHE"] = "off"
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro import compat

    cache = compat.enable_compile_cache()
    device = device_phase(args.four_chips)
    log("device", f"compile cache {cache}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        paper_phase(args.seed)
        serving_phase(args.seed)
        eig_phase(args.seed)
    log("done", f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
