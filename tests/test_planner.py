"""One planner: ``method="auto"`` is a pure function of the problem.

Every entry point that plans (the typed sequence API, the raw-array
wrapper, the serving buckets, the delayed eig buffer and the sharded
plan) freezes exactly the plan ``registry.select_plan`` gives its
problem, so the choice lives in one module; and resolving it reads and
writes no file, so a plan left on disk by anything else cannot steer
it.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apply_rotation_sequence, random_sequence, registry
from repro.core.registry import Problem, clear_plan_cache, select_plan

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _python(code: str, env=None, devices: int = 1) -> str:
    """Run ``code`` in a fresh interpreter; returns its last stdout line.

    ``env`` replaces the inherited environment (``PATH`` and the JAX
    platform pass through), so nothing but what it names can steer the
    child."""
    if env is None:
        env = dict(os.environ)
    else:
        env = dict(env, **{k: os.environ[k] for k in (
            "PATH", "LD_LIBRARY_PATH", "TMPDIR", "JAX_PLATFORMS")
            if k in os.environ})
    if devices > 1:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout.strip().splitlines()[-1]


# --------------------------------------------------- one planner ----

def _via_sequence_plan():
    seq = random_sequence(jax.random.key(1), 40, 6)
    plan = seq.plan(like=jnp.zeros((24, 40)), method="auto")
    return plan.plan, dict(m=24, n=40, k=6)


def _via_apply_rotation_sequence():
    seq = random_sequence(jax.random.key(2), 40, 6)
    A = jnp.asarray(np.random.default_rng(2).standard_normal((24, 40)),
                    jnp.float32)
    out = apply_rotation_sequence(A, seq.cos, seq.sin, method="auto")
    (plan,) = registry._PLAN_CACHE.values()   # the one plan it resolved
    pinned = seq.plan(like=A, method=plan.method, **plan.kwargs())
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(pinned.apply_direct(A)))
    return plan, dict(m=24, n=40, k=6)


def _via_rotation_service():
    from repro.serve import RotationService

    rng = np.random.default_rng(3)
    svc = RotationService(slots=4)
    # k=5 pads to the bucket's k_pad=8: 31*5 of 31*8 planes are live
    svc.apply_many([(random_sequence(jax.random.key(i), 32, 5),
                     jnp.asarray(rng.standard_normal((16, 32)),
                                 jnp.float32)) for i in range(2)])
    (bucket,) = svc._plans.values()
    return bucket.plan, dict(m=16, n=32, k=8, batch=4,
                             shared_sequence=False, live_planes=31 * 5)


def _via_delayed_buffer():
    from repro.eig import DelayedRotationBuffer

    rng = np.random.default_rng(4)
    buf = DelayedRotationBuffer(jnp.eye(24), k_delay=6)
    for _ in range(6):  # one full flush
        th = rng.standard_normal(23)
        buf.push(np.cos(th), np.sin(th))
    assert buf.flushes == 1
    (flush,) = buf._plans.values()
    return flush.plan, dict(m=24, n=24, k=6, shared_sequence=True)


_ENTRY_POINTS = {
    "SequencePlan.plan": _via_sequence_plan,
    "apply_rotation_sequence": _via_apply_rotation_sequence,
    "RotationService": _via_rotation_service,
    "DelayedRotationBuffer": _via_delayed_buffer,
}


@pytest.mark.parametrize("entry", list(_ENTRY_POINTS)
                         + ["dist.plan_sharded"])
def test_one_planner(entry):
    """The entry point's frozen plan is ``select_plan``'s for its
    problem, resolved afresh: no entry point ranks on its own."""
    if entry == "dist.plan_sharded":
        # a mesh needs host devices, fixed at a process's first JAX use
        out = _python("""
            import jax, jax.numpy as jnp
            from repro import compat, dist
            from repro.core import registry
            from repro.core.rotations import random_sequence
            mesh = compat.make_mesh((8,), ("data",))
            seq = random_sequence(jax.random.key(5), 32, 8)
            registry.clear_plan_cache()
            plan = dist.plan_sharded(seq, like=jnp.zeros((64, 32)),
                                     mesh=mesh, method="auto")
            for sharded in (True, False):
                registry.clear_plan_cache()
                want = registry.select_plan(
                    64, 32, 8, sharded=sharded,
                    devices=8 if sharded else 1)
                if sharded == plan.execute_sharded:
                    assert plan.plan == want, (plan.plan, want)
            print("ok", plan.execute_sharded, plan.method)
        """, devices=8)
        assert out.startswith("ok"), out
        return
    clear_plan_cache()
    try:
        got, problem = _ENTRY_POINTS[entry]()
        clear_plan_cache()
        want = select_plan(**problem)
    finally:
        clear_plan_cache()
    assert got == want, (entry, got, want)
    assert got.source == "model"


# ---------------------------------------- no file read or written ----

# the benchmark's three cells and a serving bucket (256, 128, k=4, 64
# requests per batch, each its own sequence), all planned for the chip
_ON_DISK_CASES = {
    "paper-k180.n3840": dict(m=3840, n=3840, k=180),
    "paper-k180.n960": dict(m=960, n=960, k=180),
    "evacc-4chip.n65536": dict(m=65536, n=65536, k=32, sharded=True,
                               devices=4),
    "serve-bucket.256x128.k4.b64": dict(m=256, n=128, k=4, batch=64,
                                        shared_sequence=False),
}

_RESOLVE = """
    import json
    import jax, jax.numpy as jnp
    from repro import compat
    from repro.core.rotations import random_sequence
    case = {case!r}
    m, n, k = case["m"], case["n"], case["k"]
    seq = random_sequence(jax.random.key(0), n, k)
    if case.get("devices", 1) > 1:
        from repro import dist
        mesh = compat.make_mesh((case["devices"],), ("data",))
        plan = dist.plan_sharded(
            seq, like=jax.ShapeDtypeStruct((m, n), jnp.float32), mesh=mesh,
            method="auto", platform="tpu")
        assert plan.execute_sharded
    elif case.get("batch", 1) > 1:
        import numpy as np
        from repro.serve import RotationService
        svc = RotationService(slots=case["batch"], platform="tpu")
        key = svc._bucket_key(seq, np.zeros((m, n), np.float32))
        seqs, A, rep, _ = svc.assemble_batch(
            key, [seq], [np.zeros((m, n), np.float32)])
        plan = svc._bucket_plan(key, rep, A)
    else:
        plan = seq.plan(like=jax.ShapeDtypeStruct((m, n), jnp.float32),
                        method="auto", platform="tpu")
    p = plan.plan
    print(json.dumps([p.method, p.n_b, p.k_b, p.m_blk]))
"""


def _listing(root):
    out = {}
    for dirpath, dirnames, filenames in os.walk(root):
        for name in dirnames + filenames:
            path = os.path.join(dirpath, name)
            st = os.stat(path)
            out[os.path.relpath(path, root)] = (st.st_size, st.st_mtime_ns)
    return out


def _plant(cache_dir, problem: Problem, other: registry.Plan):
    """Store files, in the layout a planner with a disk store would
    read, that name ``other`` for exactly ``problem``'s key and bucket."""
    jax_version = registry._jax_version_str()
    store = os.path.join(cache_dir, "repro")
    os.makedirs(store)
    plan_record = {"method": other.method, "n_b": other.n_b,
                   "k_b": other.k_b, "m_blk": other.m_blk,
                   "est_seconds": 1e-9}
    with open(os.path.join(store, "plans.json"), "w") as f:
        json.dump({"format": 1, "jax": jax_version, "plans": [dict(
            plan_record, key=list(registry._plan_key(problem)))]}, f)
    bucket = [problem.m, problem.n, problem.dtype, problem.k, False,
              problem.dtype]
    seq_plan = {"format": 1, "jax": jax_version, "method": other.method,
                "kwargs": other.kwargs(), "shape": [problem.n - 1,
                                                   problem.k],
                "dtype": problem.dtype, "signed": False, "reflect": False,
                "plan": dict(plan_record, source="model")}
    with open(os.path.join(store, "serve_plans.json"), "w") as f:
        json.dump({"format": 1, "jax": jax_version,
                   "plans": [{"bucket": bucket, "plan": seq_plan}]}, f)


@pytest.mark.parametrize("case", list(_ON_DISK_CASES))
def test_auto_reads_and_writes_no_file(case, tmp_path):
    """A process whose home holds plan stores naming another backend
    for this exact problem still plans the model's choice, and leaves
    the home as it found it."""
    spec = _ON_DISK_CASES[case]
    clear_plan_cache()
    try:
        want = select_plan(platform="tpu", **spec)
    finally:
        clear_plan_cache()
    problem = Problem(platform="tpu", **spec)
    other = (registry.Plan("blocked", n_b=8, k_b=4)
             if want.method != "blocked" else registry.Plan("unoptimized"))
    home = tmp_path / "home"
    cache_dir = home / ".cache"
    _plant(str(cache_dir), problem, other)
    before = _listing(str(home))
    env = {"HOME": str(home), "XDG_CACHE_HOME": str(cache_dir)}
    got = json.loads(_python(_RESOLVE.format(case=spec), env,
                             devices=spec.get("devices", 1)))
    assert got == [want.method, want.n_b, want.k_b, want.m_blk], (got, want)
    assert _listing(str(home)) == before
