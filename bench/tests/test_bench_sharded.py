"""The four-chip eigenvector cell on the CPU: its name, its driver on
four forced host devices, its probe, and its collective metric."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench import spec, trace

CELL = "evacc-4chip.n65536"
TINY = "tiny-evacc.s64"
PER_LAYER = {"rotseq_roofline.apply", "idle.apply", "collective_share.dist"}


def _driver():
    return spec.load_module(spec.resolve(CELL).driver_path)


def test_the_cell_resolves_by_name():
    cell = spec.resolve(CELL)
    assert cell.chips == 4
    assert os.path.basename(cell.driver_path) == "sharded_apply.py"
    assert (cell.config["k"], cell.config["dtype"]) == (32, "float32")
    assert (cell.traffic["m"], cell.traffic["n"]) == (65536, 65536)
    assert cell.traffic["shards"] == 4
    assert cell.traffic["limits"]["gap"] == 3e-4
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "rot_gflops"}
    assert {m["name"] for m in cell.per_layer} == PER_LAYER


def test_least_time_is_one_chips_share_of_the_flush():
    """10.49 ms per flush per chip: 34.38 GB over 4 x 819 GB/s."""
    cell = spec.resolve(CELL)
    least, bound = _driver().least_per_chip(
        cell.config, cell.traffic, {"flops_per_s": 197e12,
                                    "bytes_per_s": 819e9})
    assert bound == "memory"
    assert least == pytest.approx(10.49e-3, rel=1e-3)


def _make_checkout(root: str) -> str:
    """``BENCHMARK.json`` and ``bench/`` under ``root``, with a tiny
    four-shard cell added as a later change adds one: new files and new
    entries only."""
    shutil.copytree(spec.BENCH_DIR, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    bench = spec.load_benchmark()

    def write(rel: str, obj: dict) -> None:
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)

    limit = spec.resolve(CELL).traffic["limits"]["gap"]
    write("bench/configs/tiny-evacc.json",
          {"k": 8, "dtype": "float32", "method": "auto", "reduced": []})
    write("bench/traffic/s64.json",
          {"driver": "sharded_apply", "m": 64, "n": 64, "shards": 4,
           "in_flight": 2, "ring": 3, "check_rows": 4, "check_apps": 40,
           "trace_seconds": 1.0, "limits": {"gap": limit}})
    bench["configs"] += [
        {"name": "tiny-evacc", "source": "test", "reduced": [],
         "why": "test", "file": "bench/configs/tiny-evacc.json"}]
    bench["workloads"] += [
        {"name": TINY, "config": "tiny-evacc", "traffic": "s64",
         "chips": 4, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(TINY)
    write("BENCHMARK.json", bench)
    return root


def _run4(code: str) -> tuple:
    """``code`` in a fresh process with four host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [spec.ROOT, os.path.join(spec.ROOT, "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          cwd=spec.ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout, proc.stderr


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return _make_checkout(str(tmp_path_factory.mktemp("checkout")))


@pytest.mark.parametrize("traced", [0, 1])
def test_a_tiny_sharded_cell_runs_on_four_host_devices(checkout, traced):
    """``sharded_apply`` on four forced CPU devices, the cost model
    pricing the sharded plan cheaper as it does at 65536 on four chips:
    the plan is sharded, every flush is donated, nothing compiles inside
    the window, the result is correct, and a traced run reads every
    per-layer metric."""
    out, err = _run4(f"""
        import json
        from repro.dist import plan
        plan.modeled_crossover = lambda *a, **k: (0.0, 1.0)
        from bench import run
        r = run.run(["--workload", "{TINY}", "--seed", str(2**31 + 19),
                     "--seconds", "1.5", "--trace", "{traced}"],
                    require_tpu=False, root={checkout!r})
        print(json.dumps(r))
    """)
    line = json.loads(out.strip().splitlines()[-1])
    assert "execute_sharded True" in err
    assert "compiles inside the window: 0 []" in err
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["count"] == 4
    if traced:
        assert set(line["metrics"]) == PER_LAYER
        assert line["metrics"]["collective_share.dist"]["value"] < 1.0
    else:
        assert set(line["metrics"]) == {"setup_s", "rot_gflops"}


def test_the_shards_partial_projections_sum_to_the_whole():
    """Each shard's probe returns its checked rows and ``y_s @ X_s``;
    the host's float64 sum of the partials is ``y @ X``, and the rows
    are the rows drawn."""
    out, _ = _run4("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import compat
        from bench import spec
        drv = spec.load_module(spec.resolve("evacc-4chip.n65536").driver_path)
        mesh = compat.make_mesh((4,), ("data",))
        C, S, target, rows_d, y_d = drv.make_inputs(
            2**31 + 5, mesh, 64, 32, 4, 2, jnp.float32, 8)
        X = target()
        got = drv.assemble(np.asarray(drv.make_probe(mesh)(X, y_d, rows_d)),
                           4)
        Xh = np.asarray(X, np.float64)
        local = np.asarray(rows_d)
        rows = (np.arange(4)[:, None] * 16 + local).ravel()
        assert np.array_equal(got[:-1], Xh[rows])
        want = np.asarray(y_d, np.float64) @ Xh
        assert np.abs(got[-1] - want).max() < 1e-5 * np.abs(want).max()
        print("PROBE OK")
    """)
    assert "PROBE OK" in out


def test_a_plan_that_cannot_apply_in_place_fails_before_the_window(
        monkeypatch):
    """A program whose ``apply`` takes no ``donate`` cannot update V in
    place: ``sharded_apply`` stops at set-up with a clear message, as it
    does for a replicated plan."""
    from repro import dist

    class NoDonate:
        execute_sharded = True

        def apply(self, A, *, direct=False):
            return A

    class Replicated(NoDonate):
        execute_sharded = False

    drv = _driver()
    for stub, said in ((NoDonate(), "donate"), (Replicated(), "replicates")):
        monkeypatch.setattr(dist, "plan_sharded", lambda *a, **k: stub)
        with pytest.raises(RuntimeError, match=said):
            drv.plan_in_place(None, np.zeros((8, 8)), None, "auto")


def _reduction(ops: dict, devices: int = 4, window_s: float = 2.0):
    return trace.ReaderView(
        reduction=trace.Reduction(window_s=window_s, busy_s=1.9,
                                  devices=devices, programs={}, ops=ops,
                                  gaps=[]),
        layer={})


@pytest.mark.parametrize("ops,share", [
    ({"fusion.1": 6.0, "while": 1.2}, 0.0),
    ({"fusion.1": 6.0, "all-reduce.3": 0.04, "all-gather-start": 0.02,
      "all-gather-done": 0.02}, 1.0),
    ({"reduce-scatter.1": 0.4, "collective-permute-start.2": 0.2,
      "all-to-all": 0.2, "copy.7": 5.0}, 10.0),
])
def test_collective_share_reads_a_reduction(ops, share):
    """Seconds of top-level collective ops summed over four devices,
    averaged over them, over a 2 s window."""
    read = spec.metric_reader("collective_share.dist")
    assert read(_reduction(ops)) == pytest.approx(share)
    assert read(trace.ReaderView(reduction=None, layer={})) is None


def test_probe_copies_are_assembled_in_float64():
    drv = _driver()
    parts = np.arange(2 * 3 * 5, dtype=np.float32).reshape(6, 5)
    got = drv.assemble(parts, 2)
    assert got.dtype == np.float64 and got.shape == (5, 5)
    np.testing.assert_array_equal(got[:4], parts[[0, 1, 3, 4]])
    np.testing.assert_array_equal(got[4], parts[2] + parts[5])


def test_the_check_by_wave_sets_gives_the_one_process_gaps():
    """The reference check split over one process per wave set gives
    ``closed_apply.check``'s gaps, value for value: a chain of probes
    reads near 0, and the same probes against the ring in another order
    do not."""
    from bench import reference
    from bench.drivers import closed_apply

    rng = np.random.default_rng(8)
    ring, n, k = 3, 40, 4
    th = rng.uniform(0, 2 * np.pi, (ring, n - 1, k))
    C, S = np.cos(th), np.sin(th)
    got = [rng.standard_normal((3, n))]
    for i in range(9):
        got.append(reference.apply_waves(got[-1], C[i % ring], S[i % ring]))
    got = np.stack(got)
    apps = np.array([0, 2, 3, 4, 7, 8])
    drv = _driver()
    shifted = np.roll(np.arange(ring), 1)
    for c, s in ((C, S), (C[shifted], S[shifted])):
        np.testing.assert_array_equal(
            drv.check_by_waves(got, c, s, ring, apps),
            closed_apply.check(got, c, s, ring, apps))
    assert drv.check_by_waves(got, C, S, ring, apps).max() < 1e-12
    assert drv.check_by_waves(got, C[shifted], S[shifted], ring,
                              apps).min() > 1e-3


def test_the_check_imports_its_own_checkout_from_any_directory(
        tmp_path, monkeypatch):
    """The check's worker processes import the reference of the checkout
    that holds the driver, not a ``bench`` in the current directory or
    on an inherited ``PYTHONPATH``."""
    from bench import reference
    from bench.drivers import closed_apply

    decoy = tmp_path / "bench" / "drivers"
    decoy.mkdir(parents=True)
    for d in (tmp_path / "bench", decoy):
        (d / "__init__.py").write_text("")
    (decoy / "closed_apply.py").write_text(
        "def check(*a, **k):\n    raise SystemExit(3)\n")
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("PYTHONPATH", str(tmp_path))

    rng = np.random.default_rng(9)
    ring, n, k = 2, 24, 3
    th = rng.uniform(0, 2 * np.pi, (ring, n - 1, k))
    C, S = np.cos(th), np.sin(th)
    got = [rng.standard_normal((3, n))]
    for i in range(4):
        got.append(reference.apply_waves(got[-1], C[i % ring], S[i % ring]))
    got = np.stack(got)
    apps = np.array([0, 1, 3])
    np.testing.assert_array_equal(
        _driver().check_by_waves(got, C, S, ring, apps),
        closed_apply.check(got, C, S, ring, apps))
