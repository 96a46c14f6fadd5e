"""The harness on the CPU: names, seeds, the result line, no chip."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import spec
from bench.tests import tiny

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "checks"]


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", "paper-k180.n960", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_closed_apply_inputs_are_deterministic_in_the_seed():
    driver = spec.load_module(spec.resolve("paper-k180.n960").driver_path)
    one = driver.make_inputs(2**31 + 3, 16, 16, 4, 2, np.float32, 3)
    two = driver.make_inputs(2**31 + 3, 16, 16, 4, 2, np.float32, 3)
    other = driver.make_inputs(4, 16, 16, 4, 2, np.float32, 3)
    for a, b in zip(one, two):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(one[0]), np.asarray(other[0]))


def _closed_apply():
    return spec.load_module(spec.resolve("paper-k180.n960").driver_path)


@pytest.mark.parametrize("launched,most", [(5, 8), (8, 8), (300, 128)])
def test_checked_applications_are_drawn_from_the_seed(launched, most):
    driver = _closed_apply()
    apps = driver.pick_checked(launched, most, 2**31 + 7)
    assert len(apps) == min(launched, most)
    assert apps[-1] == launched - 1 and list(apps) == sorted(set(apps))
    np.testing.assert_array_equal(
        apps, driver.pick_checked(launched, most, 2**31 + 7))


def test_check_compares_each_application_with_its_own_waves():
    """Probes of a chain of applications agree with the reference; the
    same probes read against the ring in another order do not."""
    from bench import reference

    driver = _closed_apply()
    rng = np.random.default_rng(5)
    ring, n, k = 3, 12, 4
    th = rng.uniform(0, 2 * np.pi, (ring, n - 1, k))
    C, S = np.cos(th), np.sin(th)
    got = [rng.standard_normal((3, n))]
    for i in range(7):
        got.append(reference.apply_waves(got[-1], C[i % ring], S[i % ring]))
    got = np.stack(got)
    apps = np.arange(7)
    assert driver.check(got, C, S, ring, apps).max() < 1e-12
    shifted = np.roll(np.arange(ring), 1)
    assert driver.check(got, C[shifted], S[shifted], ring, apps).min() > 1e-3


def test_intervals_show_where_a_stall_fell():
    driver = _closed_apply()
    done = list(0.1 * np.arange(1, 11))
    done[6:] = [t + 2.0 for t in done[6:]]      # a 2 s stall before the 7th
    line = driver.describe_intervals(0.0, done)
    assert "1 of 10 over 1.1x the median" in line
    assert "/2100.00;" in line and "2.70" in line
    assert driver.describe_intervals(0.0, []) == \
        "no completion in the window"


def test_an_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.resolve("paper-k180.n7")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny.make_checkout(str(tmp_path_factory.mktemp("checkout")))


def test_a_new_cell_is_files_and_entries_only(checkout):
    """The tiny cell is added without editing a file that exists:
    every file of bench/ is unchanged and every entry of BENCHMARK.json
    is kept as it was."""
    for dirpath, _, files in os.walk(os.path.join(checkout, "bench")):
        for name in files:
            path = os.path.join(dirpath, name)
            rel = os.path.relpath(path, checkout)
            orig = os.path.join(spec.ROOT, rel)
            if os.path.exists(orig):
                with open(path, "rb") as f, open(orig, "rb") as g:
                    assert f.read() == g.read(), rel
    new = spec.load_benchmark(checkout)
    old = spec.load_benchmark()
    for key in ("configs", "workloads"):
        assert new[key][:len(old[key])] == old[key]
    for key in ("end_to_end", "per_layer"):
        for a, b in zip(new[key], old[key]):
            assert {k: v for k, v in a.items() if k != "workloads"} == \
                {k: v for k, v in b.items() if k != "workloads"}
            assert a.get("workloads", [])[:len(b.get("workloads", []))] == \
                b.get("workloads", [])


@pytest.mark.parametrize("cell", [tiny.APPLY_CELL])
def test_result_line_has_the_contract_keys(checkout, cell):
    result = tiny.run_cell(checkout, cell)
    line = json.loads(json.dumps(result))
    assert list(line) == CONTRACT_KEYS
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    c = spec.resolve(cell, checkout)
    assert set(line["metrics"]) == {m["name"] for m in c.end_to_end}
    for m in line["metrics"].values():
        assert m["value"] > 0 and set(m) == {"value", "unit"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    for check in line["checks"].values():
        assert set(check) == {"value", "limit"}


@pytest.mark.parametrize("cell", [tiny.APPLY_CELL])
def test_traced_run_reads_every_per_layer_metric(checkout, cell):
    result = tiny.run_cell(checkout, cell, trace=1)
    line = json.loads(json.dumps(result))
    assert list(line) == CONTRACT_KEYS[:3] + ["breakdown"] + \
        CONTRACT_KEYS[3:]
    assert line["correct"] is True, line
    c = spec.resolve(cell, checkout)
    assert set(line["metrics"]) == {m["name"] for m in c.per_layer}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["device_ops"]
    assert len(line["breakdown"]["idle_gaps"]) <= 10
