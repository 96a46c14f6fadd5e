"""The yardstick's arithmetic: work from shapes, peaks, the reference."""
import json
import os

import numpy as np
import pytest

from bench import reference, spec, work


def test_flops_and_bytes_of_the_paper_sizes():
    assert work.flops(3840, 3840, 180) == pytest.approx(1.592e10, rel=1e-3)
    assert work.bytes_moved(3840, 3840, 180) / 1e6 == pytest.approx(
        123.49, abs=0.005)
    assert work.bytes_moved(960, 960, 180) / 1e6 == pytest.approx(
        8.754, abs=0.0005)


def test_least_time_at_3840_is_memory_bound_at_published_peaks():
    peaks = work.load_peaks("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12 and peaks["bytes_per_s"] == 819e9
    t, bound = work.least_seconds(work.flops(3840, 3840, 180),
                                  work.bytes_moved(3840, 3840, 180), peaks)
    assert bound == "memory"
    assert t == pytest.approx(151e-6, rel=0.01)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.load_peaks("TPU v99")


@pytest.mark.parametrize("method", ["auto", "accumulated", "pallas_wave",
                                    "pallas_mxu", "blocked"])
def test_least_time_does_not_depend_on_the_backend(method):
    """The roofline counts the algorithm's work: a cell that pins
    another backend is held to the same least time."""
    cell = spec.resolve("paper-k180.n3840")
    driver = spec.load_module(cell.driver_path)
    peaks = work.load_peaks("TPU v5 lite")
    got = driver.least_per_application(dict(cell.config, method=method),
                                       cell.traffic, peaks)
    assert got == pytest.approx((123_492_960 / 819e9, "memory"))


def test_peaks_are_published_figures_only():
    with open(work.PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    for kind, row in table.items():
        assert "source" in row and row["source"], kind


def loop_reference(A, C, S):
    """Algorithm 1.2 as written: wave by wave, plane by plane."""
    A = np.array(A, np.float64)
    for p in range(C.shape[1]):
        for j in range(A.shape[1] - 1):
            c, s = C[j, p], S[j, p]
            x, y = A[:, j].copy(), A[:, j + 1].copy()
            A[:, j], A[:, j + 1] = c * x + s * y, c * y - s * x
    return A


@pytest.mark.parametrize("m,n,k", [(3, 2, 1), (5, 7, 3), (4, 9, 12),
                                   (2, 33, 5)])
def test_wavefront_reference_equals_the_loop(m, n, k):
    rng = np.random.default_rng(m * 100 + n)
    A = rng.standard_normal((m, n))
    th = rng.uniform(0, 2 * np.pi, (n - 1, k))
    got = reference.apply_waves(A, np.cos(th), np.sin(th))
    np.testing.assert_array_equal(got, loop_reference(A, np.cos(th),
                                                      np.sin(th)))


def test_reference_batches_one_sequence_per_leading_index():
    rng = np.random.default_rng(3)
    X = rng.standard_normal((3, 2, 4, 10))
    th = rng.uniform(0, 2 * np.pi, (3, 1, 9, 6))
    got = reference.apply_waves(X, np.cos(th), np.sin(th))
    for a in range(3):
        for b in range(2):
            want = loop_reference(X[a, b], np.cos(th[a, 0]), np.sin(th[a, 0]))
            np.testing.assert_allclose(got[a, b], want, rtol=0, atol=1e-13)


def test_reference_rejects_mismatched_waves():
    with pytest.raises(ValueError):
        reference.apply_waves(np.zeros((2, 5)), np.ones((3, 1)),
                              np.zeros((3, 1)))


def test_benchmark_files_resolve_by_name():
    """Every cell, configuration, traffic driver and metric named in
    BENCHMARK.json has its file."""
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"])), c
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"])
        assert os.path.isfile(cell.driver_path)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer, w["name"]
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"])), m["name"]
