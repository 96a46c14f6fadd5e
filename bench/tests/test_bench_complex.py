"""The complex cell's files on the CPU: a tiny copy of
``zsteqr-c64.z16384`` driven whole through ``bench.run``, its reference,
the faults its check must catch, the control, and the metric
``sweep_roofline.apply``.

The tiny cell is added the way ``bench/tests/tiny.py`` adds its own:
new configuration and traffic files and new entries in a copy of
``BENCHMARK.json``, with the limit of the real cell.
"""
import json
import os

import numpy as np
import pytest

from bench import reference, reference_complex, spec, trace, xspace
from bench.tests import tiny

CELL = "tiny-c64.z48"
METRIC = "sweep_roofline.apply"
SCOPED = os.path.join(spec.BENCH_DIR, "testdata",
                      "rotseq_accumulated_n256_k8.xplane.pb")


def _traffic(name: str) -> dict:
    with open(os.path.join(spec.BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tiny.make_checkout(str(tmp_path_factory.mktemp("checkout")))
    with open(os.path.join(spec.ROOT, "bench", "configs",
                           "zsteqr-c64.json")) as f:
        conf = dict(json.load(f), k=8)
    traffic = dict(_traffic("z16384"), m=32, n=48, ring=3, check_rows=4,
                   check_apps=40, trace_seconds=1.0)

    def write(rel, obj):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)

    write("bench/configs/tiny-c64.json", conf)
    write("bench/traffic/z48.json", traffic)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-c64", "source": "test",
                             "reduced": [], "why": "test",
                             "file": "bench/configs/tiny-c64.json"})
    bench["workloads"].append({"name": CELL, "config": "tiny-c64",
                               "traffic": "z48", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "zsteqr-c64.z16384" in m.get("workloads", []):
            m["workloads"].append(CELL)
    write("BENCHMARK.json", bench)
    return root


def _waves(rng, n, k):
    th = rng.uniform(0, 2 * np.pi, (n - 1, k))
    return np.cos(th), np.sin(th)


def test_reference_is_zlasr_on_the_real_and_imaginary_parts():
    """ZLASR's real rotations update the real and imaginary parts alike:
    the complex reference is the real one on each part, and the
    complex128 oracle of the program agrees with it."""
    from repro.core.ref import rot_sequence_numpy

    rng = np.random.default_rng(3)
    n, k = 13, 5
    C, S = _waves(rng, n, k)
    Z = rng.standard_normal((4, n)) + 1j * rng.standard_normal((4, n))
    got = reference_complex.apply_waves(Z, C, S)
    assert got.dtype == np.complex128
    want = reference.apply_waves(Z.real, C, S) + \
        1j * reference.apply_waves(Z.imag, C, S)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-13)
    np.testing.assert_allclose(rot_sequence_numpy(Z, C, S), got, rtol=0,
                               atol=1e-13)
    with pytest.raises(TypeError, match="real rotations"):
        reference_complex.apply_waves(Z, C + 0j, S)


def test_rel_gap_is_in_complex_modulus():
    ref = np.array([3 + 4j, 0.0])
    assert reference_complex.rel_gap(ref + [0, 1j], ref) == 0.2


def test_the_tiny_complex_cell_is_correct(checkout):
    result = tiny.run_cell(checkout, CELL)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    for name in ("gap", "probe"):
        assert result["checks"][name]["value"] < 1e-5, result["checks"]
    assert set(result["metrics"]) == {"setup_s", "rot_gflops"}


def _conjugated(orig):
    def apply(self, A, *args, **kw):
        return orig(self, A, *args, **kw).conj()
    return apply


def _imaginary_altered(orig):
    def apply(self, A, *args, **kw):
        out = orig(self, A, *args, **kw)
        # one entry's imaginary part off by about one standard deviation
        return out.at[..., 1, 2].add(1j)
    return apply


@pytest.mark.parametrize("fault", [_conjugated, _imaginary_altered],
                         ids=["conjugated", "imaginary_altered"])
def test_an_altered_imaginary_part_is_not_correct(checkout, monkeypatch,
                                                  fault):
    from repro.core.sequence import SequencePlan

    monkeypatch.setattr(SequencePlan, "apply",
                        fault(SequencePlan.apply))
    result = tiny.run_cell(checkout, CELL)
    assert result["correct"] is False, result["checks"]
    assert result["failed"] > 0


def test_a_probe_that_keeps_only_the_real_part_is_not_correct(
        checkout, monkeypatch):
    load = spec.load_module

    def load_patched(path, name=None):
        mod = load(path, name)
        if path.endswith("closed_apply_complex.py"):
            probe = mod.bench_probe

            def real_only(X, y, rows):
                # the imaginary parts read back as zeros
                return probe(X, y, rows).at[1].set(0.0)
            mod.bench_probe = real_only
        return mod

    monkeypatch.setattr(spec, "load_module", load_patched)
    result = tiny.run_cell(checkout, CELL)
    assert result["correct"] is False, result["checks"]
    probe = result["checks"]["probe"]
    assert probe["value"] > probe["limit"], result["checks"]


def test_the_complex_control_is_not_correct(checkout):
    """The program's bfloat16 path on Z's real view, in the cell's
    place, reads well over the limit."""
    result = tiny.run_cell(checkout, CELL, dtype="bfloat16")
    assert result["correct"] is False
    gap = result["checks"]["gap"]
    assert gap["value"] > 10 * gap["limit"], gap


def test_sweep_roofline_reads_the_phases_the_driver_passes():
    """Finite where the layer carries the sweep's seconds, as the driver
    reads them from a TPU trace; nothing where it does not."""
    read = spec.metric_reader(METRIC)
    phases = xspace.reduce_file(SCOPED)
    assert phases["rotseq.sweep"] > 0
    layer = {"least_s": 1e-5, "exclude_programs": "jit_bench_probe"}
    value = read(trace.ReaderView(reduction=None,
                                  layer=dict(layer, phases=phases)))
    assert value == pytest.approx(100.0 * 1e-5 / phases["rotseq.sweep"])
    assert np.isfinite(value) and value > 0
    assert read(trace.ReaderView(reduction=None, layer=layer)) is None
    assert read(trace.ReaderView(reduction=None,
                                 layer=dict(layer, phases={}))) is None
    other = {p: s for p, s in phases.items() if p != "rotseq.sweep"}
    assert read(trace.ReaderView(reduction=None,
                                 layer=dict(layer, phases=other))) is None


def test_a_traced_cpu_run_leaves_the_sweep_metric_out(checkout):
    """On the CPU the trace has no TPU plane, so the driver passes no
    phases and the metric is left out of the line, not raised."""
    result = tiny.run_cell(checkout, CELL, trace=1, seconds=0.5)
    assert result["correct"] is True, result["checks"]
    assert METRIC not in result["metrics"]
    assert "rotseq_roofline.apply" in result["metrics"]


def test_the_cell_is_declared_with_its_files():
    cell = spec.resolve("zsteqr-c64.z16384")
    assert cell.chips == 1
    assert cell.config["dtype"] == "complex64"
    assert cell.config["waves"] == "float32"
    assert os.path.basename(cell.driver_path) == "closed_apply_complex.py"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "rot_gflops"]
    assert [m["name"] for m in cell.per_layer] == [
        "rotseq_roofline.apply", "idle.apply", METRIC]
