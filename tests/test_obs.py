"""repro.obs: metrics primitives, exact dispatch counters, tracing
on the profiler's clock, and the disabled-path bit-identity contract.

The exact-count tests pin the plan-cache counter semantics across the
cold -> warm lifecycle of the in-process memo; the
determinism tests pin the acceptance contract that (a) two identical
runs produce bit-identical snapshots once timing-derived fields are
zeroed, and (b) disabling obs changes neither outputs nor plan-cache
contents.
"""
import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.registry import (clear_plan_cache, plan_cache_stats,
                                 select_plan)
from repro.core.rotations import random_sequence
from repro.serve import RotationService
from repro.serve.rotations import synthetic_stream


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.reset()
    clear_plan_cache()
    yield
    obs.reset()
    clear_plan_cache()


# ------------------------------------------------- metrics primitives ----

def test_histogram_buckets_are_a_pure_function_of_the_value():
    from repro.obs import metrics as m
    # log-spaced, 10 buckets per decade, anchored at 1e-7
    assert m.bucket_index(1e-7) == 0
    assert m.bucket_index(1e-6) == 10
    assert m.bucket_index(1e-1) == 60
    # clamped at both ends: zero/negative and absurdly large values
    assert m.bucket_index(0.0) == 0
    assert m.bucket_index(-1.0) == 0
    assert m.bucket_index(1e9) == m.bucket_index(1e12)
    lo, hi = m.bucket_bounds(m.bucket_index(1e-4))
    assert lo <= 1e-4 < hi


def test_histogram_percentiles_are_geometric_bucket_midpoints():
    with obs.override(True):
        for v in (1e-4,) * 9 + (1e-1,):
            obs.observe("lat", v)
    h = obs.snapshot()["histograms"]["lat"]
    assert h["count"] == 10
    assert h["unit"] == "seconds"
    assert h["min"] == 1e-4 and h["max"] == 1e-1
    assert h["p50"] == pytest.approx(1e-4, rel=0.2)
    assert h["p99"] == pytest.approx(1e-1, rel=0.3)


def test_zeroed_timings_zeroes_seconds_histograms_only():
    with obs.override(True):
        obs.observe("t", 0.123)                   # timing-derived
        obs.observe("waves", 7.0, unit="waves")   # deterministic count
        obs.inc("c", 3)
    z = obs.zeroed_timings(obs.snapshot())
    assert z["histograms"]["t"]["count"] == 1     # structure survives
    assert z["histograms"]["t"]["sum"] == 0.0
    assert z["histograms"]["t"]["p99"] == 0.0
    assert z["histograms"]["waves"]["sum"] == 7.0
    assert z["counters"]["c"] == 3


def test_disabled_hooks_record_nothing():
    with obs.override(False):
        obs.inc("c")
        obs.gauge("g", 1.0)
        obs.observe("h", 0.5)
    snap = obs.snapshot()
    assert snap["counters"] == {}
    assert snap["gauges"] == {}
    assert snap["histograms"] == {}


# ------------------------------------------------------------ tracing ----

@contextlib.contextmanager
def _trace_path(path):
    prev = obs.runtime.set_trace_path(path)
    try:
        yield
    finally:
        obs.runtime.set_trace_path(prev)


def test_span_is_null_without_a_trace_path():
    with obs.override(True):
        with obs.span("apply", m=4) as sp:
            sp.set(method="blocked")
    assert obs.trace.events() == []


def test_trace_exports_perfetto_loadable_chrome_events(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    with obs.override(True):
        prev = obs.runtime.set_trace_path(path)
        try:
            with obs.span("apply", m=4) as sp:
                sp.set(method="blocked")
            n = obs.write_trace()
        finally:
            obs.runtime.set_trace_path(prev)
    assert n == 1
    payload = json.loads(open(path).read())
    (ev,) = payload["traceEvents"]
    assert ev["ph"] == "X" and ev["name"] == "apply"
    assert ev["args"] == {"m": 4, "method": "blocked"}
    assert ev["dur"] >= 0 and ev["ts"] >= 0


def test_span_costs_one_check_with_no_session_and_no_buffer():
    with obs.override(True):
        assert obs.span("apply", m=4) is obs.trace.NULL_SPAN
    with obs.override(False), _trace_path("unused.json"):
        assert obs.span("apply", m=4) is obs.trace.NULL_SPAN


def _host_events(log_dir, name):
    """``(absolute start ns, duration ns)`` of the host-plane events
    called ``name`` in the one trace under ``log_dir``."""
    import glob
    import os
    import warnings

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    start = None
    found = []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            with warnings.catch_warnings():   # the binding type warns
                warnings.simplefilter("ignore", DeprecationWarning)
                start = dict(plane.stats)["profile_start_time"]
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                found += [(e.start_ns, e.duration_ns) for e in line.events
                          if e.name == name]
    assert start is not None
    return [(start + a, d) for a, d in found]


@pytest.mark.parametrize("obs_on", [False, True])
def test_span_is_a_profiler_annotation_on_the_exported_clock(
        tmp_path, obs_on):
    # under a profiler session the span reaches the trace's host plane
    # as repro.<name> whatever REPRO_OBS says; the Chrome export, when
    # on, is stamped on the same (Unix-epoch) clock
    log_dir = str(tmp_path / "xplane")
    with obs.override(obs_on), _trace_path(str(tmp_path / "t.json")):
        with jax.profiler.trace(log_dir):
            with obs.span("probe", m=4):
                jnp.ones(8).block_until_ready()
    (start_ns, dur_ns), = _host_events(log_dir, "repro.probe")
    assert dur_ns > 0
    chrome = [ev for ev in obs.trace.events() if ev["name"] == "probe"]
    if not obs_on:
        assert chrome == []
        return
    (ev,) = chrome
    assert ev["args"] == {"m": 4}
    assert abs(ev["ts"] * 1e3 - start_ns) < 1e6       # within 1 ms
    assert abs(ev["dur"] * 1e3 - dur_ns) < 1e6


def test_obs_on_apply_paths_never_wait_for_the_device(tmp_path,
                                                      monkeypatch):
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((12, 24)), jnp.float32)
    Ab = jnp.asarray(rng.standard_normal((3, 12, 24)), jnp.float32)
    seq = random_sequence(jax.random.key(1), 24, 6)
    plan = seq.plan(like=A, method="blocked")
    want = [plan.apply(A), plan.apply_direct(A), plan.apply_batched(Ab)]
    jax.block_until_ready(want)

    def refuse(*_a, **_k):
        raise AssertionError("an apply path waited for the device")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    with obs.override(True), _trace_path(str(tmp_path / "t.json")):
        got = [plan.apply(A), plan.apply_direct(A), plan.apply_batched(Ab)]
    monkeypatch.undo()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
    names = [ev["name"] for ev in obs.trace.events()]
    assert names == ["apply", "apply", "apply_batched"]
    assert obs.snapshot()["counters"]["sequence.applies.blocked"] == 3


def test_sharded_plan_and_in_place_apply_record_memory_and_donation(
        tmp_path):
    """``dist.plan`` carries the bytes of one device's rows of the
    target and sets the ``dist.shard_bytes`` gauge; an application made
    in place counts in ``dist.donated_applies`` and gives the result of
    one that keeps its input."""
    from repro import compat, dist

    mesh = compat.make_mesh((1,), ("data",))
    A = jnp.asarray(np.random.default_rng(2).standard_normal((256, 128)),
                    jnp.float32)
    seq = random_sequence(jax.random.key(3), 128, 8)
    with obs.override(True), _trace_path(str(tmp_path / "t.json")):
        dist.plan_sharded(seq, like=A, mesh=mesh, method="auto")
        plan = dist.plan_sharded(seq, like=A, mesh=mesh, method="blocked")
        out = plan.apply(A + 0, donate=True)
        plan.apply(A)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plan.apply(A)))
    spans = {ev["name"]: ev["args"] for ev in obs.trace.events()}
    assert spans["dist.plan"]["shard_bytes"] == 256 * 128 * 4
    snap = obs.snapshot()
    assert snap["counters"]["dist.donated_applies"] == 1
    assert snap["counters"]["dist.applies"] == 2
    assert snap["gauges"]["dist.shard_bytes"] == 256 * 128 * 4


@pytest.mark.parametrize("on_tpu", [True, False])
def test_plans_record_the_factor_kernels_tiles_per_step(monkeypatch,
                                                        on_tpu):
    """A plan that builds its tile factors with the kernel records, at
    plan time, the tiles per grid step it will run and the no-op tiles
    that pad each band: 11 tiles of 64/32 at n=640 run 4 a step, so 1
    pads each of 2 bands.  Single-device and sharded plans alike; a plan
    off the chip, which builds its factors with the XLA loop, records
    nothing."""
    from repro import compat, dist
    from repro.kernels.limits import factor_tiles_per_step

    monkeypatch.setattr(compat, "is_tpu", lambda: on_tpu)
    n, k = 640, 40
    assert factor_tiles_per_step(11, 64, 32) == 4
    A = jax.ShapeDtypeStruct((64, n), jnp.float32)
    seq = random_sequence(jax.random.key(4), n, k)
    mesh = compat.make_mesh((1,), ("data",))
    for plan in (
            lambda: seq.plan(like=A, method="accumulated", n_b=64, k_b=32),
            lambda: dist.plan_sharded(seq, like=A, mesh=mesh,
                                      method="accumulated", n_b=64,
                                      k_b=32)):
        obs.reset()
        with obs.override(True):
            plan()
        snap = obs.snapshot()
        gauge = snap["gauges"].get("kernels.rotseq_factors.tiles_per_step")
        padded = snap["counters"].get("kernels.rotseq_factors.padded_tiles")
        assert (gauge, padded) == ((4, 2) if on_tpu else (None, None))


@pytest.mark.parametrize("dtype,views", [("float32", 0),
                                         ("complex64", 1)])
@pytest.mark.parametrize("method", ["accumulated", "blocked"])
def test_complex_views_count_each_complex_gemm_sweep(tmp_path, method,
                                                     dtype, views):
    """``accumulate.complex_views`` counts one per application whose
    GEMM sweep ran on a complex target's real view, and none for a real
    target or a backend without the sweep; the plan and apply spans
    carry the target's dtype."""
    rng = np.random.default_rng(1)
    A = rng.standard_normal((8, 24)) + (
        1j * rng.standard_normal((8, 24)) if dtype == "complex64" else 0)
    A = jnp.asarray(A, dtype)
    seq = random_sequence(jax.random.key(2), 24, 6)
    obs.reset()
    with obs.override(True), _trace_path(str(tmp_path / "t.json")):
        plan = seq.plan(like=A, method=method, n_b=8, k_b=4)
        seq.plan(like=A)  # an auto plan opens the plan span
        jax.block_until_ready(plan.apply(A))
        jax.block_until_ready(plan.apply(A))
        snap = obs.snapshot()
    want = 2 * views if method == "accumulated" else 0
    assert snap["counters"].get("accumulate.complex_views", 0) == want
    spans = {ev["name"]: ev["args"] for ev in obs.trace.events()}
    assert spans["apply"]["dtype"] == dtype
    assert spans["plan"]["dtype"] == dtype


def test_complex_plans_record_the_factor_kernels_tiles_per_step(
        monkeypatch):
    """A complex64 target's factors are float32, so on a TPU its plan
    records the factor kernel's tiles per step as a real one does."""
    from repro import compat

    monkeypatch.setattr(compat, "is_tpu", lambda: True)
    seq = random_sequence(jax.random.key(4), 640, 40)
    obs.reset()
    with obs.override(True):
        seq.plan(like=jax.ShapeDtypeStruct((64, 640), jnp.complex64),
                 method="accumulated", n_b=64, k_b=32)
    snap = obs.snapshot()
    assert snap["gauges"]["kernels.rotseq_factors.tiles_per_step"] == 4
    assert snap["counters"]["kernels.rotseq_factors.padded_tiles"] == 2


# ------------------------------------- plan-cache counters, exactly ----

def test_plan_cache_counters_cold_warm_interpolated_upgrade():
    """Cold -> warm in the in-process memo, counted exactly: a first
    resolution is one miss, its repeat one hit, and a nearby shape is a
    miss of its own that the model ranks (nothing is borrowed)."""
    with obs.override(True):
        # cold: one miss, zero hits
        first = select_plan(16, 48, 6, platform="cpu")
        assert first.source == "model"
        c = obs.snapshot()["counters"]
        assert c.get("registry.plan_cache.hits", 0) == 0
        assert c["registry.plan_cache.misses"] == 1

        # warm: exact repeat is a pure hit, the same record
        assert select_plan(16, 48, 6, platform="cpu") is first
        c = obs.snapshot()["counters"]
        assert c["registry.plan_cache.hits"] == 1
        assert c["registry.plan_cache.misses"] == 1

        # a nearby shape is cold on its own and priced by the model
        near = select_plan(20, 64, 8, platform="cpu")
        assert near.source == "model"
        c = obs.snapshot()["counters"]
        assert c["registry.plan_cache.misses"] == 2
        assert select_plan(20, 64, 8, platform="cpu") is near
        c = obs.snapshot()["counters"]
        assert c["registry.plan_cache.hits"] == 2
        assert set(k for k in c if k.startswith("registry.")) == {
            "registry.plan_cache.hits", "registry.plan_cache.misses"}
        assert plan_cache_stats()["size"] == 2


# ------------------------------------------------ dispatch + spans ----

def test_sequence_dispatch_records_roofline_and_counters(tmp_path):
    # the blocking roofline records are gone: a dispatch is a span (its
    # device time is read from the profiler trace) and exact counters
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((12, 24)), jnp.float32)
    Ab = jnp.asarray(rng.standard_normal((3, 12, 24)), jnp.float32)
    seq = random_sequence(jax.random.key(1), 24, 6)
    plan = seq.plan(like=A)
    with obs.override(True), _trace_path(str(tmp_path / "t.json")):
        jax.block_until_ready(plan.apply(A))
        jax.block_until_ready(plan.apply_batched(Ab))
        snap = obs.snapshot()
    assert snap["counters"]["sequence.applies"] == 2
    assert snap["counters"][f"sequence.applies.{plan.method}"] == 2
    assert "roofline" not in snap
    assert "sequence.apply_seconds" not in snap["histograms"]
    spans = [ev for ev in obs.trace.events()
             if ev["name"] in ("apply", "apply_batched")]
    assert [ev["name"] for ev in spans] == ["apply", "apply_batched"]
    assert spans[0]["args"] == {"method": plan.method, "m": 12, "n": 24,
                                "dtype": "float32"}
    assert spans[1]["args"]["batch"] == 3
    assert all(ev["dur"] >= 0 for ev in spans)


def test_disabled_obs_outputs_bit_identical_and_no_new_cache_keys():
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((12, 24)), jnp.float32)
    Ab = jnp.asarray(rng.standard_normal((3, 12, 24)), jnp.float32)
    seq = random_sequence(jax.random.key(1), 24, 6)
    plan = seq.plan(like=A)
    with obs.override(False):
        off_single = plan.apply(A)
        off_batched = plan.apply_batched(Ab)
    size0 = plan_cache_stats()["size"]
    with obs.override(True):
        on_single = plan.apply(A)
        on_batched = plan.apply_batched(Ab)
    # instrumentation must not add plan-cache keys ...
    assert plan_cache_stats()["size"] == size0
    # ... nor change a single bit of the outputs
    np.testing.assert_array_equal(np.asarray(off_single),
                                  np.asarray(on_single))
    np.testing.assert_array_equal(np.asarray(off_batched),
                                  np.asarray(on_batched))


def test_instrumented_apply_stays_differentiable(tmp_path):
    # the tracer guard: jax.grad drives apply with abstract values, and
    # the host-side instrumentation must stand aside rather than crash
    rng = np.random.default_rng(2)
    A = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    seq = random_sequence(jax.random.key(0), 16, 4)
    plan = seq.plan(like=A, method="blocked")
    with obs.override(True), _trace_path(str(tmp_path / "t.json")):
        g = jax.grad(lambda a: (plan.apply(a) ** 2).sum())(A)
        snap = obs.snapshot()
    assert g.shape == A.shape
    # the traced inner call records nothing: no span, no count
    assert obs.trace.events() == []
    assert "sequence.applies" not in snap["counters"]


# --------------------------------------------------- serving + kernels ----

def test_service_metrics_account_pad_slots_and_latency():
    requests = synthetic_stream(8, seed=3)
    with obs.override(True):
        svc = RotationService(slots=4)
        outs = svc.apply_many(requests)
        jax.block_until_ready(outs[-1])
        snap = obs.snapshot()
    c = snap["counters"]
    assert c["serve.requests"] == 8
    # pad-slot accounting: executed slots split into real vs identity
    assert c["serve.slots_executed"] == svc.stats["slots_executed"]
    assert c.get("serve.pad_slots", 0) == svc.stats["padded_slots"]
    pad_fraction = snap["gauges"]["serve.pad_slot_fraction"]
    assert 0.0 <= pad_fraction < 1.0
    lat = snap["histograms"]["serve.request_latency_seconds"]
    assert lat["count"] == 8
    assert lat["p99"] >= lat["p50"] > 0


def test_service_snapshot_bit_identical_across_runs():
    def run() -> str:
        clear_plan_cache()
        obs.reset()
        svc = RotationService(slots=4)
        outs = svc.apply_many(synthetic_stream(8, seed=3))
        jax.block_until_ready(outs[-1])
        return json.dumps(obs.zeroed_timings(obs.snapshot()),
                          sort_keys=True)
    with obs.override(True):
        first = run()
        second = run()
    assert first == second


def test_fused_kernel_accounting_counts_skipped_planes():
    from repro.kernels.rotseq_batched.ops import count_live_planes
    rng = np.random.default_rng(0)
    b, m, n, k_req, k_pad = 4, 8, 16, 3, 8
    A = jnp.asarray(rng.standard_normal((b, m, n)), jnp.float32)
    seqs = [random_sequence(jax.random.key(i), n, k_req).pad_to(k_pad)
            for i in range(b)]
    plan = seqs[0].plan(like=A, method="rotseq_batched")
    with obs.override(True):
        jax.block_until_ready(plan.apply_batched(A, sequences=seqs))
        c = obs.snapshot()["counters"]
    live = sum(count_live_planes(s) for s in seqs)
    assert c["kernels.rotseq_batched.launches"] == 1
    assert c["kernels.rotseq_batched.planes_applied"] == live
    assert c["kernels.rotseq_batched.planes_skipped"] == \
        (n - 1) * k_pad * b - live
    assert c["kernels.rotseq_batched.bytes_moved"] > 0


def test_eig_flush_waves_histogram():
    from repro.eig import eigh_givens
    rng = np.random.default_rng(0)
    X = rng.standard_normal((12, 12)).astype(np.float32)
    H = jnp.asarray(X + X.T) / 2
    with obs.override(True):
        w, V = eigh_givens(H, method="qr", k_delay=4)
        jax.block_until_ready(V)
        snap = obs.snapshot()
    flushes = snap["counters"]["eig.flushes"]
    h = snap["histograms"]["eig.waves_per_flush"]
    assert flushes >= 1
    assert h["unit"] == "waves"
    assert h["count"] == flushes
    assert h["max"] <= 4  # the delay bound caps every flush


# --------------------------------------------------------- artifacts ----

def test_write_metrics_json_roundtrip(tmp_path):
    path = str(tmp_path / "OBS_metrics.json")
    with obs.override(True):
        obs.inc("x", 2)
        snap = obs.write_metrics_json(path, extra={"mode": "test"})
    on_disk = json.loads(open(path).read())
    assert on_disk == json.loads(json.dumps(snap))
    assert on_disk["counters"]["x"] == 2
    assert on_disk["meta"] == {"mode": "test"}
    assert set(on_disk) == {"counters", "gauges", "histograms", "meta"}


# ------------------------------------------------------ thread safety ----

def test_metrics_are_thread_safe_under_contention():
    """The stream engine mutates counters/histograms from three threads
    (caller, scheduler, dispatcher).  N threads hammering the same
    metrics must lose zero increments and keep histogram count/sum
    consistent — ``value += d`` without the registry lock drops both."""
    import threading

    n_threads, per_thread = 8, 2000
    with obs.override(True):
        def work():
            for i in range(per_thread):
                obs.inc("ts.counter")
                obs.observe("ts.hist", 1e-3)
                obs.gauge("ts.gauge", i)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = obs.snapshot()
    total = n_threads * per_thread
    assert snap["counters"]["ts.counter"] == total
    h = snap["histograms"]["ts.hist"]
    assert h["count"] == total
    assert h["sum"] == pytest.approx(total * 1e-3)
    assert sum(h["buckets"].values()) == total
    assert snap["gauges"]["ts.gauge"] == per_thread - 1
