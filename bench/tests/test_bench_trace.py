"""The reduction from a profiler trace to busy, idle, ops and gaps,
checked on a small trace recorded on a TPU v5e: three applications of
an n=256, k=8 sequence (the fused kernel), each enqueued and waited for
inside a ``bench.window`` span, 3 ms apart."""
import os

import pytest

from bench import spec, trace

TESTDATA = os.path.join(spec.BENCH_DIR, "testdata",
                        "rotseq_n256_k8.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return trace.reduce_file(TESTDATA)


def test_window_is_the_bench_window_span(small):
    assert small.window_s == pytest.approx(0.014595917, rel=1e-6)


def test_busy_is_the_union_of_the_programs(small):
    assert small.devices == 1
    assert small.busy_s == pytest.approx(117.979e-6, rel=1e-6)
    assert 0 < small.busy_s < small.window_s
    assert small.programs == {"jit__rot_sequence_batched_jit":
                              pytest.approx(117.979e-6, rel=1e-6)}


def test_every_launch_is_seen_on_the_device(small):
    assert small.launches == 3 and small.executed == 3


def test_ops_are_top_level_and_named_without_hlo_text(small):
    assert max(small.ops, key=small.ops.get) == "rotseq_batched_pallas.1"
    assert all(" " not in name for name in small.ops)
    assert sum(small.ops.values()) <= small.busy_s * (1 + 1e-9)


def test_idle_gaps_are_named_by_the_host_spans_open_in_them(small):
    names = [name for name, _ in small.gaps]
    seconds = [s for _, s in small.gaps]
    assert seconds == sorted(seconds, reverse=True)
    # the host slept 3 ms between applications, inside no span
    assert names[:3] == ["none", "none", "none"]
    assert "bench.enqueue" in names
    assert sum(seconds) == pytest.approx(small.window_s - small.busy_s,
                                         rel=1e-6)


def test_breakdown_holds_at_most_ten_of_each(small):
    b = small.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    for name, seconds in b["device_ops"] + b["idle_gaps"]:
        assert isinstance(name, str) and seconds > 0


def test_merge_clip_and_holes():
    merged = trace.merge([(5, 7), (1, 3), (2, 4), (6, 6.5), (9, 10)])
    assert merged == [(1, 4), (5, 7), (9, 10)]
    assert trace.clip(merged, 3, 9.5) == [(3, 4), (5, 7), (9, 9.5)]
    assert trace.holes(trace.clip(merged, 0, 12), 0, 12) == \
        [(0, 1), (4, 5), (7, 9), (10, 12)]


def test_top_level_drops_nested_events():
    events = [("while", 0.0, 10.0), ("body", 1.0, 2.0), ("body", 3.0, 9.0),
              ("after", 10.0, 11.0), ("overlap", 10.5, 12.0)]
    assert [e[0] for e in trace.top_level(events)] == ["while", "after"]


def _view(reduction, **layer):
    return trace.ReaderView(reduction=reduction, layer=layer)


def test_readers_read_nothing_without_their_source(small):
    for name in ("rotseq_roofline.apply", "idle.apply"):
        assert spec.metric_reader(name)(_view(None, least_s=1e-3)) is None
    assert spec.metric_reader("rotseq_roofline.apply")(
        _view(small, least_s=0.0)) is None


def test_readers_on_the_small_trace(small):
    idle = spec.metric_reader("idle.apply")(_view(small))
    assert idle == pytest.approx(100 * (1 - small.busy_s / small.window_s))
    least = 1e-6
    roof = spec.metric_reader("rotseq_roofline.apply")(
        _view(small, least_s=least, exclude_programs="jit_bench_probe"))
    assert roof == pytest.approx(100 * least / small.busy_s)
    # the benchmark's own probe programs are not the application's time
    roof_all = spec.metric_reader("rotseq_roofline.apply")(
        _view(small, least_s=least, exclude_programs="jit__rot"))
    assert roof_all is None
