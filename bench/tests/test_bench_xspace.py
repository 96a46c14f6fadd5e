"""The phase split of a profiler trace (``bench/xspace.py``): the wire
decoder on a hand-built XSpace, and the split on two traces recorded on
a TPU v5e.

``rotseq_accumulated_n256_k8.xplane.pb``: three applications of an
n=256, k=8 sequence through the ``accumulated`` backend (``n_b = k_b =
8``), whose phases carry ``jax.named_scope`` names, enqueued back to
back inside a ``bench.window`` span and then waited for; a tiny program
ran under the profiler before the window, and the window opens with a
3 ms pause (the device's clock reads ~1 ms ahead of the host's).  The
plane of HLO protos (``/host:metadata``), which nothing here reads, was
dropped from the file to keep it small.  ``rotseq_n256_k8.xplane.pb``
was recorded before the backends named their phases."""
import os
import struct

import pytest
from jax.profiler import ProfileData

from bench import spec, trace, xspace

TESTDATA = os.path.join(spec.BENCH_DIR, "testdata")
SCOPED = os.path.join(TESTDATA, "rotseq_accumulated_n256_k8.xplane.pb")
UNNAMED = os.path.join(TESTDATA, "rotseq_n256_k8.xplane.pb")


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        low, n = n & 0x7F, n >> 7
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _msg(*fields) -> bytes:
    return b"".join(_field(n, v) for n, v in fields)


def _plane(name: str, events, stat_names) -> bytes:
    """An XPlane: ``events`` maps an event metadata id to ``(name,
    [XStat fields...])``; ``stat_names`` a stat metadata id to its name."""
    out = [_field(1, 7), _field(2, name)]
    for sid, sname in stat_names.items():
        out.append(_field(5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    for eid, (ename, stats) in events.items():
        meta = _msg((1, eid), (2, ename),
                    *[(5, _msg(*stat)) for stat in stats])
        out.append(_field(4, _msg((1, eid), (2, meta))))
    return b"".join(out)


def _space() -> bytes:
    stat_names = {1: "tf_op", 2: "flops", 3: "jit(f)/rotseq.sweep/dot:"}
    events = {
        10: ("%acc = while(...)",
             [((1, 1), (5, "jit(f)/rotseq.accumulate/vmap()/while:")),
              ((1, 2), (2, 12.0))]),
        11: ("%gemm = fusion(...)", [((1, 1), (7, 3))]),  # a reference
        12: ("%copy.1 = copy(A)", [((1, 1), (5, "A:"))]),
        13: ("%nested = add(...)",
             [((1, 1), (5, "jit(f)/rotseq.sweep/rotseq.pack/add:"))]),
        14: ("%twice = add(...)",
             [((1, 1), (5, "jit(f)/rotseq.sweep/add:"))]),
        15: ("%twice = add(...)",
             [((1, 1), (5, "jit(g)/rotseq.pack/add:"))]),
        16: ("%bare = add(...)", []),
    }
    host = {20: ("%host = add(...)",
                 [((1, 1), (5, "jit(f)/rotseq.pack/add:"))])}
    return (_field(1, _plane("/device:TPU:0", events, stat_names))
            + _field(1, _plane("/host:CPU", host, {1: "tf_op"}))
            + _field(4, "hostname"))


@pytest.mark.parametrize("tf_op,phase", [
    ("jit(f)/rotseq.sweep/jit(rotseq_mxu_pallas)/pallas_call:",
     "rotseq.sweep"),
    ("jit(f)/rotseq.accumulate/vmap()/while/body/add:", "rotseq.accumulate"),
    ("jit(f)/rotseq.sweep/rotseq.pack/add:", "rotseq.sweep"),
    ("jit(rotseq_mxu_pallas)/dot_general:", "unscoped"),
    ("A:", "unscoped"),
    ("", "unscoped"),
])
def test_the_phase_is_the_outermost_rotseq_scope(tf_op, phase):
    assert xspace.phase_of(tf_op) == phase


def test_op_phases_on_a_hand_built_space():
    phases = xspace.op_phases(_space())
    assert phases == {
        "%acc = while(...)": "rotseq.accumulate",
        "%gemm = fusion(...)": "rotseq.sweep",
        "%copy.1 = copy(A)": "unscoped",
        "%nested = add(...)": "rotseq.sweep",
        "%twice = add(...)": "unscoped",      # two phases: no phase
        "%bare = add(...)": "unscoped",
    }                                         # the host plane is not read


def test_a_trace_recorded_before_the_scopes_is_all_unscoped():
    r = trace.reduce_file(UNNAMED)
    phases = xspace.reduce_file(UNNAMED)
    assert set(phases) == {"unscoped"}
    assert phases["unscoped"] == pytest.approx(sum(r.ops.values()),
                                               rel=1e-12)


@pytest.fixture(scope="module")
def scoped():
    return trace.reduce_file(SCOPED), xspace.reduce_file(SCOPED)


def test_the_recorded_trace_is_small_and_whole(scoped):
    r, _ = scoped
    assert os.path.getsize(SCOPED) < 150_000
    assert r.devices == 1 and r.launches == 3 and r.executed == 3
    assert r.window_s == pytest.approx(0.0061694, rel=1e-6)
    assert r.busy_s == pytest.approx(505.315e-6, rel=1e-6)


def test_phases_of_the_accumulated_backend(scoped):
    _, phases = scoped
    assert phases == {
        "rotseq.accumulate": pytest.approx(372.673e-6, rel=1e-6),
        "rotseq.pack": pytest.approx(94.075e-6, rel=1e-6),
        "rotseq.sweep": pytest.approx(34.499e-6, rel=1e-6),
        "unscoped": pytest.approx(3.926e-6, rel=1e-6),
    }


def test_phases_and_unscoped_add_up_to_the_ops(scoped):
    r, phases = scoped
    assert sum(phases.values()) == pytest.approx(sum(r.ops.values()),
                                                 rel=1e-12)


def test_a_while_takes_the_phase_of_its_body(scoped):
    # the profiler gives the accumulation loop no tf_op; its body ops
    # carry rotseq.accumulate, and so the loop's time is accumulation
    r, phases = scoped
    with open(SCOPED, "rb") as f:
        data = f.read()
    loops = [n for n in xspace.device_tf_ops(data) if n.startswith(
        "%while.39 ")]
    assert loops and xspace.op_phases(data)[loops[0]] == "unscoped"
    assert r.ops["while.39"] > 0.99 * phases["rotseq.accumulate"]


def test_top_level_phases_on_nested_events():
    phases = {"loop": "unscoped", "body": "rotseq.accumulate",
              "mixed": "unscoped", "a": "rotseq.pack", "b": "rotseq.sweep",
              "gemm": "rotseq.sweep"}
    events = [("loop", 0.0, 10.0), ("body", 1.0, 2.0), ("body", 3.0, 9.0),
              ("mixed", 10.0, 20.0), ("a", 11.0, 12.0), ("b", 13.0, 14.0),
              ("gemm", 20.0, 21.0), ("bare", 21.0, 22.0)]
    assert list(xspace.top_level_phases(events, phases)) == [
        ("rotseq.accumulate", 0.0, 10.0), ("unscoped", 10.0, 20.0),
        ("rotseq.sweep", 20.0, 21.0), ("unscoped", 21.0, 22.0)]


def test_program_spans_lie_on_the_host_plane_in_the_window():
    pd = ProfileData.from_file(SCOPED)
    spans = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, a, b in trace._events(line):
                    spans.setdefault(name, []).append((a, b))
    (window,) = spans[trace.WINDOW_SPAN]
    (enqueue,) = spans["bench.enqueue"]
    applies = spans["repro.apply"]
    assert len(applies) == 3                  # one per application
    for a, b in applies:
        assert window[0] <= enqueue[0] <= a < b <= enqueue[1] <= window[1]


def test_program_spans_name_the_gaps_they_open(monkeypatch):
    # what bench/trace.py reads once it accepts a second span prefix: the
    # device idles while the host dispatches the next application
    monkeypatch.setattr(trace, "HOST_PREFIX", ("bench.", "repro."))
    names = [name for name, _ in trace.reduce_file(SCOPED).gaps]
    assert names.count("bench.enqueue+repro.apply") == 3
