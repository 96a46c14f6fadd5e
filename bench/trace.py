"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The trace is read with ``jax.profiler.ProfileData``.  On a TPU each
chip is a plane ``/device:TPU:<i>`` whose line ``XLA Modules`` holds
one event per program run on the device and whose line ``XLA Ops``
holds the operations inside them (nested: a ``while`` op spans its
body).  The host plane ``/host:CPU`` holds the benchmark's own spans,
``jax.profiler.TraceAnnotation`` names starting with ``bench.``, on
the same clock.

* window: the span ``bench.window`` (the measured window); a trace
  without it is taken whole, from its first device event to its last;
* busy: the union of the program intervals inside the window, per
  device, averaged over the devices;
* idle gaps: the holes in that union, each named by the benchmark
  spans open on the host at its middle;
* device ops: time inside the window per program and per top-level
  operation of ``XLA Ops``, by the names the trace gives (the HLO
  text after `` = `` and program hashes cut off);
* launches and executions: the programs the host launched in the
  window against those the device plane shows.  The device's trace
  buffer holds a few million events, and the backends' loops fill it
  in seconds; a trace that lost events shows fewer executions, and its
  busy time would read low.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import warnings
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
LAUNCH = "PJRT_LoadedExecutable_Execute"   # one per program launched
TOP = 10

Interval = Tuple[float, float]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                          # mean over devices
    devices: int
    programs: Dict[str, float]             # seconds per program, summed
    ops: Dict[str, float]                  # seconds per top-level op
    gaps: List[Tuple[str, float]]          # longest idle gaps, named
    launches: int = 0       # programs the host launched in the window
    executed: int = 0       # of them, seen run on the device (the least
                            # over devices); fewer means events were lost

    def program_seconds(self, prefix: str) -> float:
        """Seconds of the programs whose name starts with ``prefix``,
        averaged over the devices."""
        return sum(s for name, s in self.programs.items()
                   if name.startswith(prefix)) / max(1, self.devices)

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        if not ops:
            ops = sorted(self.programs.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


@dataclasses.dataclass
class ReaderView:
    """What a per-layer metric reader gets: the trace's reduction and
    the quantities the driver counted (``layer``)."""
    reduction: Optional[Reduction]
    layer: dict


def merge(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def holes(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that the merged ``busy`` leaves free."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


_HASH = re.compile(r"\(\d+\)$")


def program_name(name: str) -> str:
    return _HASH.sub("", name)


def op_name(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def top_level(events):
    """The events not nested inside an earlier one of the same line."""
    end = float("-inf")
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        if a >= end:
            end = b
            yield name, a, b


def _cpu_ops(line):
    """XLA's CPU client runs a program's ops on host threads; they carry
    an ``hlo_op`` stat.  Read as one device where no TPU plane exists."""
    with warnings.catch_warnings():   # the stats' binding type warns
        warnings.simplefilter("ignore", DeprecationWarning)
        for e in line.events:
            if any(k == "hlo_op" for k, _ in e.stats):
                yield (e.name, float(e.start_ns),
                       float(e.start_ns + e.duration_ns))


def reduce_profile(pd) -> Reduction:
    host_spans: List[Tuple[str, float, float]] = []
    launch_t: List[float] = []
    devices = []
    cpu_ops = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in _events(line):
                    if ev[0].startswith(HOST_PREFIX):
                        host_spans.append(ev)
                    elif ev[0] == LAUNCH:
                        launch_t.append(ev[1])
                if line.name.startswith("tf_XLAPjRtCpuClient"):
                    cpu_ops += list(_cpu_ops(line))
        elif plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            progs = list(_events(lines["XLA Modules"])) \
                if "XLA Modules" in lines else []
            ops = list(top_level(_events(lines["XLA Ops"]))) \
                if "XLA Ops" in lines else []
            if progs or ops:
                devices.append((progs, ops))
    if not devices and cpu_ops:
        devices.append(([], list(top_level(cpu_ops))))
    if not devices:
        raise ValueError("the trace holds no device operation")
    windows = [(a, b) for name, a, b in host_spans if name == WINDOW_SPAN]
    if windows:
        lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    else:
        ends = [(a, b) for progs, ops in devices for _, a, b in progs + ops]
        lo, hi = min(a for a, _ in ends), max(b for _, b in ends)
    labels = [(n, a, b) for n, a, b in host_spans if n != WINDOW_SPAN]

    programs: Dict[str, float] = collections.Counter()
    op_s: Dict[str, float] = collections.Counter()
    busy_total = 0.0
    free: List[Interval] = []
    for progs, ops in devices:
        source = progs or ops
        busy = clip(merge([(a, b) for _, a, b in source]), lo, hi)
        busy_total += sum(b - a for a, b in busy)
        for name, a, b in progs:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                programs[program_name(name)] += (b - a) * 1e-9
        for name, a, b in ops:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                op_s[op_name(name)] += (b - a) * 1e-9
        free += holes(busy, lo, hi)
    gaps = []
    for a, b in sorted(free, key=lambda g: g[0] - g[1])[:TOP]:
        mid = (a + b) / 2
        open_ = sorted({n for n, sa, sb in labels if sa <= mid < sb})
        gaps.append(("+".join(open_) or "none", (b - a) * 1e-9))
    executed = min(sum(lo <= a <= hi for _, a, _ in progs)
                   for progs, _ in devices)
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=busy_total / len(devices) * 1e-9,
                     devices=len(devices), programs=dict(programs),
                     ops=dict(op_s), gaps=gaps,
                     launches=sum(lo <= t <= hi for t in launch_t),
                     executed=executed)


def reduce_file(path: str) -> Reduction:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def reduce_dir(log_dir: str) -> Reduction:
    """Reduce the one ``.xplane.pb`` that a trace wrote under ``log_dir``."""
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return reduce_file(found[-1])
