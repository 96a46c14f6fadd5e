"""Name the device time of a profiler trace by the program's phases.

``jax.profiler.ProfileData`` gives each event its name and its own
stats, but not the stats of the event's *metadata*.  On a TPU the
metadata of every ``XLA Ops`` event holds a ``tf_op`` stat: the JAX
name stack of the operation, e.g. ``jit(f)/rotseq.sweep/while:``.  That
is where a ``jax.named_scope`` of the program reaches the trace.  This
module decodes the ``.xplane.pb`` (an XSpace protobuf) in wire format,
with no generated classes, and reads:

* the phase of an operation: the outermost component of its name stack
  that starts with ``rotseq.`` (``rotseq.pack``, ``rotseq.accumulate``,
  ``rotseq.sweep``); an operation with none is ``unscoped``;
* operations are matched to their metadata by the name ``ProfileData``
  gives them (the HLO text); a name whose metadata entries do not all
  name the same phase counts as unscoped;
* a top-level operation that names no phase takes the one phase that
  the operations nested inside it name: the TPU's profiler gives a
  ``while`` no ``tf_op``, but the operations of its body carry theirs;
* phases: the seconds of the top-level operations inside the window per
  phase, counted as ``bench/trace.py`` counts ``Reduction.ops`` (summed
  over the devices), so that they add up to the sum of ``ops``.

Run as ``python3 -m bench.xspace <trace.xplane.pb or its directory>``
from the root of a checkout to print the phases as JSON.
"""
from __future__ import annotations

import collections
import glob
import json
import os
import re
import sys
from typing import Dict, Iterator, List, Tuple

from bench import trace

UNSCOPED = "unscoped"
PHASE = re.compile(r"rotseq\.[A-Za-z_]+")

# field numbers of tensorflow/tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_MAP_VALUE = 2
_EVENT_META_NAME, _EVENT_META_STATS = 2, 5
_STAT_META_ID, _STAT_META_NAME = 1, 2
_STAT_META, _STAT_STR, _STAT_REF = 1, 5, 7

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of each field of one message: an int
    for varints, a ``memoryview`` for everything else."""
    buf = memoryview(buf)
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
        elif wire == _BYTES:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == _FIXED64:
            value, i = buf[i:i + 8], i + 8
        elif wire == _FIXED32:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield number, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map_values(entries: List[memoryview]) -> Iterator[memoryview]:
    for entry in entries:
        for number, value in fields(entry):
            if number == _MAP_VALUE:
                yield value


def device_tf_ops(data: bytes) -> Dict[str, List[str]]:
    """Per device plane's event metadata name, the ``tf_op`` stats it
    carries (one per metadata entry of that name)."""
    out: Dict[str, List[str]] = collections.defaultdict(list)
    for number, plane in fields(data):
        if number != _SPACE_PLANES:
            continue
        name, events, stats = "", [], []
        for pnum, value in fields(plane):
            if pnum == _PLANE_NAME:
                name = _text(value)
            elif pnum == _PLANE_EVENT_METADATA:
                events.append(value)
            elif pnum == _PLANE_STAT_METADATA:
                stats.append(value)
        if not name.startswith(trace.DEVICE_PREFIX):
            continue
        stat_names = {}
        for meta in _map_values(stats):
            d = dict(fields(meta))
            stat_names[d.get(_STAT_META_ID, 0)] = \
                _text(d.get(_STAT_META_NAME, b""))
        tf_op = [k for k, v in stat_names.items() if v == "tf_op"]
        for meta in _map_values(events):
            ev_name, op = "", ""
            for enum, value in fields(meta):
                if enum == _EVENT_META_NAME:
                    ev_name = _text(value)
                elif enum == _EVENT_META_STATS:
                    stat = dict(fields(value))
                    if stat.get(_STAT_META, -1) not in tf_op:
                        continue
                    if _STAT_STR in stat:
                        op = _text(stat[_STAT_STR])
                    elif _STAT_REF in stat:
                        op = stat_names.get(stat[_STAT_REF], "")
            out[ev_name].append(op)
    return dict(out)


def phase_of(tf_op: str) -> str:
    """The outermost ``rotseq.`` scope of a name stack, or unscoped."""
    m = PHASE.search(tf_op)
    return m.group(0) if m else UNSCOPED


def op_phases(data: bytes) -> Dict[str, str]:
    """Event name -> phase, for every op of every device plane."""
    out = {}
    for name, ops in device_tf_ops(data).items():
        phases = {phase_of(op) for op in ops}
        out[name] = phases.pop() if len(phases) == 1 else UNSCOPED
    return out


def top_level_phases(events, phases: Dict[str, str]):
    """``(phase, start, end)`` of each event of ``events`` that is not
    nested inside an earlier one (as :func:`bench.trace.top_level`
    picks them); one that names no phase takes the one phase that the
    events nested inside it name."""
    tops: List[list] = []
    end = float("-inf")
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        phase = phases.get(name, UNSCOPED)
        if a >= end:
            end = b
            tops.append([phase, a, b, set()])
        elif phase != UNSCOPED:
            tops[-1][3].add(phase)
    for phase, a, b, nested in tops:
        if phase == UNSCOPED and len(nested) == 1:
            phase = nested.pop()
        yield phase, a, b


def phase_seconds(pd, phases: Dict[str, str]) -> Dict[str, float]:
    """Seconds of the top-level device ops inside the window, per phase.

    ``pd`` is the trace's ``ProfileData``; the window and the ops are
    those of :func:`bench.trace.reduce_profile`.
    """
    windows, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                windows += [(a, b) for name, a, b in trace._events(line)
                            if name == trace.WINDOW_SPAN]
        elif plane.name.startswith(trace.DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            progs = list(trace._events(lines["XLA Modules"])) \
                if "XLA Modules" in lines else []
            ops = list(top_level_phases(trace._events(lines["XLA Ops"]),
                                        phases)) \
                if "XLA Ops" in lines else []
            if progs or ops:
                devices.append((progs, ops))
    if not devices:
        raise ValueError("the trace holds no device operation")
    if windows:
        lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    else:
        ends = [(a, b) for progs, ops in devices for _, a, b in progs + ops]
        lo, hi = min(a for a, _ in ends), max(b for _, b in ends)
    out: Dict[str, float] = collections.Counter()
    for _, ops in devices:
        for phase, a, b in ops:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                out[phase] += (b - a) * 1e-9
    return dict(out)


def reduce_file(path: str) -> Dict[str, float]:
    """Device seconds per phase of the trace at ``path``, with
    ``unscoped`` for the ops no phase names."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    return phase_seconds(ProfileData.from_serialized_xspace(data),
                         op_phases(data))


def find(path: str) -> str:
    """``path``, or the last ``.xplane.pb`` under the directory ``path``."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[0], file=sys.stderr)
        return 2
    print(json.dumps(reduce_file(find(argv[0])), sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
