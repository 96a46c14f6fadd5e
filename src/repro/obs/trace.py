"""Host-side spans on the profiler's clock.

:func:`span` opens a ``jax.profiler.TraceAnnotation`` named
``repro.<name>`` whenever a profiler session is active, whatever
``REPRO_OBS`` says: the span then lands in the trace's host plane,
beside the device operations it launched and on the same clock.  The
annotation carries the name alone, so it stays stable across calls.

With obs on and a trace path (``REPRO_OBS_TRACE``) each span is also
buffered as a Chrome complete event (``"ph": "X"``) with its arguments,
one thread lane per Python thread.  Its ``ts`` is Unix-epoch
microseconds, the clock of the profiler's host plane, so a span in this
export and the same span in a profiler trace agree to the microsecond.
:func:`write_trace` emits the ``{"traceEvents": [...]}`` wrapper with
one event per line — the file loads directly in https://ui.perfetto.dev
or ``chrome://tracing``.

Spans wrap *host* code only and never wait for the device: a span
around an application covers its dispatch, and the device time it
launched is read from the profiler trace.  Nothing here is safe or
meaningful inside jit/traced code, which is why the instrumented seams
guard with ``repro.compat.is_tracer(x)`` before opening a span.  With
no profiler session and no trace path, a span costs one TraceMe check
and returns a shared null object.
"""
from __future__ import annotations

import json
import threading
from typing import Any, Dict, List

from jax.profiler import TraceAnnotation

from repro.obs import runtime, timing

PREFIX = "repro."

_lock = threading.Lock()
_events: List[Dict[str, Any]] = []


class _Span:
    """One span: a profiler annotation, a buffered Chrome event, or both."""

    __slots__ = ("name", "args", "_annotation", "_record", "_t0")

    def __init__(self, name: str, args: Dict[str, Any], annotate: bool,
                 record: bool):
        self.name = name
        self.args = args
        self._annotation = TraceAnnotation(PREFIX + name) \
            if annotate else None
        self._record = record
        self._t0 = 0

    def __enter__(self) -> "_Span":
        if self._annotation is not None:
            self._annotation.__enter__()
        if self._record:
            self._t0 = timing.wall_ns()
        return self

    def set(self, **kw: Any) -> None:
        """Attach extra args discovered mid-span (e.g. batch size)."""
        self.args.update(kw)

    def __exit__(self, *exc) -> None:
        if self._record:
            t1 = timing.wall_ns()
            with _lock:
                _events.append({
                    "name": self.name,
                    "ph": "X",
                    "ts": self._t0 / 1e3,
                    "dur": (t1 - self._t0) / 1e3,
                    "pid": 1,
                    "tid": threading.get_ident() & 0xFFFF,
                    "args": self.args,
                })
        if self._annotation is not None:
            self._annotation.__exit__(*exc)


class _NullSpan:
    """Shared no-op span: the disabled fast path allocates nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def set(self, **kw: Any) -> None:
        pass

    def __exit__(self, *exc) -> None:
        pass


NULL_SPAN = _NullSpan()


def span(name: str, **args: Any):
    """Open a span when a profiler session or the trace buffer is live;
    the shared null object otherwise."""
    annotate = TraceAnnotation.is_enabled()
    record = runtime.trace_enabled()
    if not (annotate or record):
        return NULL_SPAN
    return _Span(name, args, annotate, record)


def events() -> List[Dict[str, Any]]:
    with _lock:
        return list(_events)


def reset() -> None:
    with _lock:
        _events.clear()


def write_trace(path: str) -> int:
    """Write buffered spans as Chrome trace JSON; returns event count."""
    evs = events()
    with open(path, "w") as f:
        f.write('{"traceEvents": [\n')
        for i, ev in enumerate(evs):
            sep = ",\n" if i + 1 < len(evs) else "\n"
            f.write(json.dumps(ev, sort_keys=True) + sep)
        f.write("]}\n")
    return len(evs)
