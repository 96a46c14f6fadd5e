"""repro.obs — zero-overhead-when-disabled observability.

Two pieces:

- **Metrics** (:mod:`repro.obs.metrics`): counters, gauges, and
  deterministic log-spaced-bucket histograms, exported as a plain-JSON
  snapshot.  No wall-clock or RNG in any metrics path (RA5).
- **Tracing** (:mod:`repro.obs.trace`): host-side spans around
  plan / resolve / rebind / apply / flush / admit / drain.  Under an
  active ``jax.profiler`` session each span is a ``repro.<name>``
  annotation in the trace's host plane, on the clock of the device
  ops; with a trace path the spans are also buffered as Chrome
  trace-event JSON viewable in Perfetto.  Spans never wait for the
  device: what an application costs on the chip is read from the
  profiler trace, where the backends name their phases
  (``rotseq.pack`` / ``rotseq.accumulate`` / ``rotseq.sweep``).

Configuration is environment-first: ``REPRO_OBS=on`` enables
recording (default off — the tier-1 suite runs with every hook on the
shared null fast path), ``REPRO_OBS_TRACE=PATH`` additionally buffers
spans for trace export.  Profiler annotations need neither: they
follow the profiler session.  Tests and launchers flip the switch
programmatically via :func:`set_enabled` / :func:`override`.

This package is also the **single sanctioned home for timing**
(:mod:`repro.obs.timing`); analyzer rule RA502 lint-errors ad-hoc
``time.perf_counter`` / ``time.time`` / ``timeit`` references
anywhere else in ``repro.*`` / ``benchmarks.*`` / ``examples.*``
(``benchmarks/common.py`` is the one exempt shim).
"""
from __future__ import annotations

import json
from typing import Any

from repro.obs import metrics, runtime, timing, trace
from repro.obs.metrics import zeroed_timings
from repro.obs.runtime import enabled, override, set_enabled
from repro.obs.trace import span

__all__ = [
    "enabled", "set_enabled", "override", "span", "inc", "gauge",
    "observe", "snapshot", "reset", "write_metrics_json", "write_trace",
    "zeroed_timings", "timing", "metrics", "runtime", "trace",
]


def inc(name: str, delta: int = 1) -> None:
    """Bump a counter (no-op while obs is disabled)."""
    if runtime._enabled:
        metrics.GLOBAL.counter(name).inc(delta)


def gauge(name: str, value: float) -> None:
    """Set a gauge (no-op while obs is disabled)."""
    if runtime._enabled:
        metrics.GLOBAL.gauge(name).set(value)


def observe(name: str, value: float, unit: str = "seconds") -> None:
    """Record a histogram observation (no-op while obs is disabled)."""
    if runtime._enabled:
        metrics.GLOBAL.histogram(name, unit).observe(value)


def snapshot() -> dict:
    """Full metrics snapshot as a JSON-clean dict."""
    return metrics.GLOBAL.snapshot()


def reset() -> None:
    """Clear all recorded metrics and buffered spans."""
    metrics.GLOBAL.reset()
    trace.reset()


def write_metrics_json(path: str, extra: dict[str, Any] | None = None) -> dict:
    """Dump :func:`snapshot` (plus optional ``extra`` meta) to ``path``."""
    snap = snapshot()
    if extra:
        snap["meta"] = extra
    with open(path, "w") as f:
        json.dump(snap, f, indent=1, sort_keys=True)
        f.write("\n")
    return snap


def write_trace(path: str | None = None) -> int:
    """Export buffered spans as Chrome trace JSON; returns event count.

    Defaults to the ``REPRO_OBS_TRACE`` path; no-ops (returns 0) when
    neither is set.
    """
    target = path or runtime.trace_path()
    if not target:
        return 0
    return trace.write_trace(target)
