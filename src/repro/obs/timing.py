"""The single sanctioned wall-clock in the tree.

Every host-side timing measurement — benchmark loops, span timestamps,
launcher throughput prints — goes through this module.  Analyzer rule
RA502 bans direct ``time.perf_counter`` / ``time.time`` / ``timeit``
references everywhere else (only this module and
``benchmarks/common.py`` are exempt), so "who is allowed to look at
the clock" is a one-line grep instead of an audit.

Keeping the clock behind one function also keeps rule family RA5
honest: cost-model and plan-key code imports :mod:`repro.obs` freely
because the clock lives *here*, never inline in key paths.
"""
from __future__ import annotations

import time


def now() -> float:
    """Monotonic seconds for interval measurement (perf_counter)."""
    return time.perf_counter()


def wall_unix() -> float:
    """Unix epoch seconds — artifact timestamps only, never keys."""
    return time.time()


def wall_ns() -> int:
    """Unix epoch nanoseconds: the clock of the profiler's host plane,
    so a span stamped with it lines up with the trace's events."""
    return time.time_ns()
