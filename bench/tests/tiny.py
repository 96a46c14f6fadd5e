"""A checkout with a tiny cell added, for driving whole runs on the CPU.

The cell is added the way a later change adds one: new configuration
and traffic files and new entries in ``BENCHMARK.json``, no existing
file edited.  The limit is that of the benchmark's own cells, so a
control or a fault that passes it here would pass it there.
"""
from __future__ import annotations

import json
import os
import shutil

from bench import spec

APPLY_CELL = "tiny-k8.t64"


def _limit(traffic: str) -> float:
    with open(os.path.join(spec.BENCH_DIR, "traffic", traffic + ".json")) as f:
        return float(json.load(f)["limits"]["gap"])


def make_checkout(root: str) -> str:
    """Copy ``BENCHMARK.json`` and ``bench/`` under ``root`` and add the
    tiny cell; returns ``root``."""
    shutil.copytree(spec.BENCH_DIR, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    bench = spec.load_benchmark()

    def write(rel: str, obj: dict) -> None:
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)

    write("bench/configs/tiny-k8.json",
          {"k": 8, "dtype": "float32", "method": "auto", "reduced": []})
    write("bench/traffic/t64.json",
          {"driver": "closed_apply", "m": 64, "n": 64, "in_flight": 2,
           "ring": 3, "check_rows": 4, "check_apps": 40,
           "trace_seconds": 1.0,
           "limits": {"gap": _limit("n960")}})
    bench["configs"] += [
        {"name": "tiny-k8", "source": "test", "reduced": [], "why": "test",
         "file": "bench/configs/tiny-k8.json"}]
    bench["workloads"] += [
        {"name": APPLY_CELL, "config": "tiny-k8", "traffic": "t64",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads")
        if cells is None:
            continue
        if any(c.startswith("paper-k180.") for c in cells):
            cells.append(APPLY_CELL)
    write("BENCHMARK.json", bench)
    return root


def run_cell(root: str, cell: str, *, seed: int = 2**31 + 11,
             seconds: float = 1.5, trace: int = 0, dtype=None) -> dict:
    """One whole run of ``cell`` on the CPU, as ``bench/run.py`` makes it."""
    from bench import run

    return run.run(["--workload", cell, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", str(trace)],
                   require_tpu=False, root=root, dtype=dtype)
