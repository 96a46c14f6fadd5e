"""Resolve a cell of ``BENCHMARK.json`` to its files, by name alone.

A cell names a configuration and a traffic mix.  Each is a data file:
``bench/configs/<config>.json`` and ``bench/traffic/<traffic>.json``.
The traffic file names the driver, the general generator that reads
it: ``bench/drivers/<driver>.py``.  A per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding a cell is adding files and
entries; no file that exists has to change.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver_path: str
    end_to_end: list     # the BENCHMARK.json entries this cell reports
    per_layer: list
    root: str            # the checkout the cell was read from


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reported(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def resolve(cell: str, root: str = ROOT) -> Cell:
    """The cell ``cell`` with its configuration, traffic and metrics."""
    bench = load_benchmark(root)
    bdir = os.path.join(root, "bench")
    entries = [w for w in bench["workloads"] if w["name"] == cell]
    if not entries:
        raise KeyError(f"no cell {cell!r} in BENCHMARK.json")
    w = entries[0]
    configs = {c["name"]: c for c in bench["configs"]}
    conf_entry = configs[w["config"]]
    config = _read_json(os.path.join(root, conf_entry["file"]))
    traffic = _read_json(os.path.join(bdir, "traffic",
                                      w["traffic"] + ".json"))
    driver = os.path.join(bdir, "drivers", traffic["driver"] + ".py")
    if not os.path.isfile(driver):
        raise FileNotFoundError(driver)
    e2e = [m for m in bench["end_to_end"] if _reported(m, cell)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reported(m, cell) and m["moves"] in reported]
    return Cell(name=cell, chips=int(w["chips"]), config=config,
                traffic=traffic, driver_path=driver, end_to_end=e2e,
                per_layer=per_layer, root=root)


def load_module(path: str, name: Optional[str] = None):
    """Import the file ``path`` (its name may hold dots)."""
    name = name or "bench_" + os.path.basename(path)[:-3].replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(metric: str, root: str = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return load_module(os.path.join(root, "bench", "metrics",
                                    metric + ".py")).read
