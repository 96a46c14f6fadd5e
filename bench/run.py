"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell (an entry of
``BENCHMARK.json``) names a configuration and a traffic mix; the
traffic file names the driver that generates the load.  The run makes
its data from ``--seed``, warms up the cell's shapes (set-up), measures
for ``--seconds``, and checks what the timed path produced against the
plain reference in ``bench/reference.py``.  With ``--trace 1`` the
window runs under the profiler and the cell's per-layer metrics are
read from the trace and what the driver counted instead of the
end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), and last ``checks``, each compared number with its limit.
The checks are also the last lines of standard error.  Without a TPU,
or with fewer chips than the cell asks for, it exits with code 2 and
prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
# run as a script, Python puts bench/ first on the path, where its
# trace.py would shadow the standard library's; import it as a package
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from bench import spec, trace as trace_mod, work  # noqa: E402


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def process_start_unix() -> float:
    """When this process started, by the kernel's record of it."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        ticks = os.sysconf("SC_CLK_TCK")
        with open("/proc/stat") as f:
            btime = next(int(line.split()[1]) for line in f
                         if line.startswith("btime"))
        return btime + int(fields[19]) / ticks
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's arguments, the device."""
    cell: spec.Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    compiles: list          # one entry per backend compile, appended live
    dtype: Optional[str] = None   # set only by the control (bench/control.py)

    def window_seconds(self, traffic: dict) -> float:
        """The window's length: ``--seconds``, or in a traced run the
        traffic's ``trace_seconds`` where that is shorter."""
        if self.trace:
            return min(self.seconds, float(traffic["trace_seconds"]))
        return self.seconds

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip of the cell."""
        return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in self.devices))

    @contextlib.contextmanager
    def window(self):
        """The measured window; under the profiler when tracing.

        Yields a dict that, once the window has closed, holds the
        programs compiled inside it (``compiles``) and, when tracing,
        the trace's directory (``trace_dir``).
        """
        import jax

        box = {"compiles_before": len(self.compiles)}
        tdir = None
        if self.trace:
            tdir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                yield box
        finally:
            if tdir is not None:
                jax.profiler.stop_trace()
                box["trace_dir"] = tdir
            box["compiles"] = self.compiles[box["compiles_before"]:]


def check_devices(chips: int, require_tpu: bool) -> list:
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"[device] platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devs)}")
    if require_tpu and d.platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {d.platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def _number(x):
    """``x`` as JSON can carry it: a number that is not finite is null."""
    return x if math.isfinite(x) else None


def build_result(cell: spec.Cell, out: dict, setup_s: float, traced: bool,
                 devices, reduction) -> dict:
    checks = out["checks"]
    correct = all(v <= lim for v, lim in checks.values()) and \
        out["failed"] == 0
    d = devices[0]
    import jax

    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics = {}
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if not traced:
        values = dict(out["e2e"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": _number(values[m["name"]]),
                                  "unit": m["unit"]}
    else:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        view = trace_mod.ReaderView(reduction=reduction, layer=out["layer"])
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"], cell.root)(view)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = reduction.breakdown()
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {name: {"value": _number(v), "limit": lim}
                        for name, (v, lim) in checks.items()}
    return result


def run(argv=None, *, require_tpu: bool = True, dtype: Optional[str] = None,
        t_start: Optional[float] = None, root: str = spec.ROOT) -> dict:
    """One run of one cell; returns the result object (not printed).

    ``require_tpu=False`` lets the tests drive a run on the CPU, and
    ``root`` read the cell from another checkout's files; ``dtype``
    lets the control run the program in a lower precision.  Raises
    :class:`NoDevice` where the chip is missing.
    """
    t_start = process_start_unix() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.resolve(args.workload, root)
    # no plan persisted or measured by another run may steer this one
    os.environ["REPRO_PLAN_CACHE"] = "off"
    src = os.path.join(spec.ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    compiles: list = []

    def on_duration(event: str, duration: float, fun_name: str = "?",
                    **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    devices = check_devices(cell.chips, require_tpu)
    # a run on the CPU (the tests) is priced as if on the benchmark's chip
    peaks = work.load_peaks(devices[0].device_kind if require_tpu
                            else "TPU v5 lite")
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), devices=devices, peaks=peaks,
                  compiles=compiles, dtype=dtype)
    driver = spec.load_module(cell.driver_path)
    out = driver.run(ctx)
    setup_s = out["t0_unix"] - t_start
    log(f"[run] set-up {setup_s:.3f} s; compiles inside the window: "
        f"{len(out['compiles_in_window'])} {out['compiles_in_window']}; "
        f"reference check {out['check_s']:.3f} s")
    reduction = None
    if ctx.trace:
        tdir = out["trace_dir"]
        try:
            reduction = trace_mod.reduce_dir(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        log(f"[trace] window {reduction.window_s:.3f} s, busy "
            f"{reduction.busy_s:.3f} s; programs launched "
            f"{reduction.launches}, seen on the device {reduction.executed}")
        if reduction.executed < reduction.launches:
            log("[trace] the device trace lost events: shorten the "
                "traffic's trace_seconds")
    result = build_result(cell, out, setup_s, ctx.trace, devices, reduction)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    return result


def configure_process() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached,
    so that only a cell's first run in a checkout compiles."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 os.path.join(spec.ROOT, ".jax_cache"))
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main(argv=None) -> int:
    t_start = process_start_unix()
    configure_process()
    try:
        result = run(argv, t_start=t_start)
    except NoDevice as e:
        log(f"[device] {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
