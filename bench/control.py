"""Readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload <cell> --program-seeds 1 2 ... \
        --control-seeds 101 102 103 [--seconds 3]

For each program seed, one run of the cell as ``bench/run.py`` makes it
(the lower readings).  For each control seed, the same run with the
program's own bfloat16 path, the same backend and tiles as the float32
plan, in the program's place (the upper readings).

Prints one JSON line per run with its compared numbers.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)
sys.path.insert(0, os.path.dirname(HERE))

from bench import run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--program-seconds", type=float, default=None)
    args = ap.parse_args(argv)

    run.configure_process()
    cell = spec.resolve(args.workload)
    prog_s = args.program_seconds or args.seconds
    for side, seeds, seconds, dtype in (
            ("program", args.program_seeds, prog_s, None),
            ("control", args.control_seeds, args.seconds, "bfloat16")):
        for seed in seeds:
            r = run.run(["--workload", cell.name, "--seed", str(seed),
                         "--seconds", str(seconds)], dtype=dtype)
            print(json.dumps({"side": side, "seed": seed,
                              "correct": r["correct"], "checks": r["checks"],
                              "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
