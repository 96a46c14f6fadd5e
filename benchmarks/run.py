"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--only fig5`` restricts.
"""
import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the emitted rows as a JSON artifact "
                         "(BENCH_*.json for the CI regression compare)")
    args = ap.parse_args()

    from repro import compat
    compat.enable_compile_cache()

    from benchmarks import (bench_eig, bench_fig5, bench_fig6, bench_fig7,
                            bench_fig8, bench_iolb, bench_memops,
                            bench_serve, bench_smoke, common)
    suites = {
        "smoke": bench_smoke,
        "fig5": bench_fig5, "fig6": bench_fig6, "fig7": bench_fig7,
        "fig8": bench_fig8, "memops": bench_memops, "iolb": bench_iolb,
        "eig": bench_eig, "serve": bench_serve,
    }
    if args.only and args.only not in suites:
        ap.error(f"unknown suite {args.only!r}; one of {sorted(suites)}")
    print("name,us_per_call,derived")
    common.reset_results()
    failed = []
    for name, mod in suites.items():
        if args.only and name != args.only:
            continue
        try:
            mod.run()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if args.json:
        common.write_json(args.json, meta={"only": args.only})
        print(f"wrote {args.json}", file=sys.stderr)
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == '__main__':
    main()
