"""CLI for the repro static invariant analyzer.

Exit status is 0 when every violation is suppressed or baselined, 1
otherwise — ``make lint`` and the CI lint job gate on it.

Examples::

    python -m repro.analysis                      # whole tree
    python -m repro.analysis --rules RA2          # one family
    python -m repro.analysis src/repro/eig        # one subtree
    python -m repro.analysis --list-rules         # rule table
    python -m repro.analysis --update-baseline    # grandfather the tree
"""
from __future__ import annotations

import argparse
import json
import sys

from .engine import (DEFAULT_BASELINE, analyze_paths, baseline_key,
                     default_roots, load_baseline, write_baseline)
from .rules import all_rules, rules_matching


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="AST-based invariant analyzer (see rules with "
                    "--list-rules)")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to analyze (default: src/repro, "
                         "examples, tests)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule/family selectors, e.g. "
                         "'RA2' or 'RA101,RA3'")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline file of grandfathered violations")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report baselined violations too")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from the current tree")
    ap.add_argument("--no-cache", action="store_true",
                    help="ignore the mtime cache for this run")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule table and exit")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rule in all_rules():
            print(f"{rule.id}  {rule.title}")
            doc = (type(rule).__doc__ or "").strip().splitlines()
            for line in doc:
                print(f"       {line.strip()}")
            print()
        return 0

    if args.rules:
        selectors = [s.strip() for s in args.rules.split(",") if s.strip()]
        rules = rules_matching(selectors)
        if not rules:
            print(f"error: no rules match {args.rules!r}", file=sys.stderr)
            return 2
    else:
        rules = all_rules()

    paths = args.paths or default_roots()
    violations = analyze_paths(paths, rules, use_cache=not args.no_cache,
                               explicit_fixtures=bool(args.paths))

    if args.update_baseline:
        path = write_baseline(violations, args.baseline)
        print(f"baseline: {len(violations)} entr"
              f"{'y' if len(violations) == 1 else 'ies'} -> {path}")
        return 0

    baseline = set() if args.no_baseline else load_baseline(args.baseline)
    fresh = [v for v in violations if baseline_key(v) not in baseline]
    grandfathered = len(violations) - len(fresh)

    if args.as_json:
        print(json.dumps({
            "violations": [
                {"rule": v.rule, "path": v.path, "line": v.line,
                 "message": v.message} for v in fresh],
            "grandfathered": grandfathered,
        }, indent=1))
    else:
        for v in fresh:
            print(v.format())
        tail = f" ({grandfathered} baselined)" if grandfathered else ""
        print(f"repro.analysis: {len(fresh)} violation"
              f"{'' if len(fresh) == 1 else 's'}{tail}, "
              f"{len(rules)} rules")
    return 1 if fresh else 0


if __name__ == "__main__":
    raise SystemExit(main())
