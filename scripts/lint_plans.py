#!/usr/bin/env python
"""Lint the registered kernel×engine plan space without compiling it.

Sweeps every (kernel, engine) pair the registry admits at a
representative bucket/batch through the trace-time rules in
``repro.analyze`` and exits nonzero iff any error-severity finding
survives.  Wired into tier-1 (scripts/tier1.sh) and CI.

Examples:
    python scripts/lint_plans.py                      # full sweep, text
    python scripts/lint_plans.py --json               # machine-readable
    python scripts/lint_plans.py --rules R3 R401      # one family + one rule
    python scripts/lint_plans.py --ignore R303        # drop HLO scan
    python scripts/lint_plans.py --kernels 11 12 --engines banded \\
        --bucket 48x64 --batch 8
    python scripts/lint_plans.py --list-rules
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def parse_bucket(text):
    try:
        q, r = text.lower().split("x")
        return int(q), int(r)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"bucket must look like 64x64, got {text!r}")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--kernels", nargs="+", default=None,
                    help="kernel ids or names (default: whole zoo)")
    ap.add_argument("--engines", nargs="+", default=None,
                    help="engine names (default: all registered)")
    ap.add_argument("--bucket", type=parse_bucket, default=(64, 64),
                    metavar="QxR", help="bucket shape (default 64x64)")
    ap.add_argument("--batch", type=int, default=4,
                    help="batch size; 0 means single-pair plans")
    ap.add_argument("--rules", nargs="+", default=None, metavar="ID",
                    help="only these rule IDs/prefixes (e.g. R3 R401)")
    ap.add_argument("--ignore", nargs="+", default=None, metavar="ID",
                    help="drop these rule IDs/prefixes")
    ap.add_argument("--no-hlo", action="store_true",
                    help="skip HLO-lowering rules (faster; R303 off)")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON")
    ap.add_argument("--verbose", "-v", action="store_true",
                    help="include info-severity findings in text output")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule table and exit")
    args = ap.parse_args(argv)

    from repro import analyze

    if args.list_rules:
        for rule in analyze.ALL_RULES:
            print(f"{rule.id}  {rule.severity:7s} {rule.scope:6s} "
                  f"{rule.title:14s} {rule.doc}")
        return 0

    kernels = None
    if args.kernels is not None:
        kernels = [int(k) if k.isdigit() else k for k in args.kernels]

    config = analyze.LintConfig(hlo_rules=not args.no_hlo)
    try:
        report = analyze.lint_all(
            kernels=kernels, engines=args.engines, bucket=args.bucket,
            batch_size=args.batch or None, rules=args.rules,
            ignore=args.ignore, config=config)
    except ValueError as e:                      # bad selector / kernel name
        print(f"lint_plans: {e}", file=sys.stderr)
        return 2

    if args.json:
        print(report.to_json())
    else:
        print(report.format_text(verbose=args.verbose))
    return 0 if report.ok else 1


if __name__ == "__main__":
    from repro.runtime import compile_cache
    compile_cache.enable()
    sys.exit(main())
