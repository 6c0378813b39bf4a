#!/usr/bin/env python3
"""graftlint CLI — run the paddle_tpu.analysis invariant checker.

Usage:
    python tools/lint.py paddle_tpu tools tests          # lint (text)
    python tools/lint.py --json paddle_tpu               # machine output
    python tools/lint.py --update-baseline --reason "..." paddle_tpu ...
    python tools/lint.py --gen-knobs                     # regen registry
    python tools/lint.py --check-knobs                   # registry sync

Exit codes: 0 clean (modulo baseline), 1 findings / out of sync,
2 usage error.

Imports paddle_tpu.analysis through a STUB parent package so linting
never executes paddle_tpu/__init__ (which imports jax — a lint run must
not claim the chip or pay a backend start-up).  The analysis package is
stdlib-only by design.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_analysis():
    if "paddle_tpu" not in sys.modules:
        stub = types.ModuleType("paddle_tpu")
        stub.__path__ = [os.path.join(ROOT, "paddle_tpu")]
        sys.modules["paddle_tpu"] = stub
    import importlib
    return importlib.import_module("paddle_tpu.analysis")


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="lint.py", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint, relative to the repo root")
    ap.add_argument("--json", action="store_true",
                    help="JSON output: {findings, baselined, stats}")
    ap.add_argument("--baseline",
                    default=os.path.join("tools",
                                         "graftlint_baseline.json"),
                    help="baseline file (default: "
                         "tools/graftlint_baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline (report everything)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="write current findings into the baseline "
                         "(requires --reason)")
    ap.add_argument("--reason", default="",
                    help="reason recorded on new baseline entries")
    ap.add_argument("--rule", action="append", default=[],
                    help="restrict to the given rule id(s)")
    ap.add_argument("--gen-knobs", action="store_true",
                    help="regenerate docs/ENV_KNOBS.md (descriptions "
                         "preserved) and exit")
    ap.add_argument("--check-knobs", action="store_true",
                    help="verify docs/ENV_KNOBS.md is in sync and exit")
    args = ap.parse_args(argv)

    an = _load_analysis()

    if args.gen_knobs:
        an.knobs.generate(ROOT)
        print("regenerated docs/ENV_KNOBS.md")
        return 0
    if args.check_knobs:
        ok, msg = an.knobs.check_sync(ROOT)
        if not ok:
            print(msg, file=sys.stderr)
            return 1
        print("docs/ENV_KNOBS.md in sync")
        return 0

    if not args.paths:
        ap.error("no paths given (try: paddle_tpu tools tests)")

    rules = an.ALL_RULES
    if args.rule:
        unknown = [r for r in args.rule if r not in an.RULES_BY_ID]
        if unknown:
            ap.error(f"unknown rule id(s): {unknown}; "
                     f"known: {sorted(an.RULES_BY_ID)}")
        rules = [an.RULES_BY_ID[r] for r in args.rule]

    findings, stats = an.run_paths(args.paths, ROOT, rules)

    baseline_path = os.path.join(ROOT, args.baseline)
    if args.update_baseline:
        if not args.reason.strip():
            ap.error("--update-baseline requires a non-empty --reason "
                     "(every baseline entry must say why it is "
                     "grandfathered)")
        an.save_baseline(baseline_path, findings, args.reason.strip())
        print(f"baseline written: {len(findings)} entr"
              f"{'y' if len(findings) == 1 else 'ies'} -> "
              f"{args.baseline}")
        return 0

    baselined = []
    if not args.no_baseline:
        baseline, bad_entries = an.load_baseline(baseline_path)
        findings.extend(bad_entries)
        findings, baselined = an.apply_baseline(findings, baseline)

    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    if args.json:
        print(json.dumps({
            "findings": [f.to_json() for f in findings],
            "baselined": [f.to_json() for f in baselined],
            "stats": dict(stats, new=len(findings),
                          baselined=len(baselined)),
        }, indent=1))
    else:
        for f in findings:
            print(f)
        print(f"graftlint: {len(findings)} finding"
              f"{'' if len(findings) == 1 else 's'} "
              f"({len(baselined)} baselined, "
              f"{stats['suppressed']} suppressed) "
              f"across {stats['files']} files")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
