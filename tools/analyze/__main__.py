"""CLI runner: `python -m tools.analyze [root] [--json] [--pass <id>]`.

Exit-code contract (CI consumes this — keep it stable):

  0  zero NEW findings: everything emitted was either suppressed
     inline (`# lint: disable=<id> -- why`) or grandfathered in
     tools/analyze/baseline.json.  Warnings (stale baseline entries,
     unused suppressions, unparseable files) NEVER affect the exit
     code — they print to stdout and are advisory.
  1  at least one new finding.  Human mode prints each to stderr as
     `[pass] file:line (qualname): message`; --json mode prints the
     document to stdout and still exits 1.
  2  usage error (unknown --pass id, bad arguments).

--json emits the schema-stable (version 2) document from
Report.to_json(): each finding carries {pass, severity, file, line,
qualname, message, suppressed}.  Suppressed findings are included with
suppressed=true for auditability; only suppressed=false findings drive
the exit code.  `notes` holds per-pass tables (lock-order's canonical
acquisition order); `counts` and `warnings` round out the document.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# `python tools/analyze/__main__.py` (not -m): make tools.* importable
_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from tools.analyze import (ALL_PASSES, BY_ID, Baseline,  # noqa: E402
                           analyze_tree, default_baseline_path)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m tools.analyze",
        description="multi-pass static analysis for the paddle_tpu "
                    "corpus (paddle_tpu/, tools/)")
    ap.add_argument("root", nargs="?", default=None,
                    help="tree to analyze (default: this repo)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the version-2 JSON document (findings "
                         "with qualname + suppressed flag, notes)")
    ap.add_argument("--pass", dest="passes", action="append",
                    metavar="ID", default=None,
                    help="run only this pass (repeatable)")
    ap.add_argument("--list-passes", action="store_true",
                    help="print the pass catalogue and exit")
    ap.add_argument("--tables", action="store_true",
                    help="print per-pass summary tables (e.g. the "
                         "lock-order canonical acquisition order)")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="baseline file (default: "
                         "tools/analyze/baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline (every finding is new)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="re-write the baseline from the current "
                         "findings (ratchet helper; justifications "
                         "must then be filled in by hand)")
    args = ap.parse_args(argv)

    if args.list_passes:
        for p in ALL_PASSES:
            print(f"{p.PASS_ID:18s} {p.DESCRIPTION}")
        return 0

    root = args.root or os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if args.passes:
        unknown = [p for p in args.passes if p not in BY_ID]
        if unknown:
            print(f"unknown pass id(s): {', '.join(unknown)} "
                  f"(known: {', '.join(sorted(BY_ID))})",
                  file=sys.stderr)
            return 2

    report = analyze_tree(
        root, pass_ids=args.passes,
        baseline_path=args.baseline,
        use_baseline=not args.no_baseline)

    if args.write_baseline:
        path = args.baseline or default_baseline_path()
        Baseline.dump(report.new + report.baselined, path,
                      prior=Baseline.load(path),
                      ran_pass_ids=set(args.passes) if args.passes
                      else set(BY_ID))
        print(f"tools.analyze: wrote {len(report.new) + len(report.baselined)} "
              f"baseline entr(ies) to {path}")
        return 0

    if args.as_json:
        print(json.dumps(report.to_json(), indent=2))
        return report.exit_code

    for w in report.warnings:
        print(f"tools.analyze: warning: {w}")
    if args.tables:
        for pid, lines in sorted(report.notes.items()):
            print(f"-- {pid} --")
            for line in lines:
                print(f"  {line}")
    if report.new:
        print(f"tools.analyze: {len(report.new)} new finding(s) "
              f"({len(report.baselined)} baselined, "
              f"{len(report.suppressed)} suppressed):", file=sys.stderr)
        for f in report.new:
            print(f"  {f.render()}", file=sys.stderr)
        return 1
    print(f"tools.analyze: clean — {len(ALL_PASSES if not args.passes else args.passes)} "
          f"pass(es), 0 new finding(s) "
          f"({len(report.baselined)} baselined, "
          f"{len(report.suppressed)} suppressed)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
