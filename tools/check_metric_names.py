#!/usr/bin/env python
"""Fail CI when a metric or span instrumentation site is off-catalogue.

THIN SHIM: the scanner now lives in the unified static-analysis
framework as the `metric-names` pass
(tools/analyze/passes/metric_names.py) and runs with the full suite via
`python -m tools.analyze`. This CLI (and its `scan(root)` / `ALLOWED`
surface, used by tests/test_metric_names_tool.py) is kept so nothing
downstream breaks.

Usage: python tools/check_metric_names.py [root]
Exit 0 = clean, 1 = undocumented or unauditable names found. Stale
catalogue entries (documented but never instrumented) are reported as
a warning without failing — scrape-time-only metrics and mid-migration
names are legitimate.
"""
from __future__ import annotations

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from tools.analyze.passes.metric_names import (  # noqa: E402,F401
    ACQUIRERS, ALLOWED, INSTRUMENTS, SPAN_CALLS, scan, scan_spans)


def main(argv):
    root = argv[1] if len(argv) > 1 else _ROOT
    violations, seen, catalogue = scan(root)
    if violations:
        print(f"check_metric_names: {len(violations)} off-catalogue "
              "metric site(s):", file=sys.stderr)
        for rel, no, call, why in violations:
            print(f"  {rel}:{no}: {call}\n      -> {why}",
                  file=sys.stderr)
        return 1
    stale = sorted(k for k in catalogue if k not in seen)
    if stale:
        # warn only: a catalogued metric may be recorded through a
        # non-gated path (exporters) or be mid-migration
        print("check_metric_names: warning, catalogue entries with no "
              f"literal call site: {stale}")
    _v, spans_seen, spans = scan_spans(root)
    print(f"check_metric_names: clean ({len(seen)} literal name(s) "
          f"across the package, {len(catalogue)} catalogued; "
          f"{len(spans_seen)} span name(s) of {len(spans)})")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
