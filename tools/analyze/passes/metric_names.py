"""Pass `metric-names` — every metric instrumentation site is catalogued.

Port of tools/check_metric_names.py: `observability/metrics.py` carries
METRICS, the closed catalogue of every metric name. An instrumentation
call (`inc`/`observe`/`set_gauge`) with an uncatalogued or non-literal
name would mint a metric invisible to operators reading the docs;
acquisition calls (`counter`/`gauge`/`histogram`) are checked only when
their first argument IS a literal (np.histogram/jnp.histogram share the
method name with array first arguments and must not false-positive).

Span names are held the same way: `observability/trace.py` carries
SPANS, and every `observability.span(...)` / `step_span(...)` call in
the package must name a literal from it (a span the catalogue does not
list is one no benchmark reader and no operator knows to look for).

The legacy `scan(root) -> (violations, seen, catalogue)` surface is
kept for tools/check_metric_names.py (now a shim) and its tests; the
span findings ride the same violations list, `scan_spans(root)` gives
their (violations, seen, catalogue) alone.
"""
from __future__ import annotations

import ast
import importlib.util
import os

from tools.analyze.core import Finding, build_index

PASS_ID = "metric-names"
DESCRIPTION = ("metric instrumentation names must be string literals "
               "from the observability/metrics.py METRICS catalogue")

# literal-REQUIRED instrumentation calls
INSTRUMENTS = {"inc", "observe", "set_gauge"}
# literal-checked-when-literal acquisition calls
ACQUIRERS = {"counter", "gauge", "histogram"}
# span entry points: literal-REQUIRED when called on `observability`
SPAN_CALLS = {"span", "step_span"}

# the registry implementation itself passes `name` variables around;
# same for the module-level helper shims in the package __init__.
# observability/requests.py (the request-tracing SLO instrumentation)
# is deliberately NOT here: its request.* literals are audited like
# any other call site (tests/test_metric_names_tool.py pins that).
ALLOWED = {
    os.path.join("paddle_tpu", "observability", "metrics.py"),
    os.path.join("paddle_tpu", "observability", "__init__.py"),
}


def _load_catalogue(root: str, module="metrics.py",
                    attr="METRICS") -> dict:
    path = os.path.join(root, "paddle_tpu", "observability", module)
    if not os.path.isfile(path):
        return {}                   # no catalogue: nothing to audit
    spec = importlib.util.spec_from_file_location(
        "_catalogue_" + module[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)        # stdlib-only module (no jax)
    return dict(getattr(mod, attr, {}))


def _literal_of(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _scan_index(index):
    """(violations, seen, catalogue); violations are (rel, lineno,
    call, problem)."""
    catalogue = _load_catalogue(index.root)
    violations = []
    seen = set()
    for mod in index.under("paddle_tpu"):
        if mod.rel in ALLOWED or mod.tree is None:
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            func = node.func
            name = (func.attr if isinstance(func, ast.Attribute)
                    else func.id if isinstance(func, ast.Name)
                    else None)
            if name not in INSTRUMENTS and name not in ACQUIRERS:
                continue
            metric = _literal_of(node.args[0])
            call = f"{name}({ast.unparse(node.args[0])})"
            if metric is None:
                if name in INSTRUMENTS:
                    violations.append(
                        (mod.rel, node.lineno, call,
                         "metric name is not a string literal — "
                         "cannot be audited against the METRICS "
                         "catalogue"))
                continue
            seen.add(metric)
            if metric not in catalogue:
                violations.append(
                    (mod.rel, node.lineno, call,
                     f"metric {metric!r} is not in the METRICS "
                     "catalogue (observability/metrics.py) — "
                     "register it there"))
    return violations, seen, catalogue


def _scan_spans(index):
    """(violations, seen, catalogue) of `observability.span(...)` and
    `observability.step_span(...)` call sites against trace.SPANS.
    Only calls ON `observability` count: `re.Match.span()` and the
    like share the method name."""
    catalogue = _load_catalogue(index.root, "trace.py", "SPANS")
    violations, seen = [], set()
    if not catalogue:
        return violations, seen, catalogue
    for mod in index.under("paddle_tpu"):
        if mod.rel in ALLOWED or mod.tree is None:
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            func = node.func
            if not (isinstance(func, ast.Attribute)
                    and func.attr in SPAN_CALLS
                    and isinstance(func.value, ast.Name)
                    and func.value.id in ("observability", "obs")):
                continue
            name = _literal_of(node.args[0])
            call = f"{func.attr}({ast.unparse(node.args[0])})"
            if name is None:
                violations.append(
                    (mod.rel, node.lineno, call,
                     "span name is not a string literal — cannot be "
                     "audited against the SPANS catalogue"))
                continue
            seen.add(name)
            if name not in catalogue:
                violations.append(
                    (mod.rel, node.lineno, call,
                     f"span {name!r} is not in the SPANS catalogue "
                     "(observability/trace.py) — register it there"))
    return violations, seen, catalogue


def run(index):
    violations, _seen, _cat = _scan_index(index)
    violations += _scan_spans(index)[0]
    for rel, no, call, why in violations:
        yield Finding(PASS_ID, rel, no, f"{call}: {why}")


def _index(root):
    # only paddle_tpu/ — all this scanner ever looked at
    return build_index(root, subdirs=("paddle_tpu",))


def scan(root: str):
    """Legacy surface (tools/check_metric_names.py shim + its tests):
    metric sites as ever, with the span sites' violations appended."""
    index = _index(root)
    violations, seen, catalogue = _scan_index(index)
    return violations + _scan_spans(index)[0], seen, catalogue


def scan_spans(root: str):
    """(violations, seen, catalogue) of the span call sites alone."""
    return _scan_spans(_index(root))
