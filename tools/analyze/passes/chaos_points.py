"""Pass `chaos-points` — every chaos injection site is registered.

Port of tools/check_chaos_points.py: `distributed/chaos.py` carries
POINTS, the documented registry of every named fault-injection site.
An injection call whose site literal is not registered is invisible to
operators reading the catalogue, so every
`chaos.should_fire/maybe_*("site")` call in paddle_tpu/ must name a
registered site (registry keys ending in "/" cover dynamically-suffixed
f-string sites by static prefix), and the site argument must BE a
literal/f-string — a variable cannot be audited.

The legacy `scan(root) -> (violations, seen, points)` surface is kept
for tools/check_chaos_points.py (now a shim) and its tests.
"""
from __future__ import annotations

import ast
import importlib.util
import os

from tools.analyze.core import Finding, build_index

PASS_ID = "chaos-points"
DESCRIPTION = ("chaos injection sites must be string literals "
               "registered in distributed/chaos.py POINTS")

INJECTORS = {"should_fire", "maybe_delay", "maybe_drop",
             "maybe_preempt", "maybe_corrupt_file", "grad_poison",
             "loss_spike"}

# the registry module itself (its function bodies pass `site` variables
# around, which is the implementation, not an injection site)
ALLOWED = {os.path.join("paddle_tpu", "distributed", "chaos.py")}


def _load_points(root: str) -> dict:
    path = os.path.join(root, "paddle_tpu", "distributed", "chaos.py")
    if not os.path.isfile(path):
        return {}                   # no registry: nothing to audit
    spec = importlib.util.spec_from_file_location("_chaos_registry", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)        # stdlib-only module (no jax)
    return dict(getattr(mod, "POINTS", {}))


def _site_of(node):
    """(site, is_prefix) of an injection call's first argument, or
    (None, False) when it is not a literal. An f-string yields its
    static leading text as a prefix."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value, False
    if isinstance(node, ast.JoinedStr):
        if node.values and isinstance(node.values[0], ast.Constant) \
                and isinstance(node.values[0].value, str):
            return node.values[0].value, True
        return None, False
    return None, False


def _covered(site: str, is_prefix: bool, points: dict) -> bool:
    if not is_prefix:
        return site in points or any(
            k.endswith("/") and site.startswith(k) for k in points)
    # an f-string's static prefix must match a registered prefix key
    return any(k.endswith("/") and site.startswith(k) for k in points)


def _scan_index(index):
    """(violations, seen, points): violations are (rel, lineno, call,
    problem); seen is the set of (site, is_prefix) literals."""
    points = _load_points(index.root)
    violations = []
    seen = set()
    for mod in index.under("paddle_tpu"):
        if mod.rel in ALLOWED or mod.tree is None:
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            name = (func.attr if isinstance(func, ast.Attribute)
                    else func.id if isinstance(func, ast.Name)
                    else None)
            if name not in INJECTORS or not node.args:
                continue
            site, is_prefix = _site_of(node.args[0])
            call = f"{name}({ast.unparse(node.args[0])})"
            if site is None:
                violations.append(
                    (mod.rel, node.lineno, call,
                     "site is not a string literal / f-string — "
                     "cannot be audited against chaos.POINTS"))
                continue
            seen.add((site, is_prefix))
            if not _covered(site, is_prefix, points):
                violations.append(
                    (mod.rel, node.lineno, call,
                     f"site {site!r} is not in the chaos.POINTS "
                     "registry (distributed/chaos.py) — document "
                     "it there"))
    return violations, seen, points


def run(index):
    violations, _seen, _points = _scan_index(index)
    for rel, no, call, why in violations:
        yield Finding(PASS_ID, rel, no, f"{call}: {why}")


def scan(root: str):
    """Legacy surface (tools/check_chaos_points.py shim + its tests).
    Indexes only paddle_tpu/ — all this scanner ever looked at."""
    return _scan_index(build_index(root, subdirs=("paddle_tpu",)))
