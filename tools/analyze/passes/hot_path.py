"""Pass `hot-path-sync` — no host syncs inside traced/jit bodies.

The dispatch hot path (PR 4 made `Trainer.step` zero-`device_put`;
PR 6's paged tick is one fused jit call) dies by a thousand implicit
host syncs: `.item()`, `float()/int()/bool()` on array values,
`np.asarray`, `jax.device_get`, `.block_until_ready()` and `print`
all force the dispatch thread to wait on the device (or fail outright
under tracing). This pass flags them inside

  - functions decorated with `@jax.jit` / `@partial(jax.jit, ...)` /
    `@pl.pallas_call(...)`,
  - functions *wrapped* at a distance: any name referenced in the
    first argument of a call whose callee name contains "jit"
    (`jax.jit(step, ...)`, `self._jit_step(step)`) or is
    `pallas_call(kernel, ...)` — lambdas in that argument count too,
  - the configured known hot bodies (KNOWN_HOT qualnames).

`int()/float()/bool()` on constants or on shape/ndim/dtype expressions
are static under tracing and exempt; `jax.debug.print` is the
sanctioned in-graph print and is not flagged.
"""
from __future__ import annotations

import ast

from tools.analyze.core import Finding
from tools.analyze.passes._util import dotted

PASS_ID = "hot-path-sync"
DESCRIPTION = ("host syncs (.item/float/np.asarray/device_get/print) "
               "inside jit-traced or known-hot functions")

# qualnames treated as hot even without a visible jit wrapper: the
# trainer's per-step dispatch body (PR 4's zero-device_put contract)
KNOWN_HOT = {"Trainer.step", "Trainer._step"}

_NUMPY_MATERIALIZERS = {"asarray", "array"}
_CAST_BUILTINS = {"float", "int", "bool"}
_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef)


def _numpy_aliases(tree):
    """Names the module binds to the numpy module ('np', 'numpy')."""
    aliases = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    aliases.add(a.asname or "numpy")
    return aliases


def _callee_is_jitlike(call):
    """True when `call` wraps its first argument in a traced context:
    the callee's last name component contains 'jit' (jax.jit, jit,
    self._jit_step) or is 'pallas_call'."""
    name = dotted(call.func)
    if name is None and isinstance(call.func, ast.Attribute):
        name = call.func.attr
    if not name:
        return False
    last = name.rsplit(".", 1)[-1].lower()
    return "jit" in last or last == "pallas_call"


def _decorator_is_jitlike(dec):
    """@jax.jit / @jit / @partial(jax.jit, ...) / @pl.pallas_call(...)."""
    exprs = [dec]
    if isinstance(dec, ast.Call):
        exprs = [dec.func] + list(dec.args)
    for e in exprs:
        name = dotted(e)
        if not name:
            continue
        last = name.rsplit(".", 1)[-1].lower()
        if "jit" in last or last == "pallas_call":
            return True
    return False


def _local_defs(tree):
    """name -> [def nodes] for every function def in the module (any
    nesting level); jit-wrap references resolve by name module-wide,
    which is the right granularity for `jax.jit(run, ...)` closures."""
    defs = {}
    for node in ast.walk(tree):
        if isinstance(node, _DEFS):
            defs.setdefault(node.name, []).append(node)
    return defs


def _traced_defs(mod):
    """The set of def/lambda nodes whose bodies execute under trace (or
    are configured hot), each with the reason it was selected."""
    tree = mod.tree
    defs_by_name = _local_defs(tree)
    traced = {}

    def mark(node, reason):
        traced.setdefault(node, reason)

    for node in ast.walk(tree):
        if isinstance(node, _DEFS):
            if any(_decorator_is_jitlike(d) for d in node.decorator_list):
                mark(node, f"`{node.name}` is jit/pallas-decorated")
            qn = mod.qualname(node)
            if qn in KNOWN_HOT:
                mark(node, f"`{qn}` is a known hot body")
        elif isinstance(node, ast.Call) and node.args \
                and _callee_is_jitlike(node):
            wrapper = dotted(node.func) or "jit"
            for ref in ast.walk(node.args[0]):
                if isinstance(ref, ast.Lambda):
                    mark(ref, f"lambda passed to {wrapper}(...)")
                elif isinstance(ref, ast.Name):
                    for d in defs_by_name.get(ref.id, ()):
                        mark(d, f"`{d.name}` is wrapped by "
                                f"{wrapper}(...)")
    return traced


def _is_static_cast_arg(arg):
    """float/int/bool on constants or shape/ndim/dtype/len expressions
    is resolved at trace time — not a device sync."""
    if isinstance(arg, ast.Constant):
        return True
    for n in ast.walk(arg):
        if isinstance(n, ast.Attribute) and n.attr in (
                "shape", "ndim", "dtype", "itemsize"):
            return True
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                and n.func.id == "len":
            return True
    return False


def _scan_body(mod, fn_node, reason, np_aliases, seen):
    for node in walk_no_defs_body(fn_node):
        if not isinstance(node, ast.Call):
            continue
        key = (node.lineno, node.col_offset)
        if key in seen:
            continue
        msg = None
        f = node.func
        if isinstance(f, ast.Attribute):
            base = dotted(f.value)
            if f.attr == "item" and not node.args:
                msg = ".item() forces a blocking device->host sync"
            elif f.attr == "block_until_ready":
                msg = ".block_until_ready() is a host sync"
            elif f.attr == "device_get":
                msg = "jax.device_get pulls values to host"
            elif f.attr in _NUMPY_MATERIALIZERS and base in np_aliases:
                msg = (f"{base}.{f.attr}(...) materializes on host "
                       "(TracerArrayConversionError under tracing, "
                       "a sync otherwise)")
        elif isinstance(f, ast.Name):
            if f.id == "print":
                msg = ("print() breaks async dispatch (use "
                       "jax.debug.print inside traced code)")
            elif f.id in _CAST_BUILTINS and node.args \
                    and not all(_is_static_cast_arg(a)
                                for a in node.args):
                msg = (f"{f.id}() on an array value forces a "
                       "device sync / concretization")
        if msg:
            seen.add(key)
            yield Finding(PASS_ID, mod.rel, node.lineno,
                          f"{msg} — {reason}")


def walk_no_defs_body(fn_node):
    """Walk a traced function's WHOLE subtree including nested defs:
    a def nested in a traced body is traced too (lax.scan bodies,
    closures), so unlike the thread pass we do descend."""
    yield from ast.walk(fn_node)


def run(index):
    for mod in index.modules:
        if mod.tree is None:
            continue
        np_aliases = _numpy_aliases(mod.tree)
        traced = _traced_defs(mod)
        seen = set()
        # deterministic order: by position in file
        for fn_node in sorted(traced, key=lambda n: (n.lineno,
                                                     n.col_offset)):
            yield from _scan_body(mod, fn_node, traced[fn_node],
                                  np_aliases, seen)
