"""Data-dependent control flow under @to_static.

Reference: the dy2static AST transpiler (python/paddle/jit/dy2static/
program_translator.py:325, transformers/ifelse_transformer.py,
while_loop_transformer.py) rewrites `if`/`while` on Tensor predicates
into `paddle.static.nn.cond/while_loop` calls via runtime-dispatch
wrappers (convert_ifelse / convert_while); the SOT path (jit/sot/
opcode_translator/executor/opcode_executor.py:303) does the same at
bytecode level with graph-break fallback.

TPU-native version: the same source-to-source rewrite, but the target is
`lax.cond` / `lax.while_loop` so the branch/loop lands INSIDE the traced
XLA program. The dispatch is at runtime — a python-bool predicate keeps
plain python control flow (and stays unrolled under tracing, exactly like
before); a Tensor predicate routes to the lax primitive. If the rewrite
or the lax lowering fails, @to_static "graph-breaks" COARSELY: the whole
function falls back to eager execution with a one-time warning (the SOT
equivalent breaks at the offending op; one-program-or-eager is the
compiled-framework tradeoff, SURVEY.md §3.3).

Transform contract (checked at transform time, clear errors otherwise):
- `if` on a Tensor predicate: both branches may assign locals; a branch
  that `return`s requires the other branch (or the code after) to return
  too. Assigned-in-one-branch names must already exist before the `if`.
- `while` on a Tensor predicate: the loop carry is every local assigned
  in the body; shapes/dtypes must be loop-invariant (lax.while_loop).
- `for` loops are left untouched (they unroll statically under tracing;
  use paddle_tpu.jit.scan for long rolled loops).
"""
from __future__ import annotations

import ast
import functools
import inspect
import textwrap
import warnings
import weakref

import jax
import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor

__all__ = ["cond", "while_loop", "scan", "convert_ifelse", "convert_while",
           "ast_transform", "Dy2StaticTransformError"]


class Dy2StaticTransformError(Exception):
    pass


def _unwrap(x):
    return x._value if isinstance(x, Tensor) else x


def _wrap_like(arrays, template):
    out = []
    for a, t in zip(arrays, template):
        if isinstance(t, Tensor):
            out.append(Tensor(a, stop_gradient=t.stop_gradient))
        else:
            out.append(a)
    return out


def _is_tensor_pred(pred):
    return isinstance(pred, Tensor) or isinstance(pred, jax.Array) \
        or isinstance(pred, jax.core.Tracer)


# ---------------------------------------------------------------------------
# public control-flow ops (paddle.static.nn.cond / while_loop parity)
# ---------------------------------------------------------------------------

def cond(pred, true_fn, false_fn, *operands):
    """lax.cond over Tensor-valued branch functions (reference:
    python/paddle/static/nn/control_flow.py cond). Both branches must
    return matching structures of equal shapes/dtypes."""
    pv = _unwrap(pred)
    arrs = [_unwrap(o) for o in operands]

    def mk(fn):
        def body(ops):
            out = fn(*_wrap_like(ops, operands)) if operands else fn()
            return jax.tree.map(_unwrap, out,
                                is_leaf=lambda x: isinstance(x, Tensor))
        return body

    out = jax.lax.cond(jnp.asarray(pv).astype(bool).reshape(()),
                       mk(true_fn), mk(false_fn), arrs)
    return jax.tree.map(lambda a: Tensor(a, stop_gradient=True)
                        if isinstance(a, (jax.Array, jax.core.Tracer))
                        else a, out)


def while_loop(cond, body, loop_vars, is_test=False, name=None):
    """lax.while_loop over Tensor loop vars (reference:
    python/paddle/static/nn/control_flow.py while_loop — param names
    match; is_test is a static-graph hint with no meaning here).
    Carried shapes/dtypes must be loop-invariant."""
    cond_fn, body_fn = cond, body
    template = list(loop_vars)
    init = [_unwrap(v) for v in template]

    def c(carry):
        return jnp.asarray(
            _unwrap(cond_fn(*_wrap_like(carry, template)))
        ).astype(bool).reshape(())

    def b(carry):
        out = body_fn(*_wrap_like(carry, template))
        if not isinstance(out, (tuple, list)):
            out = (out,)
        return [_unwrap(o) for o in out]

    final = jax.lax.while_loop(c, b, init)
    return _wrap_like(final, template)


def scan(f, init, xs):
    """lax.scan over Tensors: f(carry, x) -> (carry, y)."""
    def body(carry, x):
        c, y = f(Tensor(carry, stop_gradient=True),
                 Tensor(x, stop_gradient=True))
        return _unwrap(c), _unwrap(y)

    carry, ys = jax.lax.scan(body, _unwrap(init), _unwrap(xs))
    return (Tensor(carry, stop_gradient=True),
            Tensor(ys, stop_gradient=True))


# ---------------------------------------------------------------------------
# runtime dispatch helpers (targets of the AST rewrite)
# ---------------------------------------------------------------------------

def convert_ifelse(pred, true_fn, false_fn, ops=()):
    """`if` rewrite target: python-bool predicates branch in python
    (staying unrolled under tracing); Tensor predicates lower to
    lax.cond. `ops` are the call-site values of the names the branches
    read (passed as parameters so python scoping cannot shadow them);
    both fns return the tuple of branch-assigned locals."""
    if not _is_tensor_pred(pred):
        return true_fn(*ops) if pred else false_fn(*ops)

    def mk(fn):
        def body(_):
            out = fn(*ops)     # ops closed over: tracers ride the closure
            return jax.tree.map(
                _unwrap, out, is_leaf=lambda x: isinstance(x, Tensor))
        return body

    pv = jnp.asarray(_unwrap(pred)).astype(bool).reshape(())
    out = jax.lax.cond(pv, mk(true_fn), mk(false_fn), ())
    return jax.tree.map(
        lambda a: Tensor(a, stop_gradient=False)
        if isinstance(a, (jax.Array, jax.core.Tracer)) else a, out)


def convert_while(cond_fn, body_fn, init):
    """`while` rewrite target: evaluate the predicate once on the initial
    carry — python bool keeps a python loop; Tensor lowers to
    lax.while_loop with the assigned-locals tuple as carry."""
    first = cond_fn(*init)
    if not _is_tensor_pred(first):
        vals = tuple(init)
        ok = first
        while ok:
            vals = body_fn(*vals)
            ok = cond_fn(*vals)
            if _is_tensor_pred(ok):
                raise Dy2StaticTransformError(
                    "while predicate changed from python bool to Tensor "
                    "mid-loop; make it a Tensor from the start or use "
                    "paddle_tpu.jit.while_loop")
        return vals

    template = tuple(init)

    def c(carry):
        return jnp.asarray(
            _unwrap(cond_fn(*_wrap_like(carry, template)))
        ).astype(bool).reshape(())

    def b(carry):
        out = body_fn(*_wrap_like(carry, template))
        return tuple(jax.tree.map(
            _unwrap, tuple(out),
            is_leaf=lambda x: isinstance(x, Tensor)))

    init_arr = tuple(jax.tree.map(
        _unwrap, template, is_leaf=lambda x: isinstance(x, Tensor)))
    final = jax.lax.while_loop(c, b, init_arr)
    return tuple(_wrap_like(final, template))


# ---------------------------------------------------------------------------
# the AST transformer
# ---------------------------------------------------------------------------

class _AssignedNames(ast.NodeVisitor):
    """Names bound by a statement list (assign/augassign/for/with/etc.),
    not descending into nested function/class definitions."""

    def __init__(self):
        self.names: set[str] = set()

    def visit_FunctionDef(self, node):   # don't descend
        self.names.add(node.name)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        self.names.add(node.name)

    def visit_Name(self, node):
        if isinstance(node.ctx, (ast.Store, ast.Del)):
            self.names.add(node.id)

    def visit_arg(self, node):
        self.names.add(node.arg)


def _assigned(stmts):
    v = _AssignedNames()
    for s in stmts:
        v.visit(s)
    return v.names


def _has_return(stmts):
    for s in stmts:
        for n in ast.walk(s):
            if isinstance(n, ast.Return):
                return True
    return False


def _read_first(stmts):
    """Names whose FIRST use in this statement list is a Load —
    sequential approximation (nested branches merged, load wins).
    These must be fed into the extracted branch function as parameters,
    else python scoping turns `y = y * 2` into UnboundLocalError."""
    first: dict[str, str] = {}

    def note(name, kind):
        first.setdefault(name, kind)

    def walk_expr(node):
        for n in ast.walk(node):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
                note(n.id, "load")

    def walk_stmt(s):
        if isinstance(s, (ast.Assign, ast.AnnAssign)):
            if s.value is not None:
                walk_expr(s.value)
            targets = s.targets if isinstance(s, ast.Assign) else [s.target]
            for t in targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name) and isinstance(
                            n.ctx, ast.Store):
                        note(n.id, "store")
                    elif isinstance(n, ast.Name):
                        note(n.id, "load")   # x[i] = ... reads x
        elif isinstance(s, ast.AugAssign):
            walk_expr(s.value)
            for n in ast.walk(s.target):
                if isinstance(n, ast.Name):
                    note(n.id, "load")       # x += 1 reads x first
        elif isinstance(s, (ast.If, ast.While)):
            walk_expr(s.test)
            for b in (s.body, s.orelse):
                for st in b:
                    walk_stmt(st)
        elif isinstance(s, ast.For):
            walk_expr(s.iter)
            for n in ast.walk(s.target):
                if isinstance(n, ast.Name):
                    note(n.id, "store")
            for st in list(s.body) + list(s.orelse):
                walk_stmt(st)
        elif isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)):
            # a nested def BINDS its name (the transformer's own
            # _pt_true_N/_pt_false_N helpers land here). Decorators,
            # default values and class bodies evaluate AT the def
            # statement; the function body's free-variable reads are
            # deferred to call time but must still be bound in the
            # extracted scope — count both, minus names the inner
            # function binds itself.
            for dec in s.decorator_list:
                walk_expr(dec)
            if isinstance(s, ast.ClassDef):
                for base in list(s.bases) + [kw.value for kw in
                                             s.keywords]:
                    walk_expr(base)
                note(s.name, "store")
                for st in s.body:        # class bodies run immediately
                    walk_stmt(st)
            else:
                for d in (list(s.args.defaults)
                          + [d for d in s.args.kw_defaults
                             if d is not None]):
                    walk_expr(d)         # defaults run at def time
                note(s.name, "store")
                inner = ({a.arg for a in s.args.args}
                         | {a.arg for a in s.args.kwonlyargs}
                         | _assigned(s.body) | {s.name})
                if s.args.vararg:
                    inner.add(s.args.vararg.arg)
                if s.args.kwarg:
                    inner.add(s.args.kwarg.arg)
                for st in s.body:
                    for n in ast.walk(st):
                        if isinstance(n, ast.Name) \
                                and isinstance(n.ctx, ast.Load) \
                                and n.id not in inner:
                            note(n.id, "load")
        else:
            for n in ast.walk(s):
                if isinstance(n, ast.Name):
                    note(n.id, "load" if isinstance(n.ctx, ast.Load)
                         else "store")

    for s in stmts:
        walk_stmt(s)
    return {k for k, v in first.items() if v == "load"}


class _BreakFinder(ast.NodeVisitor):
    def __init__(self):
        self.found = False

    def visit_Break(self, node):
        self.found = True

    visit_Continue = visit_Break

    def visit_For(self, node):        # inner loops own their breaks
        pass

    visit_While = visit_For
    visit_FunctionDef = visit_For
    visit_AsyncFunctionDef = visit_For


def _has_break(stmts):
    f = _BreakFinder()
    for s in stmts:
        f.visit(s)
    return f.found


class _TailReturnNormalizer(ast.NodeTransformer):
    """`if p: ... return X` followed by more statements becomes
    `if p: ... return X else: <rest>` — semantically identical (the body
    path never falls through) and it turns the ubiquitous early-return
    pattern into the both-branches-return form the If rewrite accepts."""

    def _fix_body(self, stmts):
        out = []
        i = 0
        while i < len(stmts):
            s = stmts[i]
            rest = stmts[i + 1:]
            if (isinstance(s, ast.If) and s.body
                    and isinstance(s.body[-1], ast.Return)
                    and rest
                    and not (s.orelse
                             and isinstance(s.orelse[-1], ast.Return))):
                s.orelse = self._fix_body(list(s.orelse) + list(rest))
                out.append(self.visit(s))
                return out
            out.append(self.visit(s))
            i += 1
        return out

    def visit_FunctionDef(self, node):
        node.body = self._fix_body(node.body)
        return node

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_If(self, node):
        node.body = self._fix_body(node.body)
        node.orelse = self._fix_body(node.orelse)
        return node

    def visit_While(self, node):
        node.body = self._fix_body(node.body)
        return node

    visit_For = visit_While


class _CtrlFlowTransformer(ast.NodeTransformer):
    """Rewrite If/While into convert_ifelse/convert_while dispatch."""

    def __init__(self):
        self.counter = 0

    # -- if ---------------------------------------------------------------
    def visit_If(self, node):
        self.generic_visit(node)
        self.counter += 1
        n = self.counter
        body_ret = _has_return(node.body)
        else_ret = _has_return(node.orelse)

        if body_ret or else_ret:
            # only the tail form `if p: return X else: return Y` (possibly
            # with leading statements) maps onto cond cleanly
            if not (node.body and isinstance(node.body[-1], ast.Return)
                    and node.orelse
                    and isinstance(node.orelse[-1], ast.Return)):
                raise Dy2StaticTransformError(
                    f"line {node.lineno}: `return` inside a branch is "
                    "only supported when BOTH branches end in `return`; "
                    "restructure or use paddle_tpu.jit.cond")
            params = sorted(_read_first(node.body)
                            | _read_first(node.orelse))
            args = _params(params)
            tfn = _fdef(f"_pt_true_{n}", args, list(node.body))
            ffn = _fdef(f"_pt_false_{n}", args, list(node.orelse))
            ret = ast.Return(value=_call(
                "_pt_convert_ifelse",
                [node.test, ast.Name(f"_pt_true_{n}", ast.Load()),
                 ast.Name(f"_pt_false_{n}", ast.Load()),
                 _name_tuple(params)]))
            return [tfn, ffn, ret]

        stores_t = _assigned(node.body)
        stores_f = _assigned(node.orelse)
        bound_before = getattr(node, "_pt_bound_before", None)
        if bound_before is None:        # un-annotated (nested def): old rule
            names = sorted(stores_t | stores_f)
        else:
            # branch-local temps (assigned in ONE branch, no prior
            # binding) stay inside the extracted branch function — they
            # are not cond outputs and never read at the call site
            names = sorted(_if_outs(node, bound_before))
        # parameters: names the branches read before writing, plus out
        # names one branch passes through unchanged (it reads them for
        # the return tuple) — evaluated at the CALL SITE so python
        # scoping can't turn `y = y * 2` into UnboundLocalError
        params = sorted(
            _read_first(node.body) | _read_first(node.orelse)
            | {x for x in names if x not in stores_t or x not in stores_f})
        args = _params(params)
        out_tuple = ast.Tuple(
            elts=[ast.Name(x, ast.Load()) for x in names], ctx=ast.Load())
        tfn = _fdef(f"_pt_true_{n}", args,
                    list(node.body) + [ast.Return(out_tuple)])
        ffn = _fdef(f"_pt_false_{n}",
                    args, (list(node.orelse) or [ast.Pass()])
                    + [ast.Return(out_tuple)])
        assign = ast.Assign(
            targets=[ast.Tuple(
                elts=[ast.Name(x, ast.Store()) for x in names],
                ctx=ast.Store())],
            value=_call(
                "_pt_convert_ifelse",
                [node.test, ast.Name(f"_pt_true_{n}", ast.Load()),
                 ast.Name(f"_pt_false_{n}", ast.Load()),
                 _name_tuple(params)]))
        if not names:
            assign = ast.Expr(value=assign.value)
        return [tfn, ffn, assign]

    # -- while ------------------------------------------------------------
    def visit_While(self, node):
        self.generic_visit(node)
        if node.orelse:
            raise Dy2StaticTransformError(
                f"line {node.lineno}: while/else is not supported under "
                "to_static")
        if _has_return(node.body) or _has_break(node.body):
            raise Dy2StaticTransformError(
                f"line {node.lineno}: return/break/continue inside a "
                "`while` on a Tensor predicate cannot lower to "
                "lax.while_loop; restructure or use "
                "paddle_tpu.jit.while_loop")
        self.counter += 1
        n = self.counter
        # carry = names the body rebinds AND that live across iterations
        # (bound before / read-first / test-read); write-first temps stay
        # body-local. Everything else the test/body reads stays a
        # closure read (globals, helper fns, constants)
        bound_before = getattr(node, "_pt_bound_before", None)
        if bound_before is None:
            names = sorted(_assigned(node.body))
        else:
            names = sorted(_while_carries(node, bound_before))
        if not names:
            raise Dy2StaticTransformError(
                f"line {node.lineno}: `while` body assigns no locals — "
                "nothing to carry through lax.while_loop")
        args = ast.arguments(
            posonlyargs=[], args=[ast.arg(arg=x) for x in names],
            vararg=None, kwonlyargs=[], kw_defaults=[], kwarg=None,
            defaults=[])
        out_tuple = ast.Tuple(
            elts=[ast.Name(x, ast.Load()) for x in names], ctx=ast.Load())
        cfn = _fdef(f"_pt_wcond_{n}", args, [ast.Return(node.test)])
        bfn = _fdef(f"_pt_wbody_{n}", args,
                    list(node.body) + [ast.Return(out_tuple)])
        assign = ast.Assign(
            targets=[ast.Tuple(
                elts=[ast.Name(x, ast.Store()) for x in names],
                ctx=ast.Store())],
            value=_call(
                "_pt_convert_while",
                [ast.Name(f"_pt_wcond_{n}", ast.Load()),
                 ast.Name(f"_pt_wbody_{n}", ast.Load()),
                 ast.Tuple(elts=[ast.Name(x, ast.Load()) for x in names],
                           ctx=ast.Load())]))
        return [cfn, bfn, assign]


def _fdef(name, args, body):
    kw = {}
    import sys
    if sys.version_info >= (3, 12):
        kw["type_params"] = []
    return ast.FunctionDef(name=name, args=args, body=body,
                           decorator_list=[], returns=None,
                           type_comment=None, **kw)


def _noargs():
    return ast.arguments(posonlyargs=[], args=[], vararg=None,
                         kwonlyargs=[], kw_defaults=[], kwarg=None,
                         defaults=[])


def _params(names):
    return ast.arguments(
        posonlyargs=[], args=[ast.arg(arg=x) for x in names],
        vararg=None, kwonlyargs=[], kw_defaults=[], kwarg=None,
        defaults=[])


def _name_tuple(names):
    return ast.Tuple(elts=[ast.Name(x, ast.Load()) for x in names],
                     ctx=ast.Load())


def _call(name, args):
    return ast.Call(func=ast.Name(name, ast.Load()), args=args,
                    keywords=[])


def _uses_ctrl_flow(tree):
    for n in ast.walk(tree):
        if isinstance(n, (ast.If, ast.While)):
            return True
    return False


def _check_while_carries(fdef):
    """Reject (at transform time) any `while` whose body assigns a name
    that is not provably bound before the loop: visit_While makes every
    body-assigned local a lax.while_loop carry and reads it in the
    call-site init tuple, so an unbound carry is an UnboundLocalError at
    runtime with no eager fallback. Raising here instead routes the
    function through the existing Dy2StaticTransformError fallback
    (trace the original body)."""
    a = fdef.args
    bound = {x.arg for x in a.posonlyargs + a.args + a.kwonlyargs}
    if a.vararg:
        bound.add(a.vararg.arg)
    if a.kwarg:
        bound.add(a.kwarg.arg)
    _annotate_outside_loads(fdef)
    _check_block(fdef.body, bound)


def _test_reads(test):
    return {n.id for n in ast.walk(test)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}


def _annotate_outside_loads(fdef):
    """For each If/While in fdef, record the names LOADED anywhere in
    the function OUTSIDE that statement's own subtree — the liveness
    signal that distinguishes a private temp from a value the rest of
    the function consumes."""
    all_loads = [n for n in ast.walk(fdef)
                 if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)]
    for s in ast.walk(fdef):
        if isinstance(s, (ast.If, ast.While)):
            inside = set(map(id, ast.walk(s)))
            s._pt_outside_loads = frozenset(
                n.id for n in all_loads if id(n) not in inside)


def _while_carries(node, bound_before):
    """lax.while_loop carry = body-assigned names that are live OUTSIDE
    one iteration: bound before the loop, read-before-written in the
    body, read by the test, or read anywhere after/outside the loop.
    Pure write-first temps (incl. `_` unpacking slots) stay body-local —
    they caused spurious unbound-carry rejections."""
    assigned = _assigned(node.body)
    outside = getattr(node, "_pt_outside_loads", frozenset())
    return assigned & (set(bound_before) | _read_first(node.body)
                       | _test_reads(node.test) | set(outside))


def _if_outs(node, bound_before):
    """Names the if-transform's call-site assign binds: assigned in BOTH
    branches (cond can produce them whichever side runs), or assigned in
    one branch with a pre-existing binding to pass through. One-branch
    temps with no prior binding are private to the branch body —
    _check_block rejects them at transform time (-> eager fallback) if
    the rest of the function reads them, since lax.cond cannot produce
    a value with no else-side initial."""
    st, sf = _assigned(node.body), _assigned(node.orelse)
    return {x for x in st | sf
            if x in bound_before or (x in st and x in sf)}


def _check_block(stmts, bound):
    for s in stmts:
        if isinstance(s, ast.While):
            s._pt_bound_before = frozenset(bound)
            carries = _while_carries(s, bound)
            missing = sorted(carries - bound)
            if missing:
                raise Dy2StaticTransformError(
                    f"line {s.lineno}: `while` carries "
                    f"{', '.join(missing)} read before any binding; "
                    "lax.while_loop carries need an initial value — "
                    "initialize it before the loop")
            _check_block(s.body, set(bound) | _assigned(s.body))
            bound |= carries          # call-site assign rebinds carries
        elif isinstance(s, ast.If):
            s._pt_bound_before = frozenset(bound)
            st_a, sf_a = _assigned(s.body), _assigned(s.orelse)
            dropped = {x for x in (st_a ^ sf_a) if x not in bound}
            leaked = sorted(dropped
                            & getattr(s, "_pt_outside_loads", frozenset()))
            if leaked:
                raise Dy2StaticTransformError(
                    f"line {s.lineno}: {', '.join(leaked)} is assigned in "
                    "only one `if` branch but read after it; lax.cond "
                    "needs a value from both sides — bind it before the "
                    "`if` or in both branches")
            bt, bf = set(bound), set(bound)
            _check_block(s.body, bt)
            _check_block(s.orelse, bf)
            # the if-transform's call-site assign binds visit_If `names`
            bound |= _if_outs(s, bound)
        elif isinstance(s, ast.For):
            for n in ast.walk(s.target):
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
                    bound.add(n.id)
            # lenient: python `for` bodies usually run ≥1 time in traced
            # code; treat their assignments as binding
            _check_block(s.body, bound)
            bound |= _assigned(s.body)
        elif isinstance(s, ast.With):
            for item in s.items:
                if item.optional_vars is not None:
                    for n in ast.walk(item.optional_vars):
                        if isinstance(n, ast.Name) and isinstance(
                                n.ctx, ast.Store):
                            bound.add(n.id)
            _check_block(s.body, bound)
        elif isinstance(s, ast.Try):
            _check_block(s.body, bound)
            for h in s.handlers:
                _check_block(h.body, set(bound))
            _check_block(s.finalbody, bound)
        else:
            # assign/augassign/annassign/import/def/walrus-in-expr — the
            # same binder the while-transform uses to compute carries
            bound |= _assigned([s])


# fn.__code__ -> None (nothing to transform) | (compiled module, fdef name).
# Only the SOURCE transform is memoized by code object — closure values are
# bound per function instance below, so two closures created from the same
# factory do not share captured values.
_transform_memo: dict = {}
_instance_memo: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def ast_transform(fn):
    """Source-to-source rewrite of `fn` routing if/while through the
    convert_* dispatchers. Returns the transformed function, or None if
    `fn` has no if/while (nothing to do). Raises
    Dy2StaticTransformError for unsupported shapes."""
    try:
        cached = _instance_memo.get(fn)
    except TypeError:
        cached = None
    if cached is not None:
        return cached
    key = getattr(fn, "__code__", None)
    if key not in _transform_memo:
        _transform_memo[key] = _compile_transform(fn, key)
    entry = _transform_memo[key]
    if entry is None:
        return None
    code, fname = entry

    glb = dict(fn.__globals__)
    glb["_pt_convert_ifelse"] = convert_ifelse
    glb["_pt_convert_while"] = convert_while
    # closures: snapshot THIS instance's freevars (cells are read-only
    # here); never shared across instances of the same code object
    if fn.__closure__:
        for name, cell in zip(fn.__code__.co_freevars, fn.__closure__):
            try:
                glb[name] = cell.cell_contents
            except ValueError:
                pass
    loc: dict = {}
    exec(code, glb, loc)
    new_fn = functools.wraps(fn)(loc[fname])
    # wraps() sets new_fn.__wrapped__ = fn: a strong value→key reference
    # would make every WeakKeyDictionary entry immortal (and pin the
    # globals snapshot). Drop it so instances are evicted with their fn.
    del new_fn.__wrapped__
    try:
        _instance_memo[fn] = new_fn
    except TypeError:
        pass
    return new_fn


def _compile_transform(fn, key):
    try:
        src = textwrap.dedent(inspect.getsource(fn))
    except (OSError, TypeError):
        return None
    tree = ast.parse(src)
    fdef = tree.body[0]
    if not isinstance(fdef, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return None
    if not _uses_ctrl_flow(fdef):
        return None
    _check_while_carries(fdef)
    fdef.decorator_list = []          # drop @to_static etc.
    tree = _TailReturnNormalizer().visit(tree)
    new_tree = _CtrlFlowTransformer().visit(tree)
    ast.fix_missing_locations(new_tree)
    return (compile(new_tree, f"<dy2static:{fn.__qualname__}>", "exec"),
            fdef.name)
