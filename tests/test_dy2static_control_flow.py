"""Data-dependent control flow under to_static (reference:
test/dygraph_to_static/test_ifelse.py, test_while_op.py; dy2static
ifelse/while transformers). The AST rewrite must lower Tensor-predicate
if/while to lax.cond/while_loop inside ONE traced program, python-bool
control flow must stay python, and untraceable host-dependence must
graph-break to eager with a warning — matching eager numerics in every
case."""
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
import paddle_tpu.tensor as T


def test_jit_cond_api():
    x = paddle.to_tensor(np.array([2.0], "float32"))
    out = paddle.jit.cond(T.sum(x) > 1.0,
                          lambda: x * 2.0, lambda: x - 1.0)
    np.testing.assert_allclose(out.numpy(), [4.0])
    out = paddle.jit.cond(T.sum(x) > 5.0,
                          lambda: x * 2.0, lambda: x - 1.0)
    np.testing.assert_allclose(out.numpy(), [1.0])


def test_jit_while_loop_api():
    i = paddle.to_tensor(np.array(0.0, "float32"))
    s = paddle.to_tensor(np.array(1.0, "float32"))
    i2, s2 = paddle.jit.while_loop(
        lambda i, s: i < 5.0,
        lambda i, s: (i + 1.0, s * 2.0), [i, s])
    assert float(i2) == 5.0 and float(s2) == 32.0


def test_tensor_if_under_to_static():
    """`if tensor:` with branch-assigned locals lowers to lax.cond and
    matches eager for both predicate values."""

    def f(x):
        y = x * 1.0
        if T.sum(x) > 0.0:
            y = y * 2.0
            z = y + 1.0
        else:
            z = y - 1.0
        return z + y

    sf = paddle.jit.to_static(f)
    for sign in (1.0, -1.0):
        x = paddle.to_tensor(np.full((3,), sign, "float32"))
        np.testing.assert_allclose(sf(x).numpy(), f(x).numpy(),
                                   rtol=1e-6)


def test_tensor_if_both_return():
    def f(x):
        if T.sum(x) > 0.0:
            return x * 2.0
        else:
            return x - 3.0

    sf = paddle.jit.to_static(f)
    for sign in (1.0, -1.0):
        x = paddle.to_tensor(np.full((3,), sign, "float32"))
        np.testing.assert_allclose(sf(x).numpy(), f(x).numpy())


def test_tensor_while_under_to_static():
    def f(x):
        s = x * 0.0
        n = paddle.to_tensor(np.array(0.0, "float32"))
        while T.sum(s) < 10.0:
            s = s + x
            n = n + 1.0
        return s, n

    sf = paddle.jit.to_static(f)
    x = paddle.to_tensor(np.ones((4,), "float32"))
    se, ne = f(x)
    ss, ns = sf(x)
    np.testing.assert_allclose(ss.numpy(), se.numpy())
    assert float(ns) == float(ne) == 3.0


def test_python_bool_if_stays_python():
    """Python predicates keep plain control flow (and retrace per value
    via the jit cache key, like before)."""

    def f(x, flag):
        if flag:
            return x * 2.0
        return x + 1.0

    sf = paddle.jit.to_static(f)
    x = paddle.to_tensor(np.ones((2,), "float32"))
    np.testing.assert_allclose(sf(x, True).numpy(), [2.0, 2.0])
    np.testing.assert_allclose(sf(x, False).numpy(), [2.0, 2.0][:2]
                               if False else [2.0, 2.0])
    np.testing.assert_allclose(sf(x, False).numpy(), (x + 1.0).numpy())


def test_model_with_data_dependent_branching():
    """VERDICT item 5 'done' criterion: a model whose forward branches on
    its data runs under to_static and matches eager."""

    class GatedNet(nn.Layer):
        def __init__(self):
            super().__init__()
            self.a = nn.Linear(4, 4)
            self.b = nn.Linear(4, 4)

        def forward(self, x):
            h = self.a(x)
            if T.sum(T.abs(h)) > 4.0:       # data-dependent gate
                out = self.b(h)
            else:
                out = h * 0.5
            steps = paddle.to_tensor(np.array(0.0, "float32"))
            while T.sum(T.abs(out)) > 2.0:  # data-dependent normalize
                out = out * 0.5
                steps = steps + 1.0
            return out, steps

    paddle.seed(0)
    net = GatedNet()
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(2, 4).astype("float32") * 3)
    eager_out, eager_steps = net(x)
    snet = paddle.jit.to_static(net)
    s_out, s_steps = snet(x)
    np.testing.assert_allclose(eager_out.numpy(), s_out.numpy(),
                               rtol=1e-5, atol=1e-6)
    assert float(eager_steps) == float(s_steps)


def test_graph_break_falls_back_to_eager():
    """Host-side data dependence the rewrite can't capture (np.asarray on
    a traced value) must warn and run eagerly, not crash."""

    def f(x):
        arr = np.asarray((x * 2.0).numpy())   # host pull: untraceable
        return paddle.to_tensor(arr + 1.0)

    sf = paddle.jit.to_static(f)
    x = paddle.to_tensor(np.ones((2,), "float32"))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = sf(x)
    np.testing.assert_allclose(out.numpy(), [3.0, 3.0])
    assert any("EAGER" in str(wi.message) for wi in w)


def test_tracer_bool_error_message():
    """Without the rewrite (explicit raw jit), bool() on a tracer gives
    the targeted error naming jit.cond/while_loop."""
    import jax

    def f(a):
        t = paddle.to_tensor(a)
        if t.sum() > 0:          # Tensor.__bool__ on a tracer
            return a
        return -a

    with pytest.raises(TypeError, match="jit.cond"):
        jax.jit(f)(np.ones((2,), "float32"))


def test_early_return_pattern_normalized():
    """`if p: return X` followed by code is folded into if/else-return
    and lowers to lax.cond (matching eager for both predicate values)."""

    def f(x):
        if T.sum(x) > 0.0:
            return x * 2.0
        x = x + 1.0
        return x * 3.0

    sf = paddle.jit.to_static(f)
    for sign in (1.0, -1.0):
        x = paddle.to_tensor(np.full((3,), sign, "float32"))
        np.testing.assert_allclose(sf(x).numpy(), f(x).numpy())


def test_transform_error_break_in_while():
    from paddle_tpu.jit.dy2static import (ast_transform,
                                          Dy2StaticTransformError)

    def f(x):
        while T.sum(x) < 10.0:
            x = x + 1.0
            if T.sum(x) > 5.0:
                break
        return x

    with pytest.raises(Dy2StaticTransformError, match="break"):
        ast_transform(f)


def test_closure_values_not_shared_across_instances():
    """Two to_static functions built from the same factory code must keep
    their OWN captured closure values (advisor r2 high: the transform memo
    baked the first instance's cells into shared globals)."""

    def make(k):
        def f(x):
            if T.sum(x) > 0.0:
                y = x * k
            else:
                y = x - k
            return y
        return paddle.jit.to_static(f)

    f2, f3 = make(2.0), make(3.0)
    x = paddle.to_tensor(np.ones((3,), "float32"))
    np.testing.assert_allclose(f2(x).numpy(), np.full((3,), 2.0))
    np.testing.assert_allclose(f3(x).numpy(), np.full((3,), 3.0))
    xn = paddle.to_tensor(np.full((3,), -1.0, "float32"))
    np.testing.assert_allclose(f3(xn).numpy(), np.full((3,), -4.0))


def test_while_body_temp_local_transforms():
    """A while body that first-binds a temp local is no longer rejected
    (r5: write-first temps are body-local, not carries — they used to
    force an eager fallback; advisor r2 medium was the UnboundLocalError
    this check replaced, VERDICT r4 item 9 the rejection it relaxes)."""

    def f(x):
        n = 0
        while n < 3:
            y = x * 2.0       # temp first bound INSIDE the body
            x = x + y
            n = n + 1
        return x

    from paddle_tpu.jit.dy2static import ast_transform
    assert ast_transform(f) is not None     # transforms cleanly now

    sf = paddle.jit.to_static(f)
    x = paddle.to_tensor(np.ones((2,), "float32"))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = sf(x)
    assert not any("could not be traced" in str(wi.message) for wi in w)
    np.testing.assert_allclose(out.numpy(), np.full((2,), 27.0))


def test_while_carry_bound_by_if_before_loop():
    """Names bound by BOTH if-branches (or by the if-transform's call-site
    assign) before the loop are valid carries."""

    def f(x):
        if T.sum(x) > 0.0:
            acc = x * 1.0
        else:
            acc = x * -1.0
        n = paddle.to_tensor(np.array(0.0, "float32"))
        while T.sum(n) < 2.0:
            acc = acc + 1.0
            n = n + 1.0
        return acc

    sf = paddle.jit.to_static(f)
    x = paddle.to_tensor(np.full((2,), -3.0, "float32"))
    np.testing.assert_allclose(sf(x).numpy(), f(x).numpy())


def test_nested_tail_return_ifs_with_emitted_helpers():
    """Regression (r3): NESTED tail-return ifs make the transformer emit
    _pt_true/_pt_false helper defs inside an extracted branch body; the
    read-before-write analysis must treat a nested def as BINDING its
    name (and its body's free reads as reads), else the helper name
    leaks into the call-site parameter tuple -> NameError at runtime."""
    from paddle_tpu.jit.dy2static import ast_transform

    def f(x, mode=None, extra=None):
        if mode is not None:
            if extra is not None:
                return x * 3.0 + extra
            return x * 2.0
        y = x + 1.0
        if y.sum() > 1e9:           # Tensor predicate -> lax.cond
            return y * 10.0
        return y

    g = ast_transform(f)
    x = paddle.to_tensor(np.ones((2, 2), "float32"))
    np.testing.assert_allclose(g(x).numpy(), 2.0)
    np.testing.assert_allclose(g(x, mode="m").numpy(), 2.0 * 1.0)
    e = paddle.to_tensor(np.ones((2, 2), "float32"))
    np.testing.assert_allclose(g(x, mode="m", extra=e).numpy(), 4.0)


def test_nested_def_default_arg_reads_outer_name():
    """A nested def's DEFAULT VALUE evaluates at def time: a name it
    reads must be fed into the extracted tail-return branch function
    (code-review r3 finding on the nested-def scan)."""
    from paddle_tpu.jit.dy2static import ast_transform

    def f(x, mode=None):
        base = x * 2.0
        if mode is not None:
            def h(v=base):
                return v + 1.0
            return h()
        return base

    g = ast_transform(f)
    x = paddle.to_tensor(np.ones((2,), "float32"))
    np.testing.assert_allclose(g(x).numpy(), 2.0)
    np.testing.assert_allclose(g(x, mode="m").numpy(), 3.0)


# -- round 4: guard/retrace observability (VERDICT r3 item 7) ----------------

@pytest.mark.quick
def test_retrace_cause_shape_and_dtype():
    """explain()/stats() report WHICH guard moved on each retrace."""
    import paddle_tpu

    @paddle.jit.to_static
    def f(x):
        return T.sum(x * 2.0)

    f(paddle.to_tensor(np.zeros((2, 3), "float32")))
    f(paddle.to_tensor(np.zeros((2, 3), "float32")))   # cache hit
    f(paddle.to_tensor(np.zeros((4, 3), "float32")))   # shape retrace
    # int32 (x64 is disabled, so float64 would silently truncate to
    # float32 and cache-hit)
    f(paddle.to_tensor(np.zeros((4, 3), "int32")))     # dtype retrace
    st = f.stats()
    assert st["calls"] == 4
    assert st["traces"] == 3 and st["cache_entries"] == 3
    kinds = [e["kind"] for e in st["retraces"]]
    assert kinds == ["first_trace", "shape", "dtype"]
    assert "(2, 3)" in st["retraces"][1]["detail"]
    assert "(4, 3)" in st["retraces"][1]["detail"]
    assert "int32" in st["retraces"][2]["detail"]
    report = paddle_tpu.jit.explain(f)
    assert "3 traces" in report and "[shape]" in report \
        and "[dtype]" in report


@pytest.mark.quick
def test_retrace_cause_treedef_and_static():
    @paddle.jit.to_static
    def g(batch):
        return T.sum(batch["a"]) if "b" not in batch \
            else T.sum(batch["a"]) + T.sum(batch["b"])

    a = paddle.to_tensor(np.ones((2,), "float32"))
    g({"a": a})
    g({"a": a, "b": a})                 # treedef retrace (new dict key)
    st = g.stats()
    assert [e["kind"] for e in st["retraces"]] == ["first_trace",
                                                   "treedef"]

    @paddle.jit.to_static
    def h(x, flag):
        return T.sum(x) * (2.0 if flag else 3.0)

    h(a, True)
    h(a, False)                         # static python arg changed
    st2 = h.stats()
    assert [e["kind"] for e in st2["retraces"]] == ["first_trace",
                                                    "static_value"]
    assert "True" in st2["retraces"][1]["detail"] \
        or "False" in st2["retraces"][1]["detail"]


def test_compilation_cache_stats_and_layer_explain():
    import paddle_tpu
    from paddle_tpu.jit.api import compilation_cache_stats

    class M(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.lin = paddle.nn.Linear(4, 2)

        def forward(self, x):
            return self.lin(x)

    m = paddle.jit.to_static(M())
    m(paddle.to_tensor(np.zeros((1, 4), "float32")))
    m(paddle.to_tensor(np.zeros((5, 4), "float32")))
    report = paddle_tpu.jit.explain(m)
    assert "2 traces" in report and "[shape]" in report
    # the registry is WEAK (dead functions drop out), so assert on
    # this function's own entry rather than process-total deltas
    after = compilation_cache_stats()
    assert after["functions"] >= 1 and after["total_traces"] >= 2
    assert any(s["traces"] == 2 and "M.forward" in s["name"]
               for s in after["per_function"])
    with pytest.raises(ValueError, match="to_static"):
        paddle_tpu.jit.explain(lambda x: x)


# -- round 5: liveness-aware carries ------------------------------------------
# Branch-local temps and `_` unpacking used to fall back to eager (spurious
# unbound-carry rejections); they now capture into ONE lax.cond/while_loop
# program.

def _assert_one_program(fn, *args):
    """Run a to_static fn and assert NO eager-fallback warning fired."""
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = fn(*args)
    assert not any("could not be traced" in str(wi.message) for wi in w), \
        [str(wi.message) for wi in w]
    return out


def test_branch_local_temp_captures():
    @paddle.jit.to_static
    def f(x):
        if T.sum(x) > 0:
            tmp = x * 2.0            # branch-local, no prior binding
            out = tmp + 1.0
        else:
            out = x - 1.0
        return out

    x = paddle.to_tensor(np.ones((4,), "float32"))
    np.testing.assert_allclose(_assert_one_program(f, x).numpy(),
                               np.full(4, 3.0, "float32"))
    xn = paddle.to_tensor(np.full((4,), -1.0, "float32"))
    np.testing.assert_allclose(f(xn).numpy(), np.full(4, -2.0, "float32"))


def test_underscore_unpacking_in_branch_captures():
    @paddle.jit.to_static
    def f(x):
        if T.sum(x) > 0:
            a, _ = T.topk(x, 2)      # `_` is a branch-local junk slot
            r = a * 2.0
        else:
            r = x[:2]
        return r

    x = paddle.to_tensor(np.array([1., 3., 2., 4.], "float32"))
    np.testing.assert_allclose(_assert_one_program(f, x).numpy(),
                               [8.0, 6.0])
    xn = paddle.to_tensor(np.array([-1., -3., -2., -4.], "float32"))
    np.testing.assert_allclose(f(xn).numpy(), [-1.0, -3.0])


def test_while_write_first_temp_captures():
    @paddle.jit.to_static
    def f(x):
        i = paddle.to_tensor(np.int32(0))
        while i < 3:
            t = x * 2.0              # write-first temp: NOT a carry
            x = t + 1.0
            i = i + 1
        return x

    x = paddle.to_tensor(np.ones((2,), "float32"))
    np.testing.assert_allclose(_assert_one_program(f, x).numpy(),
                               np.full(2, 15.0, "float32"))


def test_passthrough_still_carried():
    """A name bound BEFORE the if and assigned in one branch must still
    pass through the untaken branch (regression guard for the filter)."""
    @paddle.jit.to_static
    def f(x):
        y = x + 1.0
        if T.sum(x) > 0:
            y = y * 10.0
        return y

    x = paddle.to_tensor(np.ones((2,), "float32"))
    np.testing.assert_allclose(_assert_one_program(f, x).numpy(),
                               np.full(2, 20.0, "float32"))
    xn = paddle.to_tensor(np.full((2,), -1.0, "float32"))
    np.testing.assert_allclose(f(xn).numpy(), np.zeros(2, "float32"))


def test_unbound_carry_still_rejected():
    """Reading a while-carry that was never initialized is a real error
    and must still route to the clear transform-time message."""
    from paddle_tpu.jit.dy2static import (ast_transform,
                                          Dy2StaticTransformError)

    def f(x):
        i = paddle.to_tensor(np.int32(0))
        while i < 3:
            acc = acc + x            # read-first, never bound: broken
            i = i + 1
        return acc

    with pytest.raises(Dy2StaticTransformError, match="initial value"):
        ast_transform(f)
