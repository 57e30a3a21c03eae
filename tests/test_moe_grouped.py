"""The grouped expert kernel (kernels/moe_grouped.py) against the grouped
path through `ragged_dot`, in interpret mode on the CPU; the tiling rule
at the serve cells' chunks; where the kernel is taken and where it is
not; and what the serving engine counts of it.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.kernels.moe_grouped import (grouped_plan, grouped_problems,
                                            moe_grouped, recording)
from paddle_tpu.nn.functional import moe as FM

D, F = 128, 256
kernel = functools.partial(moe_grouped, interpret=True)


def _weights(rng, e, dtype=jnp.float32):
    wg, wu = (jnp.asarray(rng.standard_normal((e, D, F)) * 0.05, dtype)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((e, F, D)) * 0.05, dtype)
    return wg, wu, wd


def _rows(rng, n, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal((n, D)), dtype)


def _sorted(sizes, dtype=jnp.float32):
    """The kernel and ragged_dot on rows sorted into groups of `sizes`,
    compared over the rows of a group (past them the kernel leaves
    whatever was there)."""
    def case(rng):
        wg, wu, wd = _weights(rng, len(sizes), dtype)
        n = sum(sizes)
        x = _rows(rng, n + 37, dtype)
        gs = jnp.asarray(sizes, jnp.int32)
        return (kernel(x, wg, wu, wd, gs)[:n],
                FM.ragged_swiglu(x, wg, wu, wd, gs)[:n])
    return case


def _dropless(rng):
    """`moe_dropless_mlp` at small widths, routed top 2 of 8."""
    t, e, k = 200, 8, 2
    wg, wu, wd = _weights(rng, e)
    x = _rows(rng, t)
    idx, gates, _aux = FM.topk_gating_dropless(
        jnp.asarray(rng.standard_normal((t, e)), jnp.float32), k)
    return (FM.moe_dropless_mlp(x, wg, wu, wd, idx, gates, swiglu=kernel),
            FM.moe_dropless_mlp(x, wg, wu, wd, idx, gates))


def _held(rng):
    """`moe_held_experts`: 4 of 16 experts held from the 6th on; rows of
    experts held elsewhere are sorted last and come back as zeros."""
    n, e = 300, 16
    wg, wu, wd = _weights(rng, 4)
    x = _rows(rng, n)
    ids = jnp.asarray(rng.integers(0, e, n), jnp.int32)
    got = FM.moe_held_experts(x, ids, wg, wu, wd, 6,
                              functools.partial(kernel, routed=e))
    want = FM.moe_held_experts(x, ids, wg, wu, wd, 6)
    elsewhere = np.asarray((ids < 6) | (ids >= 10))
    assert elsewhere.sum() > 200 and not np.asarray(got)[elsewhere].any()
    return got, want


@pytest.mark.parametrize("case", [
    _dropless,
    _held,
    _sorted([0, 40, 0, 0, 25, 0]),              # empty experts
    _sorted([5, 300, 20]),                      # a group over three tiles
    _sorted([100, 60, 3]),                      # one across a tile's edge
    _sorted([0, 0, 257, 0]),                    # one group, every row
    _sorted([5, 300, 20], jnp.bfloat16),        # rounded as ragged_dot is
], ids=["dropless", "held", "empty", "larger-than-a-tile", "straddling",
        "one-group", "bf16"])
def test_the_kernel_equals_the_ragged_dot_path(case):
    got, want = case(np.random.default_rng(5))
    assert got.shape == want.shape and got.dtype == want.dtype
    # float32 to its accumulation order; bf16 to one rounding of the
    # largest output: gate, up and their product are rounded where the
    # three ragged_dots round them
    tol = 2.0 ** -8 if got.dtype == jnp.bfloat16 else 1e-4
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.abs(got - want).max() < tol * max(1.0, np.abs(want).max())


def test_the_tiling_rule_at_the_serve_cells_chunks():
    """The plan of one layer's call at each expert cell's prefill chunk,
    from the shapes alone (`grouped_plan`'s docstring has the
    arithmetic)."""
    bf16 = jnp.bfloat16
    # doc-QA: 1,024 tokens x 8 of 128 experts of 2048 x 768
    assert grouped_plan(8192, 128, 2048, 768, bf16) == (
        8192, 128, 128, 2048, 768, 64 + 127)
    # gen-1k: 2,048 tokens x 8 of the same experts
    assert grouped_plan(16384, 128, 2048, 768, bf16).tm == 128
    # mixed-1k-16k: 4,096 tokens x 4 on 32 held of 256 experts of 3072 x
    # 3072: three tiles of f, the weights read a visit
    assert grouped_plan(16384, 32, 3072, 3072, bf16, routed=256) == (
        16384, 32, 256, 3072, 1024, 64 + 31)
    assert grouped_problems(2048, 768, bf16) == []
    assert grouped_problems(2048, 100, bf16)


def _layer(**kw):
    from paddle_tpu.nn.layer.moe import MoEMLP
    paddle_tpu.seed(3)
    layer = MoEMLP(D, F, 8, top_k=2, dropless=True, **kw)
    x = Tensor(jnp.asarray(np.random.default_rng(3).standard_normal(
        (600, D)), jnp.float32))
    return layer, x


def _lowered(layer, x, platform):
    """The layer's forward for `platform`: the lowered text for the TPU,
    the jaxpr for the CPU (whose lowering expands ragged_dot)."""
    fn = jax.jit(lambda v: layer(Tensor(v))._value)
    if platform == "cpu":
        return str(jax.make_jaxpr(fn)(x._value))
    return fn.trace(x._value).lower(lowering_platforms=(platform,)).as_text()


def test_cpu_and_training_take_ragged_dot(monkeypatch):
    from paddle_tpu.nn.layer import moe as moe_layer
    layer, x = _layer()
    layer.eval()
    # on the CPU: ragged_dot, whatever the rows
    assert layer._kernel(x) is None
    text = _lowered(layer, x, "cpu")
    assert "ragged_dot" in text and "moe_grouped" not in text
    # on a TPU, past the few-rows kernel's rows: the grouped kernel
    monkeypatch.setattr(moe_layer, "on_tpu", lambda: True)
    assert layer._kernel(x) == "grouped"
    text = _lowered(layer, x, "tpu")
    assert "moe_grouped" in text and "ragged_dot" not in text
    # under training (a gradient): ragged_dot on a TPU too
    layer.train()
    assert layer._kernel(x) is None
    text = _lowered(layer, x, "tpu")
    assert "ragged_dot" in text and "moe_grouped" not in text


def test_the_engine_counts_the_kernels_calls_and_exports_its_plan(
        monkeypatch):
    """Every expert layer of a prefill program through the kernel (on a
    TPU as the layer sees it, the few-rows kernel's rows at 0, interpret
    mode): the engine's tokens are the ragged_dot engine's, and it counts
    a program's kernel calls by the plans its trace recorded."""
    from paddle_tpu.inference import PagedKVEngine
    from paddle_tpu.kernels import moe_experts
    from paddle_tpu.models.sparse_attn_moe import (
        SparseAttnMoeForCausalLM, tiny_sparse_attn_moe_config)
    from paddle_tpu.nn.layer import moe as moe_layer
    from paddle_tpu.observability.metrics import MetricsRegistry
    cfg = tiny_sparse_attn_moe_config(hidden_size=128, head_dim=128,
                                      moe_intermediate_size=128)
    paddle_tpu.seed(7)
    model = SparseAttnMoeForCausalLM(cfg)
    model.eval()
    prompts = [np.arange(1, 14, dtype=np.int32), np.arange(3, 9,
                                                           dtype=np.int32)]
    got = {}
    for path in ("ragged_dot", "kernel"):
        if path == "kernel":
            monkeypatch.setattr(moe_layer, "on_tpu", lambda: True)
            monkeypatch.setattr(moe_experts, "MAX_ROWS", 0)
            monkeypatch.setattr(moe_layer, "moe_grouped", kernel)
        eng = PagedKVEngine(model, max_slots=2, page_size=8, num_pages=17,
                            max_pages_per_slot=8, kernel="jnp")
        got[path] = eng.generate(prompts, max_new_tokens=6)
        eng.stop()
    assert [list(t) for t in got["kernel"]] == \
        [list(t) for t in got["ragged_dot"]]
    plans = {p for fn in eng._programs.values()
             for p in getattr(fn, "grouped_plans", ())}
    s = eng.stats
    assert plans and s["moe_grouped_calls"] == (
        cfg.num_hidden_layers * (s["prefill_rows_run"]))
    reg = MetricsRegistry()
    eng.export_metrics(reg)
    plan = sorted(plans)[0]
    assert reg.gauge("moe.grouped_plan").value(
        m=plan.m, e=plan.e, tm=plan.tm, tk=plan.tk, tn=plan.tn) == \
        plan.visits


def test_recording_collects_a_traces_plans_and_starts_empty():
    rng = np.random.default_rng(0)
    wg, wu, wd = _weights(rng, 4)
    x = _rows(rng, 64)
    gs = jnp.asarray([16, 16, 16, 16], jnp.int32)
    plans = ["stale"]
    with recording(plans):
        jax.eval_shape(lambda v: kernel(v, wg, wu, wd, gs), x)
    assert plans == [grouped_plan(64, 4, D, F, jnp.float32)]
