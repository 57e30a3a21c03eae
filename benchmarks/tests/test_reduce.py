"""reduce.py against hand-computed values (a synthetic plane) and against two
recorded traces cut from chip runs of PR 23 (`data/*.xplane.pb`: real event
text of the real kernels, a few hundred events each).

Run by hand: JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import costs, reduce, run  # noqa: E402
from benchmarks.builders import llama_dense  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PAGED = r"custom-call\(s32\[{slots},{pages_per_slot}\]"

# One device, times in ns. Ops nest: the while holds a paged-attention call
# and a copy; the all-reduce holds a fusion that runs under it.
OPS = [
    ("%fusion.1 = bf16[4]{0} fusion(bf16[4]{0} %p0), kind=kLoop", 0, 40),
    ("%while.2 = (s32[]{:T(128)}, bf16[4]{0}) while(%tuple.1)", 40, 50),
    ("%closed_call.3 = f32[2,4,8,64]{3,2,1,0:T(8,128)} custom-call("
     "s32[2,8]{1,0:T(8,128)S(1)} %bt, s32[2]{0} %lens)", 45, 20),
    ("%copy.4 = bf16[4]{0} copy(bf16[4]{0} %x)", 70, 10),
    ("%all-gather-done.5 = bf16[8]{0} all-gather-done(%ags.5)", 95, 10),
    ("%all-reduce.6 = f32[8]{0} all-reduce(f32[8]{0} %g)", 130, 30),
    ("%fusion.7 = bf16[4]{0} fusion(bf16[4]{0} %p1), kind=kLoop", 140, 10),
    ("%fusion.8 = bf16[4]{0} fusion(bf16[4]{0} %p2), kind=kOutput", 170, 40),
]
MODULES = [("jit_step(111)", 0, 100), ("jit_step(111)", 120, 100),
           ("jit_convert_element_type(7)", 230, 1)]
BUSY, WINDOW = 170.0, 231.0     # 40 + 50 + 10 + 30 + 40; 0 .. 231


def synthetic():
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": MODULES},
        {"name": "XLA Ops", "events": OPS}]}]
    return {"trace": reduce.Trace(planes), "window": {}, "config": {},
            "device_kind": "TPU v5 lite",
            "sizes": {"slots": 2, "pages_per_slot": 8}}


def test_busy_idle_union():
    ctx = synthetic()
    tr = ctx["trace"]
    assert tr.busy_s == pytest.approx(BUSY / 1e9)
    assert tr.window_s == pytest.approx(WINDOW / 1e9)
    assert reduce.idle_share(ctx) == pytest.approx(100 * (1 - BUSY / WINDOW))


def test_self_time_of_nested_ops():
    st = {n.split(" = ")[0]: sd for n, _s, _d, sd in reduce.self_times(OPS)}
    assert st["%while.2"] == 20           # 50 less the call's 20, the copy's 10
    assert st["%closed_call.3"] == 20
    assert st["%all-reduce.6"] == 20      # 30 less the fusion under it


def test_pattern_shares():
    ctx = synthetic()
    assert reduce.device_share(ctx, pattern=PAGED) \
        == pytest.approx(100 * 20 / BUSY)
    dense = reduce.device_share(
        ctx, exclude=r" custom-call\(|^%?(all-gather|all-reduce)")
    assert dense == pytest.approx(100 * (40 + 20 + 10 + 10 + 40) / BUSY)


def test_module_median_and_selection():
    ctx = synthetic()
    assert reduce.module_ms_p50(ctx, name="^jit_step") \
        == pytest.approx(100 / 1e6)
    dev = ctx["trace"].devices[0]
    pat = reduce.substitute(PAGED, ctx["sizes"])
    assert [m[1] for m in dev.select_modules("^jit_step", holds=pat)] == [0]
    assert [m[1] for m in dev.select_modules("^jit_step", lacks=pat)] == [120]
    # the second step's interval holds [130,160] and [170,210] of busy time
    assert reduce.module_share(ctx, name="^jit_step", lacks=PAGED) \
        == pytest.approx(100 * 70 / BUSY)


def test_no_match_leaves_the_metric_absent():
    ctx = synthetic()
    nothing = r"custom-call\(bf16\[9,9,9,9\]"
    assert reduce.device_share(ctx, pattern=nothing) is None
    assert reduce.module_ms_p50(ctx, name="^jit_nothing") is None
    ctx["config"] = {"num_hidden_layers": 1}
    ctx["sizes"].update(B=1, H=2, Hkv=2, S=1024, hd=64)
    assert reduce.roofline(ctx, cost="flash_step", module={"name": "^jit_step"},
                           pattern=nothing) is None
    ctx["trace"] = None
    assert reduce.idle_share(ctx) is None
    assert reduce.host_clock({"window": {}}, span="input_wait_s") is None


def test_roofline_is_least_over_measured():
    cfg = {"num_hidden_layers": 1}
    sizes = {"B": 1, "H": 2, "Hkv": 2, "S": 1024, "hd": 64}
    least, bound = costs.least_seconds("flash_step", cfg, sizes, {},
                                       "TPU v5 lite")
    took_ns = 4 * least * 1e9               # a kernel at a quarter of peak
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [("jit_step(1)", 0, 2 * took_ns)]},
        {"name": "XLA Ops", "events": [
            ("%jvp__.1 = bf16[1,2,1024,64]{3,2,1,0} custom-call("
             "bf16[1,2,1024,64]{3,2,1,0:T(8,128)} %q, bf16[1,2,64,1024] %k)",
             10, took_ns)]}]}]
    ctx = {"trace": reduce.Trace(planes), "window": {}, "config": cfg,
           "device_kind": "TPU v5 lite", "sizes": sizes}
    got = reduce.roofline(ctx, cost="flash_step", module={"name": "^jit_step"},
                          pattern=r"custom-call\(bf16\[{B},{H},{S},{hd}\]")
    assert got == pytest.approx(25.0)
    assert bound == "compute" and ctx["notes"]["flash_step"] == "compute-bound"


def test_counters_and_clocks():
    ctx = {"window": {"tokens_out": 900, "ticks": 15, "input_wait_s": 0.03,
                      "window_s": 30.0},
           "sizes": {"slots": 16, "steps_per_tick": 4}}
    assert reduce.counter_ratio(ctx, ["tokens_out"],
                                ["ticks", "slots", "steps_per_tick"]) \
        == pytest.approx(100 * 900 / 960)
    assert reduce.host_clock(ctx, span="input_wait_s") == pytest.approx(0.1)
    assert reduce.counter_ratio(ctx, ["absent"], ["ticks"]) is None
    assert reduce.window_value(ctx, "input_wait_s", 1000.0) == 30.0
    assert reduce.window_value(ctx, "absent") is None


def test_stall_share_and_mfu_take_the_rate_over_the_whole_window():
    cfg = {"num_hidden_layers": 8, "hidden_size": 2048,
           "intermediate_size": 8192, "num_attention_heads": 32,
           "num_key_value_heads": 32, "vocab_size": 49152}
    ctx = {"window": {"rate": 27_027.0, "median_reading_rate": 27_300.0},
           "config": cfg, "sizes": {"S": 2048}, "device_kind": "TPU v5 lite"}
    assert reduce.stall_share(ctx) == pytest.approx(1.0)
    assert reduce.mfu(ctx) == pytest.approx(
        costs.mfu(cfg, 2048, 27_027.0, "TPU v5 lite"))
    assert reduce.stall_share({"window": {"rate": 66.0}}) is None
    assert reduce.mfu(dict(ctx, device_kind=None)) is None      # a rehearsal


def test_mfu_and_roofline_take_the_builders_yardstick_when_it_offers_one():
    import types
    cfg = {"num_hidden_layers": 1}
    sizes = {"B": 1, "H": 2, "Hkv": 2, "S": 1024, "hd": 64}
    dense_least, _ = costs.least_seconds("flash_step", cfg, sizes, {},
                                         "TPU v5 lite")
    took_ns = 4 * dense_least * 1e9
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [("jit_step(1)", 0, 2 * took_ns)]},
        {"name": "XLA Ops", "events": [
            ("%flash_fwd.1 = bf16[1,2,1024,64]{3,2,1,0} custom-call("
             "bf16[1,2,1024,64]{3,2,1,0} %q)", 10, took_ns)]}]}]
    family = types.SimpleNamespace(costs=types.SimpleNamespace(
        train_flops_per_token=lambda cfg, seq: 2.0 * seq,
        KERNEL_COSTS={"flash_step": lambda cfg, sizes, window:
                      tuple(2 * x for x in costs.flash_step(cfg, sizes))}))
    args = {"cost": "flash_step", "module": {"name": "^jit_step"},
            "pattern": r"custom-call\(bf16\[{B},{H},{S},{hd}\]"}
    ctx = {"trace": reduce.Trace(planes), "window": {"rate": 1e9},
           "config": cfg, "device_kind": "TPU v5 lite", "sizes": sizes}
    assert reduce.roofline(ctx, **args) == pytest.approx(25.0)
    assert reduce.roofline(dict(ctx, builder=family), **args) \
        == pytest.approx(50.0)
    assert reduce.mfu(dict(ctx, builder=family)) \
        == pytest.approx(100 * 2.0 * 1024 * 1e9 / 197e12)
    # a builder that offers none (or only some) leaves the rest to costs.py
    plain = types.SimpleNamespace()
    assert reduce.roofline(dict(ctx, builder=plain), **args) \
        == pytest.approx(25.0)
    assert reduce.roofline(dict(ctx, builder=llama_dense), **args) \
        == pytest.approx(25.0)
    assert reduce.mfu(dict(ctx, builder=family, device_kind=None)) is None


def test_the_reader_table_holds_the_readers_of_both_files():
    from benchmarks import spans
    assert run.READERS["device_share"] is reduce.device_share
    for name in ("span_share", "span_ms_p50", "idle_under", "scope_share"):
        assert run.READERS[name] is getattr(spans, name)
    assert len(run.READERS) == len(reduce.READERS) + len(spans.READERS)


def test_op_kind_names_kernels_by_shapes():
    assert reduce.op_kind(reduce.strip_layouts(OPS[2][0])) \
        == "custom-call(s32[2,8],s32[2])"
    assert reduce.op_kind(OPS[0][0]) == "fusion(kLoop)"
    assert reduce.op_kind(OPS[4][0]) == "all-gather-done"


def test_xplane_round_trip(tmp_path):
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [(n, float(s), float(d))
                                       for n, s, d in OPS]}]}]
    path = str(tmp_path / "t.xplane.pb")
    reduce.save_xplane(planes, path)
    assert reduce.load_xplane(path) == planes


def _sweep_busy(events):
    """Busy time by counting open intervals at every endpoint: an
    independent check of `union`."""
    points = sorted([(s, 1) for _n, s, _d in events]
                    + [(s + d, -1) for _n, s, d in events],
                    key=lambda p: (p[0], -p[1]))
    busy, depth, last = 0.0, 0, None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    return busy


@pytest.mark.parametrize("name,cell,config,traffic,window,recorded_with", [
    ("train_two_steps", "train.smollm2-1.7b.s2048", "smollm2-1.7b-8l",
     "pretrain-s2048", {}, {}),
    # PR 23 recorded it with 64 pages a slot; the mix has 48 since
    ("serve_prefill_tick", "serve.smollm2-1.7b.batch-decode", "smollm2-1.7b",
     "batch-decode", {"live_context_tokens": 6400.0}, {"pages_per_slot": 64}),
])
def test_recorded_trace(name, cell, config, traffic, window, recorded_with):
    """The patterns of the metric files that list the trace's cell
    (`run.load_cell`'s filter, so a family's metric over its builder's
    `costs` is not asked of the dense cell's trace) over real event text."""
    bench = os.path.join(ROOT, "benchmarks")
    path = os.path.join(DATA, name + ".xplane.pb")
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "traffic", traffic + ".json")) as f:
        tr = json.load(f)
    with open(os.path.join(DATA, "expected.json")) as f:
        want = json.load(f)[name]
    planes = reduce.load_xplane(path)
    trace = reduce.Trace(planes)
    raw = next(ln["events"] for p in planes
               if p["name"].startswith("/device:")
               for ln in p["lines"] if ln["name"] == reduce.OPS_LINE)
    assert trace.busy_s == pytest.approx(_sweep_busy(raw) / 1e9)
    ctx = {"trace": trace, "window": window, "config": cfg,
           "device_kind": "TPU v5 lite",
           "sizes": dict(llama_dense.sizes(cfg, tr), **recorded_with)}
    got = {"window_s": trace.window_s, "busy_s": trace.busy_s}
    for m in run.load_cell(cell, rehearse=False)["metrics"]:
        if m["source"] == "device_trace":
            # a cut trace keeps no scope paths: the metrics over them read
            # nothing here (test_spans.py builds their planes by hand)
            value = run.READERS[m["reader"]](ctx, **m["args"])
            if value is not None:
                got[m["name"]] = value
    assert set(got) == set(want)
    for key, value in want.items():
        assert got[key] == pytest.approx(value, rel=1e-9), key
