"""spans.py against planes built by hand: two host threads, nested spans, a
step-span suffix, Python frames and runtime events that are no spans, and an
idle gap of the device under each phase of a tick.

Run by hand: JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import os

import pytest

from benchmarks import reduce, spans

# One device, times in us (`ctx_of` makes them ns): busy 0-100, 200-300,
# 420-500, 600-1000 of a window 0-1000, so the idle gaps are 100-200,
# 300-420 and 500-600.
OPS = [("%fusion.1 = bf16[4]{0} fusion(bf16[4]{0} %p0), kind=kLoop", 0, 100),
       ("%paged_attention_decode.2 = f32[2,4,8,64]{3,2,1,0} custom-call("
        "s32[2,8]{1,0} %bt)", 200, 100),
       ("%copy.3 = bf16[4]{0} copy(bf16[4]{0} %x)", 420, 80),
       ("%fusion.4 = bf16[4]{0} fusion(bf16[4]{0} %p1), kind=kOutput",
        600, 400)]
BUSY, WINDOW = 680.0, 1000.0
# the ticker: two ticks and the sleep between them; the gap 100-200 has
# its middle (150) under accept, 300-420 (360) under the sleep, 500-600
# (550) under the second tick's upload
TICKER = [
    ("engine.tick#seq=7#", 0, 290),
    ("engine.tick.retire", 1, 4),
    ("engine.tick.admit", 6, 30),
    ("engine.prefill#bucket=128,rows=1,group=1#", 10, 20),
    ("engine.tick.alloc", 37, 3),
    ("engine.tick.upload", 41, 9),
    ("engine.tick.launch", 51, 9),
    ("engine.tick.readback", 61, 60),
    ("engine.tick.accept", 122, 160),
    ("$paged.py:2031 _accept_tick", 123, 150),     # a frame, no span
    ("engine.idle", 295, 100),
    ("engine.tick#seq=8#", 400, 590),
    ("engine.tick.retire", 401, 4),
    ("engine.tick.admit", 406, 20),
    ("engine.tick.alloc", 427, 3),
    ("engine.tick.upload", 431, 170),
    ("engine.tick.launch", 602, 8),
    ("engine.tick.readback", 611, 370),
    ("engine.tick.accept", 982, 8),
    ("PjitFunction(traced)", 602, 8),               # the runtime's
]
# a handler thread: its writes cover 130-180 of the first gap
HANDLER = [("http.write#rid=req-1#", 130, 50), ("http.write", 700, 10),
           ("$socket.py:1 sendall", 131, 48)]


def ns(events):
    return [(n, s * 1000.0, d * 1000.0) for n, s, d in events]


def planes_of(host=(TICKER, HANDLER), ops=OPS):
    return [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": ns([("jit_traced(1)", 0, 1000)])},
        {"name": "XLA Ops", "events": ns(ops)}]},
        {"name": "/host:CPU", "lines": [
            {"name": f"python {i}", "events": ns(evs)}
            for i, evs in enumerate(host)]}]


def ctx_of(host=(TICKER, HANDLER), ops=OPS):
    return {"trace": reduce.Trace(planes_of(host, ops)), "window": {},
            "config": {}, "sizes": {}, "device_kind": "TPU v5 lite"}


def test_spans_are_indexed_by_name_without_suffix_or_frames():
    sp = spans.Spans(ctx_of()["trace"].host_lines)
    assert set(sp.by_name) == {
        "engine.tick", "engine.prefill", "engine.idle", "http.write"} | {
        "engine.tick." + p for p in ("retire", "admit", "alloc", "upload",
                                     "launch", "readback", "accept")}
    assert [(s, e) for s, e, _t in sp.by_name["engine.tick"]] \
        == [(0, 290e3), (400e3, 990e3)]
    assert sp.owner() == 0                 # the ticker's thread
    assert {t for _s, _e, t in sp.by_name["http.write"]} == {1}
    assert spans.bare("train.step#step_num=7,_r=1#") == "train.step"


def test_span_share_and_median_less_children():
    ctx = ctx_of()
    assert spans.span_share(ctx, r"engine\.tick") \
        == pytest.approx(100 * (290 + 590) / WINDOW)
    assert spans.span_share(ctx, r"engine\.idle") == pytest.approx(10.0)
    # a tick less its readback and its prefill: 290 - 60 - 20, 590 - 370
    assert spans.span_ms_p50(
        ctx, r"engine\.tick",
        minus=[r"engine\.tick\.readback", r"engine\.prefill"]) \
        == pytest.approx((210 + 220) / 2 / 1e3)
    assert spans.span_ms_p50(ctx, r"engine\.tick\.accept") \
        == pytest.approx((160 + 8) / 2 / 1e3)
    # another thread's span of the same name is not taken off
    other = [("engine.tick.readback", 0, 290)]
    ctx = ctx_of(host=(TICKER, other))
    assert spans.span_ms_p50(ctx, r"engine\.tick",
                             minus=[r"engine\.tick\.readback"]) \
        == pytest.approx((230 + 220) / 2 / 1e3)


def test_idle_gaps_fall_under_the_phase_that_holds_their_middle():
    ctx = ctx_of()
    assert reduce.idle_share(ctx) == pytest.approx(100 * (1 - BUSY / WINDOW))
    accept = spans.idle_under(ctx, r"engine\.tick\.(accept|retire)")
    launch = spans.idle_under(
        ctx, r"engine\.tick\.(admit|alloc|upload|launch)")
    between = spans.idle_under(ctx, outside=r"engine\.tick")
    assert accept == pytest.approx(100 * 100 / WINDOW)
    assert launch == pytest.approx(100 * 100 / WINDOW)
    assert between == pytest.approx(100 * 120 / WINDOW)
    assert accept + launch + between \
        == pytest.approx(reduce.idle_share(ctx))
    assert spans.idle_under(ctx, r"engine\.idle") == pytest.approx(between)
    # a gap under the least length is in no share
    assert spans.idle_gaps(ctx["trace"], least_ns=101e3) \
        == [(300e3, 420e3)]
    short = ctx_of(ops=OPS + [("%copy.9 = bf16[4]{0} copy(%y)", 110, 85)])
    assert spans.idle_under(
        short, r"engine\.tick\.(accept|retire)") == 0.0


def test_until_next_gives_the_time_between_phases_to_the_one_before():
    """A ticker that loses the interpreter lock after accept has nothing on
    its thread until the next tick: the gap belongs to accept."""
    late = [e for e in TICKER if e[0] != "engine.idle"]
    late[8] = ("engine.tick.accept", 122, 8)          # ends at 130
    ctx = ctx_of(host=(late, HANDLER))
    pat = r"engine\.tick\.(accept|retire)"
    assert spans.idle_under(ctx, pat) == 0.0           # 150 is under the tick
    assert spans.idle_under(ctx, pat, until_next=True) \
        == pytest.approx(100 * (100 + 120) / WINDOW)   # up to retire at 401
    sp = spans.Spans(ctx["trace"].host_lines)
    drawn = sp.until_next(r"engine\.tick\.accept", 0)
    assert [(s, e) for s, e, _t in drawn] == [(122e3, 401e3), (982e3, 990e3)]
    lines = []
    spans.report(planes_of(host=(late, HANDLER)), [], out=lines.append)
    assert any(ln.startswith("  engine.tick after engine.tick.accept | 0.10 ms")
               for ln in lines)
    assert any(ln.startswith("  between two engine.tick | 0.12 ms")
               for ln in lines)


def test_a_trace_without_the_programs_spans_reads_nothing():
    frames = [e for e in TICKER if not e[0].startswith("engine.")]
    ctx = ctx_of(host=(frames,))
    for name in ("span_share", "span_ms_p50", "idle_under"):
        assert spans.READERS[name](ctx, r"engine\.tick") is None
    assert spans.scope_share(ctx, "kv_write") is None
    assert spans.span_share({"trace": None}, "x") is None
    assert set(spans.READERS) == {"span_share", "span_ms_p50",
                                  "idle_under", "scope_share"}
    # registered: a metric file names them like any reader of reduce.py
    from benchmarks import run
    assert all(run.READERS[name] is fn
               for name, fn in spans.READERS.items())


def test_scope_share_takes_self_time_by_a_part_of_the_path():
    ctx = ctx_of()
    scoped = [(path, n, s * 1000.0, d * 1000.0) for path, n, s, d in [
        ("jit(traced)/while/body/attn/core/kv_write/scatter",
         "%copy.3", 420, 80),
        ("jit(traced)/while/body/attn/core/paged_attn/pallas_call",
         "%paged_attention_decode.2", 200, 100),
        ("jit(step)/transpose(jvp(attn))/qkv/dot_general",
         "%fusion.4", 600, 400),
        ("jit(step)/transpose(jvp(attn))/qkv/inner", "%x", 700, 100)]]
    ctx["scopes"] = spans.op_scopes(scoped)
    assert spans.scope_share(ctx, "kv_write") \
        == pytest.approx(100 * 80 / BUSY)
    assert spans.scope_share(ctx, "attn") == pytest.approx(100 * 580 / BUSY)
    assert spans.scope_share(ctx, "qkv") == pytest.approx(100 * 400 / BUSY)
    assert spans.scope_share(ctx, "kv") is None          # a whole part
    assert spans.scope_parts(scoped[2][0]) == ["attn", "qkv"]
    assert spans.scope_parts(scoped[0][0]) == ["attn", "core", "kv_write"]


def test_report_and_reload_of_a_saved_trace(tmp_path):
    path = os.path.join(str(tmp_path), "t.xplane.pb")
    reduce.save_xplane(planes_of(), path)
    loaded, scoped = spans.load_xplane(path)
    assert scoped == []                     # a cut trace keeps no stats
    lines = []
    spans.report(loaded, scoped, out=lines.append)
    text = "\n".join(lines)
    assert "engine.tick.accept | 2 |" in text
    assert "engine.tick.upload | 0.10 ms | 10.000 %" in text
    assert "http.write on other threads covers 15.6 %" in text   # 50 / 320
    assert "no device op carries a scope path" in text
    assert "traced window 1.0 ms, busy 0.7 ms, idle 32.00 %" in text


def test_scope_paths_are_read_from_the_event_metadata(tmp_path):
    """The profiler keeps an op's scope path as the stat `tf_op` of its
    instruction's event metadata; `load_xplane` joins it to the events."""
    from jax.profiler import ProfileData
    text = '''
    planes { id: 1 name: "/host:CPU"
      lines { id: 1 name: "python" events { metadata_id: 1 offset_ps: 0
                                             duration_ps: 5000000 } }
      event_metadata { key: 1 value { id: 1 name: "train.step" } } }
    planes { id: 2 name: "/device:TPU:0"
      stat_metadata { key: 7 value { id: 7 name: "flops" } }
      stat_metadata { key: 26 value { id: 26 name: "tf_op" } }
      event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[4] fusion()"
        stats { metadata_id: 7 uint64_value: 300 }
        stats { metadata_id: 26
                str_value: "jit(step)/transpose(jvp(attn))/qkv/dot_general:" }
      } }
      event_metadata { key: 2 value { id: 2 name: "%copy.2 = f32[4] copy()" } }
      lines { id: 1 name: "XLA Ops"
        events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
        events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 } } }
    '''
    path = os.path.join(str(tmp_path), "m.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    with open(path, "rb") as f:
        raw = f.read()
    assert spans.metadata_stat(raw) == {
        "%fusion.1 = f32[4] fusion()":
        "jit(step)/transpose(jvp(attn))/qkv/dot_general"}
    assert spans.metadata_stat(raw, stat="nothing") == {}
    planes, scoped = spans.load_xplane(path)
    assert [p["name"] for p in planes] == ["/host:CPU", "/device:TPU:0"]
    assert scoped == [("jit(step)/transpose(jvp(attn))/qkv/dot_general",
                       "%fusion.1 = f32[4] fusion()", 0.0, 3000.0)]
    ctx = {"trace": reduce.Trace(planes), "scopes": spans.op_scopes(scoped)}
    assert spans.scope_share(ctx, "qkv") == pytest.approx(75.0)
    assert spans.scope_share(ctx, "attn") == pytest.approx(75.0)
    assert spans.scope_share(ctx, "mlp") is None


# -- PR 27's six metric files, each by its own reader and arguments ------
# the training loop's thread: three steps, p50 4 us
TRAINER = [("train.step#step_num=3#", 0, 3), ("train.step.place", 0, 1),
           ("train.step.dispatch", 1, 2), ("train.step#step_num=4#", 300, 5),
           ("train.step#step_num=5#", 600, 4)]
SCOPED = [("jit(traced)/while/body/attn/core/kv_write/scatter",
           "%copy.3", 420, 80),
          ("jit(traced)/while/body/attn/core/paged_attn/pallas_call",
           "%paged_attention_decode.2", 200, 100),
          ("jit(step)/transpose(jvp(attn))/qkv/dot_general",
           "%fusion.4", 600, 400),
          ("jit(step)/jvp(mlp)/dot_general", "%fusion.1", 0, 100)]


@pytest.mark.parametrize("name,want", [
    ("step.mlp_share", 100 * 100 / BUSY),
    ("step.attn_share", 100 * 580 / BUSY),
    ("step.host_dispatch_ms_p50", 4 / 1e3),
    ("programs.kv_write_share", 100 * 80 / BUSY),
    ("programs.pool_copy_share", 100 * 80 / BUSY),          # %copy.3
    ("sched.tick_host_ms_p50", (210 + 220) / 2 / 1e3),      # as above
])
def test_metric_files_of_pr27_over_hand_built_planes(name, want):
    import json
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "metrics", name + ".json")) as f:
        m = json.load(f)
    ctx = ctx_of(host=(TICKER, HANDLER, TRAINER))
    ctx["scopes"] = spans.op_scopes(
        [(path, n, s * 1000.0, d * 1000.0) for path, n, s, d in SCOPED])
    from benchmarks import run
    assert run.READERS[m["reader"]](ctx, **m["args"]) == pytest.approx(want)
    # a parent's trace has no such span or scope: nothing, never 0
    bare_ctx = ctx_of(host=([e for e in TICKER if e[0].startswith("$")],),
                      ops=[o for o in OPS if "copy" not in o[0]])
    assert run.READERS[m["reader"]](bare_ctx, **m["args"]) is None
