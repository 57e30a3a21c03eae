"""The unified observability subsystem (paddle_tpu/observability/):
registry thread-safety, Prometheus exposition validity, span nesting +
ring bounds, the telemetry MFU formula, the disabled-path contract,
store RPC instrumentation, and the O(ws) barrier's store-RPC-count bound.
"""
import re
import threading

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import metrics as M
from paddle_tpu.observability import trace
from paddle_tpu.observability import telemetry as T


@pytest.fixture(autouse=True)
def _clean_slate():
    """Every test starts disabled with empty global state and leaves
    the process the same way (observability is process-global)."""
    obs.disable()
    obs.REGISTRY.reset()
    trace.clear()
    yield
    obs.disable()
    obs.REGISTRY.reset()
    trace.clear()


# ---------------------------------------------------------------------------
# registry basics
# ---------------------------------------------------------------------------

def test_disabled_by_default_and_scoped_restores():
    assert obs.ENABLED is False
    with obs.scoped() as reg:
        assert obs.ENABLED is True
        assert reg is obs.REGISTRY
    assert obs.ENABLED is False
    # nested: inner exit restores ENABLED, not disables it
    obs.enable()
    with obs.scoped():
        pass
    assert obs.ENABLED is True
    obs.disable()


def test_counter_gauge_histogram_and_labels():
    reg = M.MetricsRegistry()
    reg.inc("serving.requests", outcome="ok")
    reg.inc("serving.requests", 2, outcome="ok")
    reg.inc("serving.requests", outcome="shed")
    assert reg.counter("serving.requests").value(outcome="ok") == 3
    assert reg.counter("serving.requests").value(outcome="shed") == 1
    reg.set_gauge("train.mfu", 0.41)
    assert reg.gauge("train.mfu").value() == 0.41
    reg.observe("store.rpc.latency_ms", 7.0, op="get")
    h = reg.histogram("store.rpc.latency_ms")
    assert h.count(op="get") == 1
    assert h.percentile(50, op="get") == 7.0
    with pytest.raises(ValueError):
        reg.inc("serving.requests", -1)


def test_unknown_and_miskinded_names_raise():
    reg = M.MetricsRegistry()
    with pytest.raises(KeyError):
        reg.inc("made.up.metric")
    with pytest.raises(TypeError):
        reg.observe("serving.requests", 1.0)    # a counter, not a hist


def test_registry_thread_safety():
    """N threads x M increments lose nothing (the lock is real)."""
    reg = M.MetricsRegistry()
    n_threads, per = 8, 2000

    def worker():
        for _ in range(per):
            reg.inc("train.steps")
            reg.observe("train.step.seconds", 0.01)
    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.counter("train.steps").value() == n_threads * per
    assert reg.histogram("train.step.seconds").count() == n_threads * per


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"
    r" [0-9.eE+-]+(\+Inf)?$")


def test_prometheus_text_is_valid_and_complete():
    reg = M.MetricsRegistry()
    reg.inc("serving.requests", 3, outcome="ok")
    reg.set_gauge("serving.draining", 0)
    reg.observe("serving.request.latency_ms", 12.0)
    reg.observe("serving.request.latency_ms", 9000.0)
    text = reg.prometheus_text()
    assert text.endswith("\n")
    for line in text.strip().split("\n"):
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        assert _SAMPLE.match(line), line
    # counters end in _total; histogram exposes bucket/sum/count
    assert 'paddle_tpu_serving_requests_total{outcome="ok"} 3' in text
    assert "# TYPE paddle_tpu_serving_requests_total counter" in text
    assert "paddle_tpu_serving_draining 0" in text
    assert re.search(
        r'paddle_tpu_serving_request_latency_ms_bucket\{le="\+Inf"\} 2',
        text)
    assert "paddle_tpu_serving_request_latency_ms_count 2" in text
    # buckets are CUMULATIVE: the +Inf bucket equals count, and counts
    # never decrease as le grows
    les = [int(m.group(1)) for m in re.finditer(
        r'latency_ms_bucket\{le="[^"]+"\} (\d+)', text)]
    assert les == sorted(les)


def test_prometheus_label_escaping():
    reg = M.MetricsRegistry()
    reg.inc("chaos.injections", site='we"ird\nsite')
    text = reg.prometheus_text()
    assert '\\"' in text and "\\n" in text
    assert "\n\n" not in text


def test_snapshot_is_jsonable():
    import json
    reg = M.MetricsRegistry()
    reg.inc("ckpt.saves")
    reg.observe("ckpt.save.seconds", 0.5)
    snap = json.loads(reg.to_json())
    assert snap["ckpt.saves"]["kind"] == "counter"
    assert snap["ckpt.save.seconds"]["series"][0]["count"] == 1


# ---------------------------------------------------------------------------
# spans / trace ring
# ---------------------------------------------------------------------------

def test_span_nesting_and_chrome_export():
    obs.enable()
    with obs.span("outer", step=3):
        with obs.span("inner"):
            pass
    evs = trace.chrome_events()
    by_name = {e["name"]: e for e in evs}
    assert by_name["inner"]["args"]["depth"] == 1
    assert by_name["outer"]["args"]["depth"] == 0
    assert by_name["outer"]["args"]["step"] == 3
    # inner is contained in outer on the timeline
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3
    doc = trace.export_chrome_trace()
    assert {e["name"] for e in doc["traceEvents"]} == {"outer", "inner"}


def test_span_ring_is_bounded():
    old = trace.ring_capacity()
    try:
        trace.set_ring_capacity(16)
        obs.enable()
        for i in range(100):
            with obs.span("s", i=i):
                pass
        spans = trace.spans()
        assert len(spans) == 16
        assert spans[-1].attrs["i"] == 99      # newest kept
    finally:
        trace.set_ring_capacity(old)


def test_span_records_error_and_disabled_span_is_free():
    obs.enable()
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    assert trace.spans()[-1].attrs["error"] == "RuntimeError"
    obs.disable()
    # disabled: no Span and nothing in the ring; what comes back is
    # the bare profiler annotation (jax is loaded here), which costs
    # one atomic load while no capture runs
    trace.clear()
    assert not isinstance(obs.span("a"), trace.Span)
    assert hasattr(obs.span("a"), "__enter__")
    with obs.span("nope"):
        pass
    assert trace.spans() == []


def test_export_merges_host_tracer_events():
    """The chrome export can merge the profiler's HostTracer scopes
    into one timeline (the documented jax.profiler workflow)."""
    from paddle_tpu.profiler import utils as putils
    obs.enable()
    putils.clear_host_events()
    putils.enable_host_tracer(True)
    try:
        with putils.RecordEvent("host_scope"):
            with obs.span("obs_scope"):
                pass
    finally:
        putils.enable_host_tracer(False)
    names = {e["name"]
             for e in trace.export_chrome_trace(
                 merge_host_tracer=True)["traceEvents"]}
    assert "obs_scope" in names and "host_scope" in names


# ---------------------------------------------------------------------------
# telemetry: the MFU math, in-framework
# ---------------------------------------------------------------------------

def test_one_peaks_table_and_unknown_kind_raises(monkeypatch):
    """The telemetry gauge reads the ONE table (device/peaks.py), keyed
    exactly by device_kind, every row with its source; a device that is
    not in it is an error, never a default."""
    from paddle_tpu.core import jax_compat
    from paddle_tpu.device import peaks
    assert not hasattr(T, "PEAK_FLOPS")

    class Dev:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    v5e = peaks.peaks_for_kind("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bytes_per_s) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    assert all(p.source for p in peaks.PEAKS.values())
    for kind in ("weird device", "", "TPU v5 lite pod", "TPU v5p"):
        with pytest.raises(ValueError, match="no published peaks"):
            peaks.peaks_for_kind(kind)

    # the in-program gauge: absent off-TPU, an error on an unknown TPU
    assert T.detect_peak_flops() is None
    import jax
    monkeypatch.setattr(jax_compat, "on_tpu", lambda: True)
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev("TPU v5 lite")])
    assert T.detect_peak_flops() == 197e12
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev("weird device")])
    with pytest.raises(ValueError, match="no published peaks"):
        T.detect_peak_flops()


def test_mfu_formula_at_the_train_cells_widths():
    """FLOPs a token = 6 x (the layers' matmul weights + hidden x
    vocabulary, tied or not) + causal attention 6 x layers x heads x
    head_dim x seq; recomputed work is not model work. At SmolLM2-1.7B's
    widths, 8 layers, seq 2048, and the rate the ledger holds for that
    cell, the gauge reads the ledger's `step.mfu` (55.8 %)."""
    from types import SimpleNamespace
    from paddle_tpu.models.llama import LlamaConfig
    layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    for recompute in (False, True):
        model = SimpleNamespace(config=LlamaConfig(
            vocab_size=49152, hidden_size=2048, intermediate_size=8192,
            num_hidden_layers=8, num_attention_heads=32,
            num_key_value_heads=32, tie_word_embeddings=True,
            recompute=recompute))
        assert T.flops_per_token_for(model, 2048) == 4_026_531_840 \
            == 6 * (8 * layer + 2048 * 49152) + 6 * 8 * 32 * 64 * 2048
        tel = T.TrainingTelemetry.for_model(model, peak_flops=197e12)
        assert tel.mfu(27_300, 2048) == pytest.approx(0.5580, abs=1e-4)
    # and the generic fallback path stays sane for non-llama configs
    class P:
        stop_gradient = False
        size = 1000
    generic = SimpleNamespace(config=None, parameters=lambda: [P(), P()])
    assert T.flops_per_token_for(generic, 2048) == 6.0 * 2000


def test_telemetry_reporter_publishes_and_lags_loss():
    reg = M.MetricsRegistry()
    tel = T.TrainingTelemetry(flops_per_token=100.0, peak_flops=1e6,
                              registry=reg, loss_lag=2)
    for i in range(3):
        tel.step(tokens=1000, step_time_s=0.1, loss=float(i))
    assert reg.counter("train.steps").value() == 3
    assert reg.gauge("train.tokens_per_sec").value() == \
        pytest.approx(10000.0)
    assert reg.gauge("train.mfu").value() == \
        pytest.approx(10000.0 * 100.0 / 1e6)
    # loss published with a 2-step lag: only step 0's loss is out
    assert reg.gauge("train.loss").value() == 0.0
    assert tel.snapshot()["loss"] == 2.0        # flush drains the rest


def test_trainer_step_drives_telemetry():
    """Trainer.step publishes tokens/sec + MFU through the shared
    helper when observability is on, and costs one attribute check
    (no telemetry object at all) when off."""
    import paddle_tpu
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.llama import tiny_llama_config
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.parallel import Trainer, TrainStepConfig

    paddle_tpu.seed(0)
    cfg = tiny_llama_config()
    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())
    trainer = Trainer(model, optimizer,
                      config=TrainStepConfig(compute_dtype=None))
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 16)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}

    trainer.step(batch)                 # disabled: no reporter built
    assert trainer.telemetry is None

    with obs.scoped() as reg:
        for _ in range(3):
            float(trainer.step(batch))
    tel = trainer.telemetry
    assert tel is not None and tel.steps == 2   # intervals, not calls
    assert reg.counter("train.steps").value() == 2
    assert reg.gauge("train.tokens_per_sec").value() > 0
    tel.flush()
    assert tel.last_loss is not None    # lazy loss materialized
    # off-TPU MFU is 0 by design (no peak to score against)
    assert reg.gauge("train.mfu").value() == 0.0


# ---------------------------------------------------------------------------
# store instrumentation + the O(ws) barrier
# ---------------------------------------------------------------------------

def test_store_rpc_metrics_and_disabled_path():
    from paddle_tpu.distributed.store import TCPStore
    s = TCPStore(is_master=True, world_size=1, timeout=5.0)
    try:
        # disabled: the global registry stays EMPTY (the whole
        # instrumentation is behind one attribute check)
        s.set("k", b"v")
        assert s.get("k") == b"v"
        assert obs.REGISTRY.snapshot() == {}
        with obs.scoped() as reg:
            s.set("k2", b"v2")
            assert s.get("k2") == b"v2"
            s.add("ctr", 1)
        c = reg.counter("store.rpc.total")
        assert c.value(op="set") == 1
        assert c.value(op="get") == 1
        assert c.value(op="add") == 1
        assert reg.histogram("store.rpc.latency_ms").count(op="set") == 1
    finally:
        s.close()


def test_chaos_injections_counted():
    from paddle_tpu.distributed import chaos
    with obs.scoped() as reg:
        with chaos.scoped(seed=0, rates={"x.site": 1.0}):
            assert chaos.should_fire("x.site")
    assert reg.counter("chaos.injections").value(site="x.site") == 1


def test_retry_attempts_counted():
    from paddle_tpu.distributed.retries import (RetryPolicy,
                                                RetryBudgetExceeded)
    pol = RetryPolicy(max_attempts=3, base_delay=0, sleep=lambda s: None)
    with obs.scoped() as reg:
        with pytest.raises(RetryBudgetExceeded):
            pol.run(lambda: (_ for _ in ()).throw(ConnectionError("x")))
    assert reg.counter("retry.attempts").value() == 2   # 3 tries
    assert reg.counter("retry.exhausted").value() == 1


def test_barrier_store_rpc_count_is_linear():
    """ROADMAP open item: the set()-scan barrier issued O(ws^2) store
    round trips. The counter/arrival-scan hybrid must stay linear: per
    rank one set + one add + one wait, plus a single closing rank's
    O(ws) arrival scan — bounded here at 5*ws, far under ws*ws."""
    from paddle_tpu.distributed.store import TCPStore
    ws = 8
    master = TCPStore(is_master=True, world_size=ws, timeout=10.0)
    clients = [master] + [TCPStore(master.host, master.port,
                                   is_master=False, timeout=10.0,
                                   world_size=ws)
                          for _ in range(ws - 1)]
    errs = []

    def go(rank):
        try:
            clients[rank].barrier("lin", rank, world_size=ws,
                                  timeout=20.0)
        except Exception as e:          # noqa: BLE001
            errs.append(e)

    try:
        with obs.scoped() as reg:
            ts = [threading.Thread(target=go, args=(r,))
                  for r in range(ws)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(30)
        assert errs == []
        total = sum(reg.counter("store.rpc.total").labeled().values())
        assert total <= 5 * ws, total
        assert total < ws * ws
        assert reg.counter("store.barrier.rounds").value() >= 1
    finally:
        for c in clients[1:]:
            c.close()
        master.close()


def test_barrier_gc_cleans_previous_round_count_key():
    """Round GC now also removes the hint counter (server state stays
    ~one round per barrier name)."""
    from paddle_tpu.distributed.store import TCPStore
    s = TCPStore(is_master=True, world_size=1, timeout=5.0)
    try:
        for _ in range(3):
            s.barrier("gc", 0, world_size=1, timeout=5.0)
        assert not s.check("barrier/a/gc/0/count")
        assert not s.check("barrier/a/gc/1/count")
        assert s.check("barrier/a/gc/2/done")
    finally:
        s.close()


def test_resilient_loop_and_checkpoint_metrics(tmp_path):
    """run_resilient under an injected failure leaves a durable signal:
    saves/loads counted with durations, the restart counted."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import checkpoint as ckpt
    from paddle_tpu.distributed.elastic import run_resilient

    w = paddle.to_tensor(np.zeros(2, np.float32))
    calls = {"n": 0}

    def save_fn(step, path):
        ckpt.save_state_dict({"w": w}, path)

    def load_fn(path):
        ckpt.load_state_dict({"w": w}, path)

    def train_fn(start, end):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("injected chunk failure")
        w._value = w._value + (end - start)

    with obs.scoped() as reg:
        out = run_resilient(train_fn, total_steps=4,
                            checkpoint_dir=str(tmp_path),
                            save_fn=save_fn, load_fn=load_fn,
                            checkpoint_interval=2, max_restarts=3)
    assert out["steps"] == 4
    assert reg.counter("elastic.restarts").value() == 1
    assert reg.counter("ckpt.saves").value() >= 3
    assert reg.counter("ckpt.loads").value() >= 1
    assert reg.histogram("ckpt.save.seconds").count() >= 3
