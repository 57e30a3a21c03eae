"""The span seam (observability/trace.py): a span is always a profiler
annotation, and a ring record when observability is enabled.

What is held here, all on the CPU: a span inside a jax.profiler capture
lands in the xplane's host lines with its name and attributes; the
engine's tick holds its seven phases in order and its always-on
counters add up; the prefetcher's wait counter follows a slow source;
the trainer's step span carries the optimizer's step number; every span
name at a call site is in the catalogue.
"""
import glob
import json
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

import jax

import paddle_tpu
from paddle_tpu import observability as obs
from paddle_tpu.inference.paged import TICK_PHASES, PagedKVEngine
from paddle_tpu.io.prefetch import DevicePrefetcher
from paddle_tpu.models.llama import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.observability import trace

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disabled():
    obs.disable()
    trace.clear()
    yield
    obs.disable()
    trace.clear()


def _capture(fn, logdir):
    """Run fn under jax's profiler; return (window, threads): the
    capture's (start, end) over every host event and, per host thread,
    its events [(name, start_ns, end_ns, stats)] in start order. A name
    loses a `#k=v#` suffix where the profiler left one."""
    jax.profiler.start_trace(str(logdir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(logdir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    threads, lo, hi = [], float("inf"), 0.0
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(
                ((e.name.split("#")[0], e.start_ns,
                  e.start_ns + e.duration_ns, dict(e.stats))
                 for e in line.events), key=lambda e: (e[1], -e[2]))
            if evs:
                threads.append(evs)
                lo = min(lo, evs[0][1])
                hi = max(hi, max(e[2] for e in evs))
    return (lo, hi), threads


def _named(threads, prefix):
    """Per thread, the events whose name starts with prefix; threads
    that hold none are left out."""
    out = [[e for e in evs if e[0].startswith(prefix)] for evs in threads]
    return [evs for evs in out if evs]


# the columns of a `tick_log` row, in order
TICK_LOG = ("seq", "start", *TICK_PHASES, "live", "prefills", "host_cpu")


def _tiny_model():
    paddle_tpu.seed(0)
    return LlamaForCausalLM(tiny_llama_config(
        num_hidden_layers=2, vocab_size=97, hidden_size=32,
        intermediate_size=64, num_attention_heads=4,
        num_key_value_heads=2))


# -- (a) the seam ---------------------------------------------------------

@pytest.mark.parametrize("enabled", [False, True])
def test_span_lands_in_a_capture_with_name_and_attrs(tmp_path, enabled):
    if enabled:
        obs.enable(reset=True)

    def work():
        with obs.span("engine.tick", seq=7):
            with obs.span("engine.tick.admit"):
                time.sleep(0.002)
        with obs.step_span("train.step", 41):
            time.sleep(0.001)
    (lo, hi), threads = _capture(work, tmp_path)
    (mine,) = _named(threads, "engine.tick")
    tick, admit = mine
    assert tick[0] == "engine.tick" and tick[3]["seq"] == 7
    assert admit[0] == "engine.tick.admit"
    assert lo <= tick[1] <= admit[1] <= admit[2] <= tick[2] <= hi
    assert admit[2] - admit[1] >= 2e6              # the sleep, in ns
    ((step,),) = _named(threads, "train.step")
    assert step[3]["step_num"] == 41
    # the ring holds them only when enabled
    ring = [s.name for s in trace.spans()]
    assert ring == (["engine.tick.admit", "engine.tick", "train.step"]
                    if enabled else [])
    if enabled:
        assert trace.spans()[-1].attrs == {"step_num": 41}


def test_disabled_span_is_the_bare_annotation_and_noop_without_jax(
        monkeypatch):
    a = obs.span("engine.idle")
    assert isinstance(a, jax.profiler.TraceAnnotation)
    assert isinstance(obs.step_span("train.step", 1),
                      jax.profiler.StepTraceAnnotation)
    # a process that never loaded jax has no profiler to write to
    monkeypatch.setattr(trace, "_annotations", None)
    monkeypatch.setitem(sys.modules, "jax", None)
    assert obs.span("a") is obs.span("b")
    with obs.span("a"), obs.step_span("train.step", 2):
        pass
    obs.enable(reset=True)
    with obs.span("engine.idle"):
        pass
    assert [s.name for s in trace.spans()] == ["engine.idle"]


# -- (b) the engine's tick -------------------------------------------------

def test_engine_tick_holds_its_phases_and_counters_add_up(tmp_path):
    eng = PagedKVEngine(_tiny_model(), max_slots=2, page_size=4,
                        num_pages=24, max_pages_per_slot=8,
                        steps_per_tick=2)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 97, size=n).astype(np.int32)
               for n in (5, 9, 6)]
    eng.generate(prompts[:1], max_new_tokens=2)      # compile outside
    s0, log0 = dict(eng.stats), len(eng.tick_log)
    _, threads = _capture(
        lambda: eng.generate(prompts, max_new_tokens=7), tmp_path)
    (evs,) = _named(threads, "engine.")        # one thread drove it all
    ticks = [e for e in evs if e[0] == "engine.tick"]
    decoded = 0
    for tick in ticks:
        inside = [e for e in evs if e is not tick
                  and tick[1] <= e[1] and e[2] <= tick[2]]
        kids = [e[0].rsplit(".", 1)[1] for e in inside
                if e[0].startswith("engine.tick.")]
        # all seven in order, or the first two when admission left no
        # live slot
        assert kids in (list(TICK_PHASES), list(TICK_PHASES[:2])), kids
        decoded += len(kids) == len(TICK_PHASES)
        # a prefill lies inside its tick's admit phase
        admit = next(e for e in inside if e[0] == "engine.tick.admit")
        for e in inside:
            if e[0] == "engine.prefill":
                assert admit[1] <= e[1] and e[2] <= admit[2]
                assert {"bucket", "rows", "group"} <= set(e[3])
    seqs = [t[3]["seq"] for t in ticks]
    assert seqs == sorted(set(seqs))
    d = {k: eng.stats[k] - s0[k] for k in
         ("ticks", "prefills", "tick_wall_s", "tick_host_s",
          "readback_s", "prefill_s")}
    assert decoded == d["ticks"] >= 4 and d["prefills"] == 3
    assert sum(1 for e in evs if e[0] == "engine.prefill") >= 2
    # one row a tick, and the counters are the rows' sums
    rows = list(eng.tick_log)[log0:]
    assert len(rows) == len(ticks)
    assert [r[0] for r in rows] == seqs
    assert all(len(r) == len(TICK_LOG) for r in rows)
    walls = [sum(r[2:2 + len(TICK_PHASES)]) for r in rows]
    assert sum(walls) == pytest.approx(d["tick_wall_s"], rel=0.05)
    assert d["tick_host_s"] + d["readback_s"] + d["prefill_s"] \
        == pytest.approx(d["tick_wall_s"], rel=0.05)
    assert d["tick_host_s"] > 0 and d["readback_s"] > 0
    assert eng.stats["tick_max_s"] >= max(walls) * 0.999
    assert sum(r[TICK_LOG.index("prefills")] for r in rows) \
        == d["prefills"]
    assert sum(1 for r in rows if r[TICK_LOG.index("live")]) == d["ticks"]
    # scrape-time gauges
    reg = obs.MetricsRegistry()
    eng.export_metrics(reg)
    assert reg.gauge("engine.tick_max_seconds").value() \
        == eng.stats["tick_max_s"]
    assert reg.gauge("engine.tick_host_seconds").value() \
        == eng.stats["tick_host_s"]


def test_idle_polls_are_no_ticks_and_the_ticker_names_its_sleep(tmp_path):
    eng = PagedKVEngine(_tiny_model(), max_slots=2, page_size=4,
                        num_pages=24, max_pages_per_slot=8)
    assert eng.step() is False and len(eng.tick_log) == 0

    def idle():
        eng.start()
        time.sleep(0.08)
        eng.stop()
    _, threads = _capture(idle, tmp_path)
    (evs,) = _named(threads, "engine.")
    assert {e[0] for e in evs} == {"engine.idle"} and len(evs) >= 2
    assert len(eng.tick_log) == 0 and eng.stats["tick_wall_s"] == 0.0


_HOST_PATH = ("tick_host_s", "tick_host_wait_s", "prefill_s",
              "prefill_cpu_s", "queue_wait_s", "request_s")


def _served(eng, prompts, new, spinners=0):
    """Serve `prompts` to their end, with `spinners` threads spinning in
    pure Python beside the ticker: the host path's counters'
    differences."""
    s0 = dict(eng.stats)
    stop = threading.Event()

    def spin():
        n = 0
        while not stop.is_set():
            n += 1
    threads = [threading.Thread(target=spin, daemon=True)
               for _ in range(spinners)]
    for t in threads:
        t.start()
    try:
        eng.generate(prompts, max_new_tokens=new)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    return {k: eng.stats[k] - s0[k] for k in _HOST_PATH}


def test_host_wait_and_prefill_cpu_lie_inside_their_walls():
    eng = PagedKVEngine(_tiny_model(), max_slots=2, page_size=4,
                        num_pages=24, max_pages_per_slot=8,
                        steps_per_tick=2)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 97, size=n).astype(np.int32)
               for n in (5, 9, 6)]
    eng.generate(prompts[:1], max_new_tokens=2)      # compile outside
    log0 = len(eng.tick_log)
    d = _served(eng, prompts, 7)
    s = eng.stats
    # the wait is a sum of differences between two clocks, never clamped
    # a tick: a run that hardly waits may read a hair under 0
    assert -1e-4 <= s["tick_host_wait_s"] <= s["tick_host_s"]
    assert 0.0 < s["prefill_cpu_s"] <= s["prefill_s"]
    assert 0.0 < d["queue_wait_s"] <= d["request_s"]
    rows = list(eng.tick_log)[log0:]
    readback, cpu = TICK_LOG.index("readback"), TICK_LOG.index("host_cpu")
    for r in rows:
        # the host phases' CPU time: within the row's wall less readback
        wall = sum(r[2:2 + len(TICK_PHASES)])
        assert 0.0 <= r[cpu] <= wall - r[readback] + 1e-6
    # and within the host wall of the same ticks, which also leaves out
    # the prefills
    assert sum(r[cpu] for r in rows) <= d["tick_host_s"] + 1e-6
    reg = obs.MetricsRegistry()
    eng.export_metrics(reg)
    assert reg.gauge("engine.tick_host_wait_seconds").value() \
        == s["tick_host_wait_s"]


def test_threads_spinning_beside_the_ticker_show_as_its_wait():
    """Two threads spinning in pure Python pass the interpreter lock
    between them every switch interval, so whenever the ticker lets go of
    it in a host phase it queues to take it back: its wait rises well
    above the quiet runs', alternated three times. (One spinner alone
    can miss: the ticker's short releases keep restarting a lone
    waiter's switch timer, and it then takes the lock only at the
    readback's waits, which the counter leaves out.)"""
    eng = PagedKVEngine(_tiny_model(), max_slots=2, page_size=4,
                        num_pages=24, max_pages_per_slot=8,
                        steps_per_tick=2)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 97, size=n).astype(np.int32)
               for n in (5, 9, 6, 7)]
    _served(eng, prompts, 12)                   # compile outside
    runs = {0: [], 2: []}
    for _ in range(3):
        for spinners, out in runs.items():
            out.append(_served(eng, prompts, 12, spinners))
    wait, host = ({n: sum(d[k] for d in out) for n, out in runs.items()}
                  for k in ("tick_host_wait_s", "tick_host_s"))
    for n in runs:
        assert -1e-4 <= wait[n] <= host[n]
    assert wait[2] >= 4 * wait[0], (wait, host)
    assert wait[2] / host[2] >= wait[0] / host[0] + 0.25, (wait, host)


def test_a_request_waits_for_the_slot_the_one_before_holds():
    eng = PagedKVEngine(_tiny_model(), max_slots=1, page_size=4,
                        num_pages=24, max_pages_per_slot=8,
                        steps_per_tick=2)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 97, size=n).astype(np.int32)
               for n in (5, 9, 6)]
    eng.generate(prompts[:1], max_new_tokens=2)      # compile outside
    s0 = dict(eng.stats)
    first, second, dropped = (eng.submit(p, 6) for p in prompts)
    dropped.cancel()            # leaves the queue without a slot
    while not first.done.is_set():
        eng._step(ahead=True)
    life = eng.stats["request_s"] - s0["request_s"]
    wait = eng.stats["queue_wait_s"] - s0["queue_wait_s"]
    assert wait == pytest.approx(first.admitted_at - first.queued_at)
    assert wait < life <= time.monotonic() - first.queued_at
    eng.run_until_idle()
    assert dropped.admitted_at is None and eng.stats["cancelled"] == 1
    d = {k: eng.stats[k] - s0[k] for k in _HOST_PATH}
    # each request that held a slot counted once, the dropped one not
    assert d["queue_wait_s"] - wait == pytest.approx(
        second.admitted_at - second.queued_at)
    assert d["request_s"] > life
    # the second waited out the first's whole life, less the instant
    # between the two submits
    assert d["queue_wait_s"] - wait \
        >= life - (second.queued_at - first.queued_at)
    assert d["queue_wait_s"] <= d["request_s"]
    assert eng.step() is False
    assert {k: eng.stats[k] - s0[k] for k in _HOST_PATH} == d


def test_cancel_all_ends_every_stream_in_order():
    eng = PagedKVEngine(_tiny_model(), max_slots=2, page_size=4,
                        num_pages=24, max_pages_per_slot=8,
                        steps_per_tick=2)
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(1, 97, size=6).astype(np.int32), 20)
            for _ in range(3)]
    eng.step()                      # two in slots, one still queued
    assert sum(s is not None for s in eng._slots) == 2
    assert eng.cancel_all() == 3
    eng.run_until_idle()
    assert not eng.has_work() and eng.stats["cancelled"] == 3
    for r in reqs:
        assert r.done.is_set() and r.error is None
        assert 0 <= len(r.tokens) < 20
        assert list(r.stream_tokens()) == r.tokens     # closed by None
    assert len(eng._free) == eng.num_pages - 1          # pages returned
    assert eng.cancel_all() == 0


# -- (c) the prefetcher's wait counter -----------------------------------

def _source(n, delay):
    for i in range(n):
        time.sleep(delay)
        yield {"x": np.full((2, 2), i, np.float32)}


def test_prefetch_wait_counter_follows_a_slow_source(tmp_path):
    fast = DevicePrefetcher(_source(6, 0.0), depth=2)
    give_up = time.perf_counter() + 10
    while fast.qsize() < 2 and time.perf_counter() < give_up:
        time.sleep(0.01)                    # the queue fills
    with fast:
        for _ in range(2):
            next(fast)
    assert fast.wait_s < 0.05

    slow = DevicePrefetcher(_source(4, 0.05), depth=2)

    def drain():
        with slow:
            assert sum(1 for _ in slow) == 4
    _, threads = _capture(drain, tmp_path)
    assert 0.12 <= slow.wait_s <= 2.0       # ~4 sleeps of the source
    waits = [e for evs in _named(threads, "input.wait") for e in evs]
    assert len(waits) == 5                  # four batches and the end
    assert sum(e[2] - e[1] for e in waits) / 1e9 \
        == pytest.approx(slow.wait_s, rel=0.2)
    h2d = [e for evs in _named(threads, "input.h2d") for e in evs]
    assert len(h2d) == 4 == slow.batches_prefetched


# -- (d) the trainer's step span ---------------------------------------

def test_trainer_step_span_carries_the_optimizer_step(tmp_path):
    import paddle_tpu.optimizer as opt
    from paddle_tpu.parallel import Trainer, TrainStepConfig
    model = _tiny_model()
    optimizer = opt.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    trainer = Trainer(model, optimizer, config=TrainStepConfig())
    ids = np.random.default_rng(0).integers(
        0, 97, (2, 16)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    float(trainer.step(batch))              # compile outside
    first = optimizer._step_count

    def steps():
        for _ in range(3):
            loss = trainer.step(batch)
        float(loss)
    _, threads = _capture(steps, tmp_path)
    (evs,) = _named(threads, "train.step")
    whole = [e for e in evs if e[0] == "train.step"]
    assert [e[3]["step_num"] for e in whole] == [first, first + 1,
                                                  first + 2]
    for step in whole:
        (kid,) = [e for e in evs if e[0] == "train.step.dispatch"
                  and step[1] <= e[1] and e[2] <= step[2]]
        assert kid[2] - kid[1] <= step[2] - step[1]
    assert optimizer._step_count == first + 3


# -- (f) the catalogue ------------------------------------------------------

def test_span_names_at_call_sites_are_catalogued(tmp_path):
    sys.path.insert(0, _ROOT)
    from tools.analyze.passes import metric_names
    violations, seen, catalogue = metric_names.scan_spans(_ROOT)
    assert violations == []
    assert seen == set(catalogue) == set(trace.SPANS)
    assert {"engine.tick." + p for p in TICK_PHASES} <= seen
    for layer, covers, feeds in trace.SPANS.values():
        assert layer and covers and feeds
    # every metric the third column names is one of the benchmark's
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        metrics = {m["name"] for m in json.load(f)["per_layer"]}
    named = {m for _l, _c, feeds in trace.SPANS.values()
             for m in re.findall(r"\b[a-z]+\.[a-z0-9_]+_(?:share|p50)\b",
                                 feeds)}
    assert named and named <= metrics, named - metrics
    # an uncatalogued or computed name is a violation of the same gate
    pkg = tmp_path / "paddle_tpu"
    (pkg / "observability").mkdir(parents=True)
    (pkg / "observability" / "metrics.py").write_text("METRICS = {}\n")
    (pkg / "observability" / "trace.py").write_text(
        "SPANS = {'a.b': ('l', 'c', 'm')}\n")
    (pkg / "mod.py").write_text(
        "from paddle_tpu import observability\n"
        "import re\n"
        "def f(n):\n"
        "    with observability.span('a.b'):\n"
        "        pass\n"
        "    with observability.span('a.c'):\n"
        "        pass\n"
        "    with observability.step_span(n, 1):\n"
        "        pass\n"
        "    return re.match('x', 'x').span(0)\n")
    bad, seen, _cat = metric_names.scan_spans(str(tmp_path))
    assert seen == {"a.b", "a.c"}
    assert [(rel, no) for rel, no, _c, _w in bad] == [
        (os.path.join("paddle_tpu", "mod.py"), 6),
        (os.path.join("paddle_tpu", "mod.py"), 8)]
    assert len(metric_names.scan(str(tmp_path))[0]) == 2
