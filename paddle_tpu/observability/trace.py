"""Span tracing: a profiler annotation always, a ring record when enabled.

`observability.span(name, **attrs)` (the entry point — see
observability/__init__.py) wraps a host-side scope. Two things can
hold it:

- the profiler. Wherever jax is loaded a span enters a
  `jax.profiler.TraceAnnotation(name, **attrs)`, so ANY capture
  (`jax.profiler.start_trace`, the benchmark's `--trace 1`, an
  operator's on-demand capture) holds the program's spans on the same
  clock as the device ops, whether observability is enabled or not.
  With no capture running an annotation is one atomic load.
- the ring. With observability ENABLED the span is also a `Span`:
  completed spans land in a process-wide ring buffer (oldest evicted
  first, so a long-running job's memory is bounded) and export as
  chrome-trace JSON that loads in chrome://tracing / perfetto.
  `export_chrome_trace` merges the native profiler's HostTracer events
  on request. The ring's remaining users are the slow-request
  exemplars of observability/requests.py (`record_span`, rebuilt from
  a request's timeline after the fact), the overlap phase spans of
  parallel/overlap.py and Trainer.measure_phase_seconds, and the
  flight recorder's trace.json (fleet.py).

The ring's clock is `time.perf_counter()`; the profiler's is its own.
Nothing the ring holds can be laid over a device trace: read the
annotations in the capture for that.

SPANS is the closed catalogue of span names at call sites, like
metrics.METRICS: name -> (layer, what it covers, the per-layer metric
designed to read it; benchmarks/spans.py holds the readers, PERF.md
says which are registered). tools/check_metric_names.py holds call
sites to it.

Spans nest naturally: chrome-trace "X" (complete) events reconstruct
the stack from ts/dur containment per thread; `depth` is also recorded
explicitly in args for programmatic consumers.

Stdlib-only; importing this module never imports jax (it uses the
profiler of a jax that something else has already loaded).
"""
from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time

__all__ = ["Span", "SPANS", "annotation", "step_annotation",
           "record_span", "set_ring_capacity", "ring_capacity",
           "spans", "clear", "export_chrome_trace", "chrome_events"]

# name -> (layer, what the span covers, the per-layer metric designed to
# read it).
# The thread is the one that owns the work: the ticker for engine.*,
# the training loop for train.* and input.wait, the prefetch worker for
# input.h2d, a handler thread for http.write.
# The children of engine.tick are paged.TICK_PHASES, always all seven in
# that order (or the first two, when admission left no live slot), one
# decode tick landed an iteration. With a tick in flight (ISSUE 32) the
# phases belong to two ticks: alloc, upload and launch to the tick
# launched ahead (N+1, from N's rows on the device), readback and accept
# to the one landed (N); an iteration that must not chain has the first
# five empty. What holds meanwhile (a slot ended in N is dead in N+1;
# accept goes by request; freed pages go to later programs only; errors,
# stop() and the stall guard see the tick in flight) is PagedKVEngine's
# class doc.
SPANS = {
    "input.wait": (
        "input", "DevicePrefetcher.__next__ blocked on its queue",
        "input.prefetch_wait_share (counter DevicePrefetcher.wait_s)"),
    "input.h2d": (
        "input", "the prefetch worker placing one batch on the device",
        "none yet; io.h2d.seconds times the same work when enabled"),
    "train.step": (
        "step", "the whole of Trainer.step; a step span whose step_num "
        "is the optimizer's step count before the step",
        "step.host_dispatch_ms_p50"),
    "train.step.place": (
        "step", "the mesh device_put loop over the batch's leaves",
        "none yet; a child of train.step"),
    "train.step.dispatch": (
        "step", "the call of the jitted step (trace and compile on a "
        "first call, else the enqueue)",
        "none yet; a child of train.step"),
    "engine.tick": (
        "scheduler", "one scheduler tick that found work, attr seq and, "
        "where a tick settles whole blocks (a model that generates by "
        "diffusion over blocks: counters block_forwards_denoise, "
        "block_forwards_store, block_positions_unmasked, blocks_done), "
        "attr blocks; its children follow in this order",
        "sched.tick_host_ms_p50 (counters tick_wall_s, tick_host_s)"),
    "engine.tick.retire": (
        "scheduler", "the cancel sweep and the session suspend sweep",
        "serve.idle_in_accept_share"),
    "engine.tick.admit": (
        "scheduler", "_admit(): queue swap, prefix lookups, page "
        "reservations, and the prefills (engine.prefill) inside it",
        "serve.idle_in_launch_share"),
    "engine.tick.alloc": (
        "scheduler", "pages for the tokens of the tick to land and of "
        "the one chained after it, and with nothing in flight the "
        "per-slot host arrays", "serve.idle_in_launch_share"),
    "engine.tick.upload": (
        "scheduler", "with nothing in flight the tick program's lookup "
        "and its arguments made device arrays; for a chained tick the "
        "block table if it changed", "serve.idle_in_launch_share"),
    "engine.tick.launch": (
        "scheduler", "the calls of the tick program (the enqueue): the "
        "tick to land if none flew, the one chained after it (counter "
        "ticks_chained)", "sched.tick_chained_share"),
    "engine.tick.readback": (
        "scheduler", "np.asarray of the landed tick's tokens and "
        "lengths: the wait for the device, which by then works on "
        "the tick launched ahead", "counter readback_s"),
    "engine.tick.accept": (
        "scheduler", "_accept_tick of the landed tick: tokens to the "
        "requests' queues, retirements", "serve.idle_in_accept_share"),
    "engine.prefill": (
        "scheduler", "the prefill of the prompts admitted together "
        "to one bucket: its program calls (attr calls: one call group "
        "rows wide, or at group 1 a call a row or a chunk, dispatched "
        "back to back) and the one read back after the last, attrs "
        "bucket, rows, group, chunks, calls",
        "sched.prefill_time_share (counter prefill_s); counters "
        "prefill_rows_run, prefill_rows_padded, prefill_rows_split"),
    "engine.idle": (
        "scheduler", "the ticker's sleep after a step() with no work",
        "serve.idle_between_ticks_share"),
    "http.write": (
        "HTTP", "one chunk of a streamed reply written and flushed, "
        "attr rid where the request is traced",
        "none yet; laid beside the ticker's idle gaps"),
}

_annotations = None     # (TraceAnnotation, StepTraceAnnotation) of jax


def _profiler_classes():
    """jax's annotation classes if jax is loaded, else None. Never
    imports jax: a process that has not loaded it has no profiler to
    write to."""
    global _annotations
    if _annotations is None:
        prof = getattr(sys.modules.get("jax"), "profiler", None)
        if prof is None:
            return None
        _annotations = (prof.TraceAnnotation, prof.StepTraceAnnotation)
    return _annotations


def annotation(name, attrs):
    """The profiler annotation of a span, or None where jax is not
    loaded."""
    classes = _profiler_classes()
    return classes[0](name, **attrs) if classes else None


def step_annotation(name, step_num):
    classes = _profiler_classes()
    return classes[1](name, step_num=step_num) if classes else None

_DEFAULT_CAPACITY = 4096

_lock = threading.Lock()
_ring: collections.deque = collections.deque(maxlen=_DEFAULT_CAPACITY)
_tls = threading.local()


def set_ring_capacity(n: int):
    """Resize the span ring (keeps the newest spans)."""
    global _ring
    with _lock:
        _ring = collections.deque(_ring, maxlen=int(n))


def ring_capacity() -> int:
    return _ring.maxlen


def clear():
    with _lock:
        _ring.clear()


class Span:
    """One timed scope: a ring record and, where jax is loaded, a
    profiler annotation around the same code. Use through
    observability.span(...), which skips the ring when observability
    is disabled; constructing a Span directly always records."""

    __slots__ = ("name", "attrs", "t0", "dur_us", "depth", "tid",
                 "_annotation")

    def __init__(self, name, attrs=None, ann=None):
        self.name = name
        self.attrs = attrs or {}
        self.t0 = 0.0
        self.dur_us = 0.0
        self.depth = 0
        self.tid = 0
        self._annotation = ann

    def __enter__(self):
        if self._annotation is None:
            self._annotation = annotation(self.name, self.attrs)
        if self._annotation is not None:
            self._annotation.__enter__()
        depth = getattr(_tls, "depth", 0)
        _tls.depth = depth + 1
        self.depth = depth
        self.tid = threading.get_ident()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur_us = (time.perf_counter() - self.t0) * 1e6
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        _tls.depth = self.depth
        if exc_type is not None:
            self.attrs = {**self.attrs, "error": exc_type.__name__}
        with _lock:
            _ring.append(self)
        return False


def record_span(name, t0, dur_us, *, depth=0, tid=None, attrs=None):
    """Append an externally-timed completed span to the ring. The
    slow-request exemplar path (observability/requests.py) rebuilds a
    request's lifecycle from its recorded timeline after the fact
    rather than timing a live scope; `t0` must be a
    time.perf_counter() value so the span lands on the same timeline
    as live span() scopes."""
    s = Span(name, attrs or {})
    s.t0 = float(t0)
    s.dur_us = float(dur_us)
    s.depth = int(depth)
    s.tid = int(tid) if tid is not None else threading.get_ident()
    with _lock:
        _ring.append(s)
    return s


def spans() -> list:
    """Snapshot of the ring, oldest first."""
    with _lock:
        return list(_ring)


def chrome_events() -> list:
    """Ring contents as chrome-trace event dicts. perf_counter has an
    arbitrary epoch; events are self-consistent with each other and
    with the HostTracer events merged by export_chrome_trace (both
    clocks are monotonic-since-boot on Linux)."""
    evs = []
    pid = os.getpid()
    for s in spans():
        args = {"depth": s.depth}
        args.update({str(k): v for k, v in s.attrs.items()})
        evs.append({"name": s.name, "ph": "X", "pid": pid,
                    "tid": s.tid, "ts": s.t0 * 1e6,
                    "dur": s.dur_us, "cat": "observability",
                    "args": args})
    return evs


def export_chrome_trace(path=None, merge_host_tracer=False) -> dict:
    """Chrome-trace document of the recorded spans; with
    `merge_host_tracer` the native profiler HostTracer's events (the
    per-op scopes the Profiler records) join the same timeline. Writes
    to `path` when given; always returns the document."""
    events = chrome_events()
    if merge_host_tracer:
        try:
            from paddle_tpu.profiler import utils as _utils
            events = events + list(_utils.host_chrome_events())
        except Exception:  # lint: disable=silent-swallow -- profiler backend unavailable: export spans alone
            pass
    doc = {"traceEvents": events, "displayTimeUnit": "ms",
           "metadata": {"producer": "paddle_tpu.observability"}}
    if path is not None:
        with open(path, "w") as f:
            json.dump(doc, f)
    return doc
