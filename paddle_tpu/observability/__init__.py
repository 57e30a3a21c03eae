"""Unified observability: metrics registry, span tracing, training
telemetry (reference: python/paddle/profiler is the reference's only
telemetry layer; production TPU stacks — MegaScale et al. — credit
per-step tokens/sec + MFU, RPC/collective counters and restart
accounting for keeping large runs healthy. This package is that plane
for paddle_tpu).

Three pieces, one switch:

    metrics.py    thread-safe MetricsRegistry of Counter/Gauge/
                  Histogram with a closed name catalogue (METRICS),
                  JSON snapshot + Prometheus text exposition (served
                  at GET /metrics by inference/serving.PredictorServer)
    trace.py      span(name, **attrs) -> a profiler annotation always,
                  and a bounded ring buffer -> chrome-trace JSON when
                  enabled; the closed span catalogue (SPANS)
    telemetry.py  per-step training reporter: tokens/sec/chip + MFU,
                  lagged loss, driven by parallel/trainer.py
    fleet.py      the cross-rank layer: per-rank heartbeats into the
                  rendezvous TCPStore, an aggregator computing step
                  skew + straggler flags (fleet.* instruments, served
                  at GET /debug/fleet), and the crash flight recorder
                  (atomic diagnostic bundles, tools/obs_dump.py)

Contract with the hot path.

Counters (`inc` / `observe` / `set_gauge`) are gated — the contract
distributed/chaos.py set. When observability is disabled (the
default), an instrumentation point is a single module-attribute load +
falsy branch:

    if observability.ENABLED:
        observability.inc("store.rpc.retries")

No dict lookup, no allocation, no lock. Enabling is explicit —
`observability.enable()` in-process, or PADDLE_TPU_OBS=1 in the
environment (read once at import). The serving stack's own request
counters are the exception: they are always on because they REPLACE
the /stats bookkeeping PredictorServer already paid for (per-server
registries, not this module's global one).

Spans are NOT gated: `span()` and `step_span()` always annotate.

    with observability.span("engine.tick.admit"):
        self._admit()

Disabled, the call returns the bare `jax.profiler.TraceAnnotation` (a
TraceMe: entering it is one atomic load when no capture runs; no ring,
no lock, no `Span`), and the shared no-op only in a process that has
not loaded jax. Enabled, it returns a `trace.Span`, which enters the
same annotation and also records into the ring. So a profiler capture
holds the program's spans on the device ops' clock with no switch to
flip; ENABLED decides only whether the ring (whose clock is
`time.perf_counter()`, not the profiler's) keeps them too. Spans sit
at tick and step granularity, never per token or per op.

Metric names at instrumentation sites must be string literals from
the metrics.METRICS catalogue, span names from trace.SPANS;
tools/check_metric_names.py (tier-1 wired) fails the build otherwise.

Importing this package never imports jax.
"""
from __future__ import annotations

import os

from paddle_tpu.observability import metrics as metrics  # noqa: PLC0414
from paddle_tpu.observability import trace as trace      # noqa: PLC0414
from paddle_tpu.observability import requests as requests  # noqa: PLC0414
from paddle_tpu.observability import fleet as fleet      # noqa: PLC0414
from paddle_tpu.observability.metrics import (
    METRICS, MetricsRegistry, REGISTRY)
from paddle_tpu.observability.trace import Span, export_chrome_trace
from paddle_tpu.observability.requests import RequestContext

__all__ = [
    "ENABLED", "enable", "disable", "scoped", "inc", "observe",
    "set_gauge", "span", "step_span", "METRICS", "MetricsRegistry", "REGISTRY",
    "Span", "export_chrome_trace", "metrics", "trace", "requests",
    "RequestContext", "fleet",
]

# the ONE attribute hot paths branch on
ENABLED = False


def enable(reset=False):
    """Turn instrumentation on process-wide. `reset=True` also clears
    the global registry and span ring (test harness form)."""
    global ENABLED
    if reset:
        REGISTRY.reset()
        trace.clear()
        requests.clear()
        fleet.clear()
    ENABLED = True


def disable():
    """Back to the zero-cost default; recorded data is kept."""
    global ENABLED
    ENABLED = False


class _Scoped:
    def __init__(self, reset):
        self._reset = reset

    def __enter__(self):
        self._prev = ENABLED
        enable(reset=self._reset)
        return REGISTRY

    def __exit__(self, *exc):
        global ENABLED
        ENABLED = self._prev
        return False


def scoped(reset=True):
    """`with observability.scoped() as registry:` — enable for a block,
    restoring the previous state (including disabled) on exit."""
    return _Scoped(reset)


# -- instrumentation surface ------------------------------------------------
# Call sites gate with `if observability.ENABLED:` so the disabled cost
# is one attribute check; these helpers themselves always record (into
# the global REGISTRY), which is what tests and scoped() rely on.

def inc(name, n=1, **labels):
    REGISTRY.inc(name, n, **labels)


def observe(name, v, **labels):
    REGISTRY.observe(name, v, **labels)


def set_gauge(name, v, **labels):
    REGISTRY.set_gauge(name, v, **labels)


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_SPAN = _NoopSpan()


def span(name, **attrs):
    """Timed scope: always a profiler annotation, and a ring record
    when enabled (module doc). Disabled, what comes back is the bare
    annotation — or the shared no-op where jax is not loaded."""
    if not ENABLED:
        return trace.annotation(name, attrs) or _NOOP_SPAN
    return Span(name, attrs)


def step_span(name, step_num):
    """`span` for one step of a loop: a `StepTraceAnnotation`, which
    the profiler's per-step analysis keys on `step_num`."""
    ann = trace.step_annotation(name, step_num)
    if not ENABLED:
        return ann or _NOOP_SPAN
    return Span(name, {"step_num": step_num}, ann)


# -- env bootstrap (read once at import) ------------------------------------

if os.environ.get("PADDLE_TPU_OBS") == "1":
    enable()
