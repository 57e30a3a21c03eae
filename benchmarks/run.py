#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and per-layer metrics are data files
found by the names BENCHMARK.json gives (README.md). The last line of stdout
is the result: `correct`, `attempted`, `failed`, `metrics`, `device`, traced
also `breakdown`, and last `compared` (what `correct` held against which
limit). Every other print is on an earlier line. Without a TPU,
or with fewer chips than the cell asks for, the run ends non-zero and prints
no result; `--rehearse` (never passed by the driver) shrinks the cell by
`rehearse.json` and runs it on the CPU to prove the control flow, and its
numbers are not measurements.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()       # set-up is counted from here (see Clock)

import argparse         # noqa: E402
import importlib        # noqa: E402
import json             # noqa: E402
import os               # noqa: E402
import shutil           # noqa: E402
import sys              # noqa: E402
import tempfile         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce, spans    # noqa: E402

# every reader a metric file can name
READERS = {**reduce.READERS, **spans.READERS}


def log(*parts):
    print(*(json.dumps(p) if isinstance(p, (dict, list)) else p
            for p in parts), flush=True)


def load_json(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


class Clock:
    """Set-up by parts: `mark(name)` closes the part that began at the
    last mark. A part marked `counted=False` is printed and left out of
    `total()`: importing jax and reaching the chip are the platform's, no
    PR to this repo moves them, and they drift by seconds over a machine's
    first runs (PERF.md section 2), which a 10 % bound on a 25 s set-up
    cannot carry."""

    def __init__(self, start):
        self.start = self.last = start
        self.parts = {}
        self.left_out = 0.0

    def mark(self, name, counted=True):
        now = time.perf_counter()
        self.parts[name] = self.parts.get(name, 0.0) + now - self.last
        if not counted:
            self.left_out += now - self.last
        self.last = now

    def total(self):
        return self.last - self.start - self.left_out


class CompileLog:
    """Compilations and persistent-cache hits, from jax's monitoring
    events (copied from chip_smoke.py, whose count is sound)."""

    def __init__(self):
        import jax
        self.durations, self.cache_hits, self.cache_misses = [], 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.durations.append(float(secs))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        return (len(self.durations), self.cache_hits, self.cache_misses)

    def since(self, mark):
        n, h, m = mark
        return {"programs": len(self.durations) - n,
                "compile_s": round(sum(self.durations[n:]), 2),
                "cache_hits": self.cache_hits - h,
                "cache_misses": self.cache_misses - m}


class Tracer:
    """Runs a piece of the window under jax's profiler and keeps the
    `.xplane.pb`; it is read after the window, not inside it."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def record(self, fn):
        import jax
        jax.profiler.start_trace(self.dir)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()

    def load(self):
        """(the trace, each device op's scope path and self time)."""
        try:
            planes, scoped = spans.load_xplane(reduce.find_xplane(self.dir))
            return reduce.Trace(planes), spans.op_scopes(scoped)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def load_cell(name, rehearse):
    """The cell's entry and the three kinds of files it names. A per-layer
    metric is read in the cell when its file's `kinds` hold the traffic's
    kind and its entry under `per_layer` lists the cell."""
    bench = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(ROOT, conf["file"])
    traffic = load_json(HERE, "traffic", entry["traffic"] + ".json")
    builder = importlib.import_module("benchmarks.builders."
                                      + config["builder"])
    if rehearse:
        small = load_json(HERE, "rehearse.json")
        # a family whose keys rehearse.json does not know brings its own
        config.update(builder.rehearse(config) if hasattr(builder, "rehearse")
                      else small["config"])
        for key, val in small[traffic["kind"]].items():
            if isinstance(val, dict):
                traffic[key].update(val)
            else:
                traffic[key] = val
    listed = {m["name"] for m in bench["per_layer"]
              if name in m["workloads"]}
    metrics = []
    for fn in sorted(os.listdir(os.path.join(HERE, "metrics"))):
        m = load_json(HERE, "metrics", fn)
        if traffic["kind"] in m["kinds"] and m["name"] in listed:
            metrics.append(m)
    return {"name": name, "chips": entry["chips"], "config": config,
            "traffic": traffic, "metrics": metrics,
            "units": {m["name"]: m["unit"] for m in bench["end_to_end"]},
            "builder": builder}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; not a measurement")
    args = ap.parse_args()
    args.seed = abs(args.seed)

    cell = load_cell(args.workload, args.rehearse)
    # autotune sweeps unseeded shapes by wall clock at trace time, so the
    # blocks could differ from run to run: pinned off for the benchmark
    os.environ["PADDLE_TPU_AUTOTUNE"] = "0"
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    clock = Clock(_T0)
    import jax
    clock.mark("import_jax", counted=False)
    devices = jax.devices()
    clock.mark("device", counted=False)
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"no accelerator: jax reports platform {platform!r}",
              file=sys.stderr)
        return 3
    if len(devices) < cell["chips"]:
        print(f"the cell needs {cell['chips']} chips, jax reports "
              f"{len(devices)}", file=sys.stderr)
        return 3
    # every program goes to the persistent cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    clog = CompileLog()

    import paddle_tpu  # noqa: F401
    from paddle_tpu.core import autotune, compile_cache
    cache_dir = compile_cache.ensure()
    clock.mark("import_program")
    kind = devices[0].device_kind
    log("[cell]", {"workload": cell["name"], "chips": cell["chips"],
                   "seed": args.seed, "seconds": args.seconds,
                   "trace": args.trace, "rehearse": args.rehearse,
                   "platform": platform, "kind": kind,
                   "devices": len(devices), "compile_cache": cache_dir})
    log("[blocks]", {f"{k}|{key}": autotune.get(k, key)
                     or "not in the seeded table: the kernel's default, 512 x 512"
                     for k, key in cell["builder"].flash_block_keys(
                         cell["config"], cell["traffic"])})

    cell["tracer"] = Tracer() if args.trace else None
    module = importlib.import_module("benchmarks." + cell["traffic"]["kind"])
    res = module.run(cell, args, clock, clog, log)
    log("[setup]", {k: round(v, 3) for k, v in clock.parts.items()},
        "setup_s", round(res["setup_s"], 3), "| left out, the platform's",
        round(clock.left_out, 3), "| compile", clog.since((0, 0, 0)))

    used = devices[:cell["chips"]]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"]}
    if not args.trace:
        values = dict(res["end_to_end"], setup_s=res["setup_s"])
        out["metrics"] = {k: {"value": v, "unit": cell["units"][k]}
                          for k, v in values.items()}
    else:
        trace, scopes = cell["tracer"].load()
        ctx = {"trace": trace, "scopes": scopes, "window": res["window"],
               "config": cell["config"], "builder": cell["builder"],
               # a rehearsal has no chip, so nothing is held against peaks
               "device_kind": None if args.rehearse else kind,
               "sizes": cell["builder"].sizes(cell["config"],
                                              cell["traffic"])}
        out["metrics"] = {}
        for m in cell["metrics"]:
            value = READERS[m["reader"]](ctx, **m.get("args", {}))
            if value is not None:       # nothing to read: left out, never 0
                out["metrics"][m["name"]] = {"value": value,
                                             "unit": m["unit"]}
        device.update(busy_s=trace.busy_s, window_s=trace.window_s)
        out["breakdown"] = {"device_ops": trace.top_ops(),
                            "idle_gaps": trace.idle_gaps()}
        log("[trace]", {"devices": len(trace.devices),
                        "window_s": trace.window_s, "busy_s": trace.busy_s,
                        "bounds": ctx.get("notes", {})})
    out["device"] = device
    # what `correct` compared, each number beside its limit: the last key of
    # the line and the last lines of stderr, which the driver's record keeps
    out["compared"] = {k: {"value": v, "limit": limit}
                       for k, (v, limit) in res["compared"].items()}
    print(json.dumps(out), flush=True)
    for k, c in out["compared"].items():
        print(f"[compared] {k} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
