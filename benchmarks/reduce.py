"""From a profiler trace, counters and host clocks to per-layer metrics.

A trace is the `.xplane.pb` jax's profiler writes. `load_xplane` turns it
into plain lists (`planes -> lines -> (name, start_ns, duration_ns)`), and
`Trace` keeps, for every device plane, the `XLA Ops` and `XLA Modules`
lines. Ops may nest (a `while` holds the ops of its body), so every share
is taken over *self* time: an op's duration less what its children cover.

The readers at the bottom, with the four of `spans.py` over the program's
own spans and scopes (`run.READERS` joins them), are the whole vocabulary of
`metrics/*.json`. Each takes the run's context and the arguments its file gives, and returns
a number or None; None leaves the metric out of the result line. A pattern
is a regular expression over the event text with layouts (`{1,0:T(8,128)}`)
stripped and the cell's sizes substituted for `{B}`, `{S}`, ... first.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import statistics

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_LAYOUT = re.compile(r"\{[^{}]*\}")


# -- xplane in and out --------------------------------------------------

def find_xplane(logdir):
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def load_xplane(path):
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = [{"name": line.name,
                  "events": [(e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def save_xplane(planes, path):
    """Write planes back as a real xplane (through its text form): how the
    recorded traces under `tests/data` were cut."""
    from jax.profiler import ProfileData
    out = []
    for pi, plane in enumerate(planes):
        ids = {}
        body = []
        for li, line in enumerate(plane["lines"]):
            evs = []
            for name, start, dur in line["events"]:
                mid = ids.setdefault(name, len(ids) + 1)
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{int(round(start * 1000))} duration_ps: "
                           f"{int(round(dur * 1000))} }}")
            body.append(f'lines {{ id: {li + 1} name: "{line["name"]}" '
                        f"timestamp_ns: 0 {' '.join(evs)} }}")
        for name, mid in ids.items():
            esc = name.replace("\\", "\\\\").replace('"', '\\"')
            body.append(f"event_metadata {{ key: {mid} value {{ id: {mid} "
                        f'name: "{esc}" }} }}')
        out.append(f'planes {{ id: {pi + 1} name: "{plane["name"]}" '
                   f"{' '.join(body)} }}")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace("\n".join(out)))


# -- interval arithmetic ------------------------------------------------

def union(intervals):
    """Sorted, disjoint cover of (start, end) pairs."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the disjoint cover `a` that the disjoint cover `b` leaves."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """[(name, start, duration, self_duration)] in start order: an event's
    self time is its duration less the time its direct children cover."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = [[n, s, d, d] for n, s, d in evs]
    stack = []
    for i, (_n, s, d) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= d
        stack.append(i)
    return [tuple(o) for o in out]


def strip_layouts(text):
    return _LAYOUT.sub("", text)


def substitute(pattern, sizes):
    """`{B}` -> the size B. Braces that name no size (regex counts) stay."""
    return re.sub(r"\{(\w+)\}",
                  lambda m: str(sizes[m.group(1)]) if m.group(1) in sizes
                  else m.group(0), pattern)


# -- the trace ----------------------------------------------------------

class DeviceTrace:
    def __init__(self, plane):
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        self.name = plane["name"]
        self.ops = [(strip_layouts(n), s, d, sd)
                    for n, s, d, sd in self_times(lines.get(OPS_LINE, []))]
        self.modules = sorted(lines.get(MODULES_LINE, []),
                              key=lambda e: e[1])
        self.busy = union((s, s + d) for _n, s, d, _sd in self.ops)

    def span(self):
        evs = [(s, s + d) for _n, s, d, _sd in self.ops]
        evs += [(s, s + d) for _n, s, d in self.modules]
        return (min(s for s, _ in evs), max(e for _, e in evs)) if evs \
            else None

    def matching(self, pattern=None, exclude=None):
        pat = re.compile(pattern) if pattern else None
        exc = re.compile(exclude) if exclude else None
        return [o for o in self.ops
                if (pat is None or pat.search(o[0]))
                and (exc is None or not exc.search(o[0]))]

    def select_modules(self, name=None, holds=None, lacks=None):
        """Modules by name and by what their interval holds: a tick and a
        prefill are both `jit_traced(<fingerprint>)`, and only the tick
        holds paged-attention custom calls."""
        pat = re.compile(name) if name else None
        marks = {}
        for key, p in (("holds", holds), ("lacks", lacks)):
            if p:
                marks[key] = sorted(s for _n, s, _d, _sd in self.matching(p))
        out = []
        for n, s, d in self.modules:
            if pat is not None and not pat.search(n):
                continue
            ok = True
            for key, starts in marks.items():
                i = bisect.bisect_left(starts, s)
                inside = i < len(starts) and starts[i] < s + d
                ok &= inside if key == "holds" else not inside
            if ok:
                out.append((n, s, d))
        return out


class Trace:
    def __init__(self, planes):
        self.devices = [DeviceTrace(p) for p in planes
                        if p["name"].startswith("/device:")
                        and any(ln["name"] == OPS_LINE and ln["events"]
                                for ln in p["lines"])]
        self.host_lines = [ln for p in planes
                           if p["name"].startswith("/host:")
                           for ln in p["lines"] if ln["events"]]
        spans = [d.span() for d in self.devices]
        self.start = min(s for s, _ in spans) if spans else 0.0
        self.end = max(e for _, e in spans) if spans else 0.0

    @property
    def window_s(self):
        return (self.end - self.start) / 1e9

    @property
    def busy_s(self):
        """Mean over the devices of the time an operation ran there."""
        if not self.devices:
            return 0.0
        return sum(total(d.busy) for d in self.devices) \
            / len(self.devices) / 1e9

    def mean_over_devices(self, fn):
        vals = [v for v in (fn(d) for d in self.devices) if v is not None]
        return sum(vals) / len(vals) if vals else None

    # -- what `breakdown` carries -----------------------------------
    def top_ops(self, n=10):
        """Self seconds by operation kind, summed over devices and divided
        by their number."""
        acc = {}
        for d in self.devices:
            for name, _s, _d, sd in d.ops:
                k = op_kind(name)
                acc[k] = acc.get(k, 0.0) + sd
        k = max(1, len(self.devices))
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / k / 1e9] for name, ns in top]

    def idle_gaps(self, n=10, least_ns=20_000):
        """The first device's idle gaps, named by the innermost host event
        over the middle of the gap: on the thread that launches the
        programs if it has one there, else on any thread."""
        if not self.devices:
            return []
        gaps = subtract([(self.start, self.end)], self.devices[0].busy)
        lines = [_HostLine(ln["events"]) for ln in self.host_lines]
        launcher = max(lines, key=lambda ln: ln.launches, default=None)
        acc, short = {}, 0.0
        for s, e in gaps:
            if e - s < least_ns:
                short += e - s
                continue
            mid = (s + e) / 2
            name = launcher.innermost(mid) if launcher else None
            if name is None:
                found = [f for f in (ln.innermost(mid, named=True)
                                     for ln in lines) if f]
                name = min(found, key=lambda f: f[1])[0] if found else None
            name = name or "no_host_event"
            acc[name] = acc.get(name, 0.0) + (e - s)
        if short:
            acc["gaps_under_20us"] = short
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name[:80], ns / 1e9] for name, ns in top]


class _HostLine:
    """One host thread's events, for `innermost(t)`: the event with the
    latest start that still covers t (events of a thread nest)."""

    def __init__(self, events, look_back=4000):
        self.events = sorted(events, key=lambda e: e[1])
        self.starts = [e[1] for e in self.events]
        self.look_back = look_back
        self.launches = sum(1 for n, _s, _d in events
                            if "Execute" in n or n.startswith("PjitFunction"))

    def innermost(self, t, named=False):
        i = bisect.bisect_right(self.starts, t)
        for n, s, d in reversed(self.events[max(0, i - self.look_back):i]):
            if s + d >= t:
                return (n, d) if named else n
        return None


def op_kind(text):
    """`%closed_call.243 = f32[16,32,8,64] custom-call(s32[16,64] %a, ...)`
    -> `custom-call(s32[16,64],bf16[...])`; any other op -> its name without
    the number, a fusion with its kind (`fusion(kOutput)` holds a matmul):
    kernels have no stable names yet, shapes tell them apart."""
    m = re.match(r"%?([\w\-]+?)(\.\d+)? = ", text)
    if " custom-call(" in text:
        args = text.split(" custom-call(", 1)[1]
        shapes = re.findall(r"\w+\[[\d,]*\]", args)[:3]
        return ("custom-call(" + ",".join(shapes) + ")")[:80]
    if m:
        kind = re.search(r"\bkind=(k\w+)", text)
        return (m.group(1) + (f"({kind.group(1)})" if kind else ""))[:80]
    return re.sub(r"\.\d+$", "", text.lstrip("%"))[:80]


# -- readers ------------------------------------------------------------
# ctx: {"trace": Trace | None, "sizes": {...}, "window": {...},
#       "config": {...}, "device_kind": str, "builder": the cell's builder
#       module, "scopes": [(scope path, self ns)] | None}

def _pat(ctx, pattern):
    return substitute(pattern, ctx["sizes"]) if pattern else None


def device_share(ctx, pattern=None, exclude=None):
    """Self time of the matching ops over the device's busy time, %."""
    tr = ctx["trace"]
    if tr is None:
        return None
    pattern, exclude = _pat(ctx, pattern), _pat(ctx, exclude)

    def one(dev):
        hit = dev.matching(pattern, exclude)
        busy = total(dev.busy)
        if not hit or not busy:
            return None
        return 100.0 * sum(o[3] for o in hit) / busy
    return tr.mean_over_devices(one)


def module_ms_p50(ctx, name=None, holds=None, lacks=None):
    """Median duration of the selected program on `XLA Modules`, ms."""
    tr = ctx["trace"]
    if tr is None:
        return None

    def one(dev):
        mods = dev.select_modules(name, _pat(ctx, holds), _pat(ctx, lacks))
        return statistics.median(d for _n, _s, d in mods) / 1e6 \
            if mods else None
    return tr.mean_over_devices(one)


def module_share(ctx, name=None, holds=None, lacks=None):
    """Device time of the selected programs over busy time, %."""
    tr = ctx["trace"]
    if tr is None:
        return None

    def one(dev):
        mods = dev.select_modules(name, _pat(ctx, holds), _pat(ctx, lacks))
        busy = total(dev.busy)
        if not mods or not busy:
            return None
        inside = union((s, s + d) for _n, s, d in mods)
        return 100.0 * (total(dev.busy) - total(subtract(dev.busy, inside))) \
            / busy
    return tr.mean_over_devices(one)


def _own_costs(ctx):
    """The yardstick the cell's builder brings for its family (`costs`: its
    `train_flops_per_token` and `KERNEL_COSTS`), if it brings one; what it
    leaves out is `costs.py`'s. Operations and bytes only: the peaks are
    `costs.py`'s alone."""
    return getattr(ctx.get("builder"), "costs", None)


def roofline(ctx, cost, module, pattern=None, steps_per_module=1):
    """Least time by shapes (the builder's `costs` or `costs.py`) over
    measured time, %: the median over the selected programs, so that one
    cut at the trace's edge does not move it. Measured is the matching ops'
    self time inside the program or, with no pattern, the program's own
    duration."""
    tr = ctx["trace"]
    if tr is None or ctx["device_kind"] is None:
        return None
    from benchmarks import costs
    least, bound = costs.least_seconds(
        cost, ctx["config"], ctx["sizes"], ctx["window"], ctx["device_kind"],
        own=_own_costs(ctx))
    steps = ctx["sizes"][steps_per_module] \
        if isinstance(steps_per_module, str) else steps_per_module
    pattern = _pat(ctx, pattern)

    def one(dev):
        mods = dev.select_modules(**{k: _pat(ctx, v)
                                     for k, v in module.items()})
        hits = dev.matching(pattern) if pattern else None
        vals = []
        for _n, s, d in mods:
            took = d if hits is None else sum(
                o[3] for o in hits if s <= o[1] < s + d)
            if took > 0:
                vals.append(100.0 * least * steps / (took / 1e9))
        return statistics.median(vals) if vals else None
    value = tr.mean_over_devices(one)
    if value is not None:
        ctx.setdefault("notes", {})[cost] = f"{bound}-bound"
    return value


def idle_share(ctx):
    """1 - busy / traced window, %, mean over the devices."""
    tr = ctx["trace"]
    if tr is None or not tr.window_s:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def host_clock(ctx, span):
    """Seconds the benchmark's own clock counted under `span`, over the
    window, %."""
    w = ctx["window"]
    if span not in w or not w.get("window_s"):
        return None
    return 100.0 * w[span] / w["window_s"]


def counter_ratio(ctx, num, den):
    """Product of the `num` names over the product of the `den` names, %;
    each name is a counter of the window or a size of the cell."""
    def prod(names):
        out = 1.0
        for n in names:
            v = ctx["window"].get(n, ctx["sizes"].get(n))
            if v is None:
                return None
            out *= v
        return out
    a, b = prod(num), prod(den)
    return 100.0 * a / b if a is not None and b else None


def window_value(ctx, key, scale=1.0):
    """A number the window's own bookkeeping holds, times `scale`."""
    v = ctx["window"].get(key)
    return None if v is None else v * scale


def stall_share(ctx):
    """1 - the window's rate over all its time / its median reading's
    rate, %: the time that stalls, hiccups and (serving) prefills took
    from a window that ran all through at its median pace."""
    w = ctx["window"]
    if not w.get("median_reading_rate"):
        return None
    return 100.0 * (1.0 - w["rate"] / w["median_reading_rate"])


def mfu(ctx):
    """FLOPs a token (the builder's `costs` or `costs.py`) x the window's
    tokens/s/chip over the chip's bf16 peak, %."""
    from benchmarks import costs
    if ctx["device_kind"] is None:
        return None
    return costs.mfu(ctx["config"], ctx["sizes"]["S"], ctx["window"]["rate"],
                     ctx["device_kind"], own=_own_costs(ctx))


READERS = {f.__name__: f for f in (
    device_share, module_ms_p50, module_share, roofline, idle_share,
    host_clock, counter_ratio, window_value, stall_share, mfu)}
