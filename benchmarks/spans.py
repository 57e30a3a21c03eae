#!/usr/bin/env python3
"""Readers over the program's own spans and scopes in a profiler trace.

The program annotates its host phases (`engine.tick.*`, `engine.prefill`,
`engine.idle`, `train.step*`, `input.*`, `http.write`: the catalogue is
`paddle_tpu/observability/trace.py` SPANS) and names its kernels and blocks
(`name=` on every Pallas call, `jax.named_scope` per block). A capture holds
the spans on the host threads' lines, on the device ops' clock. The readers
here index them **by name** (a `#k=v#` suffix stripped) and never walk the
Python tracer's frames, so the 4,000-event look-back of `reduce._HostLine`
cannot hide them.

    span_share(span)              summed duration of the span / traced window, %
    span_ms_p50(span, minus=[..]) median duration, less the named children, ms
    idle_under(span, outside)     the first device's idle gaps of 20 us or more
                                  whose middle lies under that span on the
                                  thread that owns `engine.tick` / `train.step`
                                  (`outside`: under no such span) / window, %
    scope_share(scope)            self time of device ops whose scope path
                                  holds the name / busy, %

Each takes the run's context like the readers of `reduce.py` and returns a
number or None (a parent commit has no such span: nothing to read, nothing
raised). `span` and `outside` are regular expressions matched against whole
names. `run.py` joins them with `reduce.py`'s readers (`run.READERS`), so a
metric file names them like any other, and loads every trace through
`load_xplane` here, so `ctx["scopes"]` always holds each op's scope path. By
hand over a kept trace:

    python3 benchmarks/spans.py <trace.xplane.pb[.gz]>

which prints the spans by name, the idle gaps by phase, how much of the
idle time a handler thread spent in `http.write`, and the device's busy time
by scope. `scope_share` needs each op's scope path. The profiler keeps it as
the stat `tf_op` of the instruction's event metadata, which neither
`reduce.load_xplane` nor `jax.profiler.ProfileData` hands out: `metadata_stat`
here reads it from the file's bytes.
"""
from __future__ import annotations

import bisect
import gzip
import os
import re
import statistics
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce  # noqa: E402

# a program span: dotted lower-case words, never a Python frame
# (`$paged.py:2031 _admit`), a runtime event (`PjitFunction(step)`) or a
# CPU thunk (`wrapped_convert.1`)
_SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
OWNED = ("engine.tick", "train.step")     # the spans that name the owner
LEAST_GAP_NS = 20_000
# parts of a scope path that are the program's control flow, not a block
CONTROL_FLOW = {"while", "body", "cond", "scan", "closed_call"}


def bare(name):
    """`train.step#step_num=7,_r=1#` -> `train.step`."""
    return name.split("#", 1)[0]


class Spans:
    """The program's spans of a capture: `by_name[name]` is a start-ordered
    list of (start_ns, end_ns, thread), a thread being the index of its
    host line."""

    def __init__(self, host_lines):
        self.by_name = {}
        for thread, line in enumerate(host_lines):
            for name, start, dur in line["events"]:
                name = bare(name)
                if _SPAN_NAME.match(name):
                    self.by_name.setdefault(name, []).append(
                        (start, start + dur, thread))
        for evs in self.by_name.values():
            evs.sort()

    def matching(self, pattern, thread=None):
        pat = re.compile(pattern)
        return sorted(e for name, evs in self.by_name.items()
                      if pat.fullmatch(name) for e in evs
                      if thread is None or e[2] == thread)

    def until_next(self, pattern, thread):
        """The spans matching pattern on the thread, each drawn out to the
        start of the next span of its own depth there (`engine.tick.accept`
        up to the next tick's `engine.tick.retire`): the phases of a loop
        then share out all of its time, the moments between two of them
        going to the one that came before."""
        pat = re.compile(pattern)
        out = []
        for depth in {n.count(".") for n in self.by_name if pat.fullmatch(n)}:
            level = sorted((s, e, n) for n, evs in self.by_name.items()
                           if n.count(".") == depth
                           for s, e, t in evs if t == thread)
            for (s, e, n), nxt in zip(level, level[1:] + [None]):
                if pat.fullmatch(n):
                    out.append((s, max(e, nxt[0]) if nxt else e, thread))
        return sorted(out)

    def owner(self):
        """The thread with the most `engine.tick` / `train.step` spans."""
        count = {}
        for name in OWNED:
            for _s, _e, thread in self.by_name.get(name, ()):
                count[thread] = count.get(thread, 0) + 1
        return max(count, key=count.get) if count else None


def _spans(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    if "spans" not in ctx:
        ctx["spans"] = Spans(tr.host_lines)
    return ctx["spans"]


def _cover(intervals):
    return reduce.union((s, e) for s, e, *_ in intervals)


def span_share(ctx, span):
    """Time under the span (the union over its instances and threads, cut
    to the traced window) over the traced window, %."""
    sp = _spans(ctx)
    if sp is None or not ctx["trace"].window_s:
        return None
    hit = sp.matching(span)
    if not hit:
        return None
    tr = ctx["trace"]
    outside = reduce.subtract(_cover(hit), [(tr.start, tr.end)])
    inside = reduce.total(_cover(hit)) - reduce.total(outside)
    return 100.0 * inside / (tr.end - tr.start)


def span_ms_p50(ctx, span, minus=()):
    """Median duration of the span's instances, each less the time that
    spans matching `minus` cover inside it on its own thread, ms."""
    sp = _spans(ctx)
    if sp is None:
        return None
    hit = sp.matching(span)
    if not hit:
        return None
    kids = {}
    for pattern in minus:
        for s, e, thread in sp.matching(pattern):
            kids.setdefault(thread, []).append((s, e))
    kids = {t: reduce.union(v) for t, v in kids.items()}
    vals = []
    for s, e, thread in hit:
        left = reduce.subtract([(s, e)], kids.get(thread, []))
        vals.append(reduce.total(left))
    return statistics.median(vals) / 1e6


def idle_gaps(trace, least_ns=LEAST_GAP_NS):
    """The first device's idle gaps of at least `least_ns` inside the
    traced window: [(start, end)]."""
    if not trace.devices:
        return []
    gaps = reduce.subtract([(trace.start, trace.end)],
                           trace.devices[0].busy)
    return [(s, e) for s, e in gaps if e - s >= least_ns]


def _under(cover, t):
    i = bisect.bisect_right(cover, (t, float("inf"))) - 1
    return i >= 0 and cover[i][0] <= t <= cover[i][1]


def idle_under(ctx, span=None, outside=None, until_next=False):
    """The idle gaps whose middle lies under a span matching `span` on the
    owner's thread, and under none matching `outside` there, over the
    traced window, %. With `until_next` a span lasts until the next of its
    depth begins (`Spans.until_next`): a thread that loses the interpreter
    lock between two phases is then counted to the phase it came from.
    With no span of the owner in the trace: None."""
    sp = _spans(ctx)
    if sp is None or not ctx["trace"].window_s:
        return None
    thread = sp.owner()
    if thread is None:
        return None
    find = sp.until_next if until_next else sp.matching
    inside = _cover(find(span, thread)) if span else None
    without = _cover(sp.matching(outside, thread)) if outside else None
    tr = ctx["trace"]
    took = 0.0
    for s, e in idle_gaps(tr):
        mid = (s + e) / 2
        if inside is not None and not _under(inside, mid):
            continue
        if without is not None and _under(without, mid):
            continue
        took += e - s
    return 100.0 * took / (tr.end - tr.start)


def scope_share(ctx, scope):
    """Self time of the device ops whose scope path holds `scope` as one
    of its parts (`jit(step)/transpose(jvp(attn))/qkv/dot_general` holds
    attn and qkv) over busy time, %, the first device's. Needs
    `ctx["scopes"]`, what `op_scopes` gives for a trace loaded here."""
    scopes = ctx.get("scopes")
    tr = ctx["trace"]
    if not scopes or tr is None or not tr.devices:
        return None
    busy = reduce.total(tr.devices[0].busy)
    pat = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
    took = sum(sd for path, sd in scopes if pat.search(path))
    return 100.0 * took / busy if busy and took else None


READERS = {f.__name__: f for f in (span_share, span_ms_p50, idle_under,
                                   scope_share)}


# -- reading a trace with what reduce.load_xplane drops -------------------

def _fields(buf):
    """(field number, wire type, value) of one protobuf message: a varint's
    value, or the bytes of a length-delimited field; fixed-width fields are
    skipped. Enough of the wire format to reach an xplane's metadata, which
    `jax.profiler.ProfileData` does not hand out."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            byte = buf[i]
            i += 1
            key |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                break
        field, wire = key >> 3, key & 7
        if wire == 0:
            val = shift = 0
            while True:
                byte = buf[i]
                i += 1
                val |= (byte & 0x7F) << shift
                shift += 7
                if byte < 0x80:
                    break
            yield field, wire, val
        elif wire == 2:
            size = shift = 0
            while True:
                byte = buf[i]
                i += 1
                size |= (byte & 0x7F) << shift
                shift += 7
                if byte < 0x80:
                    break
            yield field, wire, buf[i:i + size]
            i += size
        else:
            i += 8 if wire == 1 else 4


def metadata_stat(raw, stat="tf_op", plane_prefix="/device:"):
    """{event name: the stat's text} from the event metadata of the first
    plane whose name starts with `plane_prefix` and that holds the stat:
    the profiler keeps an HLO op's `op_name` (its scope path,
    `jit(step)/transpose(jvp(attn))/qkv/dot_general:`) there as `tf_op`,
    once for an instruction and not on its events. Field numbers are those
    of tsl's xplane.proto."""
    for f, _w, plane in _fields(memoryview(raw)):
        if f != 1:                                  # XSpace.planes
            continue
        name, metas, stat_id = "", [], None
        for f2, _w2, v in _fields(plane):
            if f2 == 2:                             # XPlane.name
                name = bytes(v).decode()
            elif f2 == 4:                           # event_metadata entry
                metas.append(v)
            elif f2 == 5:                           # stat_metadata entry
                sid, sname = None, None
                for f3, _w3, v3 in _fields(v):
                    if f3 == 2:                     # the entry's value
                        for f4, _w4, v4 in _fields(v3):
                            if f4 == 1:
                                sid = v4
                            elif f4 == 2:
                                sname = bytes(v4).decode()
                if sname == stat:
                    stat_id = sid
        if not name.startswith(plane_prefix) or stat_id is None:
            continue
        out = {}
        for entry in metas:
            for f3, _w3, md in _fields(entry):
                if f3 != 2:                         # the entry's value
                    continue
                ev_name, text = None, None
                for f4, _w4, v4 in _fields(md):
                    if f4 == 2:                     # XEventMetadata.name
                        ev_name = bytes(v4).decode(errors="replace")
                    elif f4 == 5:                   # XEventMetadata.stats
                        mid, val = None, None
                        for f5, _w5, v5 in _fields(v4):
                            if f5 == 1:
                                mid = v5
                            elif f5 == 5:           # XStat.str_value
                                val = bytes(v5).decode(errors="replace")
                        if mid == stat_id and val:
                            text = val
                if ev_name and text:
                    out[ev_name] = text.rstrip(":")
        if out:
            return out
    return {}


def load_xplane(path):
    """(planes, scoped): the planes as `reduce.load_xplane` gives them, and
    for the first device's `XLA Ops` line a list of (scope path, name,
    start, duration) of the ops whose instruction has one (`metadata_stat`);
    an empty list where none has."""
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f, tempfile.NamedTemporaryFile(
                suffix=".xplane.pb") as tmp:
            tmp.write(f.read())
            tmp.flush()
            return load_xplane(tmp.name)
    with open(path, "rb") as f:
        path_of = metadata_stat(f.read())
    planes = reduce.load_xplane(path)
    ops = next((line["events"] for plane in planes
                if plane["name"].startswith("/device:")
                for line in plane["lines"]
                if line["name"] == reduce.OPS_LINE and line["events"]), [])
    return planes, [(path_of[e[0]], *e) for e in ops if e[0] in path_of]


def op_scopes(scoped):
    """[(scope path, self ns)] of the ops `load_xplane` found a path for."""
    path_of = {(n, s, d): p for p, n, s, d in scoped}
    return [(path_of[(n, s, d)], sd) for n, s, d, sd in
            reduce.self_times([(n, s, d) for _p, n, s, d in scoped])]


def scope_parts(path):
    """`jit(step)/transpose(jvp(attn))/qkv/dot_general` -> [attn, qkv]:
    the named scopes of a path, without the program, the transforms'
    wrappers, control flow (`while/body`) and the op itself."""
    parts = path.split("/")[1:-1]
    out = []
    for p in parts:
        while True:
            m = re.fullmatch(r"\w+\((.*)\)", p)
            if not m:
                break
            p = m.group(1)
        if p and p not in CONTROL_FLOW:
            out.append(p)
    return out


def report(planes, scoped, out=print):
    """What `python3 benchmarks/spans.py <trace>` prints."""
    trace = reduce.Trace(planes)
    ctx = {"trace": trace, "scopes": op_scopes(scoped)}
    sp = _spans(ctx)
    if not trace.devices:
        # a CPU capture has no device plane: the window is the spans'
        every = [e for evs in sp.by_name.values() for e in evs]
        if not every:
            out("no device plane and no program span in this trace")
            return
        trace.start = min(s for s, _e, _t in every)
        trace.end = max(e for _s, e, _t in every)
        out("no device plane (a CPU capture): shares are of the spans' "
            "own extent, and there are no idle gaps")
    window = trace.end - trace.start
    out(f"traced window {window / 1e6:.1f} ms, busy "
        f"{trace.busy_s * 1e3:.1f} ms, idle "
        f"{100 * (1 - trace.busy_s * 1e9 / window):.2f} %")
    out("span | n | sum ms | p50 ms | max ms | share of window %")
    for name in sorted(sp.by_name):
        durs = [(e - s) / 1e6 for s, e, _t in sp.by_name[name]]
        out(f"{name} | {len(durs)} | {sum(durs):.2f} | "
            f"{statistics.median(durs):.3f} | {max(durs):.3f} | "
            f"{span_share(ctx, re.escape(name)):.3f}")
    thread = sp.owner()
    if thread is None:
        out("no engine.tick / train.step span: idle gaps not attributed")
    else:
        gaps = idle_gaps(trace)
        named = sorted(n for n in sp.by_name
                       if any(t == thread for _s, _e, t in sp.by_name[n]))
        covers = {n: _cover(sp.matching(re.escape(n), thread))
                  for n in named}
        after = {n: _cover(sp.until_next(re.escape(n), thread))
                 for n in named}
        owned = sorted(e for n in OWNED for e in sp.by_name.get(n, ())
                       if e[2] == thread)
        acc = {}
        for s, e in gaps:
            mid = (s + e) / 2
            under = [n for n in named if _under(covers[n], mid)]
            # the innermost: the longest name of a dotted family
            key = max(under, key=len) if under else "under no span"
            # under a parent and none of its children: after which child
            was = [n for n in named if n.startswith(key + ".")
                   and n.count(".") == key.count(".") + 1
                   and _under(after[n], mid)]
            if was:
                key += " after " + was[0]
            elif not under and owned and owned[0][0] < mid < owned[-1][1]:
                key = "between two " + "/".join(
                    n for n in OWNED if n in sp.by_name)
            acc[key] = acc.get(key, 0.0) + (e - s)
        out(f"idle gaps of {LEAST_GAP_NS / 1e3:.0f} us or more: "
            f"{len(gaps)}, {sum(e - s for s, e in gaps) / 1e6:.2f} ms; "
            f"by the innermost span on the owner's thread:")
        for key, ns in sorted(acc.items(), key=lambda kv: -kv[1]):
            out(f"  {key} | {ns / 1e6:.2f} ms | "
                f"{100 * ns / window:.3f} % of the window")
        writes = _cover(e for e in sp.by_name.get("http.write", ())
                        if e[2] != thread)
        if writes and gaps:
            left = reduce.total(reduce.subtract(reduce.union(gaps), writes))
            idle = sum(e - s for s, e in gaps)
            out(f"http.write on other threads covers "
                f"{100 * (idle - left) / idle:.1f} % of that idle time "
                f"({reduce.total(writes) / 1e6:.1f} ms of writes in all)")
    if not ctx["scopes"]:
        out("no device op carries a scope path (the stat tf_op of its "
            "event metadata)")
        return
    busy = reduce.total(trace.devices[0].busy)
    acc = {}
    for path, sd in ctx["scopes"]:
        parts = scope_parts(path)
        key = "/".join(parts[:3]) if parts else "(no scope)"
        acc[key] = acc.get(key, 0.0) + sd
    # compiler-made ops (copies, slices) have no instruction metadata
    acc["(no path)"] = busy - sum(acc.values())
    out("scope (three levels) | self ms | share of busy %")
    for key, ns in sorted(acc.items(), key=lambda kv: -kv[1])[:24]:
        out(f"  {key} | {ns / 1e6:.2f} | {100 * ns / busy:.2f}")


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__.split("\n\n")[-2])
    report(*load_xplane(sys.argv[1]))
