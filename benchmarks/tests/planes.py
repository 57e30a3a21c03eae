"""Hand-built device planes for the families' tests: a tick program of named
Pallas calls with known durations, in the event text a chip's trace holds,
and a roofline metric file read over it."""
import json
import os

import pytest

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def tick_of(calls, steps=4, prefill=()):
    """A device plane with one tick program of `steps` decode steps, each
    the `calls` in turn ((event text, ns) pairs), and after it a prefill
    program that holds the `prefill` calls: what the roofline files read."""
    ops, t = [], 1000.0
    for _ in range(steps):
        for text, ns in calls:
            ops.append((text, t, float(ns)))
            t += ns + 500.0
    modules = [("jit_traced(11)", 0.0, t)]
    start = t = t + 10_000.0
    for text, ns in prefill:
        ops.append((text, t, float(ns)))
        t += ns + 500.0
    if prefill:
        modules.append(("jit_traced(12)", start, t - start))
    return [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops}]}]


def kernel_call(name, number, out, operands):
    """The event text of a named Pallas call as a chip's trace holds it
    (my chip runs, PR 35): `out` the result's shapes, `operands` (shape,
    layout) pairs."""
    return (f"%{name}.{number} = {out} custom-call("
            + ", ".join(f"{shape}{{{layout}}} %arg.{i}" for i, (shape, layout)
                        in enumerate(operands)) + "), custom_call_target="
            '"tpu_custom_call"')


def roofline_file_reads(context, name, cost, ns_a_step):
    """The metric file `name` read over `context` is the least time of the
    builder's `cost` (memory-bound at these sizes) over `ns_a_step`, and
    nothing in a rehearsal, which holds nothing against a peak -> the file."""
    from benchmarks import costs, run
    with open(os.path.join(METRICS, name + ".json")) as f:
        m = json.load(f)
    assert (m["reader"], m["args"]["cost"], m["unit"], m["better"]) \
        == ("roofline", cost, "%", "higher")
    least, bound = costs.least_seconds(
        cost, context["config"], context["sizes"], context["window"],
        context["device_kind"], own=context["builder"].costs)
    assert bound == "memory"
    got = run.READERS[m["reader"]](context, **m["args"])
    assert got == pytest.approx(100 * least / (ns_a_step / 1e9))
    assert 10.0 < got < 100.0
    assert run.READERS[m["reader"]](
        dict(context, device_kind=None), **m["args"]) is None
    return m
