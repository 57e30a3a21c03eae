"""The benchmark's own tests (`benchmarks/tests`) under the tier-1
command, which collects `tests/` only: every test function and fixture of
every `benchmarks/tests/test_*.py` is collected here under its own name, so
each counts and each failure names its test. (By hand they still run as
`JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`.)
"""
import glob
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _is_fixture(obj):
    return hasattr(obj, "_pytestfixturefunction") \
        or type(obj).__name__ == "FixtureFunctionDefinition"


COLLECTED = {}
for _path in sorted(glob.glob(os.path.join(ROOT, "benchmarks", "tests",
                                           "test_*.py"))):
    _name = os.path.splitext(os.path.basename(_path))[0]
    _module = importlib.import_module("benchmarks.tests." + _name)
    for _attr, _obj in vars(_module).items():
        if (_attr.startswith("test") and callable(_obj)) \
                or _is_fixture(_obj):
            # two files' tests of one name would shadow each other here
            assert _attr not in COLLECTED, (_attr, _name, COLLECTED[_attr])
            COLLECTED[_attr] = _name
            globals()[_attr] = _obj


def test_the_benchmarks_tests_are_collected_here():
    files = {os.path.splitext(os.path.basename(p))[0] for p in glob.glob(
        os.path.join(ROOT, "benchmarks", "tests", "test_*.py"))}
    assert files and set(COLLECTED.values()) == files
    assert sum(1 for n in COLLECTED if n.startswith("test")) >= 48


def test_tick_chained_share_reads_the_counter_or_nothing():
    """ISSUE 32's metric is data alone: `counter_ratio` reads it over a
    window that holds `ticks_chained`, and over one that does not (a
    parent's engine) it reads nothing, which `run.py` leaves out of the
    line, never 0. Its entry is the last of `per_layer` and lists both
    serve cells. (Here and not under `benchmarks/tests`: the issue allows
    that directory's owner, the benchmark, two additions only.)"""
    import json

    import pytest

    from benchmarks import reduce, run
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "sched.tick_chained_share")
    # every serve cell: a later cell is appended to the list (PR 33)
    assert entry["workloads"] == [
        w["name"] for w in bench["workloads"] if w["name"].startswith("serve.")]
    assert entry["workloads"][:2] == ["serve.smollm2-1.7b.batch-decode",
                                      "serve.keye-vl-2.0-30b-a3b.doc-qa"]
    assert entry["moves"] == "serve_tokens_per_s" \
        and entry["better"] == "higher" and entry["layer"] == "scheduler"
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           entry["name"] + ".json")) as f:
        spec = json.load(f)
    reader = run.READERS[spec["reader"]]
    assert reader is reduce.counter_ratio
    has = {"window": {"ticks": 640, "ticks_chained": 560}, "sizes": {}}
    assert reader(has, **spec["args"]) == pytest.approx(87.5)
    never = {"window": {"ticks": 640, "ticks_chained": 0}, "sizes": {}}
    assert reader(never, **spec["args"]) == 0.0
    parents = {"window": {"ticks": 640}, "sizes": {}}
    assert reader(parents, **spec["args"]) is None
    for cell in entry["workloads"]:
        assert entry["name"] in {m["name"] for m in run.load_cell(
            cell, rehearse=False)["metrics"]}


# ISSUE 34's two per-layer metrics (in BENCHMARK.json since PR 36, over
# every serve cell): the engine's counters under the names the metric files
# read, and their reader, over a hand-built window of 40 prompts: 10 alone,
# 12 in six pairs split into calls at width 1, 18 short tails in three
# groups padded to 16 rows.
_PREFILL_WINDOW = {"prefills": 40, "prefill_rows_run": 10 + 12 + 3 * 16,
                   "prefill_rows_padded": 3 * 16 - 18,
                   "prefill_rows_split": 12}
_PREFILL_ROW_METRICS = {
    "sched.prefill_pad_row_share": (
        {"num": ["prefill_rows_padded"], "den": ["prefill_rows_run"]},
        100.0 * 30 / 70),
    "sched.prefill_split_share": (
        {"num": ["prefill_rows_split"], "den": ["prefills"]}, 30.0)}


# The two metrics over the serve host path's counters: the ticker's
# host phases off a CPU over the window, and of the requests' time in the
# engine the share before a slot. `window_s` is the window's own length,
# every other counter a key of engine.stats.
_HOST_PATH_WINDOW = {"window_s": 50.0, "tick_host_s": 12.0,
                     "tick_host_wait_s": 5.0, "queue_wait_s": 30.0,
                     "request_s": 600.0}
_HOST_PATH_METRICS = {
    "sched.tick_host_wait_share": (
        {"num": ["tick_host_wait_s"], "den": ["window_s"]}, 10.0),
    "sched.queue_wait_share": (
        {"num": ["queue_wait_s"], "den": ["request_s"]}, 5.0)}


def _check_counter_metric(name, metrics, window):
    """`name`'s file reads `counter_ratio` over the engine's counters: a
    window that holds them gives `value`, one whose numerator is 0 reads
    0, and a parent's engine, which counts neither, reads nothing. Its
    entry lists every serve cell."""
    import json

    import pytest

    from benchmarks import reduce, run
    from paddle_tpu.inference import paged
    args, value = metrics[name]
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert (spec["reader"], spec["args"]) == ("counter_ratio", args)
    src = open(paged.__file__).read()
    for counter in args["num"] + args["den"]:
        if counter != "window_s":
            assert f'"{counter}": 0' in src     # a key of engine.stats
    reader = run.READERS["counter_ratio"]
    assert reader is reduce.counter_ratio
    has = {"window": dict(window), "sizes": {}}
    assert reader(has, **args) == pytest.approx(value)
    none = {"window": dict(window, **{args["num"][0]: 0}), "sizes": {}}
    assert reader(none, **args) == 0.0
    # `run.py` leaves a metric that reads nothing out of the line
    parents = {"window": {"prefills": 40, "ticks": 640, "window_s": 50.0},
               "sizes": {}}
    assert reader(parents, **args) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [
        w["name"] for w in bench["workloads"] if w["name"].startswith("serve.")]
    assert (entry["layer"], entry["moves"]) \
        == ("scheduler", "serve_tokens_per_s")


def _check_prefill_row_metric(name):
    _check_counter_metric(name, _PREFILL_ROW_METRICS, _PREFILL_WINDOW)


def test_prefill_pad_row_share_reads_the_counters_or_nothing():
    """Of the rows the window's prefill calls computed, the share that
    was padding."""
    _check_prefill_row_metric("sched.prefill_pad_row_share")


def test_prefill_split_share_reads_the_counters_or_nothing():
    """Of the window's prompts, the share that met others of their
    bucket and ran at width 1."""
    _check_prefill_row_metric("sched.prefill_split_share")


def test_tick_host_wait_share_reads_the_counter_or_nothing():
    """Of the window, the time the ticker spent in its host phases
    without a CPU."""
    _check_counter_metric("sched.tick_host_wait_share", _HOST_PATH_METRICS,
                          _HOST_PATH_WINDOW)


def test_queue_wait_share_reads_the_counters_or_nothing():
    """Of the time requests spent in the engine, the share before they
    held a slot."""
    _check_counter_metric("sched.queue_wait_share", _HOST_PATH_METRICS,
                          _HOST_PATH_WINDOW)
