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
