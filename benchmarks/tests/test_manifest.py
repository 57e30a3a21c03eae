"""BENCHMARK.json and the data files under benchmarks/ against the contract,
and a CPU rehearsal of run.py for each kind of traffic.

Run by hand: JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return load(ROOT, "BENCHMARK.json")


def metric_files():
    return [load(BENCH, "metrics", fn)
            for fn in sorted(os.listdir(os.path.join(BENCH, "metrics")))]


def line_ok(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert m["source"] in SOURCES, m
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        # `workloads` too: run.py reads a metric where its entry lists the cell
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert line_ok(m["layer"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line_ok(w["why"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])


def test_every_cell_finds_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    assert len({c["file"] for c in configs.values()}) == len(configs)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    for w in bench["workloads"]:
        conf = configs[w["config"]]
        assert conf["file"].startswith("benchmarks/")
        cfg = load(ROOT, conf["file"])
        assert cfg["source"] == conf["source"] and len(cfg["source"]) <= 200
        assert sorted(cfg["reduced"]) == sorted(conf["reduced"])
        assert os.path.exists(os.path.join(
            BENCH, "builders", cfg["builder"] + ".py"))
        traffic = load(BENCH, "traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(BENCH, traffic["kind"] + ".py"))


def test_a_cut_never_names_a_width(bench):
    width = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$"
                       r"|_rank$|head_dim|expansion|experts_per_tok")
    for c in bench["configs"]:
        assert not [k for k in c["reduced"] if width.search(k)]


def test_at_most_a_quarter_of_the_cells_take_four_chips(bench):
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)


def cells_of(metric, bench):
    return metric.get("workloads") or [w["name"] for w in bench["workloads"]]


def test_moves_is_reported_wherever_the_metric_is(bench):
    end = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in end and "workloads" not in end["setup_s"]
    assert end["setup_s"]["bound"] <= 0.1
    known = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in end and m["moves"] != "setup_s", m
        for cell in cells_of(m, bench):
            assert cell in known
            assert cell in cells_of(end[m["moves"]], bench), (m["name"], cell)
    for w in bench["workloads"]:
        mine = [m for m in bench["end_to_end"]
                if w["name"] in cells_of(m, bench)]
        assert len(mine) >= 2, w["name"]          # setup_s and one more
        assert any(w["name"] in cells_of(m, bench)
                   for m in bench["per_layer"]), w["name"]


def test_metric_files_agree_with_the_manifest(bench):
    """A metric is read in a cell when its file's `kinds` hold the cell's
    kind and its entry under `per_layer` lists the cell (run.py:load_cell):
    so every file has an entry, every entry a file, the two say the same,
    and an entry lists only cells that exist and are of the file's kinds."""
    from benchmarks import run
    kinds = {w["name"]: load(BENCH, "traffic", w["traffic"] + ".json")["kind"]
             for w in bench["workloads"]}
    listed = {m["name"]: m for m in bench["per_layer"]}
    files = metric_files()
    assert sorted(m["name"] for m in files) == sorted(listed)
    for m in files:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert callable(run.READERS[m["reader"]]), m["name"]
        assert m["source"] in SOURCES
        entry = listed[m["name"]]
        cells = cells_of(entry, bench)
        assert cells and set(cells) <= set(kinds), m["name"]
        assert all(kinds[c] in m["kinds"] for c in cells), m["name"]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entry[key] == m[key], (m["name"], key)


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  load(ROOT, "BENCHMARK.json")["workloads"]])
def test_a_cell_reads_the_metrics_that_list_it(bench, cell):
    from benchmarks import run
    got = {m["name"] for m in run.load_cell(cell, rehearse=False)["metrics"]}
    assert got == {m["name"] for m in bench["per_layer"]
                   if cell in cells_of(m, bench)}
    assert got


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  load(ROOT, "BENCHMARK.json")["workloads"]])
def test_what_a_metric_asks_of_a_cells_builder_is_there(cell):
    """A metric file names sizes (`{slots}` in a pattern, `steps_per_module`)
    and, for a roofline, a cost function. Each has to be there in the builder
    of every cell the entry lists: a missing cost raises KeyError in the
    traced run on the chip, and a size that is not substituted leaves a
    pattern that matches nothing, so the metric is absent where the driver
    expects it. Neither shows in a rehearsal, which holds nothing against a
    peak and has no device trace."""
    from benchmarks import costs, run
    found = run.load_cell(cell, rehearse=False)
    sizes = found["builder"].sizes(found["config"], found["traffic"])
    own = getattr(getattr(found["builder"], "costs", None), "KERNEL_COSTS", {})

    def strings(value):
        if isinstance(value, dict):
            for v in value.values():
                yield from strings(v)
        elif isinstance(value, str):
            yield value
    for m in found["metrics"]:
        args = m.get("args", {})
        for text in strings({k: v for k, v in args.items()
                             if k in ("pattern", "exclude", "module",
                                      "holds", "lacks")}):
            named = [n for n in re.findall(r"\{(\w+)\}", text)
                     if not n.isdigit()]
            assert set(named) <= set(sizes), (m["name"], text)
            re.compile(text)
        if m["reader"] == "roofline":
            assert args["cost"] in own or args["cost"] in costs.KERNEL_COSTS, \
                (m["name"], args["cost"])
            steps = args.get("steps_per_module", 1)
            assert steps in sizes or isinstance(steps, int), m["name"]


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_cpu_rehearsal_prints_the_contract(bench, kind):
    cell = next(w for w in bench["workloads"]
                if load(BENCH, "traffic", w["traffic"] + ".json")["kind"] == kind
                and w["chips"] == 1)
    for trace in (0, 1):
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
             cell["name"], "--seed", "3000000007", "--seconds", "2",
             "--trace", str(trace), "--rehearse"],
            cwd=ROOT, capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert set(out) == RESULT_KEYS | ({"breakdown"} if trace else set())
        # what `correct` compared, each number beside its limit: the
        # line's last key and the last lines of stderr
        assert list(out)[-1] == "compared" and out["compared"]
        assert all(set(c) == {"value", "limit"}
                   for c in out["compared"].values())
        said = proc.stderr.strip().splitlines()[-len(out["compared"]):]
        assert [ln.split()[:2] for ln in said] \
            == [["[compared]", k] for k in out["compared"]]
        assert out["correct"] is True and out["failed"] == 0
        assert out["attempted"] > 0
        assert out["device"]["platform"] == "cpu"      # never a measurement
        want = {"platform", "kind", "count", "memory_peak_bytes"}
        assert set(out["device"]) == want | (
            {"busy_s", "window_s"} if trace else set())
        declared = bench["per_layer"] if trace else bench["end_to_end"]
        mine = {m["name"] for m in declared
                if cell["name"] in cells_of(m, bench)}
        assert set(out["metrics"]) <= mine
        if not trace:
            assert set(out["metrics"]) == mine
        for m in out["metrics"].values():
            assert set(m) == {"value", "unit"} and UNIT.match(m["unit"])


def test_no_tpu_and_no_rehearsal_is_a_failure(bench):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert not proc.stdout.strip()
