"""A second model family arrives as new files and appended entries only: in
a temporary copy of `benchmarks/` and `BENCHMARK.json` a scratch family (a
builder that wraps the dense model and offers its own `costs`, `counters`
and `rehearse`, a configuration, one metric listed for its cell alone) is
added without touching a file that is there, its cell runs, and the tests
of every other file of this directory pass in the copy, where the scratch
entries stand last in their lists.

Run by hand: JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import filecmp
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DENSE, SCRATCH = "train.smollm2-1.7b.s2048", "train.scratch.s2048"

BUILDER = '''"""A scratch family: the dense model under another yardstick."""
from benchmarks.builders.llama_dense import (  # noqa: F401
    build, flash_block_keys, reference, sizes)


class costs:
    """What the family's arithmetic counts; the peaks stay costs.py's."""

    @staticmethod
    def train_flops_per_token(cfg, seq):
        return 1.0e9 * cfg["num_hidden_layers"] + seq

    KERNEL_COSTS = {}


def counters(trainer):
    return {"scratch_optimizer_steps": trainer.optimizer._step_count}


def rehearse(cfg):
    return {"hidden_size": 32, "intermediate_size": 64,
            "num_hidden_layers": 1, "num_attention_heads": 2,
            "num_key_value_heads": 2, "vocab_size": 128,
            "max_position_embeddings": 512}
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The copy's root, with the scratch family added."""
    root = str(tmp_path_factory.mktemp("family"))
    bench = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(bench, "builders", "scratch_family.py"), "w") as f:
        f.write(BUILDER)
    with open(os.path.join(bench, "configs", "smollm2-1.7b-8l.json")) as f:
        cfg = json.load(f)
    cfg["builder"] = "scratch_family"
    with open(os.path.join(bench, "configs", "scratch-8l.json"), "w") as f:
        json.dump(cfg, f)
    metric = {"name": "scratch.steps_counted", "unit": "steps",
              "better": "higher", "layer": "step", "source": "program_counter",
              "moves": "train_tokens_per_s_per_chip", "kinds": ["train"],
              "reader": "window_value",
              "args": {"key": "scratch_optimizer_steps"}}
    with open(os.path.join(bench, "metrics", metric["name"] + ".json"),
              "w") as f:
        json.dump(metric, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "scratch-8l", "source": cfg["source"],
        "file": "benchmarks/configs/scratch-8l.json",
        "reduced": ["num_hidden_layers"], "why": "a scratch family"})
    manifest["workloads"].append({
        "name": SCRATCH, "config": "scratch-8l", "traffic": "pretrain-s2048",
        "chips": 1, "why": "the dense step under a family's own yardstick"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in ("train_tokens_per_s_per_chip", "step.mfu",
                         "input.wait_share"):
            m["workloads"].append(SCRATCH)
    manifest["per_layer"].append(
        {k: metric[k] for k in ("name", "unit", "better", "source", "layer",
                                "moves")} | {"workloads": [SCRATCH]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def in_copy(root, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.run([sys.executable, *argv], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_nothing_that_was_there_is_edited(copy):
    """New files and appended entries only."""
    def walk(a, b):
        cmp = filecmp.dircmp(a, b, ignore=["__pycache__", "tests"])
        assert not cmp.left_only and not cmp.diff_files, (a, cmp.diff_files)
        for sub in cmp.common_dirs:
            yield from walk(os.path.join(a, sub), os.path.join(b, sub))
        yield from (os.path.join(b, f) for f in cmp.right_only)
    added = sorted(os.path.relpath(p, copy) for p in walk(
        os.path.join(ROOT, "benchmarks"), os.path.join(copy, "benchmarks")))
    assert added == ["benchmarks/builders/scratch_family.py",
                     "benchmarks/configs/scratch-8l.json",
                     "benchmarks/metrics/scratch.steps_counted.json"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        old = json.load(f)
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        new = json.load(f)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(new[group]) >= len(old[group])
        for was, now in zip(old[group], new[group]):
            lists = {k for k in was if isinstance(was[k], list)}
            assert {k: v for k, v in was.items() if k not in lists} \
                == {k: v for k, v in now.items() if k not in lists}
            assert all(now[k][:len(was[k])] == was[k] for k in lists)


def test_appended_entries_leave_the_other_files_tests_passing(copy):
    """The seam, proven: a test of this directory that finds an entry of
    `BENCHMARK.json` by its position (`per_layer[-6:]`, `workloads[-1]`)
    passes in the PR that writes it and fails the next PR that appends, which
    may not edit it. So every other file's tests run here in the copy, where
    a later configuration, cell and metric already stand last; all but the
    rehearsals (`*rehearsal_prints*`: a minute each, and they read entries
    by the cell's name)."""
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert [manifest[g][-1]["name"]
            for g in ("configs", "workloads", "per_layer")] \
        == ["scratch-8l", SCRATCH, "scratch.steps_counted"]
    tests = os.path.join(copy, "benchmarks", "tests")
    shutil.copytree(os.path.join(ROOT, "benchmarks", "tests"), tests,
                    ignore=shutil.ignore_patterns("__pycache__"))
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", tests, "-v", "-p",
             "no:cacheprovider", "--ignore",
             os.path.join(tests, "test_family.py"), "-k",
             "not rehearsal_prints"], cwd=copy, capture_output=True,
            text=True, timeout=600,
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
    finally:
        shutil.rmtree(tests)
    assert proc.returncode == 0, proc.stdout[-4000:]
    # they read the copy's manifest, and as many ran as the directory has
    assert f"test_a_cell_reads_the_metrics_that_list_it[{SCRATCH}] PASSED" \
        in proc.stdout
    assert int(re.search(r"(\d+) passed", proc.stdout).group(1)) >= 75


def test_each_cell_reads_what_lists_it_and_rehearses_at_its_own_sizes(copy):
    code = ("import json; from benchmarks import run; "
            f"cells = [run.load_cell(n, True) for n in ({DENSE!r}, {SCRATCH!r})]; "
            "print(json.dumps([{'metrics': [m['name'] for m in c['metrics']], "
            "'config': c['config'], 'file': run.__file__} for c in cells]))")
    dense, scratch = in_copy(copy, "-c", code)
    assert scratch["file"].startswith(copy)
    # a metric listed for one of two cells of a kind is read in that one only
    assert scratch["metrics"] == ["input.wait_share", "scratch.steps_counted",
                                  "step.mfu"]
    assert "scratch.steps_counted" not in dense["metrics"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = [m["name"] for m in json.load(f)["per_layer"]
                  if DENSE in m["workloads"]]
    assert dense["metrics"] == sorted(listed)
    # the family's own rehearsal sizes, and rehearse.json's for the dense one
    assert (scratch["config"]["hidden_size"],
            scratch["config"]["num_hidden_layers"]) == (32, 1)
    with open(os.path.join(ROOT, "benchmarks", "rehearse.json")) as f:
        small = json.load(f)["config"]
    assert {k: dense["config"][k] for k in small} == small


def test_the_scratch_cell_prints_its_own_metric_from_its_counters(copy):
    out = in_copy(copy, os.path.join(copy, "benchmarks", "run.py"),
                  "--workload", SCRATCH, "--seed", "3000000019", "--seconds",
                  "2", "--trace", "1", "--rehearse")
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    # the difference of the builder's counter over the window: the steps
    # the window dispatched, whole readings and the traced steps
    counted = out["metrics"]["scratch.steps_counted"]
    assert counted["unit"] == "steps"
    assert counted["value"] >= out["attempted"] > 0
    assert counted["value"] == int(counted["value"])
    # held against no peak in a rehearsal, so step.mfu is left out
    assert set(out["metrics"]) == {"scratch.steps_counted", "input.wait_share"}


def test_the_scratch_cells_mfu_is_its_builders_arithmetic(copy):
    from benchmarks import costs, reduce
    spec = importlib.util.spec_from_file_location(
        "scratch_family", os.path.join(copy, "benchmarks", "builders",
                                       "scratch_family.py"))
    family = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(family)
    with open(os.path.join(copy, "benchmarks", "configs",
                           "scratch-8l.json")) as f:
        cfg = json.load(f)
    ctx = {"config": cfg, "sizes": {"S": 2048}, "window": {"rate": 27_300.0},
           "device_kind": "TPU v5 lite", "builder": family}
    assert reduce.mfu(ctx) == pytest.approx(
        100 * (8.0e9 + 2048) * 27_300.0 / 197e12)
    from benchmarks.builders import llama_dense
    assert reduce.mfu(dict(ctx, builder=llama_dense)) == pytest.approx(
        costs.mfu(cfg, 2048, 27_300.0, "TPU v5 lite"))
    assert reduce.mfu(ctx) != reduce.mfu(dict(ctx, builder=llama_dense))
