"""The family `sparse_attn_moe` (builder, reference, configuration, traffic
mix, metric files) as the harness reads it: the new cell's rehearsal prints
the contract and its own metrics, the builder's yardstick and counters on
small shapes, and every file under `benchmarks/` is reached from an entry
of `BENCHMARK.json`.

Run by hand: JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks.tests.planes import (kernel_call, roofline_file_reads,
                                     tick_of)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "serve.keye-vl-2.0-30b-a3b.doc-qa"
NEW_METRICS = {"programs.moe_share", "programs.indexer_share",
               "programs.select_share", "moe.experts_hit_share",
               "sched.select_engaged_share",
               "kernels.moe_experts_decode_share"}
# the key selection is this model's alone; the expert block's three read
# in every cell whose layers route to experts
OWN_MECHANISM = {"programs.indexer_share", "programs.select_share",
                 "sched.select_engaged_share"}


# a window's counters: 8 live slots at ~4,000 keys each, 51.6 experts hit a
# layer a step
COUNTED = {"live_context_tokens": 32_000.0, "ticks": 100,
           "decode_slot_steps": 3200, "moe_experts_hit": 51.6 * 2800,
           "moe_layer_steps": 2800}


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def published():
    return load(BENCH, "configs", "keye-vl-2.0-30b-a3b-lm.json")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cells_rehearsal_prints_the_contract_and_its_metrics(trace):
    # at the rehearsal's widths one seed in a dozen selects, in bf16, a key
    # at the edge that float32 does not, and fails the 1.0 sd of
    # rehearse.json (PERF.md section 7); this seed's keys are clear of it
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "2", "--trace", str(trace),
         "--rehearse"], cwd=ROOT, capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(out)[-1] == "compared"
    assert set(out["compared"]) == {"logit_gap_sd", "failed",
                                    "compiled_in_window"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    bench = load(ROOT, "BENCHMARK.json")
    if not trace:
        assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert NEW_METRICS <= listed and set(out["metrics"]) <= listed
    # what the engine counts reads without a chip; what a device trace
    # holds (scopes, kernels) reads nothing here and is left out, never 0
    assert out["metrics"]["sched.select_engaged_share"]["value"] == 100.0
    hit = out["metrics"]["moe.experts_hit_share"]["value"]
    assert 100 * 2 / 8 <= hit <= 100.0          # top 2 of 8 at 1-4 rows
    engine = next(json.loads(ln.split("[window] engine ")[1].split(" | ")[0])
                  for ln in proc.stdout.splitlines()
                  if ln.startswith("[window] engine"))
    assert engine["decode_slot_steps"] == engine["select_engaged_steps"] > 0
    assert engine["moe_layer_steps"] == 2 * 4 * engine["ticks"]


def test_the_new_metrics_list_this_cell_alone():
    bench = load(ROOT, "BENCHMARK.json")
    found = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    assert {m["name"] for m in found} == NEW_METRICS
    for m in found:
        assert m["moves"] == "serve_tokens_per_s"
        if m["name"] in OWN_MECHANISM:
            assert m["workloads"] == [CELL]
        else:       # a later cell with routed experts is appended
            assert m["workloads"][0] == CELL
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["chips"] == 1
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]


def test_costs_count_the_least_work_at_the_published_widths(published):
    from benchmarks.builders import sparse_attn_moe as family
    traffic = load(BENCH, "traffic", "doc-qa-2k-6k.json")
    sizes = family.sizes(published, traffic)
    assert (sizes["slots"], sizes["pages_per_slot"], sizes["E"],
            sizes["topk"]) == (8, 416, 128, 2048)
    attn, index, router, expert = family.costs.layer_weights(published)
    assert (attn, index, router, expert) == (18_874_368, 2_260_992, 262_144,
                                             4_718_592)
    window = COUNTED
    flops, bytes_ = family.costs.decode_step(published, sizes, window)
    experts = 7 * 51.6 * expert * 2
    other = 7 * (attn + index + router) * 2
    head = 2048 * 151_936 * 2
    selected = 7 * 2 * 8 * 2048 * 4 * 128 * 2      # K and V, cut to topk
    index_keys = 7 * 32_000 * 64 * 2
    assert bytes_ == pytest.approx(experts + other + head + selected
                                   + index_keys)
    assert 4.4e9 < bytes_ < 4.8e9 and experts / bytes_ > 0.7
    # the rows a mask throws away are not counted: 32,000 live keys, 16,384
    # selected
    assert family.costs.selected_tokens(window, sizes) == 8 * 2048
    short = dict(window, live_context_tokens=8000.0)
    assert family.costs.selected_tokens(short, sizes) == 8000.0
    _f, moe = family.costs.moe_experts_step(published, sizes, window)
    assert moe == pytest.approx(experts + 7 * 2 * 8 * 2048 * 2)
    _f, attn_bytes = family.costs.paged_attn_step(published, sizes, window)
    assert attn_bytes == pytest.approx(selected
                                       + 7 * 8 * 32 * 128 * (2 + 4))
    # without the engine's count: the most that 8 rows of 8 can hit
    assert family.costs.hits({}, sizes) == 64.0
    assert set(family.costs.KERNEL_COSTS) == {
        "decode_step", "moe_experts_step", "paged_attn_step"}


def test_the_kernels_rooflines_read_the_builders_costs_over_a_tick(published):
    """The cell's two roofline files over a hand-built tick of seven layers:
    each kernel by its name, its calls inside tick programs only, against
    the builder's own cost function and the window's counts."""
    from benchmarks import reduce
    from benchmarks.builders import sparse_attn_moe as family
    sizes = family.sizes(published, load(BENCH, "traffic",
                                         "doc-qa-2k-6k.json"))
    outs = "(" + ", ".join(["f32[8,4,8,128]{3,2,1,0:T(8,128)}"] * 3) + ")"
    attn = kernel_call("paged_attention_decode", 35, outs, [
        ("s32[8,416]", "1,0:T(8,128)S(1)"), ("s32[8]", "0"),
        ("bf16[8,4,8,128]", "3,2,1,0:T(8,128)(2,1)"),
        ("f32[8,52,1,128]", "3,2,1,0")])
    moe = kernel_call("moe_experts_decode", 35, "f32[16,2048]{1,0:T(8,128)}", [
        ("s32[64]", "0"), ("bf16[16,2048]", "1,0:T(8,128)(2,1)"),
        ("bf16[128,2048,768]", "2,1,0:T(8,128)(2,1)")])
    planes = tick_of([(attn, 200_000), (moe, 650_000)] * 7,
                     prefill=[(moe, 9_000_000)])
    context = {"trace": reduce.Trace(planes), "window": COUNTED,
               "config": published, "device_kind": "TPU v5 lite",
               "sizes": sizes, "builder": family}
    bench = load(ROOT, "BENCHMARK.json")
    for name, cost, ns_a_step in (
            ("kernels.paged_attn_roofline", "paged_attn_step", 7 * 200_000),
            ("kernels.moe_experts_decode_roofline", "moe_experts_step",
             7 * 650_000)):
        m = roofline_file_reads(context, name, cost, ns_a_step)
        entry = next(e for e in bench["per_layer"] if e["name"] == name)
        assert entry["workloads"][0] == CELL
        assert entry["moves"] == m["moves"] == "serve_tokens_per_s"


def test_counters_read_the_engines_counts_and_zero_where_it_has_none():
    from benchmarks.builders import sparse_attn_moe as family
    eng = types.SimpleNamespace(stats={"moe_experts_hit": 7, "ticks": 3})
    assert family.counters(eng) == {
        "moe_experts_hit": 7, "moe_layer_steps": 0, "decode_slot_steps": 0,
        "select_engaged_steps": 0}


def test_the_rehearsal_widths_are_the_familys_own(published):
    from benchmarks import run
    from benchmarks.builders import sparse_attn_moe as family
    small = family.rehearse(published)
    assert set(small) <= set(published)
    assert small["sa_config"]["topk"] == 8 \
        and set(small["sa_config"]) == set(published["sa_config"])
    cell = run.load_cell(CELL, rehearse=True)
    assert cell["config"]["hidden_size"] == 64
    # the rehearsal's prompts (8-32) and its check (12 + 8) cross topk
    assert cell["traffic"]["prompt_tokens"][0] >= small["sa_config"]["topk"]
    assert cell["builder"] is family and family.flash_block_keys(
        cell["config"], cell["traffic"]) == []


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference_sparse_attn_moe.py")) as f:
        text = f.read()
    assert "paddle_tpu" not in text.replace("no code of the program", "")
    assert "import jax" in text and '"highest"' in text


def test_every_file_under_benchmarks_is_reached_from_an_entry():
    """configs[].file -> its builder -> the builder's reference; each
    cell's traffic file and its kind's driver; each per_layer entry's
    metric file. No file of those directories is left over."""
    bench = load(ROOT, "BENCHMARK.json")
    reached = set()
    for c in bench["configs"]:
        reached.add(os.path.normpath(os.path.join(ROOT, c["file"])))
        builder = importlib.import_module(
            "benchmarks.builders." + load(ROOT, c["file"])["builder"])
        reached.add(builder.__file__)
        reached.add(builder.reference.__file__)
    for w in bench["workloads"]:
        path = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        reached.add(path)
        reached.add(os.path.join(BENCH, load(path)["kind"] + ".py"))
    for m in bench["per_layer"]:
        reached.add(os.path.join(BENCH, "metrics", m["name"] + ".json"))
    there = {os.path.join(BENCH, d, f)
             for d in ("configs", "builders", "traffic", "metrics")
             for f in os.listdir(os.path.join(BENCH, d))
             if f.endswith((".json", ".py")) and f != "__init__.py"}
    there |= {os.path.join(BENCH, f) for f in os.listdir(BENCH)
              if f.startswith("reference")}
    assert there <= reached, sorted(there - reached)
    assert all(os.path.exists(p) for p in reached)
