"""The family `window_attn_moe` (builder, reference, configuration, traffic
mix, metric files) as the harness reads it: the new cell's rehearsal prints
the contract and its own metrics, the builder's yardstick, sizes and counters
on small shapes, and every file the family brought under `benchmarks/` is
reached from an entry of `BENCHMARK.json`.

Run by hand: JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
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
WINDOW_CELL = "serve.trinity-large-preview.mixed-1k-16k"
# in the order PR 33 appended them
WINDOW_METRICS = ("sched.window_engaged_share", "cache.kv_held_share",
                  "moe.pairs_held_share", "programs.window_attn_share",
                  "programs.shared_expert_share",
                  "kernels.paged_attn_decode_share")


# what a window's counters hold after 1,000 decode steps of 16 slots at 6,000
# keys each: a window layer reads 4,096 of them; 7 held experts hit a layer,
# 8 pairs on them
STEPS, CTX = 1000, 16 * 6000
COUNTED = {"live_context_tokens": float(CTX), "ticks": STEPS // 4,
           "kv_tokens_flat": 5 * CTX * STEPS,
           "kv_tokens_held": (CTX + 4 * 16 * 4096) * STEPS,
           "moe_experts_hit": 7 * 4 * STEPS, "moe_layer_steps": 4 * STEPS,
           "moe_pairs_held": 8 * 4 * STEPS,
           "moe_pairs_routed": 64 * 4 * STEPS}


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def named(entries, name):
    """An entry of one of the manifest's lists, wherever it stands: later
    PRs append to all four (README.md, "Entries are found by name")."""
    return next(e for e in entries if e["name"] == name)


@pytest.fixture(scope="module")
def as_run():
    return load(BENCH, "configs", "trinity-large-preview-ep8.json")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_window_cells_rehearsal_prints_the_contract_and_its_metrics(trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         WINDOW_CELL, "--seed", "3000000019", "--seconds", "2", "--trace",
         str(trace), "--rehearse"], cwd=ROOT, capture_output=True, text=True,
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
    listed = {m["name"] for m in bench["per_layer"]
              if WINDOW_CELL in m["workloads"]}
    assert set(WINDOW_METRICS) <= listed and set(out["metrics"]) <= listed
    # what the engine counts reads without a chip; what a device trace
    # holds (scopes, kernels) reads nothing here and is left out, never 0
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert got["sched.window_engaged_share"] == 100.0   # prompts 8-32, W 8
    assert 25.0 < got["cache.kv_held_share"] < 75.0     # 3 of 4 layers cut
    assert 20.0 < got["moe.pairs_held_share"] < 80.0    # 4 of 8 held
    assert "programs.window_attn_share" not in got
    assert "kernels.paged_attn_decode_share" not in got
    lines = proc.stdout.splitlines()
    engine = next(json.loads(ln.split("[window] engine ")[1].split(" | ")[0])
                  for ln in lines if ln.startswith("[window] engine {"))
    assert engine["decode_slot_steps"] == engine["window_engaged_steps"] > 0
    assert engine["moe_layer_steps"] == 3 * 4 * engine["ticks"]
    assert engine["moe_pairs_routed"] == 4 * 2 * engine["moe_layer_steps"]
    # the two page tables as the engine holds them: a window layer's pool
    # is its rings' size
    tables = next(json.loads(ln.split("[window] engine tables ")[1])
                  for ln in lines if ln.startswith("[window] engine tables"))
    full, ring = tables
    assert (full["layers"], full["window"]) == ([3], 0)
    assert (ring["layers"], ring["window"]) == ([0, 1, 2], 8)
    assert ring["pool_pages"] == 4 * ring["pages_per_slot"] + 1


def test_the_window_metrics_list_this_cell_alone():
    bench = load(ROOT, "BENCHMARK.json")
    new = [m for m in bench["per_layer"] if m["name"] in WINDOW_METRICS]
    assert tuple(m["name"] for m in new) == WINDOW_METRICS
    for m in new:
        assert m["workloads"] == [WINDOW_CELL]
        assert m["moves"] == "serve_tokens_per_s"
        f = load(BENCH, "metrics", m["name"] + ".json")
        assert {k: f[k] for k in ("unit", "better", "source", "layer")} \
            == {k: m[k] for k in ("unit", "better", "source", "layer")}
    cell = named(bench["workloads"], WINDOW_CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "mixed-1k-16k")
    assert named(bench["configs"], cell["config"])["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"]
    assert WINDOW_CELL in named(bench["end_to_end"],
                                "serve_tokens_per_s")["workloads"]
    # the metrics of another family's mechanism do not list it, nor the
    # operand pattern that finds one of this model's five decode-kernel
    # calls a step (none is doubled under a second name: the expert
    # block's three read here under the names the doc-QA cell gave them)
    assert not os.path.exists(os.path.join(
        BENCH, "metrics", "moe.held_experts_hit_share.json"))
    for name in ("programs.indexer_share", "programs.select_share",
                 "sched.select_engaged_share", "kernels.paged_attn_share"):
        assert WINDOW_CELL not in named(bench["per_layer"],
                                        name)["workloads"]
    for name in ("programs.moe_share", "moe.experts_hit_share",
                 "kernels.moe_experts_decode_share"):
        assert WINDOW_CELL in named(bench["per_layer"], name)["workloads"]


def test_the_configuration_states_the_share_and_cuts_no_width(as_run):
    cut = as_run["reduced"]
    assert set(cut) == {"num_hidden_layers", "num_dense_layers",
                        "layer_types", "num_experts", "vocab_size"}
    assert (as_run["num_experts"], as_run["router_experts"],
            as_run["held_experts_first"]) == (32, 256, 0)
    assert as_run["vocab_size"] * 8 == cut["vocab_size"]["from"] == 200192
    assert as_run["layer_types"] == ["sliding_attention"] * 4 \
        + ["full_attention"]
    assert (as_run["hidden_size"], as_run["intermediate_size"],
            as_run["moe_intermediate_size"], as_run["head_dim"],
            as_run["num_attention_heads"], as_run["num_key_value_heads"],
            as_run["num_experts_per_tok"], as_run["sliding_window"]) == (
                3072, 12288, 3072, 128, 48, 8, 4, 4096)
    assert {"assumed", "deployment", "draw"} <= set(as_run)
    # 8.64 GB of weights in bf16, as the file reckons them
    from benchmarks.builders import window_attn_moe as family
    attn, router, expert, dense = family.costs.layer_weights(as_run)
    assert (attn, router, expert, dense) == (62_914_560, 786_432,
                                             28_311_552, 113_246_208)
    params = 5 * attn + dense + 4 * (router + 33 * expert) \
        + 2 * 25_024 * 3072
    assert 8.63e9 < 2 * params < 8.65e9


def test_window_costs_count_the_least_work_at_the_published_widths(as_run):
    from benchmarks.builders import window_attn_moe as family
    traffic = load(BENCH, "traffic", "mixed-1k-16k.json")
    sizes = family.sizes(as_run, traffic)
    assert (sizes["slots"], sizes["pages_per_slot"], sizes["E"], sizes["Er"],
            sizes["W"], sizes["Lw"], sizes["Lf"], sizes["Ld"], sizes["Le"]) \
        == (16, 1152, 32, 256, 4096, 4, 1, 1, 4)
    attn, router, expert, dense = family.costs.layer_weights(as_run)
    steps, ctx, window = STEPS, CTX, COUNTED
    assert family.costs.kv_tokens(window, sizes) == (ctx, 16 * 4096)
    flops, bytes_ = family.costs.decode_step(as_run, sizes, window)
    experts = 4 * 7 * expert * 2
    other = (5 * attn + dense + 4 * (router + expert)) * 2
    head = 3072 * 25_024 * 2
    row = 8 * 128 * 2
    cache = 2 * (ctx + 4 * 16 * 4096) * row
    assert bytes_ == pytest.approx(experts + other + head + cache)
    assert 4.0e9 < bytes_ < 4.6e9 and 0.3 < experts / bytes_ < 0.45
    assert flops == pytest.approx(
        2.0 * ((5 * attn + dense + 4 * (router + expert) + 3072 * 25_024)
               * 16 + 4 * 8 * expert))
    _f, moe = family.costs.moe_experts_step(as_run, sizes, window)
    assert moe == pytest.approx(experts + 4 * 2 * 16 * 3072 * 2)
    q = 16 * 48 * 128 * (2 + 4)
    _f, ring = family.costs.paged_attn_window_step(as_run, sizes, window)
    assert ring == pytest.approx(4 * (2 * 16 * 4096 * row + q))
    _f, full = family.costs.paged_attn_full_step(as_run, sizes, window)
    assert full == pytest.approx(2 * ctx * row + q)
    # without the engine's counts: the most 16 rows of 4 can hit of 32, an
    # even router's share of the pairs, every slot at the mean context
    bare = {"live_context_tokens": 16 * 3000.0}
    assert family.costs.hits(bare, sizes) == 32.0
    assert family.costs.pairs_held(bare, sizes) == 8.0
    assert family.costs.kv_tokens(bare, sizes) == (48_000.0, 48_000.0)
    # a chunk of 4,096 tokens that starts at 8,192: every token of a
    # window layer sees 4,096 keys, of the full layer 8,193 to 12,288
    flops, chunk = family.costs.prefill_attn_chunk(as_run, sizes, {})
    heads = 48 * 128
    assert flops == pytest.approx(4.0 * heads * (
        4 * 4096 * 4096 + 4096 * 8192 + 4096 * 4097 // 2))
    assert chunk == pytest.approx(
        2 * (4 * (4095 + 4096) + 12288) * row + 5 * 2 * 4096 * heads * 2)
    assert family.costs.prefill_attn_chunk(
        as_run, sizes, {"chunk_tokens": 8, "context_tokens": 0})[0] \
        == pytest.approx(4.0 * heads * 5 * 36)
    assert set(family.costs.KERNEL_COSTS) == {
        "decode_step", "moe_experts_step", "paged_attn_window_step",
        "paged_attn_full_step", "prefill_attn_chunk"}


def test_the_kernels_rooflines_part_the_calls_over_the_two_tables(as_run):
    """The cell's three roofline files over a hand-built tick: the expert
    kernel by its name; the decode kernel's calls parted by their first
    operand, the full block table (`s32[slots, pages_per_slot]`) or
    anything else (a ring's view); each against its own cost function of
    the builder, over the calls inside tick programs only."""
    from benchmarks import reduce
    from benchmarks.builders import window_attn_moe as family
    sizes = family.sizes(as_run, load(BENCH, "traffic", "mixed-1k-16k.json"))
    tiled, flat = "1,0:T(8,128)S(1)", "0"
    lens, q = ("s32[16]", flat), ("bf16[16,8,8,128]", "3,2,1,0:T(8,128)(2,1)")
    outs = "(" + ", ".join(["f32[16,8,8,128]{3,2,1,0:T(8,128)}"] * 3) + ")"
    ring = kernel_call("paged_attention_decode", 31, outs, [
        ("s32[16,257]", tiled), lens, q, ("f32[16,33,1,128]", "3,2,1,0")])
    full = kernel_call("paged_attention_decode", 35, outs,
                       [("s32[16,1152]", tiled), lens, q])
    moe = kernel_call("moe_experts_decode", 20, "f32[16,3072]{1,0:T(8,128)}", [
        ("s32[32]", flat), ("bf16[16,3072]", "1,0:T(8,128)(2,1)"),
        ("bf16[32,3072,3072]", "2,1,0:T(8,128)(2,1)")])
    took = {"ring": 400_000, "full": 700_000, "moe": 800_000}
    planes = tick_of(
        [(ring, took["ring"])] * 4 + [(full, took["full"])]
        + [(moe, took["moe"])] * 4,
        # a short prompt's rows go through the few-rows kernel too: no tick
        prefill=[(moe, 5_000_000)])
    context = {"trace": reduce.Trace(planes), "window": COUNTED,
               "config": as_run, "device_kind": "TPU v5 lite",
               "sizes": sizes, "builder": family}
    bench = load(ROOT, "BENCHMARK.json")
    for name, cost, ns_a_step in (
            ("kernels.paged_attn_window_roofline", "paged_attn_window_step",
             4 * took["ring"]),
            ("kernels.paged_attn_full_roofline", "paged_attn_full_step",
             took["full"]),
            ("kernels.moe_experts_decode_roofline", "moe_experts_step",
             4 * took["moe"])):
        roofline_file_reads(context, name, cost, ns_a_step)
        assert WINDOW_CELL in named(bench["per_layer"], name)["workloads"]
    # the one pattern over both tables would hold a step's five calls
    # against one table's bytes
    assert WINDOW_CELL not in named(bench["per_layer"],
                                    "kernels.paged_attn_roofline")["workloads"]


def test_window_counters_read_the_engines_counts_and_zero_where_it_has_none(
        capsys):
    from benchmarks.builders import window_attn_moe as family
    tables = [{"layers": [0], "window": 8, "pool_pages": 5,
               "pages_per_slot": 2, "pool_bytes": 0}]
    eng = types.SimpleNamespace(stats={"moe_pairs_held": 7, "ticks": 3},
                                page_groups=lambda: tables)
    want = dict.fromkeys(("moe_experts_hit", "moe_layer_steps",
                          "moe_pairs_held", "moe_pairs_routed",
                          "decode_slot_steps", "window_engaged_steps",
                          "kv_tokens_held", "kv_tokens_flat"), 0)
    want["moe_pairs_held"] = 7
    assert family.counters(eng) == want
    said = capsys.readouterr().out
    # the engine's own account of its page tables, through its public read
    assert json.loads(said.split("[window] engine tables ")[1]) == tables


def test_the_window_rehearsal_widths_are_the_familys_own(as_run):
    from benchmarks import run
    from benchmarks.builders import window_attn_moe as family
    small = family.rehearse(as_run)
    assert set(small) <= set(as_run)
    cell = run.load_cell(WINDOW_CELL, rehearse=True)
    assert cell["config"]["hidden_size"] == 64
    # the rehearsal's prompts (8-32) and its check (12 + 8) cross the window
    assert cell["traffic"]["prompt_tokens"][0] >= small["sliding_window"]
    assert cell["builder"] is family and family.flash_block_keys(
        cell["config"], cell["traffic"]) == []
    assert family.layers_of(cell["config"]) == (1, 3, 3, 1)


def test_the_window_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference_window_attn_moe.py")) as f:
        text = f.read()
    assert "paddle_tpu" not in text.replace("no code of the program", "")
    assert "import jax" in text and '"highest"' in text


def test_the_window_familys_files_are_reached_from_the_manifest():
    """What this family brought: each file is named by an entry of
    BENCHMARK.json or by a file that one names."""
    bench = load(ROOT, "BENCHMARK.json")
    cell = named(bench["workloads"], WINDOW_CELL)
    conf = named(bench["configs"], cell["config"])
    assert conf["file"] == "benchmarks/configs/trinity-large-preview-ep8.json"
    assert load(ROOT, conf["file"])["builder"] == "window_attn_moe"
    from benchmarks.builders import window_attn_moe as family
    assert family.reference.__file__ == os.path.join(
        BENCH, "reference_window_attn_moe.py")
    assert os.path.exists(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json"))
    for name in WINDOW_METRICS:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".json"))
