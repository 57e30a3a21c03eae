"""The family `latent_attn_moe` (builder, reference, configuration, traffic
mix, metric files) as the harness reads it: the new cell's rehearsal prints
the contract and what it can read without a chip, its three metrics over a
hand-built tick, the builder's yardstick against a count by hand at the
published widths, its counters, and every file the family brought under
`benchmarks/` reached from an entry of `BENCHMARK.json`.

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
LATENT_CELL = "serve.kanana-2-30b-a3b.latent-2k-16k"
LATENT_METRICS = ("kernels.mla_decode_share", "kernels.mla_decode_roofline",
                  "programs.mla_prefill_share")
# what a window's counters hold after 1,000 decode steps of 32 slots at
# 7,450 keys each in 5 layers, ~100 experts hit a layer of 4
STEPS, CTX = 1000, 32 * 7450
LATENT_COUNTED = {"live_context_tokens": float(CTX), "ticks": STEPS // 4,
                  "mla_decode_rows": 5 * CTX * STEPS,
                  "moe_experts_hit": 100 * 4 * STEPS,
                  "moe_layer_steps": 4 * STEPS}


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def named(entries, name):
    """An entry of one of the manifest's lists, wherever it stands: later
    PRs append to all four (README.md, "Entries are found by name")."""
    return next(e for e in entries if e["name"] == name)


@pytest.fixture(scope="module")
def latent_as_run():
    return load(BENCH, "configs", "kanana-2-30b-a3b-instruct-5l.json")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_latent_cells_rehearsal_prints_the_contract(trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         LATENT_CELL, "--seed", "4100000029", "--seconds", "2", "--trace",
         str(trace), "--rehearse"], cwd=ROOT, capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    lines = proc.stdout.splitlines()
    engine = next(json.loads(ln.split("[window] engine ")[1].split(" | ")[0])
                  for ln in lines if ln.startswith("[window] engine {"))
    assert engine["mla_decode_rows"] > 0 and engine["mla_expand_rows"] > 0
    assert engine["moe_layer_steps"] == 2 * 4 * engine["ticks"]
    latent = next(json.loads(ln.split("[window] engine latent ")[1])
                  for ln in lines if ln.startswith("[window] engine latent"))
    assert latent["mla_expand_rows"] == engine["mla_expand_rows"]
    assert latent["expanded_rows_per_prompt_token"] == pytest.approx(
        engine["mla_expand_rows"] / engine["mla_prefill_tokens"])
    if not trace:
        assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    bench = load(ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in bench["per_layer"]
              if LATENT_CELL in m["workloads"]}
    assert set(LATENT_METRICS) <= listed and set(out["metrics"]) <= listed
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0.0 < got["moe.experts_hit_share"] <= 100.0
    assert "sched.tick_chained_share" in got
    # what a device trace holds (kernels, scopes) reads nothing here and is
    # left out, never 0
    assert not set(LATENT_METRICS) & set(got)


def test_the_latent_metrics_list_this_cell_alone():
    bench = load(ROOT, "BENCHMARK.json")
    for name in LATENT_METRICS:
        m = named(bench["per_layer"], name)
        assert m["workloads"] == [LATENT_CELL]
        assert m["moves"] == "serve_tokens_per_s"
        f = load(BENCH, "metrics", name + ".json")
        assert {k: f[k] for k in ("unit", "better", "source", "layer")} \
            == {k: m[k] for k in ("unit", "better", "source", "layer")}
    assert load(BENCH, "metrics", "kernels.mla_decode_share.json")[
        "args"] == {"pattern": "^%?mla_decode"}
    assert load(BENCH, "metrics", "programs.mla_prefill_share.json")[
        "args"] == {"scope": "mla_prefill"}
    cell = named(bench["workloads"], LATENT_CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "latent-2k-16k", "kanana-2-30b-a3b-instruct-5l")
    assert named(bench["configs"], cell["config"])["reduced"] == [
        "num_hidden_layers"]
    assert LATENT_CELL in named(bench["end_to_end"],
                                "serve_tokens_per_s")["workloads"]
    # the block-table operand pattern finds this cell's latent kernel, not
    # a K and V kernel: the cell is off that metric
    assert LATENT_CELL not in named(bench["per_layer"],
                                    "kernels.paged_attn_share")["workloads"]
    for name in ("programs.moe_share", "moe.experts_hit_share",
                 "kernels.moe_experts_decode_share",
                 "kernels.moe_experts_decode_roofline",
                 "kernels.moe_grouped_share", "weights.decode_roofline",
                 "programs.tick_ms_p50"):
        assert LATENT_CELL in named(bench["per_layer"], name)["workloads"]
    # the window family's tests hold this one to its own cell alone
    assert named(bench["per_layer"], "programs.shared_expert_share")[
        "workloads"] == ["serve.trinity-large-preview.mixed-1k-16k"]


def test_the_latent_configuration_cuts_depth_alone(latent_as_run):
    cfg = latent_as_run
    cut = cfg["reduced"]
    assert set(cut) == {"num_hidden_layers"}
    assert (cut["num_hidden_layers"]["from"], cfg["num_hidden_layers"]) \
        == (48, 5)
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["n_routed_experts"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"],
            cfg["vocab_size"]) == (2048, 6144, 768, 32, 512, 128, 64, 128,
                                   128, 6, 2, 128256)
    assert cfg["q_lora_rank"] is None and cfg["rope_scaling"] is None
    assert {"assumed", "deployment", "draw", "source"} <= set(cfg)
    # 6.30 GB of weights in bf16, as the file reckons them
    from benchmarks.builders import latent_attn_moe as family
    attn, router, expert, shared, dense = family.costs.layer_weights(cfg)
    assert (attn, router, expert, shared, dense) == (
        26_345_472, 262_144, 4_718_592, 9_437_184, 37_748_736)
    params = 5 * attn + dense + 4 * (router + shared + 128 * expert) \
        + 2 * 128_256 * 2048
    assert 6.29e9 < 2 * params < 6.31e9


def test_the_latent_costs_count_the_least_work_by_hand(latent_as_run):
    from benchmarks.builders import latent_attn_moe as family
    cfg = latent_as_run
    sizes = family.sizes(cfg, load(BENCH, "traffic", "latent-2k-16k.json"))
    assert (sizes["slots"], sizes["pages_per_slot"], sizes["row"],
            sizes["R"], sizes["H"], sizes["L"], sizes["Le"]) == (
                32, 1152, 576, 512, 32, 5, 4)
    w = LATENT_COUNTED
    rows = 5 * CTX                  # a step's latent rows, every layer
    assert family.costs.latent_rows(w, sizes) == rows
    flops, bytes_ = family.costs.mla_decode(cfg, sizes, w)
    # each row once at 576 values in bf16; [q~ | q_pe] in bf16 and õ in
    # float32 for 32 heads of 32 slots in 5 layers
    assert bytes_ == rows * 576 * 2 + 5 * 32 * 32 * (576 * 2 + 512 * 4)
    assert flops == 2.0 * 32 * (576 + 512) * rows
    # the rows alone are 1.37 GB a step: 1.7 ms at the HBM's peak
    assert 1.37e9 < rows * 576 * 2 < bytes_ < 1.40e9
    flops, moe = family.costs.moe_experts_step(cfg, sizes, w)
    expert = 3 * 2048 * 768
    assert moe == 4 * (100 * expert * 2 + 2 * 32 * 2048 * 2)
    assert flops == 4 * 2.0 * 32 * 6 * expert
    _f, step = family.costs.decode_step(cfg, sizes, w)
    attn, router, _e, shared, dense = family.costs.layer_weights(cfg)
    weights = 5 * attn + dense + 2048 * 128_256 \
        + 4 * (router + shared + 100 * expert)
    assert step == 2 * weights + rows * 576 * 2
    # without the engine's counts: the most 32 rows of 6 can hit, the live
    # contexts in every layer
    bare = {"live_context_tokens": float(CTX)}
    assert family.costs.hits(bare, sizes) == 128.0
    assert family.costs.latent_rows(bare, sizes) == 5 * CTX
    assert set(family.costs.KERNEL_COSTS) == {"decode_step",
                                              "moe_experts_step",
                                              "mla_decode"}


def test_the_latent_kernels_roofline_reads_the_tick_alone(latent_as_run):
    """The decode kernel's roofline file over a hand-built tick: the kernel
    found by its name inside the program that holds the block table as a
    custom call's first operand (`s32[32,1152]`: the latent kernel's own),
    against the builder's `mla_decode`; the prefill program is not a
    tick."""
    from benchmarks import reduce
    from benchmarks.builders import latent_attn_moe as family
    sizes = family.sizes(latent_as_run,
                         load(BENCH, "traffic", "latent-2k-16k.json"))
    tiled, flat = "1,0:T(8,128)S(1)", "0"
    outs = "(" + ", ".join(["f32[32,32,512]{2,1,0:T(8,128)}"]
                           + ["f32[32,32,128]{2,1,0:T(8,128)}"] * 2) + ")"
    mla = kernel_call("mla_decode", 10, outs, [
        ("s32[32,1152]", tiled), ("s32[32]", flat),
        ("bf16[32,32,640]", "2,1,0:T(8,128)(2,1)"),
        ("bf16[36865,16,640]", "2,1,0:T(8,128)(2,1)")])
    grouped = kernel_call("moe_grouped", 3, "bf16[1024,2048]{1,0}", [
        ("s32[]", flat), ("bf16[6144,2048]", "1,0:T(8,128)(2,1)")])
    took = 400_000
    planes = tick_of([(mla, took)] * 5, prefill=[(grouped, 9_000_000)])
    context = {"trace": reduce.Trace(planes), "window": LATENT_COUNTED,
               "config": latent_as_run, "device_kind": "TPU v5 lite",
               "sizes": sizes, "builder": family}
    roofline_file_reads(context, "kernels.mla_decode_roofline",
                        "mla_decode", 5 * took)


def test_latent_counters_read_first_and_say_the_windows_expansion(capsys):
    from benchmarks.builders import latent_attn_moe as family
    eng = types.SimpleNamespace(stats={"mla_expand_rows": 100,
                                       "mla_prefill_tokens": 10, "ticks": 3})
    opened = family.counters(eng)
    assert opened == dict(dict.fromkeys(family.COUNTED, 0),
                          mla_expand_rows=100, mla_prefill_tokens=10)
    assert capsys.readouterr().out == ""
    eng.stats.update(mla_expand_rows=700, mla_prefill_tokens=160)
    closed = family.counters(eng)
    assert closed["mla_expand_rows"] == 700
    said = capsys.readouterr().out
    assert json.loads(said.split("[window] engine latent ")[1]) == {
        "mla_expand_rows": 600, "mla_prefill_tokens": 150,
        "expanded_rows_per_prompt_token": 4.0}


def test_the_latent_rehearsal_widths_are_the_familys_own(latent_as_run):
    from benchmarks import run
    from benchmarks.builders import latent_attn_moe as family
    small = family.rehearse(latent_as_run)
    assert set(small) <= set(latent_as_run)
    cell = run.load_cell(LATENT_CELL, rehearse=True)
    assert cell["config"]["hidden_size"] == 64
    assert cell["builder"] is family and family.flash_block_keys(
        cell["config"], cell["traffic"]) == []


def test_the_latent_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference_latent_attn_moe.py")) as f:
        text = f.read()
    assert "paddle_tpu" not in text.replace("no code of the program", "")
    assert "import jax" in text and '"highest"' in text


def test_the_latent_familys_files_are_reached_from_the_manifest():
    bench = load(ROOT, "BENCHMARK.json")
    cell = named(bench["workloads"], LATENT_CELL)
    conf = named(bench["configs"], cell["config"])
    assert conf["file"] == \
        "benchmarks/configs/kanana-2-30b-a3b-instruct-5l.json"
    assert load(ROOT, conf["file"])["builder"] == "latent_attn_moe"
    from benchmarks.builders import latent_attn_moe as family
    assert family.reference.__file__ == os.path.join(
        BENCH, "reference_latent_attn_moe.py")
    assert os.path.exists(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json"))
    for name in LATENT_METRICS:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".json"))
