"""The family `block_diffusion_moe` (builder, reference, configuration,
traffic mix, metric files) as the harness reads it: the cell's rehearsal
prints the contract and its own metrics, the builder's yardstick counts a
TICK's forwards over `steps_per_tick`, its counters and rehearsal sizes, and
every entry is found by name.

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
CELL = "serve.sdar-30b-a3b-chat.gen-1k"
CONFIG = "sdar-30b-a3b-chat-7l"
# what generation by blocks adds, read in this cell alone
OWN_METRICS = {"sched.store_forward_share", "sched.block_reveal_share",
               "programs.unmask_share", "programs.store_share"}
# PR 34's counters, listed for every serve cell since this PR
PREFILL_ROW_METRICS = {"sched.prefill_pad_row_share",
                       "sched.prefill_split_share"}
SHARED_METRICS = {
    "sched.slot_occupancy", "sched.prefill_time_share",
    "sched.token_gap_ms_p95", "sched.tick_host_ms_p50",
    "sched.tick_chained_share", "programs.tick_ms_p50",
    "programs.prefill_share", "programs.kv_write_share",
    "programs.pool_copy_share", "programs.moe_share",
    "moe.experts_hit_share", "kernels.moe_experts_decode_share",
    "kernels.paged_attn_share", "serve.device_idle_share",
    "weights.decode_roofline", "kernels.moe_experts_decode_roofline",
    "kernels.paged_attn_roofline"}

# a window's counters: 16 live slots at ~1,400 keys each, 125.7 experts hit
# a layer a forward, three forwards a tick
COUNTED = {"live_context_tokens": 22_400.0, "ticks": 100,
           "moe_experts_hit": 125.7 * 2100, "moe_layer_steps": 2100}


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def named(entries, name):
    return next(e for e in entries if e["name"] == name)


@pytest.fixture(scope="module")
def block_config():
    return load(BENCH, "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def block_traffic():
    return load(BENCH, "traffic", "fixed-gen-1k.json")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_block_cells_rehearsal_prints_the_contract_and_its_metrics(trace):
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
    if not trace:
        assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    bench = load(ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert set(out["metrics"]) <= listed
    # what the engine counts reads without a chip; what a device trace
    # holds (scopes, kernels) reads nothing here and is left out, never 0
    values = {k: v["value"] for k, v in out["metrics"].items()}
    engine = next(json.loads(ln.split("[window] engine ")[1].split(" | ")[0])
                  for ln in proc.stdout.splitlines()
                  if ln.startswith("[window] engine"))
    # two denoising forwards and one storing forward a block, a block a
    # tick: a third of the slot-forwards store, less the steps a slot sat
    # out because prompt tokens had opened its first block
    assert engine["blocks_done"] == engine["block_forwards_store"] > 0
    assert engine["block_forwards"] == engine["block_forwards_denoise"] \
        + engine["block_forwards_store"]
    assert engine["block_forwards_denoise"] <= 2 * engine["blocks_done"]
    assert 33.3 <= values["sched.store_forward_share"] < 40.0
    assert 40.0 < values["sched.block_reveal_share"] <= 50.0
    assert engine["moe_layer_steps"] == 2 * 3 * engine["ticks"]
    hit = values["moe.experts_hit_share"]
    assert 100 * 2 / 8 <= hit <= 100.0         # top 2 of 8 at 4-16 rows
    assert 0.0 < values["sched.slot_occupancy"] <= 100.0
    assert PREFILL_ROW_METRICS <= set(values)


def test_the_block_metrics_list_this_cell_alone():
    bench = load(ROOT, "BENCHMARK.json")
    for name in OWN_METRICS:
        m = named(bench["per_layer"], name)
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "serve_tokens_per_s"
        spec = load(BENCH, "metrics", name + ".json")
        assert spec["reader"] in ("counter_ratio", "scope_share")
        assert {k: spec[k] for k in ("unit", "better", "layer", "source",
                                     "moves")} == {
            k: m[k] for k in ("unit", "better", "layer", "source", "moves")}
    serve = [w["name"] for w in bench["workloads"]
             if w["name"].startswith("serve.")]
    for name in PREFILL_ROW_METRICS:
        assert named(bench["per_layer"], name)["workloads"] == serve
    for name in SHARED_METRICS:
        assert CELL in named(bench["per_layer"], name)["workloads"], name
    cell = named(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "fixed-gen-1k", 1)
    assert CELL in named(bench["end_to_end"],
                         "serve_tokens_per_s")["workloads"]
    assert named(bench["configs"], CONFIG)["reduced"] == ["num_hidden_layers"]


def test_the_traffic_file_carries_the_cells_parameters(block_traffic):
    traffic = block_traffic
    assert (traffic["kind"], traffic["clients"],
            traffic["distinct_requests"], traffic["pair_stride"]) == (
                "serve", 16, 32, 13)
    assert traffic["prompt_tokens"] == [256, 2048]
    assert traffic["output_tokens"] == [1024, 1024]
    assert traffic["engine"] == {
        "max_slots": 16, "page_size": 16, "num_pages": 3073,
        "max_pages_per_slot": 192, "steps_per_tick": 4,
        "decode_kernel": "pallas"}
    assert traffic["model_settings"] == {
        "denoising_steps": 2, "remasking": "low_confidence_static"}
    assert traffic["model_settings_why"] and traffic["check"]["why"]
    assert (traffic["check"]["prompt_tokens"],
            traffic["check"]["new_tokens"]) == (1024, 256)
    assert traffic["trace_seconds"] == 10


def test_sizes_count_a_ticks_forwards(block_config, block_traffic):
    from benchmarks.builders import block_diffusion_moe as family
    traffic = block_traffic
    sizes = family.sizes(block_config, block_traffic)
    assert (sizes["slots"], sizes["pages_per_slot"], sizes["E"],
            sizes["block"], sizes["blocks"], sizes["denoise_forwards"],
            sizes["forwards_per_tick"], sizes["steps_per_tick"]) == (
                16, 192, 128, 4, 1, 2, 3, 4)
    eight = family.sizes(block_config, dict(
        traffic, engine=dict(traffic["engine"], steps_per_tick=8),
        model_settings={"denoising_steps": 4}))
    assert (eight["blocks"], eight["denoise_forwards"],
            eight["forwards_per_tick"]) == (2, 8, 10)
    with pytest.raises(ValueError, match="model settings are"):
        family.sizes(block_config,
                     dict(traffic, model_settings={"steps": 2}))


def test_costs_count_a_ticks_least_work_over_its_steps(block_config,
                                                      block_traffic):
    from benchmarks.builders import block_diffusion_moe as family
    sizes = family.sizes(block_config, block_traffic)
    attn, kv, router, expert = family.costs.layer_weights(block_config)
    assert (attn, kv, router, expert) == (18_874_368, 2_097_152, 262_144,
                                          4_718_592)
    # three forwards of seven layers, less the last layer of the forward
    # that stores, of which the cache needs K and V alone
    assert family.costs.layer_forwards(sizes) == 20
    window = COUNTED
    flops, bytes_ = family.costs.decode_step(block_config, sizes, window)
    experts = 20 * 125.7 * expert * 2
    other = 20 * (attn + router) * 2 + kv * 2
    head = 2 * 2048 * 151_936 * 2              # the denoising forwards'
    cache = 20 * 2 * 22_400 * 4 * 128 * 2
    assert bytes_ * 4 == pytest.approx(experts + other + head + cache)
    # ~26.6 GB a tick: 32.5 ms at the HBM peak, of which the experts 89 %
    assert 6.4e9 < bytes_ < 6.9e9 and experts / (bytes_ * 4) > 0.85
    assert flops < bytes_ * 197e12 / 819e9     # memory-bound at 64 rows
    _f, moe = family.costs.moe_experts_step(block_config, sizes, window)
    assert moe * 4 == pytest.approx(experts + 20 * 2 * 64 * 2048 * 2)
    _f, attn_bytes = family.costs.paged_attn_step(block_config, sizes, window)
    assert attn_bytes * 4 == pytest.approx(
        cache + 20 * 64 * 32 * 128 * (2 + 4))
    # without the engine's count: 64 rows of 8 can hit every expert
    assert family.costs.hits({}, sizes) == 128.0
    assert set(family.costs.KERNEL_COSTS) == {
        "decode_step", "moe_experts_step", "paged_attn_step"}


def test_the_rooflines_read_a_ticks_cost_over_a_ticks_time(block_config,
                                                          block_traffic):
    """The cell's roofline files over a hand-built tick of three forwards
    of seven layers: each kernel by its name, its calls inside the tick
    program only, a TICK's least time against a tick's time (the files
    multiply a step's cost by `steps_per_tick`, the builder divides a
    tick's by it)."""
    from benchmarks import reduce
    from benchmarks.builders import block_diffusion_moe as family
    sizes = family.sizes(block_config, block_traffic)
    outs = "(" + ", ".join(["f32[16,4,32,128]{3,2,1,0:T(8,128)}"] * 3) + ")"
    attn = kernel_call("paged_attention_decode", 14, outs, [
        ("s32[16,192]", "1,0:T(8,128)S(1)"), ("s32[16]", "0"),
        ("bf16[16,4,32,128]", "3,2,1,0:T(8,128)(2,1)")])
    moe = kernel_call("moe_experts_decode", 35, "f32[64,2048]{1,0:T(8,128)}", [
        ("s32[128]", "0"), ("bf16[64,2048]", "1,0:T(8,128)(2,1)"),
        ("bf16[128,2048,768]", "2,1,0:T(8,128)(2,1)")])
    planes = tick_of([(attn, 150_000), (moe, 2_200_000)] * 7, steps=3,
                     prefill=[(moe, 9_000_000)])
    context = {"trace": reduce.Trace(planes), "window": COUNTED,
               "config": block_config, "device_kind": "TPU v5 lite",
               "sizes": sizes, "builder": family}
    bench = load(ROOT, "BENCHMARK.json")
    for name, cost, ns_a_tick in (
            ("kernels.paged_attn_roofline", "paged_attn_step", 21 * 150_000),
            ("kernels.moe_experts_decode_roofline", "moe_experts_step",
             21 * 2_200_000)):
        m = roofline_file_reads(context, name, cost, ns_a_tick / 4)
        assert CELL in named(bench["per_layer"], name)["workloads"]
        assert m["moves"] == "serve_tokens_per_s"


def test_counters_sum_the_slot_forwards_and_read_zero_where_none_is_kept():
    from benchmarks.builders import block_diffusion_moe as family
    eng = types.SimpleNamespace(stats={
        "moe_experts_hit": 7, "ticks": 3, "block_forwards_denoise": 20,
        "block_forwards_store": 11})
    assert family.counters(eng) == {
        "moe_experts_hit": 7, "moe_layer_steps": 0,
        "block_forwards_denoise": 20, "block_forwards_store": 11,
        "block_positions_unmasked": 0, "blocks_done": 0,
        "block_forwards": 31}


def test_the_rehearsal_widths_keep_the_mask_id_inside_the_vocabulary(
        block_config):
    from benchmarks import run
    from benchmarks.builders import block_diffusion_moe as family
    small = family.rehearse(block_config)
    assert set(small) <= set(block_config)
    assert small["mask_token_id"] < small["vocab_size"] == 256
    cell = run.load_cell(CELL, rehearse=True)
    assert cell["config"]["hidden_size"] == 64
    assert cell["config"]["block_length"] == 4
    assert cell["builder"] is family and family.flash_block_keys(
        cell["config"], cell["traffic"]) == []
    # the deployment's settings reach the model through `build`, which
    # notes them where the reference's replay finds them
    assert family.generation(cell["config"], {"denoising_steps": 1}) == {
        "denoising_steps": 1, "remasking": "low_confidence_static",
        "confidence_threshold": 0.9}
    assert family.generation(cell["config"], {})["denoising_steps"] == 4


def test_the_block_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference_block_diffusion_moe.py")) as f:
        text = f.read()
    assert "paddle_tpu" not in text.replace("no code of the program", "")
    assert "import jax" in text and '"highest"' in text
    for name in ("forward", "generate", "logits", "replay"):
        assert f"\ndef {name}(" in text


def test_the_configuration_keeps_every_number_of_the_catalog_row(block_config):
    """Every key of the catalog row's `config` under the same key, equal
    but for the depth, which `reduced` names; no width is cut."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    assert block_config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if block_config[k] != v}
    assert differs == {"num_hidden_layers"} == set(block_config["reduced"])
    assert block_config["reduced"]["num_hidden_layers"]["from"] \
        == row["config"]["num_hidden_layers"]
