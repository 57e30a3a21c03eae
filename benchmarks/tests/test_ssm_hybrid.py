"""The family `ssm_hybrid` (builder, reference, configuration, traffic mix,
metric files) as the harness reads it: the new cell's rehearsal prints the
contract and what it can read without a chip, its metrics over hand-built
programs, the builder's yardstick against a count by hand at the published
widths, its counters, and every file the family brought under
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
CELL = "serve.phi-4-mini-flash-reasoning.reason-1k-8k"
CONFIG = "phi-4-mini-flash-reasoning"
OWN_METRICS = ("kernels.ssm_scan_roofline", "programs.ssm_share")
# the catalog's `config` of the model, as its public config.json gives it
PUBLISHED = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
             "intermediate_size": 10240, "layer_norm_eps": 1e-05,
             "max_position_embeddings": 262144, "mb_per_layer": 2,
             "model_type": "phi4flash", "num_attention_heads": 40,
             "num_hidden_layers": 32, "num_key_value_heads": 20,
             "resid_pdrop": 0, "sliding_window": 512,
             "tie_word_embeddings": True, "mlp_bias": False,
             "lm_head_bias": False, "vocab_size": 200064}
# what a window's counters hold after 1,000 decode steps of 32 slots at
# 4,800 keys each (the window layers' 512), and 40 prefill programs of
# 700 valid tokens a row
STEPS, CTX, RINGED = 1000, 32 * 4800, 32 * 512
COUNTED = {"live_context_tokens": float(CTX), "ticks": STEPS // 4,
           "kv_tokens_flat": 9 * CTX * STEPS,
           "kv_tokens_held": (CTX + 8 * RINGED) * STEPS,
           "ssm_scan_tokens": 40 * 700, "ssm_scan_calls": 40,
           "prefill_rows_run": 40}


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def named(entries, name):
    """An entry of one of the manifest's lists, wherever it stands: later
    PRs append to all four (README.md, "Entries are found by name")."""
    return next(e for e in entries if e["name"] == name)


@pytest.fixture(scope="module")
def ssm_as_run():
    return load(BENCH, "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def ssm_sizes(ssm_as_run):
    from benchmarks.builders import ssm_hybrid as family
    return family.sizes(ssm_as_run, load(BENCH, "traffic", "reason-1k-8k.json"))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_ssm_cells_rehearsal_prints_the_contract(trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4300000037", "--seconds", "2", "--trace", str(trace),
         "--rehearse"], cwd=ROOT, capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    state = next(json.loads(ln.split("[window] engine state ")[1])
                 for ln in proc.stdout.splitlines()
                 if ln.startswith("[window] engine state"))
    engine = next(json.loads(ln.split("[window] engine ")[1].split(" | ")[0])
                  for ln in proc.stdout.splitlines()
                  if ln.startswith("[window] engine {"))
    assert state["ssm_scan_tokens"] == engine["ssm_scan_tokens"] > 0
    # a call runs the cross-decoder at each row's last token alone (the
    # window's edges may cut a group's counts apart: no equality here)
    assert 0 < engine["cross_skipped_tokens"] < engine["ssm_scan_tokens"]
    if not trace:
        assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    bench = load(ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert set(OWN_METRICS) <= listed and set(out["metrics"]) <= listed
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert "sched.tick_chained_share" in got
    # what a device trace holds (kernels, scopes) reads nothing here and is
    # left out, never 0
    assert not set(OWN_METRICS) & set(got)


def test_the_ssm_metrics_list_this_cell_alone():
    bench = load(ROOT, "BENCHMARK.json")
    for name in OWN_METRICS:
        m = named(bench["per_layer"], name)
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        f = load(BENCH, "metrics", name + ".json")
        assert {k: f[k] for k in ("unit", "better", "source", "layer")} \
            == {k: m[k] for k in ("unit", "better", "source", "layer")}
    assert load(BENCH, "metrics", "programs.ssm_share.json")["args"] == {
        "scope": "ssm"}
    cell = named(bench["workloads"], CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "reason-1k-8k", CONFIG)
    assert named(bench["configs"], CONFIG)["reduced"] == []
    assert CELL in named(bench["end_to_end"],
                         "serve_tokens_per_s")["workloads"]
    serve = [w["name"] for w in bench["workloads"]
             if w["name"].startswith("serve.")]
    for m in bench["per_layer"]:
        if set(m["workloads"]) >= set(serve) - {CELL}:
            assert CELL in m["workloads"], m["name"]
    for name in ("kernels.paged_attn_window_roofline",
                 "kernels.paged_attn_full_roofline",
                 "weights.decode_roofline"):
        assert CELL in named(bench["per_layer"], name)["workloads"]
    # the window family's tests hold these to its own cell, and the
    # operand pattern over the block table finds half of a step's calls
    for name in ("sched.window_engaged_share", "cache.kv_held_share",
                 "programs.window_attn_share",
                 "kernels.paged_attn_decode_share", "kernels.paged_attn_share",
                 "kernels.paged_attn_roofline"):
        assert CELL not in named(bench["per_layer"], name)["workloads"]


def test_the_ssm_configuration_is_the_published_one_uncut(ssm_as_run):
    cfg = ssm_as_run
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["reduced"] == {}
    assert {"assumed", "deployment", "draw", "source", "builder"} <= set(cfg)
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_expand"],
            cfg["mamba_dt_rank"]) == (16, 4, 2, 160)
    for key in ("mamba_sizes", "memory", "differential_attention", "biases",
                "window_edge", "draws"):
        assert key in cfg["assumed"]
    # 3.85 B parameters, 7.70 GB in bf16
    from benchmarks.builders import ssm_hybrid as family
    w = family.costs.layer_weights(cfg)
    assert (w["mamba"], w["window"], w["cross"], w["gmu"], w["mlp"]) == (
        41_241_600, 19_660_800, 13_107_200, 26_214_400, 78_643_200)
    assert family.kinds(cfg) == {"mamba": 9, "window": 8, "full": 1,
                                 "cross": 7, "gmu": 7}
    params = family.costs.weight_params(cfg)
    assert 3.85e9 < params < 3.86e9 and 7.70e9 < 2 * params < 7.71e9


def test_the_ssm_costs_count_the_least_work_by_hand(ssm_as_run, ssm_sizes):
    from benchmarks.builders import ssm_hybrid as family
    cfg = ssm_as_run
    assert (ssm_sizes["slots"], ssm_sizes["pages_per_slot"], ssm_sizes["E"], ssm_sizes["N"],
            ssm_sizes["hd"], ssm_sizes["Lm"], ssm_sizes["Lw"], ssm_sizes["Lf"], ssm_sizes["Lx"]) \
        == (32, 768, 5120, 16, 64, 9, 8, 1, 7)
    w = COUNTED
    assert family.costs.kv_tokens(w, ssm_sizes) == (CTX, RINGED)
    row = 20 * 64 * 2                       # a token's K or V, bf16
    q = 32 * 40 * 64 * (2 + 2 * 4)
    flops, full = family.costs.paged_attn_full_step(cfg, ssm_sizes, w)
    assert full == 8 * (2 * CTX * row + q)
    assert flops == 8 * 2.0 * CTX * 40 * 3 * 64
    # the pages alone, read 8 times, are 6.29 GB a step: 7.7 ms at the peak
    assert 6.28e9 < 8 * 2 * CTX * row < full < 6.30e9
    _f, window = family.costs.paged_attn_window_step(cfg, ssm_sizes, w)
    assert window == 8 * (2 * RINGED * row + q)
    state = 9 * (3 * 5120 * 2 + 16 * 5120 * 4)
    assert family.costs.state_bytes(ssm_sizes) == state == 3_225_600
    _f, step = family.costs.decode_step(cfg, ssm_sizes, w)
    assert step == 2 * family.costs.weight_params(cfg) \
        + 2 * (8 * CTX + 8 * RINGED) * row + 2 * 32 * state
    flops, scan = family.costs.ssm_scan(cfg, ssm_sizes, w)
    assert scan == 9 * (700 * (3 * 5120 + 2 * 16) * 4
                        + 2 * 16 * 5120 * 4 + (16 * 5120 + 5120) * 4)
    assert flops == 9 * 700 * 5120 * (6.0 * 16 + 3)
    # without the engine's counts: every slot alike at the mean context,
    # and a scan of one row's whole chunk of 1,024
    bare = {"live_context_tokens": float(CTX)}
    assert family.costs.kv_tokens(bare, ssm_sizes) == (CTX, RINGED)
    _f, scan = family.costs.ssm_scan(cfg, ssm_sizes, bare)
    assert scan == 9 * (1024 * (3 * 5120 + 2 * 16) * 4
                        + 2 * 16 * 5120 * 4 + (16 * 5120 + 5120) * 4)
    assert set(family.costs.KERNEL_COSTS) == {
        "decode_step", "paged_attn_window_step", "paged_attn_full_step",
        "ssm_scan"}


def test_the_ssm_rooflines_read_their_kernels_in_their_programs(ssm_as_run, ssm_sizes):
    """The files over a hand-built tick of a full-table and a ring-view
    decode call a layer kind, and a prefill program of scan calls: each
    kernel by its name and its block table's shape (`s32[32,768]` for the
    full table, `s32[32,33]` a ring's view), the scan inside the program
    that holds it."""
    from benchmarks import reduce
    from benchmarks.builders import ssm_hybrid as family
    tiled, flat = "1,0:T(8,128)S(1)", "0"
    out = "f32[32,40,128]{2,1,0:T(8,128)}"
    pool = ("bf16[24577,10,16,128]", "3,2,1,0:T(16,128)(2,1)")
    full = kernel_call("paged_attention_decode", 7, out, [
        ("s32[32,768]", tiled), ("s32[32]", flat),
        ("bf16[32,40,128]", "2,1,0:T(16,128)(2,1)"), pool, pool])
    ring = kernel_call("paged_attention_decode", 8, out, [
        ("s32[32,33]", tiled), ("s32[32]", flat),
        ("bf16[32,40,128]", "2,1,0:T(16,128)(2,1)"), pool, pool])
    scan = kernel_call("ssm_scan", 3, "(f32[1,1024,5120]{2,1,0}, "
                       "f32[1,16,5120]{2,1,0})", [
                           ("f32[1,1024,5120]", "2,1,0:T(8,128)"),
                           ("f32[1,1024,5120]", "2,1,0:T(8,128)")])
    # a step's 8 calls over the full table as one, its 8 ring views as one
    took_full, took_ring, took_scan = 9_000_000, 1_200_000, 300_000
    planes = tick_of([(full, took_full), (ring, took_ring)],
                     prefill=[(scan, took_scan)] * 9)
    context = {"trace": reduce.Trace(planes), "window": COUNTED,
               "config": ssm_as_run, "device_kind": "TPU v5 lite",
               "sizes": ssm_sizes, "builder": family}
    roofline_file_reads(context, "kernels.paged_attn_full_roofline",
                        "paged_attn_full_step", took_full)
    roofline_file_reads(context, "kernels.paged_attn_window_roofline",
                        "paged_attn_window_step", took_ring)
    m = roofline_file_reads(context, "kernels.ssm_scan_roofline", "ssm_scan",
                            9 * took_scan)
    assert m["args"]["steps_per_module"] == 1


def test_ssm_counters_read_first_and_say_what_the_scan_took(capsys):
    from benchmarks.builders import ssm_hybrid as family
    eng = types.SimpleNamespace(stats={"ssm_scan_tokens": 100,
                                       "ssm_scan_calls": 1, "ticks": 3})
    opened = family.counters(eng)
    assert opened == dict(dict.fromkeys(family.COUNTED, 0),
                          ssm_scan_tokens=100, ssm_scan_calls=1)
    assert capsys.readouterr().out == ""
    eng.stats.update(ssm_scan_tokens=2100, ssm_scan_calls=5,
                     cross_skipped_tokens=1996)
    closed = family.counters(eng)
    assert closed["ssm_scan_tokens"] == 2100
    said = capsys.readouterr().out
    assert json.loads(said.split("[window] engine state ")[1]) == {
        "ssm_scan_tokens": 2000, "ssm_scan_calls": 4,
        "cross_skipped_tokens": 1996, "tokens_per_call": 500.0,
        "cross_skipped_share": 0.998}


def test_the_ssm_rehearsal_widths_are_the_familys_own(ssm_as_run):
    from benchmarks import run
    from benchmarks.builders import ssm_hybrid as family
    small = family.rehearse(ssm_as_run)
    assert set(small) <= set(ssm_as_run)
    cell = run.load_cell(CELL, rehearse=True)
    assert cell["config"]["hidden_size"] == 64
    assert cell["builder"] is family and family.flash_block_keys(
        cell["config"], cell["traffic"]) == []


def test_the_ssm_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference_ssm_hybrid.py")) as f:
        text = f.read()
    assert "paddle_tpu" not in text.replace("no code of the program", "")
    assert "import jax" in text and '"highest"' in text


def test_the_ssm_familys_files_are_reached_from_the_manifest():
    bench = load(ROOT, "BENCHMARK.json")
    cell = named(bench["workloads"], CELL)
    conf = named(bench["configs"], cell["config"])
    assert conf["file"] == "benchmarks/configs/phi-4-mini-flash-reasoning.json"
    assert load(ROOT, conf["file"])["builder"] == "ssm_hybrid"
    from benchmarks.builders import ssm_hybrid as family
    assert family.reference.__file__ == os.path.join(
        BENCH, "reference_ssm_hybrid.py")
    assert os.path.exists(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json"))
    for name in OWN_METRICS:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".json"))
