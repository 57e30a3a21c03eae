"""costs.py against the figures of ISSUE 24 and of the model cards.

Run by hand: JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import costs  # noqa: E402


def config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def test_smollm2_parameters():
    cfg = config("smollm2-1.7b-8l")
    assert costs.head_dim(cfg) == 64
    assert costs.layer_matmul_params(cfg) == 67_108_864        # 67.1 M a layer
    # 8 x 67.1 M + 100.7 M tied + norms = 638 M trained parameters
    assert costs.param_count(cfg) == 8 * (67_108_864 + 4096) + 100_663_296 + 2048
    assert round(costs.param_count(cfg) / 1e6) == 638
    whole = config("smollm2-1.7b")
    assert round(costs.param_count(whole) / 1e9, 2) == 1.71    # "1.7B"


# Mistral-7B-v0.3's published sizes (huggingface.co/mistralai/Mistral-7B-v0.3),
# cut to 8 layers: no cell runs it yet (PERF.md section 7); it keeps the
# arithmetic honest where kv heads are fewer than heads and the head is untied.
MISTRAL = {"hidden_size": 4096, "intermediate_size": 14336,
           "num_hidden_layers": 8, "num_attention_heads": 32,
           "num_key_value_heads": 8, "vocab_size": 32768,
           "tie_word_embeddings": False}


def test_mistral_parameters():
    cfg = MISTRAL
    assert costs.head_dim(cfg) == 128
    assert costs.layer_matmul_params(cfg) == 218_103_808       # 218 M a layer
    assert costs.param_count(cfg) == 2_013_335_552             # 2.01 B at 8 layers
    full = dict(cfg, num_hidden_layers=32)
    assert round(costs.param_count(full) / 1e9, 2) == 7.25     # "7B", 7.25 B


def test_train_flops_count_the_output_projection_and_causal_attention():
    cfg = config("smollm2-1.7b-8l")
    # tied, and still multiplied by: 6 x (layers + d x V)
    assert costs.matmul_params(cfg) == 8 * 67_108_864 + 2048 * 49152
    assert costs.attention_flops_per_token(cfg, 2048) == 6 * 8 * 2048 * 2048
    assert costs.train_flops_per_token(cfg, 2048) == 4_026_531_840
    # half the non-causal count of models/llama.py:flops_per_token (12*L*d*S)
    assert costs.attention_flops_per_token(cfg, 2048) * 2 == 12 * 8 * 2048 * 2048
    assert costs.mfu(cfg, 2048, 27_300.0, "TPU v5 lite") \
        == pytest.approx(55.80, abs=0.01)


def test_mistral_flops_use_query_heads_not_hidden_over_heads():
    cfg = MISTRAL
    assert costs.attention_flops_per_token(cfg, 4096) == 6 * 8 * 32 * 128 * 4096
    assert costs.train_flops_per_token(cfg, 4096) \
        == 6 * (8 * 218_103_808 + 4096 * 32768) + 6 * 8 * 4096 * 4096


def test_kernel_costs():
    cfg = config("smollm2-1.7b-8l")
    sizes = {"B": 4, "H": 32, "Hkv": 32, "S": 2048, "hd": 64,
             "T": 8192, "d": 2048, "V": 49152}
    flops, _ = costs.flash_step(cfg, sizes)
    # the attention term of the model's own FLOPs, for the step's tokens
    assert flops == costs.attention_flops_per_token(cfg, 2048) * 8192
    assert costs.least_seconds("flash_step", cfg, sizes, {}, "TPU v5 lite") \
        == (pytest.approx(flops / 197e12), "compute")
    flops, _ = costs.ce_step(cfg, sizes)
    assert flops == 6 * 8192 * 2048 * 49152
    whole = config("smollm2-1.7b")
    window = {"live_context_tokens": 6400.0}
    _f, bytes_ = costs.decode_step(whole, {"slots": 16}, window)
    weights = 2 * (24 * 67_108_864 + 2048 * 49152)
    kv = 6400 * 2 * 24 * 32 * 64 * 2
    assert bytes_ == weights + kv
    assert costs.least_seconds("decode_step", whole, {"slots": 16}, window,
                               "TPU v5 lite") \
        == (pytest.approx((weights + kv) / 819e9), "memory")


def test_a_builders_costs_are_asked_first_and_costs_py_otherwise():
    """A family brings operations and bytes (`own`); the peaks stay here."""
    import types
    cfg = config("smollm2-1.7b-8l")
    sizes = {"B": 4, "H": 32, "Hkv": 32, "S": 2048, "hd": 64,
             "T": 8192, "d": 2048, "V": 49152}
    own = types.SimpleNamespace(
        train_flops_per_token=lambda cfg, seq: 1.97e9,
        KERNEL_COSTS={"flash_step": lambda cfg, sizes, window: (197e12, 1.0),
                      "experts_step": lambda cfg, sizes, window:
                      (1.0, 819e9 * sizes["B"])})
    assert costs.mfu(cfg, 2048, 27_300.0, "TPU v5 lite", own=own) \
        == pytest.approx(100 * 1.97e9 * 27_300.0 / 197e12)
    assert costs.least_seconds("flash_step", cfg, sizes, {}, "TPU v5 lite",
                               own=own) == (pytest.approx(1.0), "compute")
    assert costs.least_seconds("experts_step", cfg, sizes, {}, "TPU v5 lite",
                               own=own) == (pytest.approx(4.0), "memory")
    # what it does not name is costs.py's, and so is everything without it
    dense = costs.least_seconds("ce_step", cfg, sizes, {}, "TPU v5 lite")
    assert costs.least_seconds("ce_step", cfg, sizes, {}, "TPU v5 lite",
                               own=own) == dense
    only_kernels = types.SimpleNamespace(KERNEL_COSTS={})
    assert costs.mfu(cfg, 2048, 27_300.0, "TPU v5 lite", own=only_kernels) \
        == costs.mfu(cfg, 2048, 27_300.0, "TPU v5 lite", own=None) \
        == costs.mfu(cfg, 2048, 27_300.0, "TPU v5 lite")
    with pytest.raises(KeyError):
        costs.least_seconds("experts_step", cfg, sizes, {}, "TPU v5 lite")
    # the dense builder offers costs.py itself: the same numbers by either way
    from benchmarks.builders import llama_dense
    assert llama_dense.costs is costs


def test_unknown_device_is_an_error():
    assert costs.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("TPU v9")
