"""The yardstick's arithmetic: peaks, parameter counts, FLOPs and bytes.

Everything here is computed from a configuration's published sizes and the
cell's traffic, never read from the program (`models/llama.py:flops_per_token`
drops the output projection and counts attention as if it were not causal).

Conventions, the same for every configuration:
- a matmul of (m, k) x (k, n) is 2*m*k*n operations;
- training costs 3x the forward (forward, grad wrt input, grad wrt weight);
- recomputed operations are not counted: not the layers a `recompute` reruns,
  not the scores flash's backward rebuilds, not the logits blockwise CE's
  backward rebuilds;
- attention is causal: half of the S x S scores.

A family that this file's dense-decoder arithmetic does not fit brings its own
in its builder (`builders/<family>.py`: `costs`, an object with
`train_flops_per_token(cfg, seq)` and / or a dict `KERNEL_COSTS` of
`name -> fn(cfg, sizes, window) -> (flops, bytes)`); `mfu` and `least_seconds`
take it as `own`, ask it first and fall back to this file. The conventions
above bind a builder's functions too, and two more: everything comes from the
configuration's sizes and the traffic, never from the program; a cut that
stands for one chip's share of a larger layout (some of the experts, a slice
of the vocabulary) counts what this chip computes. A builder supplies
operations and bytes, never a peak or a time: `PEAKS` and `_least_s` are here
alone.
"""
from __future__ import annotations

# Published peaks of one chip, keyed by jax's `device_kind`. The benchmark
# keeps its own copy so that no later PR can move the yardstick by editing
# `paddle_tpu/device/peaks.py`. A kind that is not here is an error.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
                  "16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add its "
            f"row with a source to benchmarks/costs.py PEAKS")
    return PEAKS[device_kind]


def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_matmul_params(cfg):
    """Weights of one decoder layer that sit in a matmul (norms left out)."""
    d, f, hd = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    return attn + 3 * d * f


def layer_params(cfg):
    return layer_matmul_params(cfg) + 2 * cfg["hidden_size"]


def param_count(cfg):
    """Parameters the optimizer holds: a tied head is the embedding."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return v * d + cfg["num_hidden_layers"] * layer_params(cfg) + d + head


def matmul_params(cfg):
    """Weights every token is multiplied by: the layers and the output
    projection, tied or not. The embedding lookup is a gather, not a matmul."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def attention_flops_per_token(cfg, seq):
    """Forward + backward, causal: QK^T and PV are 2*2*S*(H*hd) a token
    forward over the full square, half of it causal, three times for
    training: 6 * L * (H*hd) * S."""
    return (6.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg) * seq)


def train_flops_per_token(cfg, seq):
    return 6.0 * matmul_params(cfg) + attention_flops_per_token(cfg, seq)


def mfu(cfg, seq, tokens_per_s_per_chip, device_kind, own=None):
    """Model FLOP/s utilization of one chip, in percent; the FLOPs a token
    are `own`'s (a builder's `costs`) where it counts them."""
    per_token = getattr(own, "train_flops_per_token", train_flops_per_token)
    return (100.0 * per_token(cfg, seq) * tokens_per_s_per_chip
            / peaks(device_kind)["bf16_flops"])


def _least_s(flops, bytes_, device_kind):
    p = peaks(device_kind)
    t_c, t_m = flops / p["bf16_flops"], bytes_ / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# -- kernel costs: (flops, bytes) of all calls of that kernel on ONE chip in
# -- ONE step. `sizes` are the per-chip sizes `run.py` derives (see README).

def flash_step(cfg, sizes, window=None):
    """Flash attention forward + backward over all layers: 2 matmuls forward
    and 4 backward, each 2*B*H*S*S*hd, half of it causal. Bytes: q, k, v, o
    once forward; q, k, v, o, do read and dq, dk, dv written backward."""
    b, h, s, hd = sizes["B"], sizes["H"], sizes["S"], sizes["hd"]
    layers = cfg["num_hidden_layers"]
    flops = layers * 6 * 2.0 * b * h * s * s * hd * 0.5
    kv = b * sizes["Hkv"] * s * hd * 2
    qo = b * h * s * hd * 2
    bytes_ = layers * ((2 * qo + 2 * kv) + (3 * qo + 2 * kv + qo + 2 * kv))
    return flops, bytes_


def ce_step(cfg, sizes, window=None):
    """Blockwise cross-entropy with the output projection inside it:
    logits forward, dx and dW backward, each 2*T*d*V. Bytes: the weight read
    three times and its f32-accumulated gradient written, the hidden rows
    read three times and their gradient written."""
    t, d, v = sizes["T"], sizes["d"], sizes["V"]
    flops = 3 * 2.0 * t * d * v
    bytes_ = 4 * d * v * 2 + 4 * t * d * 2
    return flops, bytes_


def decode_step(cfg, sizes, window):
    """One decode step of the whole batch: every bf16 matmul weight read
    once (the tied head is the embedding, read whole), and the K and V of
    every live context read once. FLOPs are 2 * weights * slots, far under
    the bytes at these batch sizes."""
    weight_bytes = 2.0 * matmul_params(cfg)
    kv_token = (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
                * head_dim(cfg) * 2)
    bytes_ = weight_bytes + kv_token * window["live_context_tokens"]
    flops = 2.0 * matmul_params(cfg) * sizes["slots"]
    return flops, bytes_


KERNEL_COSTS = {"flash_step": flash_step, "ce_step": ce_step,
                "decode_step": decode_step}


def least_seconds(cost, cfg, sizes, window, device_kind, own=None):
    """(seconds, 'compute' | 'memory') the chip could not beat; the cost
    is `own`'s (a builder's `costs`) where its `KERNEL_COSTS` names it."""
    fn = getattr(own, "KERNEL_COSTS", {}).get(cost) or KERNEL_COSTS[cost]
    flops, bytes_ = fn(cfg, sizes, window)
    return _least_s(flops, bytes_, device_kind)
