"""Builder `ssm_hybrid`: a configuration file's published keys -> the
program's decoder-hybrid-decoder with Mamba layers, differential attention
in a window and over everything, and a cross-decoder through gated memory
units (`paddle_tpu.models.ssm_hybrid.SsmHybridForCausalLM`), weights made
on the device from the seed; with the family's own reference, yardstick,
counters and rehearsal sizes. Serving only: the selective scan has no
backward pass here.

The engine keeps the window layers' K and V in rings, the full layer's in
pages that the cross layers read too, and each Mamba layer's conv window
and SSM state a slot; it counts `ssm_scan_tokens` (valid prompt tokens
through the scan), `ssm_scan_calls` (the prefill programs that ran it),
`cross_skipped_tokens` (prompt positions at which the cross-decoder did
not run) and, a decode step, the keys of the two tables
(`kv_tokens_flat`, `kv_tokens_held`).
"""
from __future__ import annotations

import json
import math
import types

import numpy as np

from benchmarks import reference_ssm_hybrid as reference  # noqa: F401


def model_config(cfg, seq):
    from paddle_tpu.models.ssm_hybrid import SsmHybridConfig
    return SsmHybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        mb_per_layer=cfg["mb_per_layer"],
        sliding_window=cfg["sliding_window"],
        layer_norm_eps=cfg["layer_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        hidden_act=cfg["hidden_act"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        mlp_bias=cfg["mlp_bias"], lm_head_bias=cfg["lm_head_bias"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"],
        mamba_dt_rank=cfg["mamba_dt_rank"],
        initializer_range=cfg.get("initializer_range", 0.02), seq_length=seq)


def _mamba_init(name, shape, key):
    """Mamba's own draw of the two parameters a normal draw would make
    degenerate, or None: A = -(1 .. N) for every channel, and b_dt such
    that softplus(b_dt) is log-uniform in [1e-3, 1e-1]."""
    import jax
    import jax.numpy as jnp
    if name.endswith("mixer.A_log"):
        return jnp.log(jnp.broadcast_to(
            jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape))
    if name.endswith("mixer.dt_proj.bias"):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    return None


def build(cfg, seed, *, dtype, seq, settings):
    """The program's model object, every weight drawn in ONE jitted call
    from `seed`, in `dtype`: normal(0, initializer_range) for matrices,
    ones for norm weights, except where the configuration's `draw` names a
    parameter by the end of its name (`std` a matrix's deviation, `fill` a
    vector's constant, `normal` a vector's normal deviation about 0) and
    the two parameters Mamba draws by rule (`_mamba_init`). The
    constructor runs under `jax.eval_shape`, so it allocates nothing."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.jit.functional import state_tensors
    from paddle_tpu.models.ssm_hybrid import SsmHybridForCausalLM

    if settings:
        raise ValueError(f"this family takes no model settings: {settings}")
    mcfg = model_config(cfg, seq)
    held = {}

    def construct():
        held["model"] = SsmHybridForCausalLM(mcfg)
        return {n: t._value for n, t in state_tensors(held["model"]).items()}

    shapes = jax.eval_shape(construct)
    paddle_tpu.seed(int(seed) % (2 ** 31))   # and drop the traced key
    model = held["model"]
    names = sorted(shapes)
    std = float(mcfg.initializer_range)
    jdt = jnp.dtype(dtype)
    how = cfg.get("draw", {})

    def named(table, n, default):
        return next((float(v) for end, v in table.items() if n.endswith(end)),
                    default)

    def draw(seed_word):
        # the chip's own generator (llama_dense.build)
        key = jax.random.fold_in(jax.random.key(0, impl="rbg"), seed_word)
        out = {}
        for i, n in enumerate(names):
            shape, k = shapes[n].shape, jax.random.fold_in(key, i)
            rule = _mamba_init(n, shape, k)
            sd = named(how.get("normal", {}), n, None) if len(shape) < 2 \
                else named(how.get("std", {}), n, std)
            if rule is not None:
                out[n] = rule.astype(jdt)
            elif sd is None:
                out[n] = jnp.full(shape, named(how.get("fill", {}), n, 1.0),
                                  jdt)
            else:
                out[n] = (jax.random.normal(k, shape, jnp.float32)
                          * sd).astype(jdt)
        return out

    values = jax.jit(draw)(np.uint32(int(seed) % (2 ** 32)))
    for n, t in state_tensors(model).items():
        t._value = values[n]
    if jdt != jnp.float32:
        model = paddle_tpu.amp.decorate(models=model, level="O2",
                                        dtype=str(jdt))
    return model


def kinds(cfg):
    """How many layers of each kind: Mamba, window, full, cross, memory
    unit."""
    k = reference.kinds(cfg)
    return {name: k.count(name)
            for name in ("mamba", "window", "full", "cross", "gmu")}


def sizes(cfg, traffic):
    """The sizes patterns over event text and the cost functions are given:
    `E` the Mamba channels, `N` its states, `R` delta's rank, `K` the conv's
    width, `W` the window, `hd` a head's width (a kv pair is cached as one
    head of 2 hd), and the layers of each kind (`Lm`, `Lw`, `Lf`, `Lx`,
    `Lg`)."""
    n = kinds(cfg)
    out = {"d": cfg["hidden_size"], "f": cfg["intermediate_size"],
           "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
           "H": cfg["num_attention_heads"], "Hkv": cfg["num_key_value_heads"],
           "hd": cfg["hidden_size"] // cfg["num_attention_heads"],
           "E": cfg["mamba_expand"] * cfg["hidden_size"],
           "N": cfg["mamba_d_state"], "R": cfg["mamba_dt_rank"],
           "K": cfg["mamba_d_conv"], "W": cfg["sliding_window"],
           "Lm": n["mamba"], "Lw": n["window"], "Lf": n["full"],
           "Lx": n["cross"], "Lg": n["gmu"]}
    eng = traffic["engine"]
    out.update(slots=eng["max_slots"], page=eng["page_size"],
               pages_per_slot=eng["max_pages_per_slot"],
               steps_per_tick=eng["steps_per_tick"])
    return out


def flash_block_keys(cfg, traffic):
    return []       # no flash call: prefill attends through the paged path


# the engine's counts the cost functions and the counter metrics read
COUNTED = ("ssm_scan_tokens", "ssm_scan_calls", "cross_skipped_tokens",
           "prefill_rows_run", "decode_slot_steps", "window_engaged_steps",
           "kv_tokens_held", "kv_tokens_flat")
# what `counters` read as the window opened, by engine, to say at its close
# what the window's prefills ran through the scan and skipped
_OPENED = {}


def counters(eng):
    """What the cost functions and the counter metrics read of the engine's
    own counts, 0 where an engine does not keep one, every one read before
    anything is printed. The second call on an engine (the window's close)
    also prints, on a line of its own, the window's prompt tokens through
    the scan, a program's mean, and the share of them at which the
    cross-decoder did not run."""
    out = {k: eng.stats.get(k, 0) for k in COUNTED}
    opened = _OPENED.pop(id(eng), None)
    if opened is None:
        _OPENED[id(eng)] = out
    else:
        d = {k: out[k] - opened[k] for k in COUNTED}
        tokens, calls = d["ssm_scan_tokens"], d["ssm_scan_calls"]
        print("[window] engine state", json.dumps(
            {"ssm_scan_tokens": tokens, "ssm_scan_calls": calls,
             "cross_skipped_tokens": d["cross_skipped_tokens"],
             "tokens_per_call": tokens / calls if calls else None,
             "cross_skipped_share": d["cross_skipped_tokens"] / tokens
             if tokens else None}), flush=True)
    return out


def rehearse(cfg):
    """The family's own small widths for `--rehearse`: eight layers, every
    kind kept, a window that the rehearsal's 8-32-token prompts cross."""
    return {"hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 8, "num_attention_heads": 4,
            "num_key_value_heads": 2, "vocab_size": 256,
            "sliding_window": 8, "max_position_embeddings": 512,
            "mamba_d_state": 8, "mamba_dt_rank": 4}


def layer_weights(cfg):
    """Parameters of a layer's mixer by kind, and of its MLP: what a decode
    step reads of them (the Mamba mixer's conv, A, D and b_dt too)."""
    d, h, hkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    hd = d // h
    e, n, r, k = (cfg["mamba_expand"] * d, cfg["mamba_d_state"],
                  cfg["mamba_dt_rank"], cfg["mamba_d_conv"])
    mamba = d * 2 * e + e * (r + 2 * n) + r * e + e * d + e * (k + n + 3)
    attn = 2 * d * h * hd + 2 * d * hkv * hd
    cross = 2 * d * h * hd
    return {"mamba": mamba, "window": attn, "full": attn, "cross": cross,
            "gmu": 2 * d * e, "mlp": 3 * d * cfg["intermediate_size"]}


def weight_params(cfg):
    """Every parameter a decode step reads: the layers, and the tied head
    (the embedding, read whole)."""
    w = layer_weights(cfg)
    n = kinds(cfg)
    return (sum(n[kind] * w[kind] for kind in n)
            + cfg["num_hidden_layers"] * w["mlp"]
            + cfg["vocab_size"] * cfg["hidden_size"])


def _steps(window, sizes):
    return window.get("ticks", 0) * sizes["steps_per_tick"]


def kv_tokens(window, sizes):
    """(keys the full layer's pages hold, keys a window layer's ring holds
    for a step) a decode step, all slots together. From the engine's
    counts; without them, from the mean context as if every slot were
    alike."""
    steps = _steps(window, sizes)
    if steps and window.get("kv_tokens_flat"):
        whole = window["kv_tokens_flat"] / (sizes["Lw"] + sizes["Lf"]) / steps
        ringed = (window["kv_tokens_held"] / steps
                  - sizes["Lf"] * whole) / sizes["Lw"]
        return whole, ringed
    live = window["live_context_tokens"]
    return live, min(live, sizes["slots"] * sizes["W"])


def _kv_row(sizes):
    return sizes["Hkv"] * sizes["hd"] * 2       # one token's K or V, bf16


def _q_bytes(sizes):
    # the model's query read in bf16, a^1 and a^2 of every pair written in
    # float32 (2 hd each)
    return sizes["slots"] * sizes["H"] * sizes["hd"] * (2 + 2 * 4)


def _attn_flops(keys, sizes):
    # q^j . k^j over hd, then softmax(...) v over 2 hd, for every query head
    return 2.0 * keys * sizes["H"] * 3 * sizes["hd"]


def paged_attn_window_step(cfg, sizes, window):
    """The window layers' attention calls of one decode step: K and V of
    min(context, window) tokens a slot, the query and the output."""
    _whole, ringed = kv_tokens(window, sizes)
    return (sizes["Lw"] * _attn_flops(ringed, sizes),
            sizes["Lw"] * (2 * ringed * _kv_row(sizes) + _q_bytes(sizes)))


def paged_attn_full_step(cfg, sizes, window):
    """The calls over the full table of one decode step: the full layer's
    and every cross layer's, each reading K and V of every live context."""
    whole, _ringed = kv_tokens(window, sizes)
    layers = sizes["Lf"] + sizes["Lx"]
    return (layers * _attn_flops(whole, sizes),
            layers * (2 * whole * _kv_row(sizes) + _q_bytes(sizes)))


def state_bytes(sizes):
    """A slot's state in every Mamba layer: the conv window in bf16 and
    the SSM state in float32."""
    return sizes["Lm"] * ((sizes["K"] - 1) * sizes["E"] * 2
                          + sizes["N"] * sizes["E"] * 4)


def decode_step(cfg, sizes, window):
    """One decode step of the whole batch, the LEAST bytes whatever
    implements it: every parameter read once (the tied head is the
    embedding), the full layer's K and V of the live contexts read by it
    and by every cross layer, the rings' keys inside the window, and every
    slot's state read and written."""
    whole, ringed = kv_tokens(window, sizes)
    cache = 2 * ((sizes["Lf"] + sizes["Lx"]) * whole
                 + sizes["Lw"] * ringed) * _kv_row(sizes)
    state = 2 * sizes["slots"] * state_bytes(sizes)
    flops = (2.0 * weight_params(cfg) * sizes["slots"]
             + _attn_flops((sizes["Lf"] + sizes["Lx"]) * whole
                           + sizes["Lw"] * ringed, sizes))
    return flops, 2.0 * weight_params(cfg) + cache + state


def ssm_scan(cfg, sizes, window):
    """The selective scan kernel's calls of ONE prefill program (all its
    Mamba layers): per valid token u, delta (E each) and B, C (N each) read
    and y (E) written at the kernel's float32, and a row's state (N x E
    float32) in and out, with A and D; a program's tokens and rows are the
    window's means (the engine's `ssm_scan_tokens`, `prefill_rows_run` over
    `ssm_scan_calls`), without the counts a chunk of 1,024 tokens of one
    row. Its FLOPs are the recurrence's multiply-adds, 6 N + 3 a token a
    channel: a count for the vector unit, which never binds against the
    matrix unit's peak that `costs.py` holds it to, so the share reads the
    bytes' bound."""
    calls = window.get("ssm_scan_calls")
    tokens = window["ssm_scan_tokens"] / calls if calls else 1024.0
    rows = window.get("prefill_rows_run", 0) / calls if calls else 1.0
    e, n = sizes["E"], sizes["N"]
    bytes_ = sizes["Lm"] * (tokens * (3 * e + 2 * n) * 4
                            + rows * 2 * n * e * 4 + (n * e + e) * 4)
    return sizes["Lm"] * tokens * e * (6.0 * n + 3), bytes_


# The family's yardstick, as `reduce.py` asks for it: operations and bytes
# from the configuration's sizes and the window's counters, never a peak or
# a time (costs.py keeps those). Each counts the LEAST work, whatever
# implements it: each K and V row once at the model's width, the keys a
# window holds, a state read and written once a step.
costs = types.SimpleNamespace(
    layer_weights=layer_weights, weight_params=weight_params,
    kv_tokens=kv_tokens, state_bytes=state_bytes,
    paged_attn_window_step=paged_attn_window_step,
    paged_attn_full_step=paged_attn_full_step, decode_step=decode_step,
    ssm_scan=ssm_scan,
    KERNEL_COSTS={"decode_step": decode_step,
                  "paged_attn_window_step": paged_attn_window_step,
                  "paged_attn_full_step": paged_attn_full_step,
                  "ssm_scan": ssm_scan})
