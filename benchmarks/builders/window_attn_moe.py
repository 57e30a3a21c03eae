"""Builder `window_attn_moe`: a configuration file's published keys -> the
program's decoder of window and full attention layers with gated attention
and sigmoid-routed experts of which a share is held
(`paddle_tpu.models.WindowAttnMoeForCausalLM`), weights made on the device
from the seed; with the family's own reference, yardstick, counters and
rehearsal sizes. Serving only: the routing bias is set by a balancing rule
that is no part of a configuration.

In the configuration file `num_experts` counts the experts HELD here (the
chip's share of an expert-parallel group), `router_experts` the experts the
router scores and `held_experts_first` the first one held.
"""
from __future__ import annotations

import json
import types

import numpy as np

from benchmarks import reference_window_attn_moe as reference  # noqa: F401

WINDOW = "sliding_attention"


def model_config(cfg, seq):
    from paddle_tpu.models.window_attn_moe import WindowAttnMoeConfig
    return WindowAttnMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_dense_layers=cfg["num_dense_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], layer_types=list(cfg["layer_types"]),
        sliding_window=cfg["sliding_window"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        initializer_range=cfg.get("initializer_range", 0.02),
        mup_enabled=cfg["mup_enabled"],
        num_experts=cfg["router_experts"],
        held_experts=(cfg["held_experts_first"], cfg["num_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        score_func=cfg["score_func"], route_norm=cfg["route_norm"],
        route_scale=cfg["route_scale"], seq_length=seq)


def build(cfg, seed, *, dtype, seq, settings):
    """The program's model object, every weight drawn in ONE jitted call
    from `seed`, in `dtype`: normal(0, initializer_range) for matrices
    (the stacked experts too), ones for norm weights, except where the
    configuration's `draw` names a parameter by the end of its name: `std`
    gives a matrix another deviation, `fill` a vector a constant, `normal`
    a vector a normal draw of that deviation about 0 (the routing bias: at
    0 choosing by score + bias and weighing by score would be one thing).
    The constructor runs under `jax.eval_shape`, so it allocates nothing."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.jit.functional import state_tensors
    from paddle_tpu.models.window_attn_moe import WindowAttnMoeForCausalLM

    if settings:
        raise ValueError(f"this family takes no model settings: {settings}")
    mcfg = model_config(cfg, seq)
    held = {}

    def construct():
        held["model"] = WindowAttnMoeForCausalLM(mcfg)
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
            shape = shapes[n].shape
            sd = named(how.get("normal", {}), n, None) if len(shape) < 2 \
                else named(how.get("std", {}), n, std)
            if sd is None:
                out[n] = jnp.full(shape, named(how.get("fill", {}), n, 1.0),
                                  jdt)
            else:
                out[n] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                            jnp.float32) * sd).astype(jdt)
        return out

    values = jax.jit(draw)(np.uint32(int(seed) % (2 ** 32)))
    for n, t in state_tensors(model).items():
        t._value = values[n]
    if jdt != jnp.float32:
        model = paddle_tpu.amp.decorate(models=model, level="O2",
                                        dtype=str(jdt))
    return model


def layers_of(cfg):
    """(dense, expert, window, full) layers as run."""
    kinds = cfg["layer_types"]
    window = sum(k == WINDOW for k in kinds)
    dense = cfg["num_dense_layers"]
    return dense, len(kinds) - dense, window, len(kinds) - window


def sizes(cfg, traffic):
    """The sizes patterns over event text and the cost functions are given.
    `E` is the experts HELD: what a step can hit."""
    dense, expert, window, full = layers_of(cfg)
    out = {"d": cfg["hidden_size"], "f": cfg["moe_intermediate_size"],
           "fd": cfg["intermediate_size"], "V": cfg["vocab_size"],
           "L": cfg["num_hidden_layers"], "Ld": dense, "Le": expert,
           "Lw": window, "Lf": full, "hd": cfg["head_dim"],
           "H": cfg["num_attention_heads"],
           "Hkv": cfg["num_key_value_heads"], "E": cfg["num_experts"],
           "Er": cfg["router_experts"], "k": cfg["num_experts_per_tok"],
           "W": cfg["sliding_window"]}
    eng = traffic["engine"]
    out.update(slots=eng["max_slots"], page=eng["page_size"],
               pages_per_slot=eng["max_pages_per_slot"],
               steps_per_tick=eng["steps_per_tick"])
    return out


def flash_block_keys(cfg, traffic):
    return []       # no flash call: prefill attends through the paged path


def counters(eng):
    """What the cost functions and the counter metrics read of the engine's
    own counts, 0 where an engine does not keep one. Each call (the
    window's opening and its close) also says, on a line of its own, what
    the two page tables hold: a window layer's pool is its ring's size,
    read from the engine (`page_groups`)."""
    print("[window] engine tables", json.dumps(eng.page_groups()),
          flush=True)
    return {k: eng.stats.get(k, 0) for k in (
        "moe_experts_hit", "moe_layer_steps", "moe_pairs_held",
        "moe_pairs_routed", "decode_slot_steps", "window_engaged_steps",
        "kv_tokens_held", "kv_tokens_flat")}


def rehearse(cfg):
    """The family's own small widths for `--rehearse`: a dense window
    layer, two expert window layers and an expert full layer, 4 of 8
    experts held, with a window that the rehearsal's 8-32-token prompts
    cross."""
    return {"hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 4, "num_dense_layers": 1,
            "layer_types": [WINDOW, WINDOW, WINDOW, "full_attention"],
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 32, "vocab_size": 256, "sliding_window": 8,
            "max_position_embeddings": 512, "moe_intermediate_size": 32,
            "num_experts": 4, "router_experts": 8, "held_experts_first": 2,
            "num_experts_per_tok": 2}


def layer_weights(cfg):
    """(attention with its gate, router, one expert, the dense layer's
    second half) matmul parameters."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = 3 * d * h * hd + 2 * d * hkv * hd        # q, gate, o; k, v
    return (attn, d * cfg["router_experts"],
            3 * d * cfg["moe_intermediate_size"],
            3 * d * cfg["intermediate_size"])


def _steps(window, sizes):
    return window.get("ticks", 0) * sizes["steps_per_tick"]


def hits(window, sizes):
    """Distinct HELD experts hit a layer a decode step (the engine's
    count); without the count, the most the rows can hit."""
    if window.get("moe_layer_steps"):
        return window["moe_experts_hit"] / window["moe_layer_steps"]
    return float(min(sizes["E"], sizes["slots"] * sizes["k"]))


def pairs_held(window, sizes):
    """(row, expert) pairs that fall on held experts, a layer a decode
    step; without the count, the share an even router gives."""
    if window.get("moe_layer_steps"):
        return window["moe_pairs_held"] / window["moe_layer_steps"]
    return sizes["slots"] * sizes["k"] * sizes["E"] / sizes["Er"]


def kv_tokens(window, sizes):
    """(tokens a full layer reads, tokens a window layer reads) a decode
    step, all slots together: each live context, in a window layer cut to
    the window. From the engine's counts; without them, from the mean
    context as if every slot were alike."""
    steps = _steps(window, sizes)
    if steps and window.get("kv_tokens_flat") and sizes["Lw"]:
        whole = window["kv_tokens_flat"] / sizes["L"] / steps
        ringed = (window["kv_tokens_held"] / steps
                  - sizes["Lf"] * whole) / sizes["Lw"]
        return whole, ringed
    live = window["live_context_tokens"]
    return live, min(live, sizes["slots"] * sizes["W"])


def _kv_row(sizes):
    return sizes["Hkv"] * sizes["hd"] * 2           # one token's K or V, bf16


def _q_bytes(sizes):
    # the query read in bf16, the output written in float32
    return sizes["slots"] * sizes["H"] * sizes["hd"] * (2 + 4)


def moe_experts_step(cfg, sizes, window):
    """The routed experts of one decode step at the share: each held
    expert hit read once; the pairs that fall on held experts computed."""
    expert = layer_weights(cfg)[2]
    bytes_ = sizes["Le"] * (hits(window, sizes) * expert * 2
                            + 2 * sizes["slots"] * sizes["d"] * 2)
    return sizes["Le"] * 2.0 * pairs_held(window, sizes) * expert, bytes_


def paged_attn_window_step(cfg, sizes, window):
    """The window layers' attention calls of one decode step: K and V of
    min(context, window) tokens a slot, the query and the output."""
    _whole, ringed = kv_tokens(window, sizes)
    bytes_ = sizes["Lw"] * (2 * ringed * _kv_row(sizes) + _q_bytes(sizes))
    return (sizes["Lw"] * 2 * 2.0 * ringed * sizes["H"] * sizes["hd"],
            bytes_)


def paged_attn_full_step(cfg, sizes, window):
    """The full layers' attention calls of one decode step: K and V of
    every live context."""
    whole, _ringed = kv_tokens(window, sizes)
    bytes_ = sizes["Lf"] * (2 * whole * _kv_row(sizes) + _q_bytes(sizes))
    return sizes["Lf"] * 2 * 2.0 * whole * sizes["H"] * sizes["hd"], bytes_


def prefill_attn_chunk(cfg, sizes, window):
    """The attention calls of ONE prefill chunk of `chunk_tokens` tokens
    (default: as many as the window) that starts at `context_tokens`
    (default: two windows): in a window layer each token attends over the
    window's keys, in a full layer over every key up to its own; each key
    the chunk sees is read once, the queries read and the outputs written
    in bf16."""
    chunk = window.get("chunk_tokens", sizes["W"])
    start = window.get("context_tokens", 2 * sizes["W"])
    heads = sizes["H"] * sizes["hd"]
    ringed = sum(min(start + j + 1, sizes["W"]) for j in range(chunk))
    whole = chunk * start + chunk * (chunk + 1) // 2
    flops = 2 * 2.0 * heads * (sizes["Lw"] * ringed + sizes["Lf"] * whole)
    keys = (sizes["Lw"] * (min(start, sizes["W"] - 1) + chunk)
            + sizes["Lf"] * (start + chunk))
    bytes_ = 2 * keys * _kv_row(sizes) + sizes["L"] * 2 * chunk * heads * 2
    return flops, bytes_


def decode_step(cfg, sizes, window):
    """One decode step of the whole batch, the LEAST bytes whatever
    implements it: attention (with its gate), router and shared-expert
    weights, the dense layers, the held experts hit, the head's slice, and
    K, V of min(context, window) tokens in window layers and of the
    context in full ones."""
    attn, router, expert, dense = layer_weights(cfg)
    head = sizes["d"] * sizes["V"]
    weights = (sizes["L"] * attn + sizes["Ld"] * dense + head
               + sizes["Le"] * (router + expert
                                + hits(window, sizes) * expert))
    whole, ringed = kv_tokens(window, sizes)
    cache = 2 * (sizes["Lf"] * whole + sizes["Lw"] * ringed) * _kv_row(sizes)
    active = (sizes["L"] * attn + sizes["Ld"] * dense + head
              + sizes["Le"] * (router + expert)) * sizes["slots"] \
        + sizes["Le"] * pairs_held(window, sizes) * expert
    return 2.0 * active, 2.0 * weights + cache


# The family's yardstick, as `reduce.py` asks for it: operations and bytes
# from the configuration's sizes and the window's counters, never a peak or
# a time (costs.py keeps those). Each counts the LEAST work, whatever
# implements it: the held experts the rows hit, the K and V rows inside the
# window, never a page a mask throws away.
costs = types.SimpleNamespace(
    layer_weights=layer_weights, hits=hits, pairs_held=pairs_held,
    kv_tokens=kv_tokens, moe_experts_step=moe_experts_step,
    paged_attn_window_step=paged_attn_window_step,
    paged_attn_full_step=paged_attn_full_step,
    prefill_attn_chunk=prefill_attn_chunk, decode_step=decode_step,
    KERNEL_COSTS={"decode_step": decode_step,
                  "moe_experts_step": moe_experts_step,
                  "paged_attn_window_step": paged_attn_window_step,
                  "paged_attn_full_step": paged_attn_full_step,
                  "prefill_attn_chunk": prefill_attn_chunk})
