"""Builder `latent_attn_moe`: a configuration file's published keys -> the
program's decoder with latent attention (MLA, no q-LoRA) and routed experts
beside shared ones (`paddle_tpu.models.LatentAttnMoeForCausalLM`), weights
made on the device from the seed; with the family's own reference,
yardstick, counters and rehearsal sizes. Serving only: the routing's
correction bias is set by a balancing rule that is no part of a
configuration.

The engine keeps one latent row a token a layer ([c | k_pe], `kv_lora_rank`
+ `qk_rope_head_dim` values) and counts what its attention reads of them:
`mla_decode_rows` (each live slot's keys, every layer, a decode step) and
`mla_expand_rows` (rows a prefill expanded through W_kvb).
"""
from __future__ import annotations

import json
import types

import numpy as np

from benchmarks import reference_latent_attn_moe as reference  # noqa: F401


def model_config(cfg, seq):
    from paddle_tpu.models.latent_attn_moe import LatentAttnMoeConfig
    return LatentAttnMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        rope_interleave=cfg["rope_interleave"],
        rope_scaling=cfg["rope_scaling"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg.get("initializer_range", 0.02),
        n_routed_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        scoring_func=cfg["scoring_func"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        topk_method=cfg["topk_method"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], seq_length=seq)


def build(cfg, seed, *, dtype, seq, settings):
    """The program's model object, every weight drawn in ONE jitted call
    from `seed`, in `dtype`: normal(0, initializer_range) for matrices
    (the stacked experts too), ones for norm weights, except where the
    configuration's `draw` names a parameter by the end of its name: `std`
    gives a matrix another deviation, `fill` a vector a constant, `normal`
    a vector a normal draw of that deviation about 0 (the correction bias).
    The constructor runs under `jax.eval_shape`, so it allocates nothing."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.jit.functional import state_tensors
    from paddle_tpu.models.latent_attn_moe import LatentAttnMoeForCausalLM

    if settings:
        raise ValueError(f"this family takes no model settings: {settings}")
    mcfg = model_config(cfg, seq)
    held = {}

    def construct():
        held["model"] = LatentAttnMoeForCausalLM(mcfg)
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


def sizes(cfg, traffic):
    """The sizes patterns over event text and the cost functions are given:
    `R` the latent rank, `row` the values of a latent row as the model
    defines it (R + rope), `nope`, `rope`, `dv` the head widths."""
    dense = cfg["first_k_dense_replace"]
    out = {"d": cfg["hidden_size"], "f": cfg["moe_intermediate_size"],
           "fd": cfg["intermediate_size"], "V": cfg["vocab_size"],
           "L": cfg["num_hidden_layers"], "Ld": dense,
           "Le": cfg["num_hidden_layers"] - dense,
           "H": cfg["num_attention_heads"], "E": cfg["n_routed_experts"],
           "k": cfg["num_experts_per_tok"],
           "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
           "R": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
           "rope": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
           "row": cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]}
    eng = traffic["engine"]
    out.update(slots=eng["max_slots"], page=eng["page_size"],
               pages_per_slot=eng["max_pages_per_slot"],
               steps_per_tick=eng["steps_per_tick"])
    return out


def flash_block_keys(cfg, traffic):
    return []       # no flash call: prefill attends through the paged path


# the engine's counts the cost functions and the counter metrics read
COUNTED = ("moe_experts_hit", "moe_layer_steps", "mla_decode_rows",
           "mla_expand_rows", "mla_prefill_tokens")
# what `counters` read as the window opened, by engine, to say at its close
# how many latent rows a prompt token cost to expand inside the window
_OPENED = {}


def counters(eng):
    """What the cost functions and the counter metrics read of the engine's
    own counts, 0 where an engine does not keep one, every one read before
    anything is printed. The second call on an engine (the window's close)
    also prints, on a line of its own, the latent rows the window's
    prefills expanded a prompt token (`mla_expand_rows` /
    `mla_prefill_tokens` over the window): what the expanded form's
    re-expansion of a slot's context costs, chunk by chunk."""
    out = {k: eng.stats.get(k, 0) for k in COUNTED}
    opened = _OPENED.pop(id(eng), None)
    if opened is None:
        _OPENED[id(eng)] = out
    else:
        tokens = out["mla_prefill_tokens"] - opened["mla_prefill_tokens"]
        rows = out["mla_expand_rows"] - opened["mla_expand_rows"]
        print("[window] engine latent", json.dumps(
            {"mla_expand_rows": rows, "mla_prefill_tokens": tokens,
             "expanded_rows_per_prompt_token":
                 rows / tokens if tokens else None}), flush=True)
    return out


def rehearse(cfg):
    """The family's own small widths for `--rehearse`: a dense layer and
    two expert layers, every width cut and every kind of part kept."""
    return {"hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 3, "num_attention_heads": 4,
            "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 256,
            "kv_lora_rank": 128, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16,
            "max_position_embeddings": 512, "moe_intermediate_size": 16,
            "n_routed_experts": 8, "num_experts_per_tok": 2}


def layer_weights(cfg):
    """(attention, router, one routed expert, the shared expert, the dense
    layer's second half) matmul parameters of a layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    attn = (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + dv)
            + h * dv * d)
    f = cfg["moe_intermediate_size"]
    return (attn, d * cfg["n_routed_experts"], 3 * d * f,
            3 * d * f * cfg["n_shared_experts"],
            3 * d * cfg["intermediate_size"])


def _steps(window, sizes):
    return window.get("ticks", 0) * sizes["steps_per_tick"]


def hits(window, sizes):
    """Distinct experts hit a layer a decode step (the engine's count);
    without the count, the most the rows can hit."""
    if window.get("moe_layer_steps"):
        return window["moe_experts_hit"] / window["moe_layer_steps"]
    return float(min(sizes["E"], sizes["slots"] * sizes["k"]))


def latent_rows(window, sizes):
    """Latent rows a decode step's attention reads, every layer, all slots
    together: the engine's count over the window's steps; without it, the
    live contexts of every layer."""
    steps = _steps(window, sizes)
    if steps and window.get("mla_decode_rows"):
        return window["mla_decode_rows"] / steps
    return window["live_context_tokens"] * sizes["L"]


def mla_decode(cfg, sizes, window):
    """The latent decode kernel's calls of one decode step: every latent
    row the slots' keys hold read once, at the model's row width (a wider
    stored row reads as lost roofline), the heads' [q~ | q_pe] read in
    bf16 and õ written in float32; a row's score over the whole row and its
    value over the latent, for every head."""
    rows = latent_rows(window, sizes)
    q = sizes["L"] * sizes["slots"] * sizes["H"] * (
        sizes["row"] * 2 + sizes["R"] * 4)
    flops = 2.0 * sizes["H"] * (sizes["row"] + sizes["R"]) * rows
    return flops, rows * sizes["row"] * 2 + q


def moe_experts_step(cfg, sizes, window):
    """The routed experts of one decode step: each expert hit read once a
    layer, every row's k experts computed."""
    expert = layer_weights(cfg)[2]
    bytes_ = sizes["Le"] * (hits(window, sizes) * expert * 2
                            + 2 * sizes["slots"] * sizes["d"] * 2)
    return sizes["Le"] * 2.0 * sizes["slots"] * sizes["k"] * expert, bytes_


def decode_step(cfg, sizes, window):
    """One decode step of the whole batch, the LEAST bytes whatever
    implements it: attention, router and shared-expert weights, the dense
    layer, the experts hit, the head, and the latent rows of the live
    contexts."""
    attn, router, expert, shared, dense = layer_weights(cfg)
    head = sizes["d"] * sizes["V"]
    weights = (sizes["L"] * attn + sizes["Ld"] * dense + head
               + sizes["Le"] * (router + shared
                                + hits(window, sizes) * expert))
    rows = latent_rows(window, sizes)
    active = (sizes["L"] * attn + sizes["Ld"] * dense + head
              + sizes["Le"] * (router + shared + sizes["k"] * expert))
    flops = 2.0 * active * sizes["slots"] \
        + 2.0 * sizes["H"] * (sizes["row"] + sizes["R"]) * rows
    return flops, 2.0 * weights + rows * sizes["row"] * 2


# The family's yardstick, as `reduce.py` asks for it: operations and bytes
# from the configuration's sizes and the window's counters, never a peak or
# a time (costs.py keeps those). Each counts the LEAST work, whatever
# implements it: the experts the rows hit, each latent row once at the
# model's width.
costs = types.SimpleNamespace(
    layer_weights=layer_weights, hits=hits, latent_rows=latent_rows,
    mla_decode=mla_decode, moe_experts_step=moe_experts_step,
    decode_step=decode_step,
    KERNEL_COSTS={"decode_step": decode_step,
                  "moe_experts_step": moe_experts_step,
                  "mla_decode": mla_decode})
