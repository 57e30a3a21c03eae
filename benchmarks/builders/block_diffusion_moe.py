"""Builder `block_diffusion_moe`: a configuration file's published keys ->
the program's decoder that generates by diffusion over blocks, with routed
experts in every layer (`paddle_tpu.models.BlockDiffusionMoeForCausalLM`),
weights made on the device from the seed; with the family's own reference,
yardstick, counters and rehearsal sizes. Serving only: the configuration
gives no noise schedule to train by.

A step of this family yields several tokens. The engine settles
`steps_per_tick / block_length` whole blocks a tick, so a tick still
delivers `steps_per_tick` tokens to each live slot and `benchmarks/serve.py`
reads it as any other; a block costs `denoising_steps` forwards of
`block_length` query rows a slot and one more that stores its K and V
(`forwards_per_tick` in `sizes`). How many steps a block takes and by which
rule it unmasks are the deployment's (`traffic["model_settings"]`); `build`
notes them in the configuration it is handed, under `generation`, where the
reference's replay finds them (`reference.logits` is a teacher-forced
replay whose row `position - 1` chose the token at that position).
"""
from __future__ import annotations

import types

import numpy as np

from benchmarks import reference_block_diffusion_moe as reference  # noqa: F401

SETTINGS = ("denoising_steps", "remasking", "confidence_threshold")


def generation(cfg, settings):
    """The deployment's choices as the model's config takes them: by
    default a position a step, the most confident first."""
    unknown = set(settings) - set(SETTINGS)
    if unknown:
        raise ValueError(f"this family's model settings are {SETTINGS}: "
                         f"{sorted(unknown)}")
    return {"denoising_steps": int(settings.get("denoising_steps",
                                                cfg["block_length"])),
            "remasking": settings.get("remasking", "low_confidence_static"),
            "confidence_threshold": float(settings.get(
                "confidence_threshold", 0.9))}


def model_config(cfg, seq, chosen):
    """`chosen`: the deployment's choices, `generation`'s."""
    from paddle_tpu.models.block_diffusion_moe import BlockDiffusionMoeConfig
    return BlockDiffusionMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        initializer_range=cfg.get("initializer_range", 0.02),
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        block_length=cfg["block_length"],
        mask_token_id=cfg["mask_token_id"], seq_length=seq, **chosen)


def build(cfg, seed, *, dtype, seq, settings):
    """The program's model object, every weight drawn in ONE jitted call
    from `seed`, in `dtype`: normal(0, initializer_range) for matrices
    (the stacked experts too), ones for norm weights, except where the
    configuration's `draw` names a parameter by the end of its name: `std`
    gives a matrix another deviation, `fill` a vector a constant (the
    configuration file's `draw.why` has what this family needs of it).
    The constructor runs under `jax.eval_shape`, so it allocates nothing.
    `settings` (the traffic's `model_settings`) are noted in `cfg` under
    `generation`, for the reference's replay."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.jit.functional import state_tensors
    from paddle_tpu.models.block_diffusion_moe import (
        BlockDiffusionMoeForCausalLM)

    cfg["generation"] = generation(cfg, settings)
    mcfg = model_config(cfg, seq, cfg["generation"])
    held = {}

    def construct():
        held["model"] = BlockDiffusionMoeForCausalLM(mcfg)
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
            if len(shape) < 2:
                out[n] = jnp.full(shape, named(how.get("fill", {}), n, 1.0),
                                  jdt)
            else:
                out[n] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                            jnp.float32)
                          * named(how.get("std", {}), n, std)).astype(jdt)
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
    `block` rows a slot a forward, `blocks` a tick, and the forwards of a
    tick, those that denoise (`denoise_forwards`) and all
    (`forwards_per_tick`: one more a block, which stores it)."""
    out = {"d": cfg["hidden_size"], "f": cfg["moe_intermediate_size"],
           "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
           "hd": cfg["head_dim"], "H": cfg["num_attention_heads"],
           "Hkv": cfg["num_key_value_heads"], "E": cfg["num_experts"],
           "k": cfg["num_experts_per_tok"], "block": cfg["block_length"]}
    eng = traffic["engine"]
    out.update(slots=eng["max_slots"], page=eng["page_size"],
               pages_per_slot=eng["max_pages_per_slot"],
               steps_per_tick=eng["steps_per_tick"])
    steps = generation(cfg, traffic["model_settings"])["denoising_steps"]
    out["blocks"] = out["steps_per_tick"] // out["block"]
    out["denoise_forwards"] = steps * out["blocks"]
    out["forwards_per_tick"] = (steps + 1) * out["blocks"]
    return out


def flash_block_keys(cfg, traffic):
    return []       # no flash call: prefill attends through the paged path


def counters(eng):
    """What the cost functions and the counter metrics read of the engine's
    own counts, 0 where an engine does not keep one: the model's
    (`moe_layer_steps` counts a layer a FORWARD, so `moe_experts_hit` over
    it is the distinct experts a layer a forward), the slot-forwards of
    either kind and their sum, the positions unmasked, the blocks
    settled."""
    out = {k: eng.stats.get(k, 0) for k in (
        "moe_experts_hit", "moe_layer_steps", "block_forwards_denoise",
        "block_forwards_store", "block_positions_unmasked", "blocks_done")}
    out["block_forwards"] = (out["block_forwards_denoise"]
                             + out["block_forwards_store"])
    return out


def rehearse(cfg):
    """The family's own small widths for `--rehearse`, and a mask id inside
    the rehearsal's vocabulary."""
    return {"hidden_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 32, "vocab_size": 256,
            "max_position_embeddings": 512, "intermediate_size": 128,
            "moe_intermediate_size": 32, "num_experts": 8,
            "num_experts_per_tok": 2, "mask_token_id": 255}


def layer_weights(cfg):
    """(attention, its k and v projections alone, router, one expert)
    matmul parameters of a layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    return (attn, 2 * d * hkv * hd, d * cfg["num_experts"],
            3 * d * cfg["moe_intermediate_size"])


def hits(window, sizes):
    """Distinct experts hit a layer a forward (the engine's count);
    without the count, the most the rows can hit."""
    if window.get("moe_layer_steps"):
        return window["moe_experts_hit"] / window["moe_layer_steps"]
    return float(min(sizes["E"],
                     sizes["slots"] * sizes["block"] * sizes["k"]))


def layer_forwards(sizes):
    """Whole layers a tick must run: every layer of every forward, but the
    last layer of a forward that stores a block, of which the cache needs
    the K and V alone (no query, no output projection, no expert)."""
    return sizes["forwards_per_tick"] * sizes["L"] - sizes["blocks"]


# Each cost below is what ALL the forwards of a tick must do, divided by
# `steps_per_tick`: the roofline files multiply a cost by `steps_per_tick`
# and hold it against a tick's measured time, so that a tick's least time
# stands against a tick's time, whatever a step is.

def moe_experts_step(cfg, sizes, window):
    """The expert layers: each expert hit read once a layer a forward;
    every row's k experts computed."""
    expert = layer_weights(cfg)[3]
    rows = sizes["slots"] * sizes["block"]
    layers = layer_forwards(sizes)
    bytes_ = layers * (hits(window, sizes) * expert * 2
                       + 2 * rows * sizes["d"] * 2)
    flops = layers * 2.0 * rows * sizes["k"] * expert
    return flops / sizes["steps_per_tick"], bytes_ / sizes["steps_per_tick"]


def paged_attn_step(cfg, sizes, window):
    """The attention calls: the K and V of every live context read once a
    layer a forward, the block's queries and outputs."""
    live = window["live_context_tokens"]
    row = sizes["Hkv"] * sizes["hd"] * 2
    q = sizes["slots"] * sizes["block"] * sizes["H"] * sizes["hd"]
    layers = layer_forwards(sizes)
    bytes_ = layers * (2 * live * row + q * (2 + 4))
    flops = layers * 2 * 2.0 * live * sizes["block"] * sizes["H"] \
        * sizes["hd"]
    return flops / sizes["steps_per_tick"], bytes_ / sizes["steps_per_tick"]


def decode_step(cfg, sizes, window):
    """A tick's forwards whole: attention and router weights and the
    experts hit, a layer a forward; the K and V projections of a storing
    forward's last layer; the output projection once a DENOISING forward (a
    storing forward needs no logits); K and V of the live contexts once a
    layer a forward."""
    attn, kv, router, expert = layer_weights(cfg)
    head = sizes["d"] * sizes["V"]
    layers = layer_forwards(sizes)
    rows = sizes["slots"] * sizes["block"]
    weights = (layers * (attn + router + hits(window, sizes) * expert)
               + sizes["blocks"] * kv + sizes["denoise_forwards"] * head)
    cache = layers * 2 * window["live_context_tokens"] \
        * sizes["Hkv"] * sizes["hd"] * 2
    active = (layers * (attn + router + sizes["k"] * expert)
              + sizes["blocks"] * kv + sizes["denoise_forwards"] * head)
    return (2.0 * active * rows / sizes["steps_per_tick"],
            (2.0 * weights + cache) / sizes["steps_per_tick"])


# The family's yardstick, as `reduce.py` asks for it: operations and bytes
# from the configuration's sizes and the window's counters, never a peak or
# a time (costs.py keeps those). Each counts the LEAST work, whatever
# implements it.
costs = types.SimpleNamespace(
    layer_weights=layer_weights, hits=hits, layer_forwards=layer_forwards,
    moe_experts_step=moe_experts_step, paged_attn_step=paged_attn_step,
    decode_step=decode_step,
    KERNEL_COSTS={"decode_step": decode_step,
                  "moe_experts_step": moe_experts_step,
                  "paged_attn_step": paged_attn_step})
