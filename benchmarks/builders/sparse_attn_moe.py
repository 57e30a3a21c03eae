"""Builder `sparse_attn_moe`: a configuration file's published keys -> the
program's decoder with a learned key selection and routed experts in every
layer (`paddle_tpu.models.SparseAttnMoeForCausalLM`), weights made on the
device from the seed; with the family's own reference, yardstick, counters
and rehearsal sizes. Serving only: the configuration says nothing of how an
indexer is trained.
"""
from __future__ import annotations

import types

import numpy as np

from benchmarks import reference_sparse_attn_moe as reference  # noqa: F401


def model_config(cfg, seq):
    from paddle_tpu.models.sparse_attn_moe import SparseAttnMoeConfig
    sa = cfg["sa_config"]
    return SparseAttnMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        initializer_range=cfg.get("initializer_range", 0.02),
        index_num_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"], seq_length=seq)


def build(cfg, seed, *, dtype, seq, settings):
    """The program's model object, every weight drawn in ONE jitted call
    from `seed`, in `dtype`: normal(0, initializer_range) for matrices
    (the stacked experts too), ones for norm weights, zeros for biases,
    except where the configuration's `draw` names a parameter by the end
    of its name: `std` gives a matrix another deviation, `fill` a vector a
    constant. (Drawn all alike, attention averages thousands of keys to
    one vector a sequence and the output projection hands it on at gain 1,
    so every position of a sequence predicts the same token and `correct`
    sees no fault: the configuration file's `draw.why`.)
    The constructor runs under `jax.eval_shape`, so it allocates nothing."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.jit.functional import state_tensors
    from paddle_tpu.models.sparse_attn_moe import SparseAttnMoeForCausalLM

    if settings:
        raise ValueError(f"this family takes no model settings: {settings}")
    mcfg = model_config(cfg, seq)
    held = {}

    def construct():
        held["model"] = SparseAttnMoeForCausalLM(mcfg)
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
            if n.endswith(".bias"):
                out[n] = jnp.zeros(shape, jdt)
            elif len(shape) < 2:
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
    """The sizes patterns over event text and the cost functions are given."""
    sa = cfg["sa_config"]
    out = {"d": cfg["hidden_size"], "f": cfg["moe_intermediate_size"],
           "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
           "hd": cfg["head_dim"], "H": cfg["num_attention_heads"],
           "Hkv": cfg["num_key_value_heads"], "E": cfg["num_experts"],
           "k": cfg["num_experts_per_tok"], "topk": sa["topk"],
           "HI": sa["indexer_num_heads"], "dI": sa["indexer_head_dim"]}
    eng = traffic["engine"]
    out.update(slots=eng["max_slots"], page=eng["page_size"],
               pages_per_slot=eng["max_pages_per_slot"],
               steps_per_tick=eng["steps_per_tick"])
    return out


def flash_block_keys(cfg, traffic):
    return []       # no flash call: prefill attends through the paged path


def counters(eng):
    """What the cost functions and the counter metrics read of the engine's
    own counts, 0 where an engine does not keep one."""
    return {k: eng.stats.get(k, 0) for k in (
        "moe_experts_hit", "moe_layer_steps", "decode_slot_steps",
        "select_engaged_steps")}


def rehearse(cfg):
    """The family's own small widths for `--rehearse`; `sa_config` whole,
    with a `topk` that the rehearsal's 8-32-token prompts cross."""
    return {"hidden_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 32, "vocab_size": 256,
            "max_position_embeddings": 512, "intermediate_size": 128,
            "moe_intermediate_size": 32, "num_experts": 8,
            "num_local_experts": 8, "num_experts_per_tok": 2,
            "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 2,
                          "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                          "q_chunk_size": 512, "topk": 8}}


def layer_weights(cfg):
    """(attention, indexer, router, one expert) matmul parameters."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    index = d * sa["indexer_num_heads"] * sa["indexer_head_dim"] \
        + d * sa["indexer_head_dim"] + d * sa["indexer_num_heads"]
    return (attn, index, d * cfg["num_experts"],
            3 * d * cfg["moe_intermediate_size"])


def hits(window, sizes):
    """Distinct experts hit a layer a decode step (the engine's count);
    without the count, the most the rows can hit."""
    if window.get("moe_layer_steps"):
        return window["moe_experts_hit"] / window["moe_layer_steps"]
    return float(min(sizes["E"], sizes["slots"] * sizes["k"]))


def selected_tokens(window, sizes):
    """Keys attended over a decode step, all slots together: each live
    context, cut to topk."""
    live = window["live_context_tokens"]
    steps = window.get("ticks", 0) * sizes["steps_per_tick"]
    if steps and window.get("decode_slot_steps"):
        live = min(live, window["decode_slot_steps"] / steps * sizes["topk"])
    return min(live, sizes["slots"] * sizes["topk"])


def moe_experts_step(cfg, sizes, window):
    """The expert layers of one decode step: each expert hit read once;
    every row's k experts computed."""
    expert = layer_weights(cfg)[3]
    rows = sizes["slots"]
    bytes_ = sizes["L"] * (hits(window, sizes) * expert * 2
                           + 2 * rows * sizes["d"] * 2)
    return sizes["L"] * 2.0 * rows * sizes["k"] * expert, bytes_


def paged_attn_step(cfg, sizes, window):
    """The attention calls of one decode step: the selected rows' K and V,
    the query and the output."""
    sel = selected_tokens(window, sizes)
    row = sizes["Hkv"] * sizes["hd"] * 2
    q = sizes["slots"] * sizes["H"] * sizes["hd"]
    bytes_ = sizes["L"] * (2 * sel * row + q * (2 + 4))
    return sizes["L"] * 2 * 2.0 * sel * sizes["H"] * sizes["hd"], bytes_


def decode_step(cfg, sizes, window):
    """One decode step of the whole batch: attention, indexer and router
    weights, the experts hit, the output projection, the selected K and V
    and the index keys of the live contexts."""
    attn, index, router, expert = layer_weights(cfg)
    head = sizes["d"] * sizes["V"]
    weights = sizes["L"] * (attn + index + router
                            + hits(window, sizes) * expert) + head
    cache = sizes["L"] * (
        2 * selected_tokens(window, sizes) * sizes["Hkv"] * sizes["hd"] * 2
        + window["live_context_tokens"] * sizes["dI"] * 2)
    active = sizes["L"] * (attn + index + router
                           + sizes["k"] * expert) + head
    return 2.0 * active * sizes["slots"], 2.0 * weights + cache


# The family's yardstick, as `reduce.py` asks for it: operations and bytes
# from the configuration's sizes and the window's counters, never a peak or
# a time (costs.py keeps those). Each counts the LEAST work, whatever
# implements it: the experts the rows hit, the K and V rows the selection
# keeps, never a row a mask throws away.
costs = types.SimpleNamespace(
    layer_weights=layer_weights, hits=hits, selected_tokens=selected_tokens,
    moe_experts_step=moe_experts_step, paged_attn_step=paged_attn_step,
    decode_step=decode_step,
    KERNEL_COSTS={"decode_step": decode_step,
                  "moe_experts_step": moe_experts_step,
                  "paged_attn_step": paged_attn_step})
