"""Builder `llama_dense`: a configuration file's published keys -> the
program's dense decoder (`paddle_tpu.models.LlamaForCausalLM`), with weights
made on the device from the seed.

A builder is found by the name a configuration file gives under `builder`.
It offers `build`, `sizes`, `reference` and `flash_block_keys`; a new model
family adds one such file beside this one and edits nothing.
"""
from __future__ import annotations

import numpy as np

from benchmarks import costs
from benchmarks import reference  # noqa: F401  the plain reference of this family


def model_config(cfg, seq, settings):
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        initializer_range=cfg.get("initializer_range", 0.02),
        seq_length=seq, **settings)


def build(cfg, seed, *, dtype, seq, settings):
    """The program's model object, every weight drawn in ONE jitted call
    from `seed`, in `dtype`.

    The constructor runs under `jax.eval_shape`, so it allocates nothing:
    its own initialisers would draw leaf by leaf in float32. The draw is the constructor's own distribution: normal(0,
    initializer_range) for matrices, ones for norm weights."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.jit.functional import state_tensors
    from paddle_tpu.models import LlamaForCausalLM

    lcfg = model_config(cfg, seq, settings)
    held = {}

    def construct():
        held["model"] = LlamaForCausalLM(lcfg)
        return {n: t._value for n, t in state_tensors(held["model"]).items()}

    shapes = jax.eval_shape(construct)
    paddle_tpu.seed(int(seed) % (2 ** 31))   # and drop the traced key
    model = held["model"]
    names = sorted(shapes)
    std = float(lcfg.initializer_range)
    jdt = jnp.dtype(dtype)

    def draw(seed_word):
        # the chip's own generator: a fraction of threefry's time to
        # compile and to run over billions of values
        key = jax.random.fold_in(jax.random.key(0, impl="rbg"), seed_word)
        out = {}
        for i, n in enumerate(names):
            shape = shapes[n].shape
            if len(shape) < 2:
                out[n] = jnp.ones(shape, jdt)
            else:
                out[n] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                            jnp.float32) * std).astype(jdt)
        return out

    values = jax.jit(draw)(np.uint32(int(seed) % (2 ** 32)))
    for n, t in state_tensors(model).items():
        t._value = values[n]
    if jdt != jnp.float32:
        # what a user does to serve in bf16; the values are bf16 already
        model = paddle_tpu.amp.decorate(models=model, level="O2",
                                        dtype=str(jdt))
    return model


def sizes(cfg, traffic):
    """The sizes patterns over event text and `costs.py` are given."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = {"d": cfg["hidden_size"], "f": cfg["intermediate_size"],
           "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
           "hd": costs.head_dim(cfg), "H": h, "Hkv": hkv}
    if traffic["kind"] == "train":
        out.update(B=traffic["batch"], S=traffic["seq"],
                   T=traffic["batch"] * traffic["seq"])
    else:
        eng = traffic["engine"]
        out.update(slots=eng["max_slots"], page=eng["page_size"],
                   pages_per_slot=eng["max_pages_per_slot"],
                   steps_per_tick=eng["steps_per_tick"])
    return out


def flash_block_keys(cfg, traffic):
    """The autotune table's keys for this cell's flash calls, so that the
    run can print which blocks it took (autotune is pinned off)."""
    if traffic["kind"] != "train":
        return []
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    s, hd = traffic["seq"], costs.head_dim(cfg)
    folded = h != hkv
    key = f"q{(h // hkv) * s if folded else s}_s{s}_d{hd}_bf16_c1" \
        + ("_g" if folded else "")
    return [("flash_fwd", key), ("flash_bwd", key)]
