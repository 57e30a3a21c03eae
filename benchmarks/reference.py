"""The plain reference of a dense decoder (SmolLM2, Mistral: pre-norm
blocks, RMSNorm, rotate-half RoPE, grouped-query causal attention, SwiGLU,
tied or untied output projection), as their model cards and the Hugging Face
`modeling_llama.py` / `modeling_mistral.py` describe it.

Straightforward `jax.numpy` in float32 with `highest` matmul precision: no
kernel, no cache, no batching, no code of the program. Weights are read by
the program's parameter names, in its (in, out) layout for projections;
whatever type they are served in, they are widened to float32 here, so the
only difference from the system is the precision it computes in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, H, hd). Rotate-half: the two halves of a head are the pairs."""
    s, _h, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(params, cfg, ids):
    """ids: (S,) int32 of ONE sequence -> (S, vocab) float32."""
    f32 = lambda n: params[n].astype(jnp.float32)     # noqa: E731
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s = ids.shape[0]
    with jax.default_matmul_precision("highest"):
        x = f32("model.embed_tokens.weight")[ids]
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(cfg["num_hidden_layers"]):
            p = f"model.layers.{i}."
            y = _rms_norm(x, f32(p + "input_layernorm.weight"), eps)
            q = (y @ f32(p + "self_attn.q_proj.weight")).reshape(s, h, hd)
            k = (y @ f32(p + "self_attn.k_proj.weight")).reshape(s, hkv, hd)
            v = (y @ f32(p + "self_attn.v_proj.weight")).reshape(s, hkv, hd)
            q, k = _rope(q, theta), _rope(k, theta)
            k = jnp.repeat(k, h // hkv, axis=1)
            v = jnp.repeat(v, h // hkv, axis=1)
            sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(hd))
            sc = jnp.where(causal[None], sc, -jnp.inf)
            a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
            x = x + a.reshape(s, h * hd) @ f32(p + "self_attn.o_proj.weight")
            y = _rms_norm(x, f32(p + "post_attention_layernorm.weight"), eps)
            g = jax.nn.silu(y @ f32(p + "mlp.gate_proj.weight"))
            u = y @ f32(p + "mlp.up_proj.weight")
            x = x + (g * u) @ f32(p + "mlp.down_proj.weight")
        x = _rms_norm(x, f32("model.norm.weight"), eps)
        if cfg.get("tie_word_embeddings"):
            return x @ f32("model.embed_tokens.weight").T
        return x @ f32("lm_head.weight")


def next_token_losses(params, cfg, ids):
    """-log p(ids[t+1] | ids[:t+1]) at every position t < S-1 of ONE
    sequence -> (S-1,) float32."""
    lg = logits(params, cfg, ids)[:-1]
    logp = jax.nn.log_softmax(lg, -1)
    return -jnp.take_along_axis(logp, ids[1:, None], -1)[:, 0]
