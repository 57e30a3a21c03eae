"""The plain reference of a decoder that mixes window and full attention
layers, gates its attention's output and routes by sigmoid scores over
experts of which a share is held here (Trinity-Large-Preview, `model_type`
afmoe, as its `config.json` shapes it; what the config leaves open is listed
under `assumed` in the configuration file).

`h` the residual stream, position t, the layer's kind from `layer_types`:

- h = E[token] * sqrt(hidden) (`mup_enabled`);
- a = RMSNorm(h); q = RMSNorm_head(W_q a) in H x hd, k = RMSNorm_head(W_k a)
  in Hkv x hd, v = W_v a in Hkv x hd, g = W_gate a in H x hd. A window layer
  rotates q and k (rotate-half RoPE, theta `rope_theta`, the whole head
  width) and sees the keys s with t - `sliding_window` < s <= t; a full layer
  has no position encoding and sees every s <= t. o_h = softmax_s(q_h .
  k_g(h),s / sqrt(hd)) v_g(h),s; o = o * sigmoid(g); h = h + RMSNorm(W_o o);
- m = RMSNorm(h). The first `num_dense_layers` layers: y = W_d (silu(W_g m) *
  W_u m). The others: s = sigmoid(W_r m) over all the router's experts; S =
  the `num_experts_per_tok` experts of highest s_e + b_e (ties to the lower
  index); w_e = `route_scale` * s_e / (sum_{e in S} s_e + 1e-20)
  (`route_norm`); y = shared(m) + sum_{e in S, e held} w_e expert_e(m). h = h
  + RMSNorm(y);
- final RMSNorm, untied head.

THE SHARE. The configuration's `num_experts` counts the experts HELD
(`held_experts_first` .. + `num_experts` of the `router_experts` the router
scores); what the experts held elsewhere would add is left out, here as in
the program. `layer(…, held=(first, count))` gives any share's part of one
expert layer, so a test can add the shares up.

Straightforward `jax.numpy` in float32 with `highest` matmul precision: no
kernel, no cache, no batching, no code of the program. Weights are read by
the program's parameter names, (in, out) for projections, and widened to
float32 block by block (a kv head's group of query heads, a block of
experts, a slice of the vocabulary), and the scores of one kv head's group
are (6, 1,024, S) at a time, so that a 6,400-token check at the published widths
fits beside the served model.

`store` (the identity) is what every value a program would keep goes
through; `tools/prove_serve_check.py` passes a rounding to compute the
reference in the precision under the configuration's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 4        # experts widened to float32 at a time
VOCAB_BLOCKS = 8        # slices of the output projection
QUERY_BLOCK = 1024      # queries whose scores are held at a time
WINDOW = "sliding_attention"


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


def _keep(x):
    return x


class _Params:
    """params[name] widened to float32 on call; `.raw` as stored."""

    def __init__(self, params):
        self.params = params

    def __call__(self, name):
        return self.params[name].astype(jnp.float32)

    def raw(self, name):
        return self.params[name]


def _swiglu(m, f32, p, store):
    g = store(m @ f32(p + "gate_proj.weight"))
    u = store(m @ f32(p + "up_proj.weight"))
    return store(store(jax.nn.silu(g) * u) @ f32(p + "down_proj.weight"))


def gates(m, f32, p, cfg):
    """m (S, d) -> (S, E) the weight of every expert the router scores for
    every token: `route_scale` * s_e / sum of the chosen s where e is
    chosen (by s + b), else 0."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(m @ f32(p + "router_weight"))
    # stable, so that among equal scores the lower index comes first
    order = jnp.argsort(-(s + f32(p + "expert_bias")), axis=-1, stable=True)
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], order[:, :k]].set(True)
    w = jnp.where(chosen, s, 0.0)
    if cfg.get("route_norm", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * float(cfg.get("route_scale", 1.0))


def routed(m, f32, p, cfg, held, store=_keep):
    """The part of the routed experts' sum that the experts `held` =
    (first, count) give: every held expert is computed for every token, a
    block at a time, and the gate of an expert a token did not choose is 0.
    The stacked weights under `p` are those `count` experts'."""
    first, count = held
    w = gates(m, f32, p, cfg)[:, first:first + count]            # (S, count)
    nb = min(EXPERT_BLOCK, count)
    assert count % nb == 0

    def block(out, i):
        sl = lambda n: jax.lax.dynamic_slice_in_dim(          # noqa: E731
            f32.raw(p + n), i * nb, nb, 0).astype(jnp.float32)
        g = store(jnp.einsum("sd,edf->esf", m, sl("experts_gate_weight")))
        u = store(jnp.einsum("sd,edf->esf", m, sl("experts_up_weight")))
        o = store(jnp.einsum("esf,efd->esd", store(jax.nn.silu(g) * u),
                             sl("experts_down_weight")))
        gate = jax.lax.dynamic_slice_in_dim(w, i * nb, nb, 1)   # (S, nb)
        return out + jnp.einsum("esd,se->sd", o, gate), None
    out, _ = jax.lax.scan(block, jnp.zeros_like(m), jnp.arange(count // nb))
    return out


def held_of(cfg):
    """(first, count) of the experts the configuration holds: `num_experts`
    of the `router_experts` the router scores, from `held_experts_first`."""
    return (int(cfg.get("held_experts_first", 0)), int(cfg["num_experts"]))


def expert_layer(m, f32, p, cfg, held=None, store=_keep):
    """m (S, d) -> shared(m) + the held experts' part of the routed sum."""
    held = held_of(cfg) if held is None else held
    return store(_swiglu(m, f32, p + "shared_expert.", store)
                 + routed(m, f32, p + "moe.", cfg, held, store))


def attention(a, f32, p, cfg, window, store=_keep):
    """a (S, d), the normed stream -> (S, d), W_o (gated heads)."""
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, s, g = cfg["rms_norm_eps"], a.shape[0], h // hkv
    q = store(a @ f32(p + "q_proj.weight")).reshape(s, h, hd)
    k = store(a @ f32(p + "k_proj.weight")).reshape(s, hkv, hd)
    v = store(a @ f32(p + "v_proj.weight")).reshape(s, hkv, hd)
    gate = store(a @ f32(p + "gate_proj.weight"))
    q = store(_rms_norm(q, f32(p + "q_norm.weight"), eps))
    k = store(_rms_norm(k, f32(p + "k_norm.weight"), eps))
    if window:
        q = store(_rope(q, float(cfg["rope_theta"])))
        k = store(_rope(k, float(cfg["rope_theta"])))
    # a kv head's group of query heads, a block of queries at a time: the
    # scores are (g, QUERY_BLOCK, S), never (H, S, S)
    nq = -(-s // QUERY_BLOCK)
    # (the padding's queries stand at the last position: they see keys,
    # so nothing of them is NaN, and they are cut off again)
    t = jnp.minimum(jnp.arange(nq * QUERY_BLOCK), s - 1).reshape(
        nq, QUERY_BLOCK)
    key = jnp.arange(s)

    def group(qkv):
        qg, kg, vg = qkv              # (g, S, hd), (S, hd), (S, hd)
        qb = jnp.pad(qg, ((0, 0), (0, nq * QUERY_BLOCK - s), (0, 0)))
        qb = jnp.moveaxis(qb.reshape(g, nq, QUERY_BLOCK, hd), 1, 0)

        def block(qt):
            qq, tt = qt               # (g, B, hd), (B,)
            seen = key[None, :] <= tt[:, None]
            if window:                # the token itself counts: t - W < s
                seen = seen & (key[None, :] > tt[:, None] - window)
            sc = jnp.einsum("gqd,kd->gqk", qq, kg) / jnp.sqrt(float(hd))
            sc = jnp.where(seen[None], sc, -jnp.inf)
            return store(jnp.einsum("gqk,kd->gqd",
                                    store(jax.nn.softmax(sc, -1)), vg))
        out = jax.lax.map(block, (qb, t))                   # (nq, g, B, hd)
        return jnp.moveaxis(out, 0, 1).reshape(g, -1, hd)[:, :s]
    qg = jnp.swapaxes(q, 0, 1).reshape(hkv, g, s, hd)
    att = jax.lax.map(group, (qg, jnp.swapaxes(k, 0, 1),
                              jnp.swapaxes(v, 0, 1)))       # (hkv, g, S, hd)
    att = jnp.moveaxis(att.reshape(h, s, hd), 0, 1).reshape(s, h * hd)
    att = store(att * jax.nn.sigmoid(gate))
    return store(att @ f32(p + "o_proj.weight"))


def hidden(params, cfg, ids, store=_keep):
    """ids (S,) of ONE sequence -> the final hidden (S, d) before the last
    norm."""
    f32 = _Params(params)
    eps = cfg["rms_norm_eps"]
    x = f32("model.embed_tokens.weight")[ids]
    if cfg.get("mup_enabled", False):
        x = store(x * jnp.sqrt(float(cfg["hidden_size"])))
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        window = cfg["sliding_window"] \
            if cfg["layer_types"][i] == WINDOW else 0
        a = store(_rms_norm(x, f32(p + "input_layernorm.weight"), eps))
        a = attention(a, f32, p + "self_attn.", cfg, window, store)
        x = store(x + store(_rms_norm(
            a, f32(p + "post_attention_layernorm.weight"), eps)))
        m = store(_rms_norm(x, f32(p + "pre_mlp_layernorm.weight"), eps))
        if i < cfg["num_dense_layers"]:
            y = _swiglu(m, f32, p + "mlp.", store)
        else:
            y = expert_layer(m, f32, p + "mlp.", cfg, store=store)
        x = store(x + store(_rms_norm(
            y, f32(p + "post_mlp_layernorm.weight"), eps)))
    return x


def logits(params, cfg, ids, store=_keep):
    """ids: (S,) int32 of ONE sequence -> (S, vocab) float32."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, cfg, ids, store)
        f32 = _Params(params)
        x = store(_rms_norm(x, f32("model.norm.weight"),
                            cfg["rms_norm_eps"]))
        head = params["lm_head.weight"]                          # (d, V)
        v = head.shape[1]
        nb = VOCAB_BLOCKS if v % VOCAB_BLOCKS == 0 else 1
        blocks = jnp.moveaxis(head.reshape(head.shape[0], nb, v // nb), 1, 0)
        out = jax.lax.map(lambda wb: store(x @ wb.astype(jnp.float32)),
                          blocks)
        return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v)


def next_token_losses(params, cfg, ids):
    """-log p(ids[t+1] | ids[:t+1]) at every position t < S-1 of ONE
    sequence -> (S-1,) float32."""
    lg = logits(params, cfg, ids)[:-1]
    logp = jax.nn.log_softmax(lg, -1)
    return -jnp.take_along_axis(logp, ids[1:, None], -1)[:, 0]
