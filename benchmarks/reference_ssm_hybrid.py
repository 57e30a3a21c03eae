"""The plain reference of a decoder-hybrid-decoder with Mamba layers,
differential attention and a cross-decoder (Phi-4-mini-flash-reasoning,
`model_type` phi4flash, the SambaY architecture of arXiv:2507.06607, as its
`config.json` shapes it; what the config leaves open is listed under
`assumed` in the configuration file).

`h` the residual stream, position t, L layers, d the width, H query and Hk
kv heads of hd, E = `mamba_expand` d channels, N states, R the rank of
delta, K the conv's width, w the window; no position encoding:

- h = E[token]. Layer i is a Mamba layer for i < L/2 with i % m == 0 (m =
  `mb_per_layer`) and at i = L/2, a window layer for the other i < L/2, the
  full layer at i = L/2 + 1, then a gated memory unit where i % m == 0 and
  a cross layer else;
- every layer: x = LN1(h) (LayerNorm, weight and bias); h = h + mixer(x);
  x = LN2(h); h = h + W_d (silu(W_g x) * W_u x);
- Mamba: [u | z] = x W_in; u = silu(b + sum_j w_j u(t - K + 1 + j)), zeros
  before the first token; [delta | B | C] = u W_x; Delta =
  softplus(delta W_dt + b_dt); A = -exp(A_log); s_t = exp(Delta_t A)
  s_{t-1} + (Delta_t u_t) B_t^T from s = 0, one token after another; y_t =
  s_t C_t + D u_t; m = y * silu(z); out = m W_out. Layer L/2's m is the
  memory;
- differential attention: q = x W_q (H heads), k = x W_k, v = x W_v (Hk
  heads; a cross layer takes the full layer's k and v); query pair p =
  (heads 2p, 2p + 1), kv pair g = (heads 2g, 2g + 1), value v_g = [v_2g |
  v_2g+1]; pair p reads g = floor(p / (H / Hk)); a^j = softmax_s(q^j . k^j /
  sqrt(hd)) v_g over s <= t (window layers: t - w < s <= t); lambda =
  exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + 0.8 - 0.6 exp(-0.3 i); o_p = (1 -
  lambda_init) RMSNorm(a^1 - lambda a^2) (weight, eps of the config); out
  = [o_p] W_o;
- gated memory unit: out = (memory * silu(x W_1)) W_2;
- final LN, the head tied to the embedding.

Every layer runs at every position, the cross-decoder too (the program
skips it in a prefill: that is what this holds it to). Straightforward
`jax.numpy` in float32 with `highest` matmul precision: no kernel, no
cache, no batching, no code of the program. Weights are read by the
program's parameter names, (in, out) for projections, widened to float32
where a layer uses them (never all at once: 7.7 GB of bf16 weights would be
15.4 GB), the head a slice of the vocabulary at a time and attention's
scores a kv pair's query heads at a time.

`store` (the identity) is what every value a program would keep goes
through; `tools/prove_serve_check.py` passes a rounding to compute the
reference in the precision under the configuration's.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

VOCAB_BLOCKS = 8        # slices of the tied head


def _keep(x):
    return x


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def kinds(cfg):
    """Each layer's kind (module doc)."""
    n, m = cfg["num_hidden_layers"], cfg["mb_per_layer"]
    out = []
    for i in range(n):
        if i <= n // 2:
            out.append("mamba" if i % m == 0 else "window")
        elif i == n // 2 + 1:
            out.append("full")
        else:
            out.append("gmu" if i % m == 0 else "cross")
    return out


class _Params:
    """params[name] widened to float32 on call."""

    def __init__(self, params):
        self.params = params

    def __call__(self, name):
        return self.params[name].astype(jnp.float32)


def mamba(x, f32, p, cfg, store=_keep):
    """x (S, d), the normed stream -> (out (S, d), m (S, E))."""
    e = cfg["mamba_expand"] * cfg["hidden_size"]
    n, k = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    r = cfg.get("mamba_dt_rank") or math.ceil(cfg["hidden_size"] / 16)
    s = x.shape[0]
    xz = store(x @ f32(p + "in_proj.weight"))
    u, z = xz[:, :e], xz[:, e:]
    w = f32(p + "conv1d_weight")                                 # (E, K)
    padded = jnp.concatenate([jnp.zeros((k - 1, e)), u], 0)
    conv = sum(padded[j:j + s] * w[:, j] for j in range(k))
    u = store(jax.nn.silu(conv + f32(p + "conv1d_bias")))
    dbc = store(u @ f32(p + "x_proj.weight"))
    delta, b, c = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    dt = store(jax.nn.softplus(delta @ f32(p + "dt_proj.weight")
                               + f32(p + "dt_proj.bias")))      # (S, E)
    a = -jnp.exp(f32(p + "A_log"))                               # (E, N)

    def step(state, inp):
        dt_t, u_t, b_t, c_t = inp
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return state, state @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((e, n)), (dt, u, b, c))
    y = store(y + f32(p + "D") * u)
    m = store(y * jax.nn.silu(z))
    return store(m @ f32(p + "out_proj.weight")), m


def diff_attention(x, kv, f32, p, cfg, i, window=0, store=_keep):
    """x (S, d) -> (out (S, d), (k, v) (S, Hk, hd) each); `kv` given: a
    cross layer over it."""
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    s = x.shape[0]
    q = store(x @ f32(p + "q_proj.weight")).reshape(s, h // 2, 2, hd)
    if kv is None:
        kv = (store(x @ f32(p + "k_proj.weight")).reshape(s, hk, hd),
              store(x @ f32(p + "v_proj.weight")).reshape(s, hk, hd))
    k, v = kv
    k2 = k.reshape(s, hk // 2, 2, hd)
    v2 = v.reshape(s, hk // 2, 2 * hd)                     # v_g = [v_2g | v_2g+1]
    t = jnp.arange(s)
    seen = t[None, :] <= t[:, None]
    if window:
        seen = seen & (t[None, :] > t[:, None] - window)
    per = (h // 2) // (hk // 2)                            # query pairs a kv pair

    def pair_group(g):
        """The query pairs that read kv pair g -> (S, per, 2, 2 hd)."""
        qg = jax.lax.dynamic_slice_in_dim(q, g * per, per, 1)   # (S, per, 2, hd)
        kg = k2[:, g]                                           # (S, 2, hd)
        sc = jnp.einsum("tpjd,sjd->pjts", qg, kg) / math.sqrt(hd)
        sc = jnp.where(seen[None, None], sc, -jnp.inf)
        pr = store(jax.nn.softmax(sc, -1))
        return store(jnp.einsum("pjts,sd->tpjd", pr, v2[:, g]))
    a = jax.lax.map(pair_group, jnp.arange(hk // 2))      # (Hk/2, S, per, 2, 2hd)
    a = jnp.moveaxis(a, 0, 1).reshape(s, h // 2, 2, 2 * hd)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * i)
    lam = (jnp.exp(jnp.dot(f32(p + "lambda_q1"), f32(p + "lambda_k1")))
           - jnp.exp(jnp.dot(f32(p + "lambda_q2"), f32(p + "lambda_k2")))
           + lam_init)
    o = _rms_norm(a[:, :, 0] - lam * a[:, :, 1], f32(p + "subln.weight"),
                  cfg["layer_norm_eps"])
    o = store((1.0 - lam_init) * o).reshape(s, h * hd)
    return store(o @ f32(p + "o_proj.weight")), kv


def hidden(params, cfg, ids, store=_keep):
    """ids (S,) of ONE sequence -> the final hidden (S, d) before the last
    norm."""
    f32 = _Params(params)
    eps = cfg["layer_norm_eps"]
    h = params["model.embed_tokens.weight"][ids].astype(jnp.float32)
    memory = kv = None
    for i, kind in enumerate(kinds(cfg)):
        p = f"model.layers.{i}."
        x = store(_layer_norm(h, f32(p + "input_layernorm.weight"),
                              f32(p + "input_layernorm.bias"), eps))
        q = p + "mixer."
        if kind == "mamba":
            out, m = mamba(x, f32, q, cfg, store)
            if i == cfg["num_hidden_layers"] // 2:
                memory = m
        elif kind == "gmu":
            g = store(x @ f32(q + "in_proj.weight"))
            out = store(store(memory * jax.nn.silu(g))
                        @ f32(q + "out_proj.weight"))
        else:
            out, kv_i = diff_attention(
                x, kv if kind == "cross" else None, f32, q, cfg, i,
                cfg["sliding_window"] if kind == "window" else 0, store)
            if kind == "full":
                kv = kv_i
        h = store(h + out)
        x = store(_layer_norm(h, f32(p + "post_attention_layernorm.weight"),
                              f32(p + "post_attention_layernorm.bias"), eps))
        g = store(x @ f32(p + "mlp.gate_proj.weight"))
        u = store(x @ f32(p + "mlp.up_proj.weight"))
        h = store(h + store(store(jax.nn.silu(g) * u)
                            @ f32(p + "mlp.down_proj.weight")))
    return h


def logits(params, cfg, ids, store=_keep):
    """ids: (S,) int32 of ONE sequence -> (S, vocab) float32."""
    with jax.default_matmul_precision("highest"):
        f32 = _Params(params)
        x = hidden(params, cfg, ids, store)
        x = store(_layer_norm(x, f32("model.final_layernorm.weight"),
                              f32("model.final_layernorm.bias"),
                              cfg["layer_norm_eps"]))
        emb = params["model.embed_tokens.weight"]                # (V, d)
        v = emb.shape[0]
        nb = VOCAB_BLOCKS if v % VOCAB_BLOCKS == 0 else 1
        # a slice of the vocabulary at a time, written into the one
        # (S, V) array in place: no second copy of the logits
        def block(i, out):
            wb = jax.lax.dynamic_slice_in_dim(emb, i * (v // nb), v // nb)
            return jax.lax.dynamic_update_slice_in_dim(
                out, store(x @ wb.astype(jnp.float32).T), i * (v // nb), 1)
        return jax.lax.fori_loop(0, nb, block,
                                 jnp.zeros((x.shape[0], v), jnp.float32))


def next_token_losses(params, cfg, ids):
    """-log p(ids[t+1] | ids[:t+1]) at every position t < S-1 of ONE
    sequence -> (S-1,) float32."""
    lg = logits(params, cfg, ids)[:-1]
    logp = jax.nn.log_softmax(lg, -1)
    return -jnp.take_along_axis(logp, ids[1:, None], -1)[:, 0]
