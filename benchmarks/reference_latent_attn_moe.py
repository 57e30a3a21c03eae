"""The plain reference of a decoder with latent attention (MLA, no q-LoRA)
and routed experts beside shared ones (Kanana-2-30B-A3B, `model_type`
deepseek_v3, as its `config.json` shapes it; what the config leaves open is
listed under `assumed` in the configuration file).

`h` the residual stream, position t, H heads, nope / rope / v the head
widths, r the latent rank:

- h = E[token];
- a = RMSNorm(h); q = W_q a in H x (nope + rope), split [q_nope | q_pe];
  [c | k_pe] = W_kva a (r + rope values), c = RMSNorm(c) (`kv_a_layernorm`);
  RoPE (theta `rope_theta`, no scaling) on q_pe and the one shared k_pe,
  adjacent pairs of dims rotating together (`rope_interleave`);
  [k_nope_h | v_h] = c W_kvb,h; s_h(t, j) = (q_nope_h(t) . k_nope_h(j) +
  q_pe_h(t) . k_pe(j)) / sqrt(nope + rope) over j <= t; o_h = softmax_j(s_h)
  v_h; h = h + W_o [o_1 .. o_H];
- m = RMSNorm(h). The first `first_k_dense_replace` layers: y = W_d
  (silu(W_g m) * W_u m). The others: s = sigmoid(W_r m) over the
  `n_routed_experts`; S = the `num_experts_per_tok` of highest s_e + b_e
  (b the correction bias; ties to the lower index); w_e =
  `routed_scaling_factor` * s_e / (sum_{e in S} s_e + 1e-20); y = shared(m) +
  sum_{e in S} w_e expert_e(m), `shared` one SwiGLU of `n_shared_experts` x
  `moe_intermediate_size`. h = h + y;
- final RMSNorm, untied head.

The expanded form, as the equations are written: every head's key and value
are built from c and attended over; the program's absorbed decode is held to
this. Straightforward `jax.numpy` in float32 with `highest` matmul precision:
no kernel, no cache, no batching, no code of the program. Weights are read by
the program's parameter names, (in, out) for projections, and widened to
float32 block by block (a group of heads, a block of experts, a slice of the
vocabulary), and the scores of a group of heads are (HEAD_BLOCK,
QUERY_BLOCK, S) at a time, so that the check's prompt at the published widths
fits beside the served model.

`store` (the identity) is what every value a program would keep goes
through; `tools/prove_serve_check.py` passes a rounding to compute the
reference in the precision under the configuration's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 4        # experts widened to float32 at a time
HEAD_BLOCK = 8          # heads whose scores are held at a time
QUERY_BLOCK = 1024      # queries whose scores are held at a time
VOCAB_BLOCKS = 8        # slices of the output projection


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope_interleaved(x, theta):
    """x: (S, ..., d). Dims 2i and 2i + 1 rotate together by the angle t
    theta^(-2i/d)."""
    s, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (s,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


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
    """m (S, d) -> (S, E): `routed_scaling_factor` * s_e / the sum of the
    chosen s where e is chosen (by s + b), else 0."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(m @ f32(p + "router_weight"))
    # stable, so that among equal scores the lower index comes first
    order = jnp.argsort(-(s + f32(p + "expert_bias")), axis=-1, stable=True)
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], order[:, :k]].set(True)
    w = jnp.where(chosen, s, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * float(cfg["routed_scaling_factor"])


def routed(m, f32, p, cfg, store=_keep):
    """The routed experts' sum: every expert computed for every token, a
    block at a time; the gate of an expert a token did not choose is 0."""
    w = gates(m, f32, p, cfg)                                    # (S, E)
    e = cfg["n_routed_experts"]
    nb = min(EXPERT_BLOCK, e)
    assert e % nb == 0

    def block(out, i):
        sl = lambda n: jax.lax.dynamic_slice_in_dim(          # noqa: E731
            f32.raw(p + n), i * nb, nb, 0).astype(jnp.float32)
        g = store(jnp.einsum("sd,edf->esf", m, sl("experts_gate_weight")))
        u = store(jnp.einsum("sd,edf->esf", m, sl("experts_up_weight")))
        o = store(jnp.einsum("esf,efd->esd", store(jax.nn.silu(g) * u),
                             sl("experts_down_weight")))
        gate = jax.lax.dynamic_slice_in_dim(w, i * nb, nb, 1)   # (S, nb)
        return out + jnp.einsum("esd,se->sd", o, gate), None
    out, _ = jax.lax.scan(block, jnp.zeros_like(m), jnp.arange(e // nb))
    return out


def expert_layer(m, f32, p, cfg, store=_keep):
    """m (S, d) -> shared(m) + the routed experts' sum."""
    return store(_swiglu(m, f32, p + "shared_expert.", store)
                 + routed(m, f32, p + "moe.", cfg, store))


def attention(a, f32, p, cfg, store=_keep):
    """a (S, d), the normed stream -> (S, d): latent attention, expanded."""
    h, nope, rope, dv, r = (cfg["num_attention_heads"],
                            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                            cfg["v_head_dim"], cfg["kv_lora_rank"])
    eps, s, theta = cfg["rms_norm_eps"], a.shape[0], float(cfg["rope_theta"])
    q = store(a @ f32(p + "q_proj.weight")).reshape(s, h, nope + rope)
    q_nope, q_pe = q[..., :nope], store(_rope_interleaved(q[..., nope:],
                                                          theta))
    ckv = store(a @ f32(p + "kv_a_proj_with_mqa.weight"))
    c = store(_rms_norm(ckv[:, :r], f32(p + "kv_a_layernorm.weight"), eps))
    k_pe = store(_rope_interleaved(ckv[:, r:], theta))              # (S, rope)
    w_kvb = f32(p + "kv_b_proj.weight").reshape(r, h, nope + dv)
    scale = 1.0 / jnp.sqrt(float(nope + rope))
    hb = min(HEAD_BLOCK, h)
    assert h % hb == 0
    nq = -(-s // QUERY_BLOCK)
    # (the padding's queries stand at the last position: they see keys,
    # so nothing of them is NaN, and they are cut off again)
    t = jnp.minimum(jnp.arange(nq * QUERY_BLOCK), s - 1).reshape(
        nq, QUERY_BLOCK)
    key = jnp.arange(s)

    def heads(i):
        """Heads [i * hb, (i + 1) * hb): their keys and values expanded
        from c, then a block of queries at a time."""
        w = jax.lax.dynamic_slice_in_dim(w_kvb, i * hb, hb, 1)
        k_nope = store(jnp.einsum("sr,rhn->hsn", c, w[..., :nope]))
        v = store(jnp.einsum("sr,rhv->hsv", c, w[..., nope:]))
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * hb, hb, 1)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, i * hb, hb, 1)

        def block(tt):
            at = jnp.clip(tt, 0, s - 1)
            sc = (jnp.einsum("qhn,hkn->hqk", qn[at], k_nope)
                  + jnp.einsum("qhr,kr->hqk", qp[at], k_pe)) * scale
            sc = jnp.where((key[None, :] <= tt[:, None])[None], sc, -jnp.inf)
            return store(jnp.einsum("hqk,hkv->qhv",
                                    store(jax.nn.softmax(sc, -1)), v))
        out = jax.lax.map(block, t)                         # (nq, B, hb, dv)
        return out.reshape(nq * QUERY_BLOCK, hb, dv)[:s]
    att = jax.lax.map(heads, jnp.arange(h // hb))           # (h/hb, S, hb, dv)
    att = jnp.moveaxis(att, 0, 1).reshape(s, h * dv)
    return store(att @ f32(p + "o_proj.weight"))


def hidden(params, cfg, ids, store=_keep):
    """ids (S,) of ONE sequence -> the final hidden (S, d) before the last
    norm."""
    f32 = _Params(params)
    eps = cfg["rms_norm_eps"]
    x = f32("model.embed_tokens.weight")[ids]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        a = store(_rms_norm(x, f32(p + "input_layernorm.weight"), eps))
        x = store(x + attention(a, f32, p + "self_attn.", cfg, store))
        m = store(_rms_norm(x, f32(p + "post_attention_layernorm.weight"),
                            eps))
        if i < cfg["first_k_dense_replace"]:
            y = _swiglu(m, f32, p + "mlp.", store)
        else:
            y = expert_layer(m, f32, p + "mlp.", cfg, store)
        x = store(x + y)
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
