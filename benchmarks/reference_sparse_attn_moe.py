"""The plain reference of a decoder whose every layer is grouped-query
attention over a learned selection of keys, followed by routed experts (the
language model of Keye-VL-2.0-30B-A3B as its `config.json` shapes it; what
the config leaves open is listed under `assumed` in the configuration file).

Per layer, h the residual stream, position t, keys s <= t:

- x = RMSNorm(h); q = RMSNorm_head(W_q x) in H x hd, k = RMSNorm_head(W_k x)
  in Hkv x hd, v = W_v x in Hkv x hd; rotate-half RoPE on q and k over the
  whole head width (on text the three position streams of `mrope_section`
  are equal, so it is ordinary RoPE);
- indexer: qI = W_qI x in HI x dI, kI = LayerNorm(W_kI x) in dI (one key
  head), w = W_w x in HI; RoPE on qI and kI; I[t, s] = sum_j w_j relu(qI_j .
  kI_s); S_t = the `topk` keys s <= t of highest I[t, s] (ties to the lower
  s; every key while t + 1 <= topk), one set a token for all heads;
- head h attends over S_t only: softmax_{s in S_t}(q_h . k_g(h),s / sqrt(hd))
  v_g(h),s; then W_o; residual;
- x' = RMSNorm(h); p = softmax(W_r x') over E; top k, gates renormalised to
  sum 1; y = sum_e g_e W_d,e (silu(W_g,e x') * W_u,e x'); residual;
- final RMSNorm, untied head.

Straightforward `jax.numpy` in float32 with `highest` matmul precision: no
kernel, no cache, no batching, no code of the program. The selection is
`lax.top_k` over the causal scores. Weights are read by the program's
parameter names, (in, out) for projections, and widened to float32 block by
block (a group of heads, a block of experts, a slice of the vocabulary), so
that a 2,600-token check at the published widths fits beside the served
model: one layer's experts alone are 2.4 GB in float32.

`store` (the identity) is what every value a program would keep goes
through: a projection's result, a norm's, the residual stream, the
logits. The control that shows what `correct` sees
(`tools/prove_serve_check.py`) passes a rounding to the nearest precision
under the configuration's and rounds the matrices the same way: the
reference computed in float8.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 16       # experts widened to float32 at a time
VOCAB_BLOCKS = 8        # slices of the output projection


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _rope(x, theta):
    """x: (S, H, hd). Rotate-half: the two halves of a head are the pairs."""
    s, _h, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_scores(qi, ki, w):
    """qi (S, HI, dI), ki (S, dI), w (S, HI) -> I (S, S): sum_j w_j
    relu(qI_j . kI_s), one index head at a time."""
    def one(acc, jw):
        qj, wj = jw                                    # (S, dI), (S,)
        return acc + wj[:, None] * jax.nn.relu(qj @ ki.T), None
    s = qi.shape[0]
    acc, _ = jax.lax.scan(one, jnp.zeros((s, s), jnp.float32),
                          (jnp.swapaxes(qi, 0, 1), w.T))
    return acc


def selected(scores, topk):
    """(S, S) bool: key s is in S_t. `lax.top_k` over the causal scores
    (a key after t scores -inf); among equal scores it takes the lower
    index first."""
    s = scores.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    if s <= topk:
        return causal
    _vals, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    hit = jnp.zeros((s, s), bool).at[jnp.arange(s)[:, None], idx].set(True)
    return hit & causal


def _keep(x):
    return x


def experts(y, p, f32, cfg, store=_keep):
    """y (S, d) -> sum over each token's top-k experts, gates renormalised;
    every expert is computed for every token, a block of experts at a time,
    and the gate of an expert a token did not choose is 0."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(y @ f32(p + "mlp.router_weight"), -1)
    top, idx = jax.lax.top_k(probs, k)
    if cfg.get("norm_topk_prob", True):
        top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(y.shape[0])[:, None], idx].set(top)          # (S, E)
    wg, wu, wd = (p + "mlp.experts_gate_weight", p + "mlp.experts_up_weight",
                  p + "mlp.experts_down_weight")
    nb = min(EXPERT_BLOCK, e)
    assert e % nb == 0

    def block(out, i):
        sl = lambda n: jax.lax.dynamic_slice_in_dim(          # noqa: E731
            f32.raw(n), i * nb, nb, 0).astype(jnp.float32)
        g = store(jnp.einsum("sd,edf->esf", y, sl(wg)))
        u = store(jnp.einsum("sd,edf->esf", y, sl(wu)))
        o = store(jnp.einsum("esf,efd->esd", store(jax.nn.silu(g) * u),
                             sl(wd)))
        gate = jax.lax.dynamic_slice_in_dim(gates, i * nb, nb, 1)   # (S, nb)
        return out + jnp.einsum("esd,se->sd", o, gate), None
    out, _ = jax.lax.scan(block, jnp.zeros_like(y), jnp.arange(e // nb))
    return out


class _Params:
    """params[name] widened to float32 on call; `.raw` as stored."""

    def __init__(self, params):
        self.params = params

    def __call__(self, name):
        return self.params[name].astype(jnp.float32)

    def raw(self, name):
        return self.params[name]


def hidden_and_sets(params, cfg, ids, store=_keep):
    """ids (S,) of ONE sequence -> (final hidden (S, d) before the last
    norm, [per layer: (S, S) bool, the selected sets])."""
    f32 = _Params(params)
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s, g = ids.shape[0], h // hkv
    sets = []
    x = f32("model.embed_tokens.weight")[ids]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        y = store(_rms_norm(x, f32(p + "input_layernorm.weight"), eps))
        q = store(y @ f32(a + "q_proj.weight")).reshape(s, h, hd)
        k = store(y @ f32(a + "k_proj.weight")).reshape(s, hkv, hd)
        v = store(y @ f32(a + "v_proj.weight")).reshape(s, hkv, hd)
        q = store(_rope(_rms_norm(q, f32(a + "q_norm.weight"), eps), theta))
        k = store(_rope(_rms_norm(k, f32(a + "k_norm.weight"), eps), theta))
        qi = store(y @ f32(a + "indexer.wq.weight")).reshape(s, hi, di)
        ki = _layer_norm(store(y @ f32(a + "indexer.wk.weight")),
                         f32(a + "indexer.k_norm.weight"),
                         f32(a + "indexer.k_norm.bias"), eps)
        w = store(y @ f32(a + "indexer.weights_proj.weight"))    # (S, HI)
        qi = store(_rope(qi, theta))
        ki = store(_rope(ki[:, None, :], theta)[:, 0])
        keep = selected(index_scores(qi, ki, w), sa["topk"])
        sets.append(keep)

        def group(qkv):
            qg, kg, vg = qkv              # (g, S, hd), (S, hd), (S, hd)
            sc = jnp.einsum("gqd,kd->gqk", qg, kg) / jnp.sqrt(float(hd))
            sc = jnp.where(keep[None], sc, -jnp.inf)
            return store(jnp.einsum("gqk,kd->gqd",
                                    store(jax.nn.softmax(sc, -1)), vg))
        qg = jnp.swapaxes(q, 0, 1).reshape(hkv, g, s, hd)
        att = jax.lax.map(group, (qg, jnp.swapaxes(k, 0, 1),
                                  jnp.swapaxes(v, 0, 1)))   # (hkv, g, S, hd)
        att = jnp.moveaxis(att.reshape(h, s, hd), 0, 1).reshape(s, h * hd)
        x = store(x + store(att @ f32(a + "o_proj.weight")))
        y = store(_rms_norm(x, f32(p + "post_attention_layernorm.weight"),
                            eps))
        x = store(x + store(experts(y, p, f32, cfg, store)))
    return x, sets


def logits(params, cfg, ids, store=_keep):
    """ids: (S,) int32 of ONE sequence -> (S, vocab) float32."""
    with jax.default_matmul_precision("highest"):
        x, _sets = hidden_and_sets(params, cfg, ids, store)
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


def selected_sets(params, cfg, ids):
    """[per layer: (S, S) bool] — which keys each token attends over."""
    with jax.default_matmul_precision("highest"):
        return hidden_and_sets(params, cfg, ids)[1]


def next_token_losses(params, cfg, ids):
    """-log p(ids[t+1] | ids[:t+1]) at every position t < S-1 of ONE
    sequence -> (S-1,) float32."""
    lg = logits(params, cfg, ids)[:-1]
    logp = jax.nn.log_softmax(lg, -1)
    return -jnp.take_along_axis(logp, ids[1:, None], -1)[:, 0]
