"""Dev tool: break the bench train step into timed components on the
attached chip. Each component is repeated REPS times INSIDE one jit
(lax.scan chained) and the whole thing timed with a single host sync,
so per-dispatch host overhead does not drown sub-millisecond parts.

Usage: python tools/profile_step.py [part ...]
Parts: step flash sdpa ce embed raw  (default: all)
"""
from __future__ import annotations

import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

REPS = 16


def sync(out):
    """Block until `out` is done."""
    jax.block_until_ready(out)


def timed(fn, *args, name="", reps=REPS):
    """fn(*args) -> pytree; fn already contains `reps` repetitions."""
    sync(fn(*args))
    t0 = time.perf_counter()
    sync(fn(*args))
    dt = (time.perf_counter() - t0) / reps * 1000
    print(f"{name:38s} {dt:8.2f} ms")
    return dt


def chain(op, x0, reps=REPS):
    """Apply y = op(x) reps times inside one jit, feeding back a scalar
    perturbation so nothing is DCE'd or CSE'd."""
    def body(x, _):
        y = op(x)
        leaf = jax.tree.leaves(y)[0]
        bump = (leaf.ravel()[0]).astype(x.dtype) * 1e-20
        return x + bump, None

    return jax.jit(lambda x: jax.lax.scan(body, x, None, length=reps)[0])


def bench_cfg():
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig(
        vocab_size=32000, hidden_size=1280, intermediate_size=3584,
        num_hidden_layers=16, num_attention_heads=20,
        num_key_value_heads=4, max_position_embeddings=2048,
        rope_theta=10000.0, seq_length=2048, recompute=False,
        use_flash_attention=True)


B, S = 4, 2048


def part_step():
    import paddle_tpu
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.parallel import Trainer, TrainStepConfig
    cfg = bench_cfg()
    paddle_tpu.seed(0)
    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(), weight_decay=0.01)
    trainer = Trainer(model, optimizer,
                      config=TrainStepConfig(compute_dtype="bfloat16"))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    data = {"input_ids": ids, "labels": ids}
    trainer.step(data)
    np.asarray(trainer.params["model.norm.weight"]).ravel()[:1]
    t0 = time.perf_counter()
    n = 10
    for _ in range(n):
        trainer.step(data)
    np.asarray(trainer.params["model.norm.weight"]).ravel()[:1]
    dt = (time.perf_counter() - t0) / n * 1000
    print(f"{'full trainer step':38s} {dt:8.2f} ms")


def _attn_shapes():
    cfg = bench_cfg()
    hq, hk, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, hq, S, d), jnp.bfloat16)
    k = jax.random.normal(k2, (B, hk, S, d), jnp.bfloat16)
    v = jax.random.normal(k3, (B, hk, S, d), jnp.bfloat16)
    return q, k, v


def part_flash():
    from paddle_tpu.kernels.flash_attention import flash_attention_bhsd
    q, k, v = _attn_shapes()
    with jax.default_matmul_precision("default"):
        f = chain(lambda q: flash_attention_bhsd(q, k, v, causal=True)
                  .astype(q.dtype), q)
        timed(f, q, name="flash fwd (1 layer)")

        def fb(q):
            def loss(q, k, v):
                return flash_attention_bhsd(q, k, v, causal=True).astype(
                    jnp.float32).sum()
            g = jax.grad(loss, argnums=(0,))(q, k, v)[0]
            return g.astype(q.dtype)
        timed(chain(fb, q), q, name="flash fwd+bwd (1 layer)")


def part_sdpa():
    import paddle_tpu  # noqa: F401  (match package-global precision env)
    q, k, v = _attn_shapes()

    def sdpa(q, k, v):
        hq, hk = q.shape[1], k.shape[1]
        kk = jnp.repeat(k, hq // hk, axis=1)
        vv = jnp.repeat(v, hq // hk, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk,
                       preferred_element_type=jnp.float32)
        s = s / np.sqrt(q.shape[-1])
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vv,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    with jax.default_matmul_precision("default"):
        timed(chain(lambda q: sdpa(q, k, v), q), q, name="sdpa fwd (1 layer)")

        def fb(q):
            g = jax.grad(lambda q: sdpa(q, k, v).astype(jnp.float32).sum())(q)
            return g.astype(q.dtype)
        timed(chain(fb, q), q, name="sdpa fwd+bwd (1 layer)")


def part_ce():
    cfg = bench_cfg()
    n, d, vsz = B * S, cfg.hidden_size, cfg.vocab_size
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    h = jax.random.normal(k1, (n, d), jnp.bfloat16)
    w = jax.random.normal(k2, (d, vsz), jnp.bfloat16)
    y = jax.random.randint(jax.random.PRNGKey(1), (n,), 0, vsz)

    def ce_raw(h, w):
        logits = (h @ w).astype(jnp.float32)
        ls = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(ls, y[:, None], axis=-1).mean()

    import paddle_tpu  # noqa: F401
    from paddle_tpu.nn import functional as F
    from paddle_tpu.core.tensor import Tensor

    def ce_ours(h, w):
        logits = h @ w
        t = F.cross_entropy(Tensor(logits.reshape(-1, vsz)),
                            Tensor(y.reshape(-1)), reduction="mean")
        return t._value

    with jax.default_matmul_precision("default"):
        timed(chain(lambda h: h + ce_raw(h, w).astype(h.dtype) * 0, h),
              h, name="lm_head+CE fwd (raw)")
        timed(chain(lambda h: jax.grad(ce_raw)(h, w).astype(h.dtype), h),
              h, name="lm_head+CE fwd+bwd_h (raw)")
        timed(chain(lambda h: jax.grad(ce_ours)(h, w).astype(h.dtype), h),
              h, name="lm_head+CE fwd+bwd_h (ours)")

        def both(h):
            gh, gw = jax.grad(ce_ours, argnums=(0, 1))(h, w)
            return gh.astype(h.dtype)
        timed(chain(both, h), h, name="lm_head+CE fwd+bwd_hw (ours)")


def part_embed():
    cfg = bench_cfg()
    vsz, d = cfg.vocab_size, cfg.hidden_size
    tab = jax.random.normal(jax.random.PRNGKey(0), (vsz, d), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, vsz)

    def emb(tab):
        return tab[ids].astype(jnp.float32).sum()

    def emb_onehot(tab):
        oh = jax.nn.one_hot(ids.reshape(-1), vsz, dtype=jnp.bfloat16)
        return (oh @ tab.astype(jnp.bfloat16)).astype(jnp.float32).sum()

    with jax.default_matmul_precision("default"):
        timed(chain(lambda t: jax.grad(emb)(t), tab),
              tab, name="embed fwd+bwd (take+scatter)")
        timed(chain(lambda t: jax.grad(emb_onehot)(t), tab),
              tab, name="embed fwd+bwd (onehot matmul)")


def part_raw():
    """Dense-stack-equivalent fwd+bwd in raw jax (lower bound), REPS=1
    since the stack itself is 16 layers."""
    cfg = bench_cfg()
    d, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    x = jax.random.normal(ks[0], (B * S, d), jnp.bfloat16)
    Wq = jax.random.normal(ks[1], (L, d, d), jnp.bfloat16) * 0.02
    Wo = jax.random.normal(ks[3], (L, d, d), jnp.bfloat16) * 0.02
    W1 = jax.random.normal(ks[4], (L, d, f), jnp.bfloat16) * 0.02
    W2 = jax.random.normal(ks[5], (L, d, f), jnp.bfloat16) * 0.02
    W3 = jax.random.normal(ks[6], (L, f, d), jnp.bfloat16) * 0.02

    def fwd(x, Wq, Wo, W1, W2, W3):
        def layer(x, ws):
            wq, wo, w1, w2, w3 = ws
            a = x @ wq
            x = x + a @ wo
            h = jax.nn.silu(x @ w1) * (x @ w2)
            return x + h @ w3, None
        x, _ = jax.lax.scan(layer, x, (Wq, Wo, W1, W2, W3))
        return x.astype(jnp.float32).sum()

    with jax.default_matmul_precision("default"):
        g = jax.jit(jax.grad(fwd, argnums=(0, 1, 2, 3, 4, 5)))
        timed(g, x, Wq, Wo, W1, W2, W3, reps=1,
              name="raw dense 16-layer stack fwd+bwd")


PARTS = {"step": part_step, "flash": part_flash, "sdpa": part_sdpa,
         "ce": part_ce, "embed": part_embed, "raw": part_raw}

if __name__ == "__main__":
    names = sys.argv[1:] or list(PARTS)
    for nm in names:
        PARTS[nm]()
