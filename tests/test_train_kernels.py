"""Fused train-path kernels (ISSUE 14): blockwise cross-entropy,
RMSNorm+residual, fused RoPE — loss/grad parity vs the dense paths,
the no-logits-materialization pin, model/trainer wiring, and the
phase-attributed step telemetry.

Parity pins are exact-math (atol-pinned f32): the blockwise CE runs
the SAME per-row expressions the dense `_ce_mean_fused` fast path
runs, the fused norm the SAME expressions as the eager `rms_norm_ref`
defop, the fused rope the SAME rotation as `_apply_rope_neox` — so the
fused train path is a memory/layout optimization, not a numerics
change.
"""
import ast
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu
from paddle_tpu.kernels.blockwise_ce import (
    blockwise_ce_loss, ce_shape_problems, check_ce_shapes,
    dense_logits_bytes, logits_bytes_saved)
from paddle_tpu.kernels.fused_norm import (
    rms_norm_residual, rope_apply, norm_shape_problems,
    check_norm_shapes, rope_shape_problems, check_rope_shapes)
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- blockwise cross-entropy -------------------------------------------------

def _ce_inputs(n=33, d=16, v=250, seed=0, n_ignored=2):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, d).astype(np.float32))
    w = jnp.asarray((rng.randn(d, v) * 0.1).astype(np.float32))
    lab = rng.randint(0, v, n).astype(np.int32)
    for i in range(n_ignored):
        lab[(i * 7 + 5) % n] = -100
    return x, w, jnp.asarray(lab)


def _dense_ce(x, w, lab, ignore_index=-100):
    """Dense oracle: the `_ce_mean_fused` math over full [N, V]."""
    s = x @ w
    m = jnp.max(s, -1)
    lse = m + jnp.log(jnp.sum(jnp.exp(s - m[:, None]), -1))
    picked = jnp.take_along_axis(s, lab[:, None], -1)[:, 0]
    valid = lab != ignore_index
    cnt = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    return jnp.sum(jnp.where(valid, lse - picked, 0.0)) / cnt


@pytest.mark.parametrize("kernel,vocab_block", [
    ("jnp", 0),        # whole-vocab row chunks
    ("jnp", 64),       # vocab 250 NOT divisible by 64 (pad + mask)
    ("pallas", 0),     # interpret-mode kernels (CPU tier-1 coverage)
    ("pallas", 64),
])
def test_blockwise_ce_loss_and_grad_parity(kernel, vocab_block):
    """Exact f32 loss AND grad parity fused-vs-dense: odd N=33 not
    divisible by chunk=8, ignore_index rows masked, vocab 250 not
    divisible by the vocab block."""
    x, w, lab = _ce_inputs()
    ld, (gxd, gwd) = jax.value_and_grad(_dense_ce,
                                        argnums=(0, 1))(x, w, lab)

    def fused(x, w):
        return blockwise_ce_loss(x, w, lab, chunk=8,
                                 vocab_block=vocab_block, kernel=kernel)

    lf, (gx, gw) = jax.value_and_grad(fused, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(lf), float(ld), atol=1e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gxd),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gwd),
                               atol=1e-6)


def test_blockwise_ce_all_ignored_rows():
    """Every label ignored: loss 0, grads 0 (the count clamp, not a
    0/0 NaN)."""
    x, w, lab = _ce_inputs()
    lab = jnp.full_like(lab, -100)
    loss, (gx, gw) = jax.value_and_grad(
        lambda a, b: blockwise_ce_loss(a, b, lab, chunk=8),
        argnums=(0, 1))(x, w)
    assert float(loss) == 0.0
    assert float(jnp.abs(gx).max()) == 0.0
    assert float(jnp.abs(gw).max()) == 0.0


def test_blockwise_ce_jit_and_scan_compatible():
    x, w, lab = _ce_inputs()
    f = jax.jit(lambda a, b: jax.value_and_grad(
        lambda p, q: blockwise_ce_loss(p, q, lab, chunk=8,
                                       kernel="jnp"),
        argnums=(0, 1))(a, b))
    lf, (gx, gw) = f(x, w)
    ld = _dense_ce(x, w, lab)
    np.testing.assert_allclose(float(lf), float(ld), atol=1e-6, rtol=0)


def _iter_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for item in vals:
                inner = getattr(item, "jaxpr", None)
                if inner is not None:
                    yield from _iter_jaxprs(inner)
                elif hasattr(item, "eqns"):
                    yield from _iter_jaxprs(item)


def _max_float_aval_elems(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    biggest = 0
    for jp in _iter_jaxprs(jaxpr.jaxpr):
        for eqn in jp.eqns:
            for var in eqn.outvars:
                aval = getattr(var, "aval", None)
                if aval is None or not hasattr(aval, "shape"):
                    continue
                if not jnp.issubdtype(aval.dtype, jnp.floating):
                    continue
                n = 1
                for s in aval.shape:
                    n *= int(s)
                biggest = max(biggest, n)
    return biggest


def test_blockwise_ce_never_materializes_logits():
    """ACCEPTANCE: the traced fused loss (forward AND backward) holds
    no intermediate anywhere near [N, V]-logits size — the largest
    float aval in the whole jaxpr stays O(chunk x V) — while the dense
    control shows the [N, V] tensor plainly."""
    n, d, v, chunk = 96, 16, 128, 16
    x, w, lab = _ce_inputs(n=n, d=d, v=v)

    def fused_vg(x, w):
        return jax.value_and_grad(
            lambda a, b: blockwise_ce_loss(a, b, lab, chunk=chunk,
                                           kernel="jnp"),
            argnums=(0, 1))(x, w)

    def dense_vg(x, w):
        return jax.value_and_grad(_dense_ce, argnums=(0, 1))(
            x, w, lab)

    full = n * v                       # the dense logits element count
    fused_peak = _max_float_aval_elems(fused_vg, x, w)
    dense_peak = _max_float_aval_elems(dense_vg, x, w)
    # dW (d, v) and the x input (n, d) are the largest LEGITIMATE
    # arrays; both far below n*v at these dims
    assert fused_peak <= max(chunk * v, d * v, n * d), fused_peak
    assert fused_peak < full // 2, (fused_peak, full)
    assert dense_peak >= full, (dense_peak, full)


def test_ce_shape_contract():
    # interpret mode: no tiling constraints
    assert ce_shape_problems(33, 16, 250, 8, 64, interpret=True) == []
    # compiled: every misaligned dim named
    probs = ce_shape_problems(33, 100, 250, 7, 100, interpret=False)
    joined = " ".join(probs)
    assert "hidden % 128" in joined
    assert "chunk % 8" in joined
    assert "vocab_block % 128" in joined
    with pytest.raises(ValueError) as ei:
        check_ce_shapes(33, 100, 250, 7, 100, interpret=False)
    assert "hidden % 128" in str(ei.value)
    assert 'kernel="jnp"' in str(ei.value)
    # the entry point validates too
    x, w, lab = _ce_inputs()
    with pytest.raises(ValueError):
        blockwise_ce_loss(x, w, lab, chunk=0)
    with pytest.raises(ValueError):
        blockwise_ce_loss(x, w, lab[:5], chunk=8)
    with pytest.raises(ValueError):
        blockwise_ce_loss(x, w, lab, chunk=8, kernel="cuda")


def test_logits_bytes_accounting():
    assert dense_logits_bytes(1024, 32000, 2) == 1024 * 32000 * 2
    assert logits_bytes_saved(1024, 32000, 0) == 0
    saved = logits_bytes_saved(1024, 32000, 256, 0, 2)
    assert saved == (1024 - 256) * 32000 * 2
    saved_vb = logits_bytes_saved(1024, 32000, 256, 512, 2)
    assert saved_vb == 1024 * 32000 * 2 - 256 * 512 * 2


# -- RMSNorm + residual ------------------------------------------------------

def _norm_inputs(n=37, d=64, seed=1):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, d).astype(np.float32))
    r = jnp.asarray(rng.randn(n, d).astype(np.float32))
    w = jnp.asarray((rng.rand(d) + 0.5).astype(np.float32))
    return x, r, w


@pytest.mark.parametrize("kernel", ["jnp", "pallas"])
def test_rms_norm_residual_parity(kernel):
    """Fused norm+residual == eager `rms_norm_ref` of (x + residual),
    forward and backward (closed-form vjp vs jax AD of the raw op)."""
    from paddle_tpu.nn.functional.norm import _rms_norm
    x, r, w = _norm_inputs()

    def ref(x, r, w):
        h = x + r
        y = _rms_norm.raw_fn(h, w, epsilon=1e-6)
        return jnp.sum(y * jnp.cos(h))      # uses BOTH outputs' paths

    def fused(x, r, w):
        y, h = rms_norm_residual(x, w, residual=r, epsilon=1e-6,
                                 kernel=kernel)
        return jnp.sum(y * jnp.cos(h))

    lr, gr = jax.value_and_grad(ref, argnums=(0, 1, 2))(x, r, w)
    lf, gf = jax.value_and_grad(fused, argnums=(0, 1, 2))(x, r, w)
    np.testing.assert_allclose(float(lf), float(lr), atol=1e-5, rtol=0)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)
    # forward outputs match exactly (same expression tree)
    y_f, h_f = rms_norm_residual(x, w, residual=r, kernel=kernel)
    np.testing.assert_array_equal(np.asarray(h_f), np.asarray(x + r))
    np.testing.assert_allclose(
        np.asarray(y_f), np.asarray(_rms_norm.raw_fn(x + r, w)),
        atol=1e-7)


@pytest.mark.parametrize("kernel", ["jnp", "pallas"])
def test_rms_norm_no_residual_parity(kernel):
    from paddle_tpu.nn.functional.norm import _rms_norm
    x, _, w = _norm_inputs()

    def ref(x, w):
        return jnp.sum(_rms_norm.raw_fn(x, w, epsilon=1e-6) ** 2)

    def fused(x, w):
        y, h = rms_norm_residual(x, w, epsilon=1e-6, kernel=kernel)
        return jnp.sum(y ** 2)

    lr, gr = jax.value_and_grad(ref, argnums=(0, 1))(x, w)
    lf, gf = jax.value_and_grad(fused, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(lf), float(lr), atol=1e-5, rtol=0)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5)


def test_norm_shape_contract():
    assert norm_shape_problems(64, interpret=True) == []
    assert norm_shape_problems(128, interpret=False) == []
    probs = norm_shape_problems(100, interpret=False)
    assert probs and "hidden % 128" in probs[0]
    with pytest.raises(ValueError):
        check_norm_shapes(100, interpret=False)
    x, _, w = _norm_inputs()
    with pytest.raises(ValueError):
        rms_norm_residual(x, w[:-1])
    with pytest.raises(ValueError):
        rms_norm_residual(x, w, residual=x[:-1])


# -- fused RoPE --------------------------------------------------------------

@pytest.mark.parametrize("kernel", ["jnp", "pallas"])
def test_rope_parity(kernel):
    """Fused rope == the model's current `_apply_rope_neox` apply,
    forward + backward (inverse-rotation vjp vs jax AD), with both
    default positions and explicit (B, S) position ids."""
    from paddle_tpu.incubate.nn.functional import (_apply_rope_neox,
                                                   _rope_cos_sin)
    rng = np.random.RandomState(2)
    b, s, h, d = 2, 9, 3, 8
    x = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
    cos, sin = _rope_cos_sin(s, d, 10000.0, jnp.float32)
    ref_out = _apply_rope_neox(x, cos, sin)
    out = rope_apply(x, theta=10000.0, kernel=kernel)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=1e-6)
    g_ref = jax.grad(
        lambda a: jnp.sum(jnp.sin(_apply_rope_neox(a, cos, sin))))(x)
    g_f = jax.grad(
        lambda a: jnp.sum(jnp.sin(rope_apply(a, theta=10000.0,
                                             kernel=kernel))))(x)
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_ref),
                               atol=1e-6)
    # explicit positions (the generation/decode form)
    pos = jnp.asarray(rng.randint(0, 40, (b, s)).astype(np.int32))
    cos_p, sin_p = _rope_cos_sin(s, d, 10000.0, jnp.float32,
                                 position_ids=pos)
    ref_p = _apply_rope_neox(x, cos_p, sin_p)
    out_p = rope_apply(x, positions=pos, theta=10000.0, kernel=kernel)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(ref_p),
                               atol=1e-6)


def test_rope_shape_contract():
    assert rope_shape_problems(8, interpret=False) == []
    assert "even" in rope_shape_problems(7, interpret=True)[0]
    probs = rope_shape_problems(10, interpret=False)
    assert any("% 8" in p for p in probs)
    with pytest.raises(ValueError):
        check_rope_shapes(10, interpret=False)
    x = jnp.zeros((1, 4, 2, 6), jnp.float32)
    with pytest.raises(ValueError):
        rope_apply(x, kernel="cuda")


# -- model + trainer wiring --------------------------------------------------

def _batch_ids(cfg, b, s, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, cfg.vocab_size, (b, s)).astype(np.int32)


def _build(**over):
    paddle_tpu.seed(7)
    cfg = tiny_llama_config(**over)
    return cfg, LlamaForCausalLM(cfg)


def test_model_blockwise_loss_parity():
    """tiny-llama loss + grad parity fused-vs-dense through the EAGER
    tape: odd B*S (3 x 11 = 33) not divisible by chunk 8, vocab 256
    not divisible by vocab block 48."""
    cfg, m0 = _build()
    ids = paddle_tpu.to_tensor(_batch_ids(cfg, 3, 11))
    l0, logits0 = m0(ids, labels=ids)
    l0.backward()
    g_embed0 = m0.model.embed_tokens.weight.grad.numpy().copy()
    g_head0 = m0.lm_head.weight.grad.numpy().copy()

    cfg1, m1 = _build(loss_chunk=8, loss_vocab_block=48)
    l1, none = m1(ids, labels=ids)
    assert none is None, "blockwise path must not materialize logits"
    l1.backward()
    np.testing.assert_allclose(float(l1.numpy()), float(l0.numpy()),
                               atol=1e-6, rtol=0)
    np.testing.assert_allclose(m1.model.embed_tokens.weight.grad.numpy(),
                               g_embed0, atol=1e-6)
    np.testing.assert_allclose(m1.lm_head.weight.grad.numpy(),
                               g_head0, atol=1e-6)


def test_model_blockwise_loss_tied_embeddings():
    """Tied embeddings route the (V, D) weight through transpose_w;
    grads land back on the embedding in its own layout."""
    cfg, m0 = _build(tie_word_embeddings=True)
    ids = paddle_tpu.to_tensor(_batch_ids(cfg, 2, 16))
    l0, _ = m0(ids, labels=ids)
    l0.backward()
    g0 = m0.model.embed_tokens.weight.grad.numpy().copy()
    cfg1, m1 = _build(tie_word_embeddings=True, loss_chunk=8)
    l1, _ = m1(ids, labels=ids)
    l1.backward()
    np.testing.assert_allclose(float(l1.numpy()), float(l0.numpy()),
                               atol=1e-6, rtol=0)
    np.testing.assert_allclose(m1.model.embed_tokens.weight.grad.numpy(),
                               g0, atol=1e-6)


def test_model_blockwise_loss_tied_square_vocab():
    """Regression (review): with vocab_size == hidden_size the tied
    (V, D) weight is SQUARE — a shape-sniffed transpose guard cannot
    tell the layouts apart and silently consumed W transposed. The
    caller now states the layout explicitly; parity must hold."""
    over = dict(tie_word_embeddings=True, vocab_size=64, hidden_size=64,
                num_attention_heads=4, num_key_value_heads=2)
    cfg, m0 = _build(**over)
    assert cfg.vocab_size == cfg.hidden_size
    ids = paddle_tpu.to_tensor(_batch_ids(cfg, 2, 16))
    l0, _ = m0(ids, labels=ids)
    cfg1, m1 = _build(loss_chunk=8, **over)
    l1, _ = m1(ids, labels=ids)
    np.testing.assert_allclose(float(l1.numpy()), float(l0.numpy()),
                               atol=1e-6, rtol=0)


def test_model_fused_norm_rope_parity():
    """fused_norm + fused_rope: logits bit-for-bit vs the unfused
    model (same expression trees), loss equal, backward within f32
    rounding."""
    cfg, m0 = _build()
    ids = paddle_tpu.to_tensor(_batch_ids(cfg, 2, 16))
    l0, logits0 = m0(ids, labels=ids)
    l0.backward()
    g0 = m0.model.layers[0].self_attn.q_proj.weight.grad.numpy().copy()

    cfg2, m2 = _build(fused_norm=True, fused_rope=True)
    l2, logits2 = m2(ids, labels=ids)
    np.testing.assert_array_equal(logits2.numpy(), logits0.numpy())
    np.testing.assert_allclose(float(l2.numpy()), float(l0.numpy()),
                               rtol=1e-7)
    l2.backward()
    g2 = m2.model.layers[0].self_attn.q_proj.weight.grad.numpy()
    np.testing.assert_allclose(g2, g0, atol=1e-6)


def test_trainer_grad_accum_step_parity():
    """ACCEPTANCE: end-to-end step parity through Trainer with
    grad_accum_steps > 1 — the fully-fused train path (blockwise CE +
    fused norm + fused rope) reproduces the dense path's losses step
    for step."""
    from paddle_tpu.parallel import Trainer, TrainStepConfig
    import paddle_tpu.optimizer as opt
    ids = _batch_ids(tiny_llama_config(), 4, 32, seed=3)

    def run(**over):
        paddle_tpu.seed(7)
        cfg = tiny_llama_config(**over)
        m = LlamaForCausalLM(cfg)
        o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
        tr = Trainer(m, o, config=TrainStepConfig(
            compute_dtype=None, grad_accum_steps=2))
        return [float(tr.step({"input_ids": ids, "labels": ids}).numpy())
                for _ in range(3)]

    dense = run()
    fused = run(loss_chunk=8, fused_norm=True, fused_rope=True)
    np.testing.assert_allclose(fused, dense, rtol=1e-5, atol=1e-6)


def test_generation_unchanged_with_fused_path():
    """The fused knobs must not perturb KV-cache decode: greedy
    generation parity vs the unfused model."""
    cfg, m0 = _build()
    cfg1, m1 = _build(fused_norm=True, fused_rope=True)
    ids = paddle_tpu.to_tensor(_batch_ids(cfg, 2, 8))
    out0 = m0.generate(ids, max_new_tokens=4)
    out1 = m1.generate(ids, max_new_tokens=4)
    a0 = out0[0] if isinstance(out0, (tuple, list)) else out0
    a1 = out1[0] if isinstance(out1, (tuple, list)) else out1
    np.testing.assert_array_equal(a0.numpy(), a1.numpy())


# -- phase telemetry ---------------------------------------------------------

def test_phase_telemetry_and_logits_gauge():
    from paddle_tpu import observability as obs
    from paddle_tpu.parallel import Trainer, TrainStepConfig
    import paddle_tpu.optimizer as opt
    paddle_tpu.seed(7)
    cfg = tiny_llama_config(loss_chunk=8)
    m = LlamaForCausalLM(cfg)
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    tr = Trainer(m, o, config=TrainStepConfig(compute_dtype=None))
    batch = {"input_ids": _batch_ids(cfg, 4, 32),
             "labels": _batch_ids(cfg, 4, 32)}
    with obs.scoped(reset=True) as reg:
        tr.step(batch)
        tr.step(batch)
        phases = tr.measure_phase_seconds(batch, iters=1)
        assert set(phases) == {"fwd", "bwd", "optimizer", "step"}
        # single wall-clock timings under a loaded host: each phase is
        # positive or clamped at 0, and no ordering between them holds
        assert phases["fwd"] > 0 and phases["step"] > 0
        assert phases["bwd"] >= 0 and phases["optimizer"] >= 0
        h = reg.histogram("train.phase.seconds")
        for ph in ("fwd", "bwd", "optimizer"):
            assert h.count(phase=ph) == 1, ph
        g = reg.gauge("train.loss.logits_bytes_saved")
        # f32 compute: (B*S - chunk) * vocab * 4
        assert g.value() == (4 * 32 - 8) * cfg.vocab_size * 4
    # dense config never sets the gauge
    paddle_tpu.seed(7)
    m2 = LlamaForCausalLM(tiny_llama_config())
    tr2 = Trainer(m2, opt.AdamW(learning_rate=1e-3,
                                parameters=m2.parameters()),
                  config=TrainStepConfig(compute_dtype=None))
    with obs.scoped(reset=True) as reg2:
        tr2.step(batch)
        tr2.step(batch)
        assert reg2.gauge("train.loss.logits_bytes_saved").value() \
            is None


# -- satellites: import surface + catalogue pins -----------------------------

def test_kernels_import_surface():
    """`import paddle_tpu.kernels` in a FRESH process exposes every
    kernel module — including quant_matmul (previously missing) and
    the two new train-path modules."""
    code = (
        "import paddle_tpu.kernels as k\n"
        "mods = ['blockwise_ce', 'flash_attention', 'fused_norm',\n"
        "        'paged_attention', 'quant_matmul']\n"
        "missing = [m for m in mods if not hasattr(k, m)]\n"
        "assert not missing, missing\n"
        "from paddle_tpu.kernels.blockwise_ce import blockwise_ce_loss\n"
        "from paddle_tpu.kernels.fused_norm import rms_norm_residual\n"
        "from paddle_tpu.kernels.quant_matmul import "
        "weight_only_int8_matmul\n"
        "print('ok')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], cwd=_ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=240)
    assert out.returncode == 0, out.stderr
    assert "ok" in out.stdout


def test_train_phase_metrics_catalogued_both_directions():
    """PR 7 pattern for the new family: every train.phase.* /
    train.loss.* observability literal in parallel/trainer.py is
    catalogued, and every catalogued name of the family is recorded by
    a literal call site in trainer.py."""
    from paddle_tpu.observability.metrics import METRICS
    src = os.path.join(_ROOT, "paddle_tpu", "parallel", "trainer.py")
    tree = ast.parse(open(src).read())
    seen = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and node.args \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in ("inc", "observe", "set_gauge"):
            arg = node.args[0]
            assert isinstance(arg, ast.Constant) and \
                isinstance(arg.value, str), \
                f"non-literal metric name at trainer.py:{node.lineno}"
            assert arg.value in METRICS, arg.value
            seen.add(arg.value)
    family = {n for n in METRICS
              if n.startswith("train.phase.")
              or n.startswith("train.loss.logits_")}
    assert family == {"train.phase.seconds",
                      "train.loss.logits_bytes_saved"}
    missing = family - seen
    assert not missing, f"catalogued but never recorded: {missing}"
    assert METRICS["train.phase.seconds"][0] == "histogram"
    assert METRICS["train.loss.logits_bytes_saved"][0] == "gauge"
