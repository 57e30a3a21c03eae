#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py            (one process, no arguments, no network)

Drives the two main paths once, through the entry points a user calls,
at the full width of TinyLlama-1.1B (hidden 2048, intermediate 5632,
32 query / 4 KV heads of width 64, vocab 32,000, rope theta 10,000,
context 2,048; bf16 compute; random weights from a fixed seed):

  device   jax must report a TPU, or the run stops here, non-zero.
  kernels  every Pallas kernel on the hot path, compiled (interpret=False)
           at this model's shapes and compared with its jax.numpy
           reference.
  train    LlamaForCausalLM + AdamW + Trainer, flash attention, blockwise
           cross-entropy (loss_chunk=512), batches through
           trainer.data_iter: one compile step + four steps on a repeated
           batch of 4 x 2,048. One chip: depth cut to TRAIN_LAYERS_ONE_CHIP
           of 22 layers (16 GB holds that many at 16 bytes a parameter plus
           activations). Four chips: all 22 layers under fsdp 2 x mp 2.
  serve    all 22 layers, a fresh bf16 model, PagedKVEngine behind
           PredictorServer on 127.0.0.1: eight POST /generate requests,
           half of them streamed, on four slots.

It is a smoke, not a benchmark: it claims no speed. Every phase must
pass. The last line of stdout is one JSON object with exactly two keys,
`{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}`
(the device as jax reports it); the line before it, `[summary] {...}`,
carries what each phase measured. The exit code is 0 only if `ok` is
true; with no accelerator there is no result line at all. Token parity with a solo run is NOT asserted in
serve (random bf16 weights flip the top logit on rounding): correctness
on the chip rests on the kernels phase, which compares values.

tests/test_chip_smoke.py drives these same phase functions on the CPU at
a tiny size with the kernels in interpret mode; there is no flag or
environment variable here that skips the device check.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import math
import sys
import threading
import time
import traceback
import urllib.request

import numpy as np

SEED = 0
TRAIN_LAYERS_ONE_CHIP = 8


@dataclasses.dataclass(frozen=True)
class Size:
    """Every shape the phases use. FULL is the contract; the CPU test
    passes a tiny one."""
    vocab: int = 32000
    hidden: int = 2048
    intermediate: int = 5632
    heads: int = 32
    kv_heads: int = 4
    layers: int = 22
    context: int = 2048
    rope_theta: float = 10000.0
    # kernels phase
    kern_batch: int = 2          # flash whole-kv / rope batch
    stream_seq: int = 8192       # streamed-kv flash sequence
    ce_chunk: int = 512
    ce_vocab_block: int = 1024
    flash_block: int | None = None   # None = the kernels' own choice
    decode_slots: int = 8
    decode_tokens: int = 1024    # paged-decode window per slot
    # train phase
    train_layers_one_chip: int = TRAIN_LAYERS_ONE_CHIP
    batch: int = 4
    train_steps: int = 4
    # serve phase
    slots: int = 4
    requests: int = 8
    prompt_lo: int = 64
    prompt_hi: int = 512
    new_tokens: int = 32

    @property
    def head_dim(self):
        return self.hidden // self.heads


FULL = Size()


class PhaseFailed(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


def log(*a):
    print(*a, flush=True)


# ---------------------------------------------------------------------------
# compile accounting: jax reports every backend compile (or cache
# retrieval) through its monitoring hook; phases read the tally
# ---------------------------------------------------------------------------

class CompileLog:
    def __init__(self):
        self.durations = []      # seconds, in order
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.durations.append(float(secs))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        return (len(self.durations), self.cache_hits, self.cache_misses)

    def since(self, mark):
        n, h, m = mark
        d = self.durations[n:]
        return {"compile_s": round(sum(d), 2),
                "programs": len(d),
                "longest_s": [round(x, 2)
                              for x in sorted(d, reverse=True)[:6]],
                "cache_hits": self.cache_hits - h,
                "cache_misses": self.cache_misses - m}


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------

def phase_device():
    import importlib.metadata as md

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise PhaseFailed(
            f"no TPU found: jax reports platform {dev.platform!r} "
            f"({getattr(dev, 'device_kind', '?')}); chip_smoke.py "
            f"only runs on the chip")
    from paddle_tpu import _native
    from paddle_tpu.core import compile_cache
    from paddle_tpu.device.peaks import peaks_for_kind

    def ver(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return "not installed"

    out = {"platform": dev.platform, "kind": dev.device_kind,
           "count": len(devs),
           "versions": {p: ver(p) for p in ("jax", "jaxlib", "libtpu")},
           "compile_cache_dir": compile_cache.ensure(),
           "native": "loaded" if _native.available()
           else "python fallback",
           "peaks": peaks_for_kind(dev.device_kind)._asdict()}
    log("[device]", json.dumps(out))
    return out


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------

# bf16 inputs against an f32 reference: tests/test_flash_attention.py
# test_bf16_fwd's tolerance; f32 inputs (the CPU interpret-mode test):
# the loosest tolerance the kernels' own parity tests use
_TOL = {"bfloat16": 5e-2, "float32": 1e-4}


def _custom_calls(lowered_text):
    return lowered_text.count("@tpu_custom_call")


def _rel_err(got, want):
    """max |got - want| over max |want|, per output leaf."""
    import jax
    errs = []
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        check(g.shape == w.shape, f"shape {g.shape} != {w.shape}")
        check(np.isfinite(g).all(), "non-finite kernel output")
        errs.append(float(np.max(np.abs(g - w))
                          / (np.max(np.abs(w)) + 1e-30)))
    return max(errs)


def _attention_ref(q, k, v, g):
    """Dense causal GQA attention and its vjp in f32, one q head at a
    time (lax.map) so the (s, s) scores never exceed one head."""
    import jax
    import jax.numpy as jnp
    b, hq, s, d = q.shape
    hk = k.shape[1]
    rep = hq // hk
    f32 = jnp.float32
    mask = jnp.tril(jnp.ones((s, s), bool))

    def one(qh, kh, vh):
        sc = (qh @ kh.T) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return p @ vh

    def head(args):
        qh, gh, kh, vh = args
        o, vjp = jax.vjp(one, qh, kh, vh)
        return (o,) + vjp(gh)

    flat = lambda x: x.astype(f32).reshape(-1, s, d)       # noqa: E731
    o, dq, dk, dv = jax.lax.map(
        head, (flat(q), flat(g), flat(jnp.repeat(k, rep, axis=1)),
               flat(jnp.repeat(v, rep, axis=1))))
    o, dq = o.reshape(q.shape), dq.reshape(q.shape)
    dk = dk.reshape(b, hk, rep, s, d).sum(2)
    dv = dv.reshape(b, hk, rep, s, d).sum(2)
    return o, dq, dk, dv


def _flash_case(size, dtype, interpret, rng, *, stream):
    """flash fwd+bwd through the GQA fold (q heads sharing a kv head
    ride one folded q axis — never jnp.repeat). whole-kv: the train
    step's shape; streamed: one kv head's group at the long sequence."""
    from paddle_tpu.kernels import flash_attention as fa
    d = size.head_dim
    rep = size.heads // size.kv_heads
    if stream:
        b, hk, s = 1, 1, size.stream_seq
    else:
        b, hk, s = size.kern_batch, size.kv_heads, size.context
    hq = hk * rep
    scale = 1.0 / math.sqrt(d)
    blk = size.flash_block
    kw = dict(block_q=blk, block_k=blk, interpret=interpret, seg_len=s,
              stream_kv=True if stream else None)

    def fn(q, k, v, g):
        qf = q.reshape(b, hk, rep * s, d)
        gf = g.reshape(b, hk, rep * s, d)
        o, lse = fa._flash_fwd_pallas(qf, k, v, True, scale, **kw)
        dq, dk, dv = fa._flash_bwd_pallas(qf, k, v, o, lse, gf, True,
                                          scale, **kw)
        return o.reshape(q.shape), dq.reshape(q.shape), dk, dv

    args = [rng.standard_normal(shp, np.float32).astype(dtype)
            for shp in ((b, hq, s, d), (b, hk, s, d), (b, hk, s, d),
                        (b, hq, s, d))]
    return fn, args, _attention_ref


def _paged_case(size, dtype, interpret, rng, *, int8):
    import jax.numpy as jnp
    from paddle_tpu.inference.paged import PagedState, _attend_pages
    from paddle_tpu.kernels.paged_attention import paged_decode_attention
    page = 32 if int8 else 16
    d, hq, hk = size.head_dim, size.heads, size.kv_heads
    b = size.decode_slots
    mp = size.decode_tokens // page
    num_pages = b * mp + 1
    lens = rng.integers(1, mp * page - 1, size=b).astype(np.int32)
    bt = np.zeros((b, mp), np.int32)
    for i in range(b):
        used = int(lens[i]) // page + 1
        bt[i, :used] = 1 + i * mp + np.arange(used)
    q = rng.standard_normal((b, hq, d), np.float32).astype(dtype)
    pool = lambda: rng.standard_normal(                       # noqa: E731
        (num_pages, hk, page, d), np.float32)
    if int8:
        kp, vp = (np.clip(np.round(pool() * 40), -127, 127)
                  .astype(np.int8) for _ in range(2))
        ks, vs = (rng.uniform(0.5, 1.5, (num_pages, hk))
                  .astype(np.float32) / 40 for _ in range(2))
    else:
        kp, vp = (pool().astype(dtype) for _ in range(2))
        ks = vs = None

    def fn(q, kp, vp, bt, lens, ks, vs):
        return paged_decode_attention(q, kp, vp, bt, lens, k_scale=ks,
                                      v_scale=vs, interpret=interpret)

    def ref(q, kp, vp, bt, lens, ks, vs):
        state = PagedState(bt, lens, jnp.ones_like(lens))
        out = _attend_pages(q.astype(jnp.float32)[:, None], kp, vp,
                            state, ks, vs)
        return out.reshape(b, hq, d)

    return fn, [q, kp, vp, bt, lens, ks, vs], ref


def _kv_write_case(size, dtype, interpret, rng, *, tokens):
    """`tokens` new tokens a slot into K and V pools stored as the decode
    kernel's rows, some slots with fewer valid ones and some with none;
    the reference is XLA's scatter over the same pools by heads."""
    import jax.numpy as jnp
    from paddle_tpu.inference.paged import PagedState, _token_coords
    from paddle_tpu.kernels.paged_attention import (pages_by_head,
                                                    paged_kv_write,
                                                    pool_rows_shape)
    page = 16
    d, hk, b = size.head_dim, size.kv_heads, size.decode_slots
    mp = size.decode_tokens // page
    num_pages = b * mp + 1
    rows = pool_rows_shape(num_pages, hk, d, page, dtype)
    kp, vp = (rng.standard_normal(rows, np.float32).astype(dtype)
              for _ in range(2))
    k, v = (rng.standard_normal((b, tokens, hk, d), np.float32)
            .astype(dtype) for _ in range(2))
    bt = 1 + np.arange(b * mp, dtype=np.int32).reshape(b, mp)
    lens = rng.integers(0, mp * page - tokens, size=b).astype(np.int32)
    n_valid = rng.integers(0, tokens + 1, size=b).astype(np.int32)

    def coords(bt, lens, n_valid):
        return _token_coords(PagedState(bt, lens, n_valid), tokens, page,
                             num_pages)

    def fn(kp, vp, k, v, bt, lens, n_valid):
        return paged_kv_write(kp, vp, k, v, *coords(bt, lens, n_valid),
                              interpret=interpret)

    def ref(kp, vp, k, v, bt, lens, n_valid):
        phys, off = coords(bt, lens, n_valid)
        return tuple(
            pages_by_head(pool, hk, d).at[phys, :, off, :].set(
                toks.reshape(b * tokens, hk, d), mode="drop").reshape(rows)
            for pool, toks in ((kp, k), (vp, v)))

    return fn, [kp, vp, k, v, bt, lens, n_valid], ref


def _ce_case(size, dtype, interpret, rng, *, vocab_block):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.blockwise_ce import blockwise_ce_loss
    n, d, v = size.batch * size.context, size.hidden, size.vocab
    x = rng.standard_normal((n, d), np.float32).astype(dtype)
    w = (rng.standard_normal((d, v), np.float32) * 0.02).astype(dtype)
    labels = rng.integers(0, v, size=n).astype(np.int32)
    labels[rng.random(n) < 0.05] = -100
    # on the chip the AUTO path must pick the kernels (the lowered text
    # is checked for them); off it, force them through the interpreter
    kernel = "pallas" if interpret else None

    def fn(x, w, labels):
        return jax.value_and_grad(
            lambda x_, w_: blockwise_ce_loss(
                x_, w_, labels, chunk=size.ce_chunk,
                vocab_block=vocab_block, kernel=kernel,
                interpret=interpret), argnums=(0, 1))(x, w)

    def ref(x, w, labels):
        def loss(x_, w_):
            logp = jax.nn.log_softmax(x_ @ w_, axis=-1)
            valid = labels != -100
            picked = jnp.take_along_axis(
                logp, jnp.where(valid, labels, 0)[:, None], axis=1)[:, 0]
            return -jnp.sum(jnp.where(valid, picked, 0.0)) \
                / jnp.maximum(jnp.sum(valid), 1)
        return jax.value_and_grad(loss, argnums=(0, 1))(
            x.astype(jnp.float32), w.astype(jnp.float32))

    return fn, [x, w, labels], ref


def _norm_case(size, dtype, interpret, rng, *, residual):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.fused_norm import rms_norm_residual
    n, d = size.batch * size.context, size.hidden
    mk = lambda *s: rng.standard_normal(s, np.float32).astype(dtype)  # noqa: E731,E501
    x, w, gy, gh = mk(n, d), mk(d), mk(n, d), mk(n, d)
    res = mk(n, d) if residual else None
    kernel = "pallas" if interpret else None

    def run(x, w, res, gy, gh, f):
        if res is None:
            y, vjp = jax.vjp(lambda x_, w_: f(x_, w_, None)[0], x, w)
            return (y,) + vjp(gy)
        (y, h), vjp = jax.vjp(f, x, w, res)
        return (y, h) + vjp((gy, gh))

    def fn(x, w, res, gy, gh):
        return run(x, w, res, gy, gh,
                   lambda x_, w_, r_: rms_norm_residual(
                       x_, w_, r_, 1e-5, kernel=kernel,
                       interpret=interpret))

    def ref(x, w, res, gy, gh):
        def f(x_, w_, r_):
            h = x_ if r_ is None else x_ + r_
            ms = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
            return h * jax.lax.rsqrt(ms + 1e-5) * w_, h
        up = lambda a: None if a is None else a.astype(jnp.float32)  # noqa: E731,E501
        return run(up(x), up(w), up(res), up(gy), up(gh), f)

    return fn, [x, w, res, gy, gh], ref


def _rope_case(size, dtype, interpret, rng):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.fused_norm import rope_apply
    b, s, h, d = size.kern_batch, size.context, size.heads, size.head_dim
    x, g = (rng.standard_normal((b, s, h, d), np.float32).astype(dtype)
            for _ in range(2))
    kernel = "pallas" if interpret else None

    def fn(x, g):
        y, vjp = jax.vjp(lambda x_: rope_apply(
            x_, None, size.rope_theta, kernel=kernel,
            interpret=interpret), x)
        return y, vjp(g)[0]

    def ref(x, g):
        def f(x_):
            inv = 1.0 / (size.rope_theta ** (
                jnp.arange(0, d, 2, dtype=jnp.float32) / d))
            ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
            cos = jnp.cos(ang)[None, :, None, :]
            sin = jnp.sin(ang)[None, :, None, :]
            x1, x2 = x_[..., :d // 2], x_[..., d // 2:]
            return jnp.concatenate([x1 * cos - x2 * sin,
                                    x2 * cos + x1 * sin], axis=-1)
        y, vjp = jax.vjp(f, x.astype(jnp.float32))
        return y, vjp(g.astype(jnp.float32))[0]

    return fn, [x, g], ref


def _quant_case(size, dtype, interpret, rng):
    import jax.numpy as jnp
    from paddle_tpu.kernels.quant_matmul import weight_only_int8_matmul
    m, k, n = size.decode_slots, size.hidden, size.intermediate
    blk = next(c for c in (512, 256, 128, 64, 32, 16, 8)
               if k % c == 0 and n % c == 0)
    x = rng.standard_normal((m, k), np.float32).astype(dtype)
    qw = rng.integers(-127, 128, size=(k, n)).astype(np.int8)
    scale = (rng.uniform(0.5, 1.5, n) / 127).astype(np.float32)

    def fn(x, qw, scale):
        return weight_only_int8_matmul(x, qw, scale, block_n=blk,
                                       block_k=blk, out_dtype=jnp.float32,
                                       interpret=interpret)

    def ref(x, qw, scale):
        # the kernel's contract: bf16 MXU operands (int8 is exact in
        # bf16), f32 accumulation, f32 scale in the epilogue
        return jnp.matmul(x.astype(jnp.bfloat16), qw.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32) * scale

    return fn, [x, qw, scale], ref


def kernel_cases(size):
    """(name, builder) for every Pallas kernel on the hot path; a
    builder takes (size, dtype, interpret, rng) and returns the kernel
    call, its arguments and its jax.numpy reference."""
    import functools
    p = functools.partial
    return [
        ("flash_fwd_bwd_whole_kv", p(_flash_case, stream=False)),
        ("flash_fwd_bwd_streamed_kv", p(_flash_case, stream=True)),
        ("paged_decode_bf16_page16", p(_paged_case, int8=False)),
        ("paged_decode_int8_page32", p(_paged_case, int8=True)),
        ("paged_kv_write_one_token", p(_kv_write_case, tokens=1)),
        ("paged_kv_write_prompt", p(_kv_write_case, tokens=40)),
        ("blockwise_ce_whole_vocab", p(_ce_case, vocab_block=0)),
        ("blockwise_ce_vocab_block",
         p(_ce_case, vocab_block=size.ce_vocab_block)),
        ("rms_norm_residual", p(_norm_case, residual=True)),
        ("rms_norm_no_residual", p(_norm_case, residual=False)),
        ("rope_apply", _rope_case),
        ("weight_only_int8_matmul", _quant_case),
    ]


def phase_kernels(size, clog, *, interpret=False, dtype="bfloat16"):
    """Compile each kernel, run it, compare with its reference. A compile
    error or a mismatch fails the phase and names the kernel."""
    import jax
    import jax.numpy as jnp
    tol = _TOL[dtype]
    rows = {}
    for i, (name, build) in enumerate(kernel_cases(size)):
        try:
            fn, args, ref = build(size, jnp.dtype(dtype), interpret,
                                  np.random.default_rng(SEED + i))
            args = [None if a is None else jnp.asarray(a) for a in args]
            mark = clog.mark()
            lowered = jax.jit(fn).lower(*args)
            calls = _custom_calls(lowered.as_text())
            if not interpret:
                check(calls >= 1, "lowered with no tpu_custom_call: the "
                      "Pallas path was not the one taken")
            got = jax.block_until_ready(lowered.compile()(*args))
            compile_s = clog.since(mark)["compile_s"]
            with jax.default_matmul_precision("highest"):
                want = jax.block_until_ready(jax.jit(ref)(*args))
            err = _rel_err(got, want)
            check(err <= tol, f"max relative error {err:.3e} > {tol:g}")
        except Exception as e:
            raise PhaseFailed(
                f"kernel {name}: {type(e).__name__}: {e}") from e
        rows[name] = {"custom_calls": calls, "rel_err": float(f"{err:.3g}"),
                      "compile_s": compile_s}
        log(f"[kernels] {name}: {json.dumps(rows[name])}")
        del got, want, args
    return {"tolerance": tol, "dtype": dtype, "kernels": rows}


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------

def llama_config(size, layers, **kw):
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig(
        vocab_size=size.vocab, hidden_size=size.hidden,
        intermediate_size=size.intermediate, num_hidden_layers=layers,
        num_attention_heads=size.heads,
        num_key_value_heads=size.kv_heads,
        max_position_embeddings=size.context, rope_theta=size.rope_theta,
        seq_length=size.context, **kw)


def _memory(dev):
    st = dev.memory_stats() or {}
    return {k: int(st[k]) for k in ("bytes_in_use", "peak_bytes_in_use",
                                    "bytes_limit") if k in st}


def phase_train(size, clog, *, on_chip=True):
    """`on_chip=False` (the CPU test) drops the two assertions only a
    TPU can meet: Pallas custom calls in the lowered step, and
    per-device memory statistics."""
    import jax
    import paddle_tpu
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.parallel import Trainer, TrainStepConfig

    devs = jax.devices()
    multi = len(devs) >= 4
    layers = size.layers if multi else size.train_layers_one_chip
    cfg = llama_config(size, layers, use_flash_attention=True,
                       loss_chunk=size.ce_chunk)
    paddle_tpu.seed(SEED)
    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(), weight_decay=0.01)
    mesh = plan = None
    shape = {"fsdp": 2, "mp": 2} if multi else {"fsdp": 1, "mp": 1}
    if multi:
        from paddle_tpu.distributed.mesh import init_mesh
        from paddle_tpu.parallel.plan import llama_sharding_plan
        mesh = init_mesh(shape)
        plan = llama_sharding_plan(mesh.dim_names)
    trainer = Trainer(model, optimizer, mesh=mesh, plan=plan,
                      config=TrainStepConfig(compute_dtype="bfloat16"))
    out = {"layers": layers, "of_layers": size.layers,
           "batch": size.batch, "seq": size.context,
           "train_devices": 4 if multi else 1,
           "mesh": shape if multi else None}
    if multi:
        for n, a in trainer.params.items():
            check(len(a.sharding.device_set) == 4,
                  f"param {n} lives on {len(a.sharding.device_set)} "
                  f"devices, not 4")

    rng = np.random.default_rng(SEED)
    ids = rng.integers(0, size.vocab, (size.batch, size.context)
                       ).astype(np.int32)
    data = {"input_ids": ids, "labels": ids}

    # what the step lowers to: the auto-dispatch must have taken the
    # Pallas kernels (flash through the GQA fold, blockwise CE), and on
    # a mesh their operands must be per-shard, not global
    if on_chip:
        text = trainer.lower(data).as_text()
        calls = _custom_calls(text)
        out["custom_calls"] = calls
        want = 2 * layers + 3        # flash fwd + fused bwd a layer; CE x3
        check(calls == want, f"step lowered with {calls} tpu_custom_call, "
              f"expected {want} (flash fwd+bwd per layer + blockwise CE "
              f"fwd/dx/dw)")
        rep = size.heads // size.kv_heads
        b_loc = size.batch // shape["fsdp"]
        hk_loc = size.kv_heads // shape["mp"]
        folded = (f"tensor<{b_loc}x{hk_loc}x{rep * size.context}"
                  f"x{size.head_dim}xbf16>")
        check(folded in text, f"no flash operand of the folded per-shard "
              f"shape {folded} in the lowered step")
        out["flash_q_operand"] = folded
        del text

    it = trainer.data_iter(
        itertools.repeat(data, size.train_steps + 1), depth=2)
    losses = []
    try:
        mark = clog.mark()
        t0 = time.perf_counter()
        loss = trainer.step(next(it))
        jax.block_until_ready(loss._value)
        out["first_step_s"] = round(time.perf_counter() - t0, 2)
        out["compile"] = clog.since(mark)
        losses.append(float(loss))
        traces = trainer._trace_count()
        t0 = time.perf_counter()
        for batch in it:
            loss = trainer.step(batch)
            jax.block_until_ready(loss._value)
            losses.append(float(loss))
        out["steps_s"] = round(time.perf_counter() - t0, 2)
    finally:
        it.close()
    out["losses"] = [round(x, 4) for x in losses]
    log("[train] losses", out["losses"])
    check(len(losses) == size.train_steps + 1, "a step went missing")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    check(trainer._trace_count() == traces,
          f"the step retraced after the first call "
          f"({traces} -> {trainer._trace_count()})")
    mem = [_memory(d) for d in devs[:4 if multi else 1]]
    out["memory"] = mem
    if multi and on_chip:       # the CPU backend reports no memory stats
        used = [m.get("bytes_in_use", 0) for m in mem]
        check(min(used) > 0, f"a chip holds nothing: {used}")
        check(max(used) < 2 * min(used),
              f"memory is not spread evenly over the chips: {used}")
    log("[train]", json.dumps(out))
    return out


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def _post_generate(port, ids, new_tokens, stream):
    body = json.dumps({"ids": [ids], "max_new_tokens": new_tokens,
                       "stream": stream}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=900) as r:
        status = r.status
        raw = r.read().decode()
    if not stream:
        return status, json.loads(raw)["sequences"][0]
    lines = [json.loads(ln) for ln in raw.splitlines() if ln.strip()]
    check(lines and lines[-1].get("done"), f"stream did not end: {lines[-1:]}")
    check(not any("error" in ln for ln in lines), f"stream error: {lines}")
    return status, [ln["tokens"][0] for ln in lines if "tokens" in ln]


def phase_serve(size, clog, *, on_chip=True):
    import jax
    import paddle_tpu
    from paddle_tpu import observability
    from paddle_tpu.inference import PagedKVEngine, PredictorServer
    from paddle_tpu.models import LlamaForCausalLM

    cfg = llama_config(size, size.layers)
    paddle_tpu.seed(SEED + 1)
    # a FRESH model: a Trainer donates its model's buffers, so a trained
    # model is served only after trainer.sync_to_model()
    model = LlamaForCausalLM(cfg)
    model = paddle_tpu.amp.decorate(models=model, level="O2",
                                    dtype="bfloat16")
    model.eval()
    page = 16
    pages_per_slot = -(-(size.prompt_hi + size.new_tokens) // page) + 1
    out = {"layers": size.layers, "serve_devices": 1, "slots": size.slots,
           "requests": size.requests}
    with observability.scoped(reset=True) as reg:
        eng = PagedKVEngine(model, max_slots=size.slots, page_size=page,
                            num_pages=size.slots * pages_per_slot + 1,
                            max_pages_per_slot=pages_per_slot, kernel=None)
        out["decode_kernel"] = eng.decode_kernel
        if on_chip:
            check(eng.decode_kernel == "pallas",
                  f"PagedKVEngine(kernel=None) resolved to "
                  f"{eng.decode_kernel!r}, not the Pallas decode kernel")
        srv = PredictorServer(lambda inputs: inputs, host="127.0.0.1",
                              port=0, generator=eng).start()
        rng = np.random.default_rng(SEED + 2)
        lens = rng.integers(size.prompt_lo, size.prompt_hi + 1,
                            size=size.requests)
        prompts = [rng.integers(1, size.vocab, size=int(n)).tolist()
                   for n in lens]
        results = [None] * size.requests

        def client(i):
            try:
                results[i] = _post_generate(srv.port, prompts[i],
                                            size.new_tokens,
                                            stream=i % 2 == 1)
            except Exception as e:      # noqa: BLE001 — judged below
                results[i] = e

        mark = clog.mark()
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(size.requests)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            out["wall_s"] = round(time.perf_counter() - t0, 2)
            out["compile"] = clog.since(mark)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/stats", timeout=60) as r:
                check(r.status == 200, f"/stats answered {r.status}")
                stats = json.loads(r.read().decode())
        finally:
            eng.stop()
            srv.stop()
        check(not any(t.is_alive() for t in threads),
              "a client thread never returned")
        ticker = eng._ticker
        check(ticker is None or not ticker.is_alive(),
              "eng.stop() returned with the ticker still running")
        out["prompt_lens"] = [int(n) for n in lens]
        for i, res in enumerate(results):
            check(not isinstance(res, Exception),
                  f"request {i}: {type(res).__name__}: {res}")
            status, toks = res
            check(status == 200, f"request {i}: HTTP {status}")
            check(len(toks) == size.new_tokens,
                  f"request {i}: {len(toks)} tokens, asked for "
                  f"{size.new_tokens}")
            check(all(isinstance(t, int) and 0 <= t < size.vocab
                      for t in toks), f"request {i}: ids outside the "
                  f"vocabulary: {toks}")
        out["engine"] = {k: eng.stats[k] for k in
                         ("ticks", "prefills", "tokens_out", "admitted",
                          "finished")}
        check(eng.stats["finished"] == size.requests,
              f"engine finished {eng.stats['finished']} of "
              f"{size.requests}")
        out["programs"] = sorted(str(k) for k in eng._programs)
        out["serving_requests"] = stats.get("requests")
        counter = reg.counter("inference.decode.kernel")
        out["decode_kernel_ticks"] = {
            p: counter.value(path=p) for p in ("pallas", "jnp")}
        if on_chip:
            check(out["decode_kernel_ticks"]["pallas"] > 0
                  and out["decode_kernel_ticks"]["jnp"] == 0,
                  f"decode ticks by path: {out['decode_kernel_ticks']}")
    out["memory"] = _memory(jax.devices()[0])
    log("[serve]", json.dumps(out))
    return out


# ---------------------------------------------------------------------------

def main():
    t_start = time.perf_counter()
    result = {"ok": False, "device": None, "phases": {}}
    clog = None
    try:
        for name, run in (
                ("device", phase_device),
                ("kernels", lambda: phase_kernels(FULL, clog)),
                ("train", lambda: phase_train(FULL, clog)),
                ("serve", lambda: phase_serve(FULL, clog))):
            t0 = time.perf_counter()
            out = run()
            out["seconds"] = round(time.perf_counter() - t0, 1)
            result["phases"][name] = out
            if name == "device":
                result["device"] = {k: out[k] for k in
                                    ("platform", "kind", "count")}
                clog = CompileLog().install()
        result["ok"] = True
    except Exception as e:
        # a failed phase ends the run non-zero whatever it raised; the
        # result line says which and why
        traceback.print_exc()
        log(f"FAILED: {e}")
        if result["device"] is None:
            return 1        # no accelerator: no result line at all
        result["failed"] = f"{type(e).__name__}: {e}"[:2000]
    result["compile_s"] = round(sum(clog.durations), 1)
    result["cache_hits"] = clog.cache_hits
    result["cache_misses"] = clog.cache_misses
    result["seconds"] = round(time.perf_counter() - t_start, 1)
    result["claim"] = None          # a smoke measures nothing
    # the summary (every phase's detail) goes on the line before the
    # last; the LAST line is the verdict alone, exactly `ok` and `device`
    log("[summary]", json.dumps(result))
    print(json.dumps({"ok": result["ok"], "device": result["device"]}),
          flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
