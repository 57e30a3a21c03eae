"""Autoregressive generation with a static KV cache, TPU-first.

Reference surface: PaddleNLP's GenerationMixin (generation/utils.py —
greedy_search / sample with temperature, top-k, top-p, eos handling,
use_cache, attention_mask threading) and the reference's fused decode
loops. The TPU design differs from the reference's dynamically-growing
cache:

- The KV cache is a FIXED-SIZE buffer `(batch, max_len, kv_heads,
  head_dim)` per layer, written in place with
  `lax.dynamic_update_slice` at a TRACED position index. Static shapes
  mean exactly TWO compiles per (batch, prompt_len): one prefill step
  and one single-token decode step reused for every generated token.
- Sampling parameters (temperature / top_k / top_p) enter the compiled
  steps as TRACED scalars, so a serving process compiles per
  (batch, prompt_len, do_sample) — NOT per sampling config (every novel
  temperature used to cost a full XLA retrace). Noise for the
  Gumbel-max sample is HOST-generated and passed in as data: a
  traced-in PRNG key would be baked at trace time; noise as an input
  keeps the step compiled once and the randomness fresh and seedable.
- Prompt padding: `attention_mask` (batch, prompt_len), 1 = real
  token, 0 = pad (use LEFT padding so all rows end at the same slot).
  The mask is threaded into every compiled step; RoPE position ids are
  derived from it in-graph (cumsum - 1), so a padded batch generates
  exactly what each row would generate unpadded.
- The decode loop runs host-side by default, one jitted step per token
  (each token id is fetched for streaming + eos early-exit anyway).
  `tokens_per_fetch=N` switches to a DEVICE-SIDE `lax.while_loop` that
  emits up to N tokens per host round-trip — the shape real serving
  wants when host<->device latency dominates.

Models opt in by accepting `caches=`/`cache_index=` in forward and
returning `(logits, caches)` (LlamaForCausalLM does; see
models/llama.py). Models without cache support still generate through
the full-recompute fallback (`use_cache=False`), which re-runs the
whole prefix per token — fine for tests/small models, quadratic for
real serving.
"""
from __future__ import annotations

import inspect
import os
from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import tensor as T
from paddle_tpu.core.dispatch import defop
from paddle_tpu.core.tensor import Tensor

__all__ = ["init_kv_cache", "kv_cache_update", "process_logits",
           "generate", "generate_stream", "generate_speculative"]


@defop("kv_cache_update", differentiable=False,
       spmd_note="cache batch dim shards with dp; kv-head dim with mp")
def kv_cache_update(buf, new, index):
    """Write `new` (b, s, h, d) into the fixed cache buffer at sequence
    position `index` (traced scalar). lax.dynamic_update_slice keeps the
    buffer shape static so the decode step compiles once."""
    index = jnp.asarray(index, jnp.int32).reshape(())
    zero = jnp.zeros((), jnp.int32)
    return jax.lax.dynamic_update_slice(
        buf, new.astype(buf.dtype), (zero, index, zero, zero))


def init_kv_cache(model, batch_size, max_len, dtype=None):
    """Per-layer (k, v) buffers for `model` (a CausalLM exposing
    .config with num_hidden_layers / num_key_value_heads / head_dim).
    dtype defaults to the model's parameter dtype."""
    cfg = model.config
    n_kv = getattr(cfg, "num_key_value_heads", None) \
        or cfg.num_attention_heads
    hd = getattr(cfg, "head_dim", None) \
        or cfg.hidden_size // cfg.num_attention_heads
    if dtype is None:
        dtype = next(iter(model.parameters())).dtype
    shape = [batch_size, max_len, n_kv, hd]
    return [(T.zeros(shape, dtype=dtype), T.zeros(shape, dtype=dtype))
            for _ in range(cfg.num_hidden_layers)]


def process_logits(logits, temperature=1.0, top_k=0, top_p=1.0):
    """Standard logits pipeline (reference: generation/logits_process.py
    TemperatureLogitsWarper, TopKProcess, TopPProcess). logits: (b, v).
    Filtered-out entries are set to -1e9 so Gumbel-max never picks
    them. Pure tensor ops — safe under jit. This is the STATIC-parameter
    form (python scalars); the compiled decode steps use
    _process_logits_traced so sampling configs don't multiply compiles."""
    if temperature != 1.0:
        if temperature <= 0:
            raise ValueError(f"temperature must be > 0, got {temperature}")
        logits = logits / float(temperature)
    v = logits.shape[-1]
    if top_k and 0 < top_k < v:
        kth = T.topk(logits, top_k, axis=-1)[0][:, -1:]      # (b, 1)
        logits = T.where(logits < kth,
                         T.full_like(logits, -1e9), logits)
    if top_p < 1.0:
        sorted_logits = T.sort(logits, axis=-1, descending=True)
        probs = paddle_tpu.nn.functional.softmax(sorted_logits, axis=-1)
        cum = T.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p
        # (always keep the top-1 token)
        keep_sorted = cum - probs < top_p
        # threshold logit = smallest kept logit per row
        thresh = T.min(
            T.where(keep_sorted, sorted_logits,
                    T.full_like(sorted_logits, float("inf"))),
            axis=-1, keepdim=True)
        logits = T.where(logits < thresh,
                         T.full_like(logits, -1e9), logits)
    return logits


def _process_logits_traced(logits, temperature, top_k, top_p):
    """Traced twin of process_logits: temperature/top_k/top_p are TRACED
    scalar Tensors, so one compiled step serves every sampling config
    (ADVICE r3: float-keyed compile cache). Each filter disables itself
    in-graph: top_k <= 0 or >= v -> no-op, top_p >= 1 -> no-op. The
    top-k threshold (k-th largest, k traced) is a one-hot row-select
    off the sorted logits — no dynamic-shape gather."""
    x = T.cast(logits, "float32") / temperature
    v = x.shape[-1]
    # top-k
    sorted_desc = T.sort(x, axis=-1, descending=True)
    kk = T.clip(T.cast(top_k, "int32"), 1, v)
    onehot = T.cast(T.equal(T.arange(0, v, dtype="int32"), kk - 1),
                    "float32")
    kth = T.matmul(sorted_desc, T.reshape(onehot, [v, 1]))       # (b, 1)
    use_k = T.logical_and(top_k > 0, top_k < v)
    kth = T.where(use_k, kth, T.full_like(kth, float("-inf")))
    x = T.where(x < kth, T.full_like(x, -1e9), x)
    # top-p over the (possibly top-k-masked) logits — same order as
    # process_logits / _np_process_logits
    sorted_p = T.sort(x, axis=-1, descending=True)
    probs = paddle_tpu.nn.functional.softmax(sorted_p, axis=-1)
    cum = T.cumsum(probs, axis=-1)
    keep_sorted = cum - probs < top_p
    thresh = T.min(T.where(keep_sorted, sorted_p,
                           T.full_like(sorted_p, float("inf"))),
                   axis=-1, keepdim=True)
    use_p = top_p < 1.0
    thresh = T.where(use_p, thresh, T.full_like(thresh, float("-inf")))
    return T.where(x < thresh, T.full_like(x, -1e9), x)


def _select_token(logits, do_sample, temperature, top_k, top_p, noise):
    """(b, v) logits -> (b,) int32 next ids, STATIC sampling params
    (recompute-fallback path). Sampling = Gumbel-max over the processed
    logits with host-supplied noise (see module doc)."""
    if do_sample:
        logits = process_logits(logits, temperature, top_k, top_p)
        logits = logits + noise
    return T.cast(T.argmax(logits, axis=-1), "int32")


def _select_traced(logits, do_sample, samp):
    """In-graph token selection. samp = () for greedy, else
    (noise_t, temp_t, topk_t, topp_t) traced Tensors."""
    if not do_sample:
        return T.cast(T.argmax(logits, axis=-1), "int32")
    noise_t, temp_t, topk_t, topp_t = samp
    x = _process_logits_traced(logits, temp_t, topk_t, topp_t)
    return T.cast(T.argmax(x + noise_t, axis=-1), "int32")


def _accepts(model, name):
    try:
        sig = inspect.signature(type(model).forward)
    except (TypeError, ValueError):
        return False
    return name in sig.parameters


def _model_supports_cache(model):
    return _accepts(model, "caches")


def _gumbel(rng, shape):
    u = rng.uniform(1e-9, 1.0, size=shape).astype("float32")
    return -np.log(-np.log(u))


def _norm_attention_mask(attention_mask, b, s):
    """-> np bool (b, s) keep-mask, or None when no mask was given.
    Accepts Tensor / array-like of 1/0 or bool (HF/PaddleNLP
    attention_mask convention). LEFT padding is the supported layout
    for cached decode (all rows then end at the same cache slot)."""
    if attention_mask is None:
        return None
    m = attention_mask.numpy() if isinstance(attention_mask, Tensor) \
        else np.asarray(attention_mask)
    if m.shape != (b, s):
        raise ValueError(f"attention_mask must be (batch, prompt_len) = "
                         f"({b}, {s}), got {m.shape}")
    m = m.astype(bool)
    if not m[:, -1].all():
        raise ValueError(
            "attention_mask must be LEFT-padded (every row's last prompt "
            "position real): decode positions and the final-logit select "
            "assume rows end at the same slot. Right-padded rows would "
            "generate from a pad embedding. Re-pad on the left.")
    return m


def _graph_mask(keep_t, max_len):
    """In-graph mask expansion: (b, s) bool keep ->
    (attn (b, 1, 1, max_len) bool over cache slots, n_real (b,) int32).
    Generated positions (slots >= s) are always real."""
    b, s = keep_t.shape[0], keep_t.shape[1]
    if max_len > s:
        pad = T.cast(T.ones([b, max_len - s], dtype="int32"), "bool")
        keep_full = T.concat([keep_t, pad], axis=1)
    else:
        keep_full = keep_t
    attn = T.reshape(keep_full, [b, 1, 1, max_len])
    n_real = T.sum(T.cast(keep_t, "int32"), axis=1)
    return attn, n_real


def generate_stream(model, input_ids, max_new_tokens=32, *,
                    attention_mask=None, eos_token_id=None, pad_token_id=0,
                    do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
                    use_cache=True, seed=None, tokens_per_fetch=1):
    """Yield one (batch,) numpy int32 array of token ids per generated
    position. Sequences that hit `eos_token_id` keep yielding
    `pad_token_id`; the stream ends early once ALL sequences finished.
    This iterator is the serving streaming surface (PredictorServer
    SSE / C API callback ride on it).

    attention_mask: (batch, prompt_len) 1/0 prompt padding mask (LEFT
    padding). tokens_per_fetch>1 runs that many decode steps inside one
    XLA program (lax.while_loop) per host round-trip — tokens then
    arrive in bursts of up to that size, but the per-token host<->device
    latency disappears from the decode path. Greedy block decode emits
    the exact per-token stream; SAMPLED block decode draws its Gumbel
    noise on device from a seed-derived PRNG key (shipping host noise
    would cost block*batch*vocab floats per fetch), so it is
    seed-deterministic but a different stream than tokens_per_fetch=1."""
    ids = input_ids if isinstance(input_ids, Tensor) \
        else paddle_tpu.to_tensor(np.asarray(input_ids, "int32"))
    if ids.dtype not in ("int32", "int64"):
        raise ValueError(f"input_ids must be integer ids, got {ids.dtype}")
    b, s = ids.shape[0], ids.shape[1]
    if max_new_tokens <= 0:
        return                      # a 0-token request streams nothing
    if do_sample and temperature <= 0:
        raise ValueError(f"temperature must be > 0, got {temperature}")
    keep_np = _norm_attention_mask(attention_mask, b, s)
    rng = np.random.RandomState(seed)
    use_cache = use_cache and _model_supports_cache(model)

    was_training = getattr(model, "training", False)
    model.eval()
    try:
        with paddle_tpu.no_grad():
            if use_cache:
                yield from _stream_cached(
                    model, ids, b, s, max_new_tokens, eos_token_id,
                    pad_token_id, do_sample, temperature, top_k, top_p,
                    rng, keep_np, tokens_per_fetch)
            else:
                yield from _stream_recompute(
                    model, ids, b, s, max_new_tokens, eos_token_id,
                    pad_token_id, do_sample, temperature, top_k, top_p,
                    rng, keep_np)
    finally:
        if was_training:
            model.train()


def _finish_step(tok, finished, eos_token_id, pad_token_id):
    """Host-side eos bookkeeping: returns (emitted tokens, finished)."""
    if eos_token_id is None:
        return tok, finished
    tok = np.where(finished, pad_token_id, tok)
    finished = finished | (tok == eos_token_id)
    return tok, finished


# compiled prefill/decode step pairs, memoized ON the model instance: a
# serving process pays the XLA trace+compile ONCE per
# (batch, prompt_len, do_sample), not once per request or per sampling
# config (StaticFunction._jit_cache is per-instance; sampling params are
# traced inputs). Stored in the model's __dict__ (not a global map) so
# the cache — whose closures capture the model strongly — dies with the
# model instead of leaking it. The cache is LRU-bounded: each novel
# (batch, prompt_len) still costs a compile (static shapes), so servers
# should pad prompts to a few canonical lengths.

_GEN_CACHE_CAP = 32


def _gen_cache_get(model, key, build):
    cache = model.__dict__.setdefault("_gen_step_cache", OrderedDict())
    if key in cache:
        cache.move_to_end(key)
        return cache[key]
    val = build()
    cache[key] = val
    while len(cache) > _GEN_CACHE_CAP:
        cache.popitem(last=False)
    return val


def _mask_capable(model):
    return _accepts(model, "attn_mask") and _accepts(model, "position_ids")


def _compiled_steps(model, b, s, do_sample):
    """-> (prefill, decode) compiled steps.

    prefill(ids, keep, caches, *samp)           -> (tok, caches)
    decode(tok, index, keep, caches, *samp)     -> (tok, caches)
    samp = () greedy, else (noise, temp, topk, topp) traced Tensors.
    keep: (b, s) bool prompt mask (all-True when unpadded); RoPE
    positions derive from it in-graph, so padded rows decode at their
    own positions."""
    masked = _mask_capable(model)

    def build():
        # two body sets (masked / legacy): the dy2static scan dislikes
        # branch-local assignments, and a model without attn_mask
        # support must not receive the kwarg at all
        if masked:
            def prefill(ids_t, keep_t, caches, *samp):
                max_len = caches[0][0].shape[1]
                attn, n_real = _graph_mask(keep_t, max_len)
                posids = T.clip(
                    T.cumsum(T.cast(keep_t, "int32"), axis=1) - 1, 0, s)
                logits, new_caches = model(
                    ids_t, caches=caches, attn_mask=attn,
                    position_ids=posids,
                    cache_index=paddle_tpu.to_tensor(0, dtype="int32"))
                return _select_traced(logits[:, -1], do_sample, samp), \
                    new_caches

            def decode(tok_t, index_t, keep_t, caches, *samp):
                max_len = caches[0][0].shape[1]
                attn, n_real = _graph_mask(keep_t, max_len)
                pos = T.reshape(n_real + (index_t - s), [b, 1])
                logits, new_caches = model(
                    T.reshape(tok_t, [b, 1]), caches=caches,
                    attn_mask=attn, position_ids=pos,
                    cache_index=index_t)
                return _select_traced(logits[:, -1], do_sample, samp), \
                    new_caches
        else:
            def prefill(ids_t, keep_t, caches, *samp):
                posids = T.unsqueeze(T.arange(0, s, dtype="int32"), 0)
                logits, new_caches = model(
                    ids_t, caches=caches, position_ids=posids,
                    cache_index=paddle_tpu.to_tensor(0, dtype="int32"))
                return _select_traced(logits[:, -1], do_sample, samp), \
                    new_caches

            def decode(tok_t, index_t, keep_t, caches, *samp):
                pos = T.reshape(index_t, [1, 1])
                logits, new_caches = model(
                    T.reshape(tok_t, [b, 1]), caches=caches,
                    position_ids=pos, cache_index=index_t)
                return _select_traced(logits[:, -1], do_sample, samp), \
                    new_caches

        return (paddle_tpu.jit.to_static(prefill),
                paddle_tpu.jit.to_static(decode))

    return _gen_cache_get(model, (b, s, do_sample), build)


def _compiled_block(model, b, s, n_steps, do_sample):
    """Device-side decode loop: up to `limit` (<= n_steps) decode steps
    inside ONE XLA program (lax.while_loop with eos early-exit), so one
    host round-trip fetches a whole block of tokens (VERDICT r3 item 3;
    reference analog: the fused decode loop in
    paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu).

    block(tok, index, limit, keep, caches, fin, eos, pad, *samp)
      -> (out (b, n_steps) int32, n_done (), finished (b,), tok (b,),
          caches)
    eos < 0 means "no eos". All of limit/eos/pad are traced scalars, so
    tail blocks and different eos ids reuse the one compile."""
    def build():
        def block(tok_t, index_t, limit_t, keep_t, caches, fin_t, eos_t,
                  pad_t, *samp):
            return _block_impl(model, b, s, n_steps, do_sample, tok_t,
                               index_t, limit_t, keep_t, caches, fin_t,
                               eos_t, pad_t, samp)

        return paddle_tpu.jit.to_static(block)

    return _gen_cache_get(model, ("block", b, s, n_steps, do_sample),
                          build)


def _block_impl(model, b, s, n_steps, do_sample, tok_t, index_t, limit_t,
                keep_t, caches, fin_t, eos_t, pad_t, samp):
    """Body of the compiled block-decode program. Lives OUTSIDE the
    to_static-wrapped function so the dy2static AST pass never rewrites
    it — the lax.while_loop here is hand-built (the python `if`s branch
    on build-time constants only).

    Sampling noise is generated ON DEVICE from a traced PRNG key
    (fold_in(key, absolute position) per step): shipping host Gumbel
    noise would cost n_steps*b*vocab floats per fetch — the exact
    host<->device traffic tokens_per_fetch exists to eliminate."""
    masked = _mask_capable(model)
    nl = len(caches)
    if masked:
        attn, n_real = _graph_mask(keep_t, caches[0][0].shape[1])
        attn_v, nreal_v = attn._value, n_real._value
    idx0 = index_t._value
    limit_v = limit_t._value
    eos_v, pad_v = eos_t._value, pad_t._value
    if do_sample:
        key_v = samp[0]._value          # (2,) uint32 raw PRNG key data
        temp_t, topk_t, topp_t = samp[1:]
    cflat = [c._value for kv in caches for c in kv]

    def body(carry):
        i, tok, fin, out = carry[0], carry[1], carry[2], carry[3]
        cf = carry[4:]
        ci = [(Tensor(cf[2 * j]), Tensor(cf[2 * j + 1]))
              for j in range(nl)]
        index = Tensor(idx0 + i)
        if masked:
            pos = T.reshape(Tensor(nreal_v) + (index - s), [b, 1])
            kw = dict(attn_mask=Tensor(attn_v), position_ids=pos)
        else:
            kw = dict(position_ids=T.reshape(index, [1, 1]))
        logits, ci = model(T.reshape(Tensor(tok), [b, 1]),
                           caches=ci, cache_index=index, **kw)
        last = logits[:, -1]
        if do_sample:
            step_key = jax.random.fold_in(
                jax.random.wrap_key_data(key_v), idx0 + i)
            ni = Tensor(jax.random.gumbel(
                step_key, (b, last.shape[-1]), jnp.float32))
            x = _process_logits_traced(last, temp_t, topk_t, topp_t)
            nxt = T.cast(T.argmax(x + ni, axis=-1), "int32")
        else:
            nxt = T.cast(T.argmax(last, axis=-1), "int32")
        finT = Tensor(fin)
        nxt = T.where(finT, T.zeros_like(nxt) + Tensor(pad_v), nxt)
        has_eos = Tensor(eos_v) >= 0
        newfin = T.logical_or(
            finT, T.logical_and(has_eos, T.equal(nxt, Tensor(eos_v))))
        out = jax.lax.dynamic_update_slice(
            out, jnp.reshape(nxt._value, (b, 1)),
            (jnp.zeros((), jnp.int32), i))
        new_cf = [c._value for kv in ci for c in kv]
        return (i + 1, nxt._value, newfin._value, out, *new_cf)

    def cond(carry):
        i, fin = carry[0], carry[2]
        return jnp.logical_and(i < limit_v,
                               jnp.logical_not(jnp.all(fin)))

    init = (jnp.zeros((), jnp.int32),
            tok_t._value.astype(jnp.int32),
            fin_t._value,
            jnp.broadcast_to(pad_v, (b, n_steps)).astype(jnp.int32),
            *cflat)
    final = jax.lax.while_loop(cond, body, init)
    n_done, tok_f, fin_f, out_buf = final[0], final[1], final[2], final[3]
    cf = final[4:]
    new_caches = [(Tensor(cf[2 * j]), Tensor(cf[2 * j + 1]))
                  for j in range(nl)]
    return (Tensor(out_buf), Tensor(n_done), Tensor(fin_f),
            Tensor(tok_f), new_caches)


def _stream_cached(model, ids, b, s, max_new_tokens, eos_token_id,
                   pad_token_id, do_sample, temperature, top_k, top_p,
                   rng, keep_np, tokens_per_fetch):
    if keep_np is not None and keep_np.all():
        keep_np = None              # an all-ones mask is no mask
    if keep_np is not None and not _mask_capable(model):
        raise ValueError(
            f"{type(model).__name__} accepts caches= but not attn_mask=/"
            "position_ids=; attention_mask needs both (or use "
            "use_cache=False)")
    max_len = s + max_new_tokens
    caches = init_kv_cache(model, b, max_len)
    sf_prefill, sf_decode = _compiled_steps(model, b, s, do_sample)
    keep_t = paddle_tpu.to_tensor(
        keep_np if keep_np is not None else np.ones((b, s), bool))
    vocab = model.config.vocab_size

    # the sampling-config tensors are loop constants; only the gumbel
    # noise is fresh per step
    const_samp = () if not do_sample else (
        paddle_tpu.to_tensor(float(temperature)),
        paddle_tpu.to_tensor(int(top_k), dtype="int32"),
        paddle_tpu.to_tensor(float(top_p)))

    def samp_args(n=None):
        if not do_sample:
            return ()
        shape = (b, vocab) if n is None else (n, b, vocab)
        return (paddle_tpu.to_tensor(_gumbel(rng, shape)), *const_samp)

    tok_t, caches = sf_prefill(ids, keep_t, caches, *samp_args())
    finished = np.zeros((b,), bool)
    tok = np.asarray(tok_t.numpy(), "int32").reshape(b)
    tok, finished = _finish_step(tok, finished, eos_token_id,
                                 pad_token_id)
    yield tok

    block = int(tokens_per_fetch or 1)
    if block > 1:
        sf_block = _compiled_block(model, b, s, block, do_sample)
        eos_t = paddle_tpu.to_tensor(
            -1 if eos_token_id is None else int(eos_token_id),
            dtype="int32")
        pad_t = paddle_tpu.to_tensor(int(pad_token_id), dtype="int32")
        # block noise is device-generated from ONE key (2 words instead
        # of block*b*vocab floats per fetch); fold_in by absolute
        # position keeps every step's draw distinct and seed-stable
        block_samp = ()
        if do_sample:
            block_seed = int(rng.randint(0, 2 ** 31 - 1))
            block_samp = (Tensor(jax.random.key_data(
                jax.random.key(block_seed))), *const_samp)
        produced = 1
        while produced < max_new_tokens and not finished.all():
            limit = min(block, max_new_tokens - produced)
            out_t, n_t, fin_t, tok_t, caches = sf_block(
                paddle_tpu.to_tensor(tok.astype("int32")),
                paddle_tpu.to_tensor(s + produced - 1, dtype="int32"),
                paddle_tpu.to_tensor(limit, dtype="int32"),
                keep_t, caches, paddle_tpu.to_tensor(finished),
                eos_t, pad_t, *block_samp)
            n_done = int(np.asarray(n_t.numpy()))
            outb = np.asarray(out_t.numpy(), "int32")
            finished = np.asarray(fin_t.numpy(), bool)
            for j in range(n_done):
                yield outb[:, j]
            produced += n_done
            tok = np.asarray(tok_t.numpy(), "int32").reshape(b)
            if n_done == 0:     # all rows were already finished
                return
        return

    for step in range(1, max_new_tokens):
        if finished.all():
            return
        index_t = paddle_tpu.to_tensor(s + step - 1, dtype="int32")
        tok_t, caches = sf_decode(
            paddle_tpu.to_tensor(tok.astype("int32")), index_t, keep_t,
            caches, *samp_args())
        tok = np.asarray(tok_t.numpy(), "int32").reshape(b)
        tok, finished = _finish_step(tok, finished, eos_token_id,
                                     pad_token_id)
        yield tok


def _stream_recompute(model, ids, b, s, max_new_tokens, eos_token_id,
                      pad_token_id, do_sample, temperature, top_k, top_p,
                      rng, keep_np):
    """Cache-less fallback: re-run the full prefix per token. Works with
    ANY CausalLM forward(input_ids)->logits; each step recompiles (the
    prefix grows), so this is the correctness/compat path, not the
    serving path. attention_mask requires the model to accept
    attn_mask= (a combined causal+padding keep-mask is passed)."""
    masked = keep_np is not None and not keep_np.all()
    if masked and not _accepts(model, "attn_mask"):
        raise ValueError(
            f"{type(model).__name__} does not accept attn_mask=; "
            "cannot honor attention_mask on the recompute path")
    cur = ids
    finished = np.zeros((b,), bool)
    for _ in range(max_new_tokens):
        if finished.all():
            return
        kwargs = {}
        if masked:
            cl = cur.shape[1]
            kf = np.concatenate(
                [keep_np, np.ones((b, cl - s), bool)], axis=1)
            causal = np.tril(np.ones((cl, cl), bool))
            m = causal[None, None] & kf[:, None, None, :]
            kwargs["attn_mask"] = paddle_tpu.to_tensor(m)
            if _accepts(model, "position_ids"):
                kwargs["position_ids"] = paddle_tpu.to_tensor(
                    np.maximum(np.cumsum(kf, 1) - 1, 0).astype("int32"))
        logits = model(cur, **kwargs)
        if isinstance(logits, tuple):
            logits = logits[-1]
        last = logits[:, -1]
        noise = paddle_tpu.to_tensor(_gumbel(rng, tuple(last.shape)))
        tok_t = _select_token(last, do_sample, temperature, top_k, top_p,
                              noise)
        tok = np.asarray(tok_t.numpy(), "int32").reshape(b)
        tok, finished = _finish_step(tok, finished, eos_token_id,
                                     pad_token_id)
        yield tok
        cur = T.concat(
            [cur, paddle_tpu.to_tensor(
                tok.reshape(b, 1).astype(str(cur.dtype)))], axis=1)


def generate(model, input_ids, max_new_tokens=32, **kwargs):
    """Batch generation: returns an int32 Tensor
    (batch, prompt_len + n_generated) of prompt + generated ids
    (n_generated <= max_new_tokens when every sequence hits eos early).
    Keyword args as in generate_stream (attention_mask for padded
    prompts, tokens_per_fetch for device-side block decode)."""
    ids = input_ids if isinstance(input_ids, Tensor) \
        else paddle_tpu.to_tensor(np.asarray(input_ids, "int32"))
    steps = list(generate_stream(model, ids, max_new_tokens, **kwargs))
    prompt = np.asarray(ids.numpy(), "int32")
    if not steps:
        return paddle_tpu.to_tensor(prompt)
    gen = np.stack(steps, axis=1).astype("int32")
    return paddle_tpu.to_tensor(np.concatenate([prompt, gen], axis=1))


# -- speculative decoding ----------------------------------------------------

def generate_speculative(target, draft, input_ids, max_new_tokens=32, *,
                         num_speculative_tokens=4, eos_token_id=None,
                         do_sample=False, temperature=1.0, top_k=0,
                         top_p=1.0, seed=None, stats=None):
    """Speculative decoding (reference ecosystem: PaddleNLP's inference
    'speculate_method' draft-model path; Leviathan et al. 2211.17192):
    a cheap DRAFT model proposes `num_speculative_tokens` tokens
    autoregressively; the TARGET model scores the whole block in ONE
    cache-aware forward and accepts a prefix.

    Greedy (do_sample=False): accept the longest prefix matching the
    target's own argmax, then emit the target's correction/bonus token —
    the output EXACTLY equals the target's greedy continuation.

    Sampling (do_sample=True): standard REJECTION SAMPLING — proposal
    x_i ~ q_i (the draft's processed distribution) is accepted with
    prob min(1, p_i(x_i)/q_i(x_i)); on first rejection the emitted
    token is resampled from the residual norm(max(p_i - q_i, 0)); if
    everything is accepted, a bonus token is sampled from p_g. The
    emitted sequence is distributed EXACTLY as plain sampling from the
    target under the same temperature/top_k/top_p (the acceptance test
    and residual sample run ON DEVICE in the verify program; only two
    scalars are fetched per round).

    TPU shape: the verify step is a width-g decode (static shape, one
    compile) — g tokens enter the MXU together, so acceptance rate
    directly converts sequential decode steps into one batched-matmul
    step. Stale cache slots from rejected proposals are safe: the
    position mask hides them until the next write overwrites the slot.

    batch must be 1 (rows would diverge in acceptance length).
    Returns int32 ids (1, prompt + generated). Pass a dict as `stats`
    to receive {'target_forwards', 'generated', 'accepted_drafts'}."""
    ids = input_ids if isinstance(input_ids, Tensor) \
        else paddle_tpu.to_tensor(np.asarray(input_ids, "int32"))
    b, s = ids.shape[0], ids.shape[1]
    if b != 1:
        raise ValueError("speculative decoding is batch-1 "
                         f"(got batch {b}); rows diverge in acceptance")
    g = int(num_speculative_tokens)
    if g < 1:
        raise ValueError("num_speculative_tokens must be >= 1")
    if not (_model_supports_cache(target) and _model_supports_cache(draft)):
        raise ValueError("both target and draft need KV-cache support")
    if do_sample and temperature <= 0:
        raise ValueError(f"temperature must be > 0, got {temperature}")
    prompt = np.asarray(ids.numpy(), "int32")
    if max_new_tokens <= 0:
        return paddle_tpu.to_tensor(prompt)
    rng = np.random.RandomState(seed)

    was_t, was_d = getattr(target, "training", False), \
        getattr(draft, "training", False)
    target.eval()
    draft.eval()
    n_target_fwd = 0
    vocab = target.config.vocab_size
    keep1 = paddle_tpu.to_tensor(np.ones((1, s), bool))

    def samp_tensors():
        return (paddle_tpu.to_tensor(float(temperature)),
                paddle_tpu.to_tensor(int(top_k), dtype="int32"),
                paddle_tpu.to_tensor(float(top_p)))

    try:
        with paddle_tpu.no_grad():
            max_len = s + max_new_tokens + g
            t_caches = init_kv_cache(target, 1, max_len)
            d_caches = init_kv_cache(draft, 1, max_len)
            t_prefill, _ = _compiled_steps(target, 1, s, do_sample)
            d_prefill, d_decode = _compiled_steps(draft, 1, s, False)
            if do_sample:
                d_spec = _compiled_spec_draft(draft)
                t_verify = _compiled_spec_verify(target, g)
                tk = samp_tensors()
            else:
                t_verify = _compiled_verify(target, 1, g)

            pre_samp = ()
            if do_sample:
                pre_samp = (paddle_tpu.to_tensor(_gumbel(rng, (1, vocab))),
                            *tk)
            tok_t, t_caches = t_prefill(ids, keep1, t_caches, *pre_samp)
            n_target_fwd += 1
            _, d_caches = d_prefill(ids, keep1, d_caches)
            pending = int(np.asarray(tok_t.numpy()).ravel()[0])
            out = [pending]
            p = s                       # both caches hold positions < p
            accepted_total = 0
            while len(out) < max_new_tokens and \
                    (eos_token_id is None or pending != eos_token_id):
                # draft consumes block[i] at position p+i and proposes
                # block[i+1]; the final feed (i = g-1) discards its
                # proposal but is REQUIRED: it writes d_{g-1}'s k/v
                # into slot p+g-1, which the next round attends when
                # every proposal gets accepted
                block = [pending]
                q_rows = []
                for i in range(g):
                    if do_sample:
                        tok_t, q_t, d_caches = d_spec(
                            paddle_tpu.to_tensor(
                                np.array([block[i]], "int32")),
                            paddle_tpu.to_tensor(p + i, dtype="int32"),
                            d_caches,
                            paddle_tpu.to_tensor(
                                _gumbel(rng, (1, vocab))), *tk)
                    else:
                        tok_t, d_caches = d_decode(
                            paddle_tpu.to_tensor(
                                np.array([block[i]], "int32")),
                            paddle_tpu.to_tensor(p + i, dtype="int32"),
                            keep1, d_caches)
                    if i < g - 1:
                        block.append(
                            int(np.asarray(tok_t.numpy()).ravel()[0]))
                        if do_sample:
                            q_rows.append(q_t)
                block_t = paddle_tpu.to_tensor(np.array([block], "int32"))
                p_t = paddle_tpu.to_tensor(p, dtype="int32")
                if do_sample:
                    q_stack = (T.concat(q_rows, axis=0) if q_rows
                               else T.zeros([0, vocab], dtype="float32"))
                    u_t = paddle_tpu.to_tensor(
                        rng.uniform(size=(g - 1,)).astype("float32"))
                    gn_t = paddle_tpu.to_tensor(_gumbel(rng, (vocab,)))
                    nacc_t, emit_t, t_caches = t_verify(
                        block_t, q_stack, u_t, gn_t, p_t, t_caches, *tk)
                    n_target_fwd += 1
                    n_acc = int(np.asarray(nacc_t.numpy()))
                    emitted = block[1:1 + n_acc] + \
                        [int(np.asarray(emit_t.numpy()))]
                else:
                    preds_t, t_caches = t_verify(block_t, p_t, t_caches)
                    n_target_fwd += 1
                    preds = np.asarray(preds_t.numpy()).ravel()
                    # accept the longest prefix of proposals the target
                    # agrees with, then emit the target's own next token
                    # (correction on mismatch, bonus when all accepted)
                    n_acc = 0
                    while n_acc < g - 1 and \
                            block[n_acc + 1] == int(preds[n_acc]):
                        n_acc += 1
                    emitted = block[1:1 + n_acc] + [int(preds[n_acc])]
                accepted_total += n_acc
                # caches: target holds block[0..g-1] at p..p+g-1, draft
                # the same — the accepted prefix occupies p..p+n_acc
                # correctly; stale slots beyond are position-masked
                # until overwritten. `pending` (the emitted correction/
                # bonus/resample) enters both caches next round at p.
                p += n_acc + 1
                pending = emitted[-1]
                out.extend(emitted)
                if eos_token_id is not None and eos_token_id in emitted:
                    out = out[:out.index(eos_token_id) + 1]
                    break
            out = out[:max_new_tokens]
    finally:
        if was_t:
            target.train()
        if was_d:
            draft.train()
    if stats is not None:
        stats.update(target_forwards=n_target_fwd,
                     generated=len(out),
                     accepted_drafts=accepted_total)
    return paddle_tpu.to_tensor(
        np.concatenate([prompt, np.array([out], "int32")], axis=1))


def _compiled_verify(model, b, g):
    """Width-g greedy verify step: feed g tokens at cache position
    `index`, return the argmax token after EACH of them (b, g)."""
    def build():
        def verify(block_t, index_t, caches):
            pos = T.reshape(index_t + T.arange(0, g, dtype="int32"),
                            [1, g])
            logits, caches = model(block_t, position_ids=pos,
                                   caches=caches, cache_index=index_t)
            return T.cast(T.argmax(logits, axis=-1), "int32"), caches

        return paddle_tpu.jit.to_static(verify)

    return _gen_cache_get(model, ("verify", b, g), build)


def _compiled_spec_draft(model):
    """Sampling draft step: decode one token AND return the processed
    draft distribution q it was sampled from (needed by the rejection
    test). -> (tok (1,), q (1, v) float32, caches)."""
    def build():
        def spec_draft(tok_t, index_t, caches, noise_t, temp_t, topk_t,
                       topp_t):
            logits, caches = model(
                T.reshape(tok_t, [1, 1]),
                position_ids=T.reshape(index_t, [1, 1]),
                caches=caches, cache_index=index_t)
            x = _process_logits_traced(logits[:, -1], temp_t, topk_t,
                                       topp_t)
            q = paddle_tpu.nn.functional.softmax(x, axis=-1)
            tok = T.cast(T.argmax(x + noise_t, axis=-1), "int32")
            return tok, q, caches

        return paddle_tpu.jit.to_static(spec_draft)

    return _gen_cache_get(model, ("spec_draft",), build)


def _compiled_spec_verify(model, g):
    """Rejection-sampling verify: ONE target forward over the block,
    accept/resample ON DEVICE (only n_acc + the emitted token leave the
    chip).

    verify(block (1,g), q (g-1,v), u (g-1,), gumbel (v,), index,
           caches, temp, topk, topp) -> (n_acc (), emitted (), caches)

    p_i = target's processed distribution after block[:i+1]. Proposal
    x_i = block[i+1] accepted iff u_i * q_i(x_i) < p_i(x_i). The
    emitted token samples from max(p_row - q_row, 0) renormalized at
    row n_acc, where q is zero-padded with a bonus row — so the
    all-accepted case reduces to sampling the bonus from p_{g-1}."""
    def build():
        def spec_verify(block_t, q_t, u_t, gnoise_t, index_t, caches,
                        temp_t, topk_t, topp_t):
            v = q_t.shape[-1]
            pos = T.reshape(index_t + T.arange(0, g, dtype="int32"),
                            [1, g])
            logits, caches = model(block_t, position_ids=pos,
                                   caches=caches, cache_index=index_t)
            lg = _process_logits_traced(
                T.reshape(logits, [g, v]), temp_t, topk_t, topp_t)
            p = paddle_tpu.nn.functional.softmax(lg, axis=-1)  # (g, v)
            props = block_t[0, 1:]                             # (g-1,)
            oh = T.cast(T.equal(T.unsqueeze(props, 1),
                                T.arange(0, v, dtype="int32")),
                        "float32")                             # (g-1, v)
            pi = T.sum(p[:g - 1] * oh, axis=-1)                # (g-1,)
            qi = T.sum(q_t * oh, axis=-1)
            accept = T.cast(u_t * qi < pi, "int32")
            # leading run of accepts: positions where no rejection yet
            n_acc = T.sum(T.cast(
                T.equal(T.cumsum(1 - accept, axis=0), 0), "int32"))
            ohrow = T.cast(T.equal(T.arange(0, g, dtype="int32"), n_acc),
                           "float32")                          # (g,)
            p_row = T.matmul(T.reshape(ohrow, [1, g]), p)[0]   # (v,)
            qpad = T.concat(
                [q_t, T.zeros([1, v], dtype="float32")], axis=0)
            q_row = T.matmul(T.reshape(ohrow, [1, g]), qpad)[0]
            r = T.maximum(p_row - q_row, T.zeros_like(p_row))
            # numerically-degenerate guard: p == q at the rejected row
            # makes the residual all-zero (rejection there has measure
            # zero); fall back to p_row
            r = T.where(T.sum(r) > 0, r, p_row)
            emitted = T.cast(
                T.argmax(T.log(r + 1e-20) + gnoise_t, axis=-1), "int32")
            return n_acc, emitted, caches

        return paddle_tpu.jit.to_static(spec_verify)

    return _gen_cache_get(model, ("spec_verify", g), build)


# -- deployment bundle: exported prefill + decode programs -------------------
#
# jit.save exports ONE program; generation needs TWO (prefill fills the
# cache from the prompt, the decode step advances one token). The bundle
# is the serving artifact the PredictorServer /generate endpoint and the
# C API PT_Generator* surface load — StableHLO + params + a meta json,
# the same philosophy as the .pdmodel/.pdiparams pair (reference: the
# inference programs PaddleNLP exports for its fused decode).

def _np_process_logits(logits, temperature, top_k, top_p):
    """numpy twin of process_logits for loaded-bundle hosts (no model,
    no tape — the exported programs return raw logits)."""
    x = np.asarray(logits, "float32")
    if temperature != 1.0:
        if temperature <= 0:
            raise ValueError(f"temperature must be > 0, got {temperature}")
        x = x / float(temperature)
    v = x.shape[-1]
    if top_k and 0 < top_k < v:
        kth = np.sort(x, axis=-1)[:, -top_k][:, None]
        x = np.where(x < kth, -1e9, x)
    if top_p < 1.0:
        s = np.sort(x, axis=-1)[:, ::-1]
        e = np.exp(s - s.max(-1, keepdims=True))
        probs = e / e.sum(-1, keepdims=True)
        cum = np.cumsum(probs, axis=-1)
        keep = cum - probs < top_p
        masked = np.where(keep, s, np.inf)
        thresh = masked.min(-1, keepdims=True)
        x = np.where(x < thresh, -1e9, x)
    return x


def _np_select_token(logits, do_sample, temperature, top_k, top_p, rng):
    x = np.asarray(logits, "float32")
    if do_sample:
        x = _np_process_logits(x, temperature, top_k, top_p)
        x = x + _gumbel(rng, x.shape)
    return x.argmax(-1).astype("int32")


def export_generation_bundle(model, path, batch_size, prompt_len,
                             max_new_tokens):
    """Export `model` (cache-capable CausalLM) as a generation bundle:
    `path.prefill.pdmodel` + `path.decode.pdmodel` (StableHLO via
    jax.export), `path.pdiparams` (params), `path.genmeta` (shape/config
    json). Shapes are static: (batch_size, prompt_len) prompts,
    prompt_len + max_new_tokens cache slots. Bundles (format 2) take a
    (batch, prompt_len) bool keep-mask input, so left-padded ragged
    prompts generate exactly their unpadded continuations."""
    import json

    import jax

    from paddle_tpu.core.tape import no_grad
    from paddle_tpu.jit.functional import _swapped, state_arrays

    if not _model_supports_cache(model):
        raise ValueError(f"{type(model).__name__} has no caches= support; "
                         "the bundle needs the KV-cache decode path")
    masked = _mask_capable(model)
    cfg = model.config
    b, s = batch_size, prompt_len
    max_len = s + max_new_tokens
    state = state_arrays(model)
    caches = init_kv_cache(model, b, max_len)
    cache_avals = [jax.ShapeDtypeStruct(tuple(c._value.shape),
                                        c._value.dtype)
                   for kv in caches for c in kv]
    n_layers = len(caches)

    def pack(flat):
        return [(Tensor(flat[2 * i]), Tensor(flat[2 * i + 1]))
                for i in range(n_layers)]

    def mask_kw(keep, index=None):
        if not masked:
            if index is None:
                return dict(position_ids=T.unsqueeze(
                    T.arange(0, s, dtype="int32"), 0))
            return dict(position_ids=T.reshape(Tensor(index), [1, 1]))
        kt = Tensor(keep)
        attn, n_real = _graph_mask(kt, max_len)
        if index is None:
            posids = T.clip(
                T.cumsum(T.cast(kt, "int32"), axis=1) - 1, 0, s)
        else:
            posids = T.reshape(n_real + (Tensor(index) - s), [b, 1])
        return dict(attn_mask=attn, position_ids=posids)

    def prefill_pure(state_, ids, keep, *cache_flat):
        with no_grad(), _swapped(model, state_):
            logits, new_caches = model(
                Tensor(ids), caches=pack(cache_flat),
                cache_index=Tensor(jnp.zeros((), jnp.int32)),
                **mask_kw(keep))
        flat = [c._value for kv in new_caches for c in kv]
        return (logits[:, -1]._value, *flat)

    def decode_pure(state_, tok, index, keep, *cache_flat):
        with no_grad(), _swapped(model, state_):
            logits, new_caches = model(
                Tensor(tok), caches=pack(cache_flat),
                cache_index=Tensor(index), **mask_kw(keep, index))
        flat = [c._value for kv in new_caches for c in kv]
        return (logits[:, -1]._value, *flat)

    ids_aval = jax.ShapeDtypeStruct((b, s), jnp.int32)
    keep_aval = jax.ShapeDtypeStruct((b, s), jnp.bool_)
    tok_aval = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    idx_aval = jax.ShapeDtypeStruct((), jnp.int32)
    exp_prefill = jax.export.export(jax.jit(prefill_pure))(
        state, ids_aval, keep_aval, *cache_avals)
    exp_decode = jax.export.export(jax.jit(decode_pure))(
        state, tok_aval, idx_aval, keep_aval, *cache_avals)

    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path + ".prefill.pdmodel", "wb") as f:
        f.write(exp_prefill.serialize())
    with open(path + ".decode.pdmodel", "wb") as f:
        f.write(exp_decode.serialize())
    from paddle_tpu.framework.io_utils import save as _save
    _save(model.state_dict(), path + ".pdiparams")
    with open(path + ".genmeta", "w") as f:
        json.dump({"format": 2, "mask_input": True,
                   "mask_honored": masked,
                   "batch_size": b, "prompt_len": s,
                   "max_new_tokens": max_new_tokens,
                   "num_layers": n_layers,
                   "cache_shape": list(cache_avals[0].shape),
                   "cache_dtype": str(cache_avals[0].dtype),
                   "vocab_size": cfg.vocab_size}, f)
    return path


class GenerationPredictor:
    """Load + drive an exported generation bundle: the serving twin of
    inference.Predictor for autoregressive decode. stream() yields one
    (batch,) int32 array per token — the surface the HTTP /generate
    endpoint and the C API callback ride."""

    def __init__(self, path):
        import json

        import jax

        with open(path + ".prefill.pdmodel", "rb") as f:
            self._prefill = jax.export.deserialize(f.read())
        with open(path + ".decode.pdmodel", "rb") as f:
            self._decode = jax.export.deserialize(f.read())
        with open(path + ".genmeta") as f:
            self.meta = json.load(f)
        from paddle_tpu.framework.io_utils import load as _load
        sd = _load(path + ".pdiparams")
        self._state = {k: (v._value if isinstance(v, Tensor)
                           else np.asarray(v)) for k, v in sd.items()}

    def stream(self, input_ids, max_new_tokens=None, *,
               attention_mask=None, eos_token_id=None,
               pad_token_id=0, do_sample=False, temperature=1.0, top_k=0,
               top_p=1.0, seed=None):
        m = self.meta
        ids = np.asarray(input_ids, "int32")
        if ids.shape != (m["batch_size"], m["prompt_len"]):
            raise ValueError(
                f"bundle expects prompt shape "
                f"({m['batch_size']}, {m['prompt_len']}), got {ids.shape}"
                " — left-pad/trim client-side (exported programs are "
                "shape-monomorphic); pass attention_mask to mark pads")
        has_mask = m.get("mask_input", False)
        honored = m.get("mask_honored", has_mask)
        keep = _norm_attention_mask(attention_mask, *ids.shape)
        if keep is None:
            keep = np.ones(ids.shape, bool)
        elif not (has_mask and honored):
            raise ValueError("this bundle cannot honor attention_mask "
                             "(exported pre-format-2 or from a model "
                             "without attn_mask support); re-export")
        steps = (m["max_new_tokens"] if max_new_tokens is None
                 else max_new_tokens)
        if steps > m["max_new_tokens"]:
            raise ValueError(
                f"bundle cache holds {m['max_new_tokens']} new tokens, "
                f"asked for {steps}")
        if steps <= 0:
            return                  # a 0-token request streams nothing
        rng = np.random.RandomState(seed)
        b, s = ids.shape
        mask_args = (keep,) if has_mask else ()
        caches = [np.zeros(m["cache_shape"], m["cache_dtype"])
                  for _ in range(2 * m["num_layers"])]
        out = self._prefill.call(self._state, ids, *mask_args, *caches)
        logits, caches = np.asarray(out[0]), list(out[1:])
        tok = _np_select_token(logits, do_sample, temperature, top_k,
                               top_p, rng)
        finished = np.zeros((b,), bool)
        tok, finished = _finish_step(tok, finished, eos_token_id,
                                     pad_token_id)
        yield tok
        for step in range(1, steps):
            if finished.all():
                return
            out = self._decode.call(
                self._state, tok.reshape(b, 1).astype("int32"),
                np.int32(s + step - 1), *mask_args, *caches)
            logits, caches = np.asarray(out[0]), list(out[1:])
            tok = _np_select_token(logits, do_sample, temperature, top_k,
                                   top_p, rng)
            tok, finished = _finish_step(tok, finished, eos_token_id,
                                         pad_token_id)
            yield tok

    def generate(self, input_ids, max_new_tokens=None, **kwargs):
        steps = list(self.stream(input_ids, max_new_tokens, **kwargs))
        prompt = np.asarray(input_ids, "int32")
        if not steps:
            return prompt
        return np.concatenate([prompt, np.stack(steps, 1)], axis=1)
