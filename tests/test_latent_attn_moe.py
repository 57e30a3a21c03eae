"""The decoder with latent attention and routed experts beside shared ones
(models/latent_attn_moe.py) against its plain reference
(benchmarks/reference_latent_attn_moe.py), small, float32, on the CPU: the
model's logits without a cache and through a latent cache by hand; the
engine's prefill in chunks that split a page, then decode through the latent
pool, on the jnp path and through the interpreted Pallas kernel; the
absorbed decode against the expanded form; five planted faults that the
same comparison must refuse; and what the engine learns from the model,
counts and refuses.
"""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu  # noqa: E402
from benchmarks import reference_latent_attn_moe as ref  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.inference import PagedKVEngine, paged  # noqa: E402
from paddle_tpu.inference.paged import PagedState  # noqa: E402
from paddle_tpu.jit.functional import state_arrays  # noqa: E402
from paddle_tpu.kernels import mla_decode as mla  # noqa: E402
from paddle_tpu.models import latent_attn_moe as lam  # noqa: E402
from paddle_tpu.models.latent_attn_moe import (  # noqa: E402
    LatentAttnMoeConfig, LatentAttnMoeForCausalLM, tiny_latent_attn_moe_config)
from paddle_tpu.nn.functional import moe as FM  # noqa: E402

PAGE, CHUNK = 8, 12         # a chunk of 12 ends inside a page of 8
TOL = 2e-4                  # float32 logits against float32 "highest"
HONEST_SD = 2e-3            # the serve comparison on an honest program
FAULT_SD = 0.02             # ... and the least a planted fault must read


def ref_cfg(c):
    """The published keys the reference reads, from the model's config."""
    return {k: getattr(c, k) for k in (
        "hidden_size", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rms_norm_eps",
        "rope_theta", "num_hidden_layers", "first_k_dense_replace",
        "n_routed_experts", "num_experts_per_tok", "norm_topk_prob",
        "routed_scaling_factor")}


def make(**overrides):
    """A tiny model with its correction biases drawn (at 0 choosing by
    score + bias and weighing by score are one thing), its query and key
    projections ten times the default (scores of ~1.5 sd: attention that
    leans on few keys, so that what changes a score shows) and its experts,
    routed and shared, five times (each part of the stream's size, so that
    what changes a gate or drops a part shows)."""
    cfg = tiny_latent_attn_moe_config(**overrides)
    paddle_tpu.seed(7)
    model = LatentAttnMoeForCausalLM(cfg)
    model.eval()
    key = jax.random.key(11)
    for i, (name, t) in enumerate(sorted(model.named_parameters())):
        if name.endswith("expert_bias"):
            t._value = 0.2 * jax.random.normal(jax.random.fold_in(key, i),
                                               t.shape)
        elif name.endswith(("q_proj.weight", "kv_b_proj.weight",
                            "kv_a_proj_with_mqa.weight")):
            t._value = t._value * 10.0
        elif "experts_" in name or "shared_expert." in name:
            t._value = t._value * 5.0
    return cfg, model, state_arrays(model)


@pytest.fixture(scope="module")
def tiny():
    return make()


def engine(model, kernel="jnp", chunk=CHUNK, **kw):
    args = dict(max_slots=3, page_size=PAGE, num_pages=64,
                max_pages_per_slot=20, steps_per_tick=4, kernel=kernel,
                prefill_chunk=chunk)
    args.update(kw)
    return PagedKVEngine(model, **args)


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, size=n).astype(np.int32) for n in lengths]


def serve_gap(eng, params, cfg, lengths=(5, 21, 30), new=24):
    """`benchmarks/serve.py check_against_reference`'s comparison over
    several requests: how far, in sd of that position's logits, the
    reference's logit of the engine's token lies under the reference's
    best; the worst position of all."""
    prompts = prompts_of(lengths)
    outs = eng.generate(prompts, max_new_tokens=new)
    worst = 0.0
    for p, o in zip(prompts, outs):
        ids = np.concatenate([p, np.asarray(o[:-1], np.int64)]).astype(
            np.int32)
        lg = np.asarray(ref.logits(params, ref_cfg(cfg), jnp.asarray(ids)),
                        np.float64)[len(p) - 1:]
        chosen = lg[np.arange(len(o)), np.asarray(o)]
        worst = max(worst, float(((lg.max(-1) - chosen) / lg.std(-1)).max()))
    return worst


def latent_pools(cfg, pages, width=None):
    width = width or cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return [jnp.zeros((pages, PAGE, width)) for _ in
            range(cfg.num_hidden_layers)]


# -- the model against the reference ---------------------------------------

def test_logits_without_a_cache_match_the_reference(tiny):
    cfg, model, params = tiny
    ids = prompts_of([40])[0]
    got = np.asarray(model(paddle_tpu.to_tensor(ids[None]))._value)[0]
    want = np.asarray(ref.logits(params, ref_cfg(cfg), jnp.asarray(ids)))
    assert np.abs(got - want).max() < TOL
    # the positions differ: a check that compares tokens has something
    # to see
    assert len(set(want.argmax(-1).tolist())) > 10


def test_logits_through_a_latent_cache_match_the_reference(tiny):
    """The cached forward by hand: a 13-token prefill (the expanded form,
    over a page and a half), then one token a call (the absorbed form)."""
    cfg, model, params = tiny
    ids = prompts_of([40], seed=3)[0]
    want = np.asarray(ref.logits(params, ref_cfg(cfg), jnp.asarray(ids)))
    bt = jnp.arange(1, 7, dtype=jnp.int32)[None]            # 6 pages of 8

    def call(tokens, at, flat):
        n = tokens.shape[0]
        state = PagedState(bt, at[None], jnp.full((1,), n, jnp.int32))
        pos = Tensor((at + jnp.arange(n, dtype=jnp.int32))[None])
        logits, new, counted = model(
            Tensor(tokens[None]), position_ids=pos,
            caches=[(Tensor(p),) for p in flat], cache_index=state,
            with_counters=True)
        return (logits._value[0, -1], [kv[0]._value for kv in new],
                {k: jnp.asarray(v) for k, v in counted.items()})
    logits, flat, counted = call(jnp.asarray(ids[:13]), jnp.int32(0),
                                 latent_pools(cfg, 8))
    assert np.abs(np.asarray(logits) - want[12]).max() < TOL
    assert int(counted["moe_layer_steps"]) == 2
    step = jax.jit(call)
    for t in range(13, 40):
        logits, flat, counted = step(jnp.asarray(ids[t:t + 1]),
                                     jnp.int32(t), flat)
        assert np.abs(np.asarray(logits) - want[t]).max() < TOL, t
        # a step reads its slot's t + 1 keys in every layer
        assert int(counted["mla_decode_rows"]) == 3 * (t + 1)
    assert 1 <= int(counted["moe_experts_hit"]) <= 2 * 2


def test_the_absorbed_decode_is_the_expanded_form():
    """One decode step over a latent pool: W_uk folded into the query and
    W_uv onto the output (`latent_attention_update`, s == 1) against the
    same keys expanded through W_kvb (`_attend_latent_expanded`), three
    slots at lengths inside a page, on a page's edge and past a block."""
    rng = np.random.default_rng(5)
    b, h, nope, rope, dv, rank, pages = 3, 4, 16, 8, 16, 32, 12
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    pool = f(40, PAGE, rank + rope)
    bt = jnp.asarray(rng.permutation(39)[:b * pages].reshape(b, pages) + 1,
                     jnp.int32)
    lens = jnp.asarray([3, 15, 90], jnp.int32)
    state = PagedState(bt, lens, jnp.ones(b, jnp.int32))
    q_nope, q_pe = f(b, 1, h, nope), f(b, 1, h, rope)
    c, k_pe = f(b, 1, rank), f(b, 1, rope)
    w_uk, w_uv = f(rank, h, nope) / 6, f(rank, h, dv) / 6
    scale = 1.0 / math.sqrt(nope + rope)
    out, (new,) = paged.latent_attention_update(
        q_nope, q_pe, c, k_pe, w_uk, w_uv, (Tensor(pool),), state,
        scale=scale)
    with jax.default_matmul_precision("highest"):
        want = paged._attend_latent_expanded(
            q_nope, q_pe, new._value, w_uk, w_uv, state, scale)
    assert tuple(out.shape) == want.shape == (b, 1, h * dv)
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize(
    "lens, mp, trash",
    [([0, 7, 8, 1023], 128, False), ([1024, -1, 300, 2047], 128, False),
     ([100, 1500, -1, 2000], 128, True), ([0, 1040, 1279, 300], 80, False),
     ([1023, 1024, 1025, -1], 128, False)],
    ids=["block-edges", "a-slot-idle", "the-trash-page-past-a-length",
         "a-table-not-whole-blocks", "around-a-block-edge"])
def test_the_decode_kernel_interpreted_is_its_jnp_twin(lens, mp, trash):
    """The Pallas kernel over blocks of 64 pages (1,024 keys),
    interpreted, against `mla_decode_reference`: lengths at a page's and a
    block's edges, and a slot of negative length, which reads nothing. A
    window copies every page of its block: past a slot's last page the
    table names the trash page 0 (here full of large finite values, which
    the mask must keep out), and a block past the table's end (80 pages,
    so block 1 holds 16) copies its last page again."""
    rng = np.random.default_rng(1)
    b, h, rank, width = len(lens), 8, 128, 256
    assert mla.block_pages(16, mp) == 64
    pool = jnp.asarray(rng.standard_normal((b * mp + 1, 16, width)),
                       jnp.float32)
    bt = rng.permutation(b * mp).reshape(b, mp) + 1
    if trash:
        pool = pool.at[0].set(1e30)
        held = np.maximum(np.asarray(lens), 0)[:, None] // 16
        bt = np.where(np.arange(mp)[None, :] > held, 0, bt)
    bt = jnp.asarray(bt, jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, h, width)), jnp.float32)
    lens = jnp.asarray(lens, jnp.int32)
    got = mla.mla_decode_attention(q, pool, bt, lens, rank=rank,
                                   sm_scale=0.05, interpret=True)
    want = mla.mla_decode_reference(q, pool, bt, lens, rank=rank,
                                    sm_scale=0.05)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(got)[np.asarray(lens) < 0].any()


def test_the_prefill_kernel_interpreted_is_the_expanded_form(monkeypatch):
    """The Pallas prefill kernel, interpreted, at blocks of 64 latent rows
    and q blocks of 16 tokens, against `_attend_latent_expanded` over the
    same pool: a row at position 0 (the diagonal blocks alone), one whose
    chunk starts past two key blocks, and one whose 5 valid tokens leave
    its second q block wholly padding (zeros, and nothing expanded for
    it); rows stored at whole lane tiles, the rotary key past the latent."""
    from paddle_tpu.kernels import mla_prefill
    monkeypatch.setattr(mla_prefill, "_BLOCK_K", 64)
    monkeypatch.setattr(mla_prefill, "_BLOCK_Q", 16)
    rng = np.random.default_rng(2)
    b, s, h, nope, rope, dv, rank, width, mp = 3, 32, 4, 16, 8, 16, 32, 48, 26
    f = lambda *sh: jnp.asarray(rng.standard_normal(sh), jnp.float32)  # noqa
    pool = f(b * mp + 1, PAGE, width).at[..., rank + rope:].set(0.0)
    bt = jnp.asarray(rng.permutation(b * mp).reshape(b, mp) + 1, jnp.int32)
    lens = jnp.asarray([0, 150, 170], jnp.int32)
    n_valid = jnp.asarray([32, 20, 5], jnp.int32)
    q_nope, q_pe = f(b, s, h, nope), f(b, s, h, rope)
    w_uk, w_uv = f(rank, h, nope) / 6, f(rank, h, dv) / 6
    scale = 1.0 / math.sqrt(nope + rope)
    got = np.asarray(mla_prefill.mla_prefill_attention(
        q_nope, q_pe, pool, bt, w_uk, w_uv, lens, n_valid, sm_scale=scale,
        interpret=True))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(paged._attend_latent_expanded(
            q_nope, q_pe, pool, w_uk, w_uv,
            PagedState(bt, lens, n_valid), scale))
    for i, n in enumerate(np.asarray(n_valid)):
        np.testing.assert_allclose(got[i, :n], want[i, :n], atol=1e-4,
                                   rtol=1e-4)
    assert not got[2, 16:].any()
    # q blocks' key blocks: row 0 1 + 1, row 1 3 + 3, row 2 3
    assert mla_prefill.expanded_rows(lens, n_valid, s, mp, PAGE) \
        == (2 + 6 + 3) * 64


# -- through the engine ------------------------------------------------------

@pytest.mark.parametrize("kernel,page,chunk", [
    ("jnp", PAGE, CHUNK), ("pallas", PAGE, CHUNK), ("pallas", 16, 8)],
    ids=["jnp", "pallas", "pallas-prefill-kernel"])
def test_engine_prefill_in_chunks_then_decode_matches_the_reference(
        tiny, monkeypatch, kernel, page, chunk):
    """Prompts of 5, 21 and 30 tokens prefilled in chunks that end inside
    pages (12 in pages of 8, 8 in pages of 16), then 24 tokens each
    through the latent pool: the jnp path, and the decode kernel
    interpreted over rows stored at whole lane tiles; prefill programs of
    whole sublane tiles take the prefill kernel too, interpreted."""
    cfg, model, params = tiny
    traced = []
    real = paged._mlp.mla_prefill_attention
    monkeypatch.setattr(paged._mlp, "mla_prefill_attention",
                        lambda *a, **kw: traced.append(1) or real(*a, **kw))
    eng = engine(model, kernel=kernel, chunk=chunk, page_size=page)
    assert eng.decode_kernel == kernel and eng._cache_arity == 1
    width = 40 if kernel == "jnp" else 128
    assert [p[0].shape for p in eng.pools] == [(64, page, width)] * 3
    assert serve_gap(eng, params, cfg) < HONEST_SD
    # a program of whole sublane tiles (a bucket of 8, a chunk of 8) takes
    # the prefill kernel on the Pallas path; a chunk of 12 cannot
    assert bool(traced) == (kernel == "pallas")
    s = eng.stats
    # each request's keys at each of its 23 decode steps, in every layer
    assert s["mla_decode_rows"] == 3 * sum(23 * p + 23 * 24 // 2
                                           for p in (5, 21, 30))
    assert s["mla_prefill_tokens"] == 5 + 21 + 30
    assert s["moe_layer_steps"] == 2 * 4 * s["ticks"]
    assert len(eng._free) == eng.num_pages - 1


def test_the_engine_counts_the_rows_a_prefill_expands(tiny):
    """A 30-token prompt alone, in chunks of 12: three calls, each looping
    over the blocks of pages its keys reach (16 pages a block here: the
    scores of a chunk of 12 are small), in every layer."""
    cfg, model, _params = tiny
    eng = engine(model)
    bp = paged.latent_block_pages(1, cfg.num_attention_heads, CHUNK, 20,
                                  PAGE)
    assert bp == 16
    eng.generate(prompts_of([30]), max_new_tokens=1)
    # reaches 12, 24, 30 keys: one block of 128 columns each
    assert eng.stats["mla_expand_rows"] == 3 * 1 * bp * PAGE * 3
    assert eng.stats["mla_prefill_tokens"] == 30


# -- planted faults: the same comparison must refuse each ---------------------

def _rope_dropped(monkeypatch, model):
    """The rope term q_pe . k_pe: k_pe stored as zeros and q_pe zeroed in
    the absorbed query, so prefill and decode both lose it."""
    real = paged._latent_rows
    monkeypatch.setattr(paged, "_latent_rows", lambda c, k_pe, width: real(
        c, jnp.zeros_like(k_pe), width))


def _norm_skipped(monkeypatch, model):
    for layer in model.model.layers:
        monkeypatch.setattr(layer.self_attn.kv_a_layernorm, "forward",
                            lambda x: x)


def _scale_of_nope(monkeypatch, model):
    real = paged.latent_attention_update
    nope = model.config.qk_nope_head_dim

    def update(*a, scale, **kw):
        return real(*a, scale=1.0 / math.sqrt(nope), **kw)
    monkeypatch.setattr(paged, "latent_attention_update", update)


def _bias_in_the_gates(monkeypatch, model):
    real = FM.topk_gating_dropless

    def weighed(logits, k, bias=None, **kw):
        idx, _gates, aux = real(logits, k, bias=bias, **kw)
        picked = jnp.take_along_axis(
            jax.nn.sigmoid(logits.astype(jnp.float32))
            + bias.astype(jnp.float32), idx, -1)
        gates = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
        return idx, gates * kw["route_scale"], aux
    monkeypatch.setattr(FM, "topk_gating_dropless", weighed)


def _shared_dropped(monkeypatch, model):
    for layer in model.model.layers[1:]:
        monkeypatch.setattr(layer.mlp.shared_expert, "forward",
                            lambda x: x * 0.0)


@pytest.mark.parametrize("plant", [
    _rope_dropped, _norm_skipped, _scale_of_nope, _bias_in_the_gates,
    _shared_dropped], ids=["rope-term-dropped", "kv-norm-skipped",
                           "scale-of-nope", "bias-in-the-gates",
                           "shared-expert-dropped"])
def test_a_planted_fault_is_refused(tiny, monkeypatch, plant):
    cfg, model, params = tiny
    with monkeypatch.context() as m:
        plant(m, model)
        gap = serve_gap(engine(model), params, cfg)
    assert gap > FAULT_SD, gap


# -- what the engine learns from the model, and what it refuses ---------------

def test_the_latent_pool_holds_576_values_a_token_a_layer():
    """At the published latent widths (rank 512, rope 64) in bf16: one row
    of 576 values, 1,152 B a token a layer; where the decode kernel reads
    the pool its rows are stored at 640 (whole lane tiles)."""
    cfg, model, _ = make(kv_lora_rank=512, qk_rope_head_dim=64,
                         num_attention_heads=8)
    eng = engine(model, kv_dtype="bf16")
    assert eng.latent_width == 576
    assert eng.kv_bytes_per_slot() == 576 * 2 * 3 * PAGE * 20
    assert eng.page_groups()[0]["pool_bytes"] == 576 * 2 * 3 * PAGE * 64
    assert mla.kernel_row_width(576) == 640
    eng = engine(model, kernel="pallas", kv_dtype="bf16", page_size=16)
    assert eng.kv_bytes_per_slot() == 640 * 2 * 3 * 16 * 20
    # per-head K and V of the same heads would take (192 + 128) a head
    assert 32 * (192 + 128) * 2 / (576 * 2) == pytest.approx(17.78, 1e-3)


def test_the_engine_takes_the_kernel_on_a_tpu_of_its_own_accord(
        monkeypatch):
    _cfg, model, _ = make(kv_lora_rank=128, num_attention_heads=8)
    assert engine(model, kernel=None).decode_kernel == "jnp"
    monkeypatch.setattr(paged.jax_compat, "on_tpu", lambda: True)
    eng = engine(model, kernel=None, page_size=16)
    assert eng.decode_kernel == "pallas" and eng.kv_write == "xla"
    assert eng.pools[0][0].shape[-1] == 256         # 128 + 8 values
    # a prefill program is 2,048 tokens, all rows together, whatever the
    # score budget of K and V pages would allow
    assert eng._prefill_limit(1) == 2048 and eng._prefill_limit(32) == 64
    # a page shorter than a bf16 sublane tile cannot be copied whole
    problems = mla.mla_decode_problems(32, 640, 512, 8, jnp.bfloat16)
    assert problems and "page_size % 16" in problems[0]


@pytest.mark.parametrize("kw,named", [
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"prefix_cache_pages": 4}, "prefix_cache_pages"),
    ({"prefix_cache_pages": 4, "host_tier_bytes": 1 << 20},
     "host_tier_bytes"),
    ({"role": "decode", "prefix_cache_pages": 4}, "role='decode'"),
    ({"draft_model": "self"}, "draft_model"),
])
def test_what_assumes_k_and_v_pages_refuses_a_latent_pool_by_name(
        tiny, kw, named):
    _cfg, model, _ = tiny
    if kw.get("draft_model") == "self":
        kw = {"draft_model": model, "chunk": None}
    with pytest.raises(ValueError, match="latent row") as e:
        engine(model, **kw)
    assert named in str(e.value) and "not carried by" in str(e.value)


def test_what_the_model_does_not_build_is_refused_by_name(tiny):
    cfg, model, _ = tiny
    with pytest.raises(NotImplementedError, match="q_lora_rank=1536"):
        tiny_latent_attn_moe_config(q_lora_rank=1536)
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        tiny_latent_attn_moe_config(rope_scaling={"type": "yarn"})
    with pytest.raises(NotImplementedError, match="n_group"):
        LatentAttnMoeConfig(n_group=8, topk_group=4)
    with pytest.raises(NotImplementedError, match="pages only"):
        model(paddle_tpu.to_tensor(prompts_of([4])[0][None]),
              caches=[(Tensor(jnp.zeros((1, 8, 40))),)] * 3, cache_index=0)
    with pytest.raises(NotImplementedError, match="forward only"):
        ids = paddle_tpu.to_tensor(prompts_of([4])[0][None])
        model(ids, labels=ids)


def test_the_expert_block_is_the_one_the_other_families_use(tiny):
    from paddle_tpu.models.qwen2_moe import SharedExpertMoE
    from paddle_tpu.nn.layer.moe import MoEMLP
    cfg, model, _ = tiny
    block = model.model.layers[1].mlp
    assert type(block) is SharedExpertMoE and type(block.moe) is MoEMLP
    assert block.shared_expert_gate is None
    assert block.moe.router == {"score_func": "sigmoid", "route_norm": True,
                                "route_scale": 2.448}
    assert block.moe.expert_bias is not None
    assert block.shared_expert.down_proj.weight.shape == [
        cfg.n_shared_experts * cfg.moe_intermediate_size, cfg.hidden_size]
    assert type(model.model.layers[0].mlp) is lam.LlamaMLP
