"""The decoder with a learned key selection and routed experts
(models/sparse_attn_moe.py) against its plain reference
(benchmarks/reference_sparse_attn_moe.py), small, float32, on the CPU:
the model's logits; prefill (whole and in chunks) then paged decode
through the three pools; the selected sets; three planted faults that
the same comparison must refuse; the Pallas paths in interpret mode; and
what the engine learns from the model (the third pool, its own prefill
chunk, its counters, what it refuses).
"""
import json
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
from benchmarks import reference_sparse_attn_moe as ref  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.inference import PagedKVEngine, paged  # noqa: E402
from paddle_tpu.inference.paged import PagedState  # noqa: E402
from paddle_tpu.jit.functional import state_arrays  # noqa: E402
from paddle_tpu.nn.functional.key_selection import select_top  # noqa: E402
from paddle_tpu.models.sparse_attn_moe import (  # noqa: E402
    SparseAttnMoeForCausalLM, tiny_sparse_attn_moe_config)

PAGE, PAGES_PER_SLOT = 4, 10        # a 40-token window a slot
TOL = 2e-4                          # float32 against float32 "highest"


def ref_cfg(c):
    """The published keys the reference reads, from the model's config."""
    return {"num_attention_heads": c.num_attention_heads,
            "num_key_value_heads": c.num_key_value_heads,
            "head_dim": c.head_dim, "num_hidden_layers": c.num_hidden_layers,
            "rms_norm_eps": c.rms_norm_eps, "rope_theta": c.rope_theta,
            "num_experts": c.num_experts,
            "num_experts_per_tok": c.num_experts_per_tok,
            "norm_topk_prob": True,
            "sa_config": {"indexer_num_heads": c.index_num_heads,
                          "indexer_head_dim": c.index_head_dim,
                          "topk": c.index_topk}}


def make(**overrides):
    cfg = tiny_sparse_attn_moe_config(**overrides)
    paddle_tpu.seed(7)
    model = SparseAttnMoeForCausalLM(cfg)
    model.eval()
    return cfg, model, state_arrays(model)


@pytest.fixture(scope="module")
def tiny():
    return make()


def tokens(n, seed=3):
    return np.random.default_rng(seed).integers(1, 256, size=n).astype(
        np.int32)


BT = jnp.arange(1, PAGES_PER_SLOT + 1, dtype=jnp.int32)[None]
STEPS = {}          # the healthy model's jitted calls, by tokens a call


def call(model, n, ids, at, pools):
    """n tokens of one slot through the model and its three pools a
    layer, appended at position `at`: (last token's logits, pools)."""
    state = PagedState(BT, at[None], jnp.full((1,), n, jnp.int32))
    pos = (at + jnp.arange(n, dtype=jnp.int32))[None]
    logits, pools = model(
        Tensor(ids[None]), caches=[tuple(Tensor(a) for a in p)
                                   for p in pools],
        position_ids=Tensor(pos), cache_index=state)
    return logits._value[0, -1], [tuple(a._value for a in p) for p in pools]


def paged_logits(model, cfg, ids, prompt, chunk=None, steps=None):
    """The model's logits for positions prompt-1 .. len(ids)-1 through the
    three pools: the prompt as one call or in `chunk`-token calls, then one
    token a call. `steps`: the jitted calls by size (STEPS for the healthy
    model of the `tiny` fixture; a planted fault brings its own dict, and
    None runs eagerly, so that a test can look inside)."""
    pools = [(jnp.zeros((PAGES_PER_SLOT + 1, cfg.num_key_value_heads, PAGE,
                         cfg.head_dim)),) * 2
             + (jnp.zeros((PAGES_PER_SLOT + 1, 1, PAGE,
                           cfg.index_head_dim)),)
             for _ in range(cfg.num_hidden_layers)]
    out, at = [], 0
    pieces = [prompt] if chunk is None else \
        [min(chunk, prompt - i) for i in range(0, prompt, chunk)]
    pieces += [1] * (len(ids) - prompt)
    for n in pieces:
        fn = (lambda *a, n=n: call(model, n, *a)) if steps is None else \
            steps.setdefault(n, jax.jit(
                lambda *a, n=n: call(model, n, *a)))
        logits, pools = fn(jnp.asarray(ids[at:at + n]),
                           jnp.asarray(at, jnp.int32), pools)
        at += n
        if at >= prompt:
            out.append(np.asarray(logits))
    return np.stack(out)


REFERENCE = {}      # the reference, jitted by length


def reference_logits(params, cfg, ids):
    fn = REFERENCE.setdefault(len(ids), jax.jit(
        lambda p, row: ref.logits(p, ref_cfg(cfg), row)))
    return np.asarray(fn(params, jnp.asarray(ids)))


def worst(model, cfg, params, ids, prompt, **kw):
    """The one comparison every case below is held to: max |logit -
    reference logit| over the prompt's last position and every decoded
    one."""
    want = reference_logits(params, cfg, ids)
    got = paged_logits(model, cfg, ids, prompt, **kw)
    return float(np.abs(got - want[prompt - 1:]).max())


def test_whole_sequence_logits_match_the_reference(tiny):
    cfg, model, params = tiny
    ids = tokens(24)
    got = np.asarray(model(paddle_tpu.to_tensor(ids[None]))._value)[0]
    want = np.asarray(ref.logits(params, ref_cfg(cfg), jnp.asarray(ids)))
    assert np.abs(got - want).max() < TOL
    # the selection is at work: 24 keys against a topk of 8
    sets = ref.selected_sets(params, ref_cfg(cfg), jnp.asarray(ids))
    assert int(sets[0][-1].sum()) == cfg.index_topk


@pytest.mark.parametrize("prompt,chunk", [(5, None), (13, None), (13, 4),
                                          (13, 5)])
def test_prefill_then_paged_decode_match_the_reference(tiny, prompt, chunk):
    """Contexts that start under topk (5 tokens against 8) and cross it,
    and ones that start over it; the prompt whole and in chunks."""
    cfg, model, params = tiny
    assert worst(model, cfg, params, tokens(prompt + 12), prompt,
                 chunk=chunk, steps=STEPS) < TOL


def test_the_selected_sets_equal_the_references(tiny, monkeypatch):
    cfg, model, params = tiny
    ids, prompt = tokens(16), 9
    seen = []
    real = paged._select_keys

    def recording(*a, **kw):
        out = real(*a, **kw)
        seen.append(np.asarray(out)[0])              # (s, L) of one slot
        return out
    monkeypatch.setattr(paged, "_select_keys", recording)
    paged_logits(model, cfg, ids, prompt, chunk=5)      # eagerly
    want = [np.asarray(s) for s in
            ref.selected_sets(params, ref_cfg(cfg), jnp.asarray(ids))]
    layers = cfg.num_hidden_layers
    for layer in range(layers):
        rows = np.concatenate(seen[layer::layers])[:, :len(ids)]
        assert rows.shape == (len(ids), len(ids))
        assert (rows == want[layer]).all()
        assert rows[-1].sum() == cfg.index_topk


def _selection_off(monkeypatch):
    monkeypatch.setattr(paged, "select_top",
                        lambda scores, causal, k: causal)


def _one_experts_output_dropped(monkeypatch):
    from paddle_tpu.nn.functional import moe as FM
    real = FM.moe_dropless_mlp
    monkeypatch.setattr(
        FM, "moe_dropless_mlp",
        lambda xt, wg, wu, wd, idx, gates: real(
            xt, wg, wu, wd, idx, jnp.where(idx == 3, 0.0, gates)))


def _index_pool_not_written_on_decode(monkeypatch):
    real = paged._attend_indexed

    def stale(q, k, v, cache, state, index):
        out, new = real(q, k, v, cache, state, index)
        return out, (new if q.shape[1] > 1 else (*new[:2], cache[2]))
    monkeypatch.setattr(paged, "_attend_indexed", stale)


@pytest.mark.parametrize("plant", [_selection_off,
                                   _one_experts_output_dropped,
                                   _index_pool_not_written_on_decode])
def test_a_planted_fault_fails_the_same_comparison(tiny, monkeypatch, plant):
    cfg, model, params = tiny
    ids, prompt = tokens(25), 13
    assert worst(model, cfg, params, ids, prompt, steps=STEPS) < TOL
    plant(monkeypatch)
    assert worst(model, cfg, params, ids, prompt, steps={}) > 50 * TOL


def test_select_top_is_top_k_with_ties_to_the_lower_index():
    rng = np.random.default_rng(0)
    scores = rng.standard_normal((2, 3, 40)).astype(np.float32)
    scores[0, 0, 5:25] = 0.25           # a run of ties across the edge
    scores[1, 2, :] = 0.0               # nothing but ties
    pos = np.array([[30, 35, 39], [3, 20, 39]])
    causal = np.arange(40)[None, None, :] <= pos[..., None]
    got = np.asarray(select_top(jnp.asarray(scores), jnp.asarray(causal), 8))
    _v, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), 8)
    want = np.zeros_like(causal)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    assert (got == (want & causal)).all()
    assert got[1, 0].sum() == 4 and got[0, 0].sum() == 8


# -- the Pallas paths, interpret mode, against the jnp paths ---------------

def test_few_rows_expert_kernel_equals_the_grouped_path():
    from paddle_tpu.kernels.moe_experts import (experts_hit,
                                                moe_experts_decode)
    from paddle_tpu.nn.functional import moe as FM
    rng = np.random.default_rng(1)
    t, d, f, e, k = 5, 128, 256, 16, 4
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((e, d, f)) * 0.05, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((e, f, d)) * 0.05, jnp.float32)
    idx, gates, _aux = FM.topk_gating_dropless(
        jnp.asarray(rng.standard_normal((t, e)), jnp.float32), k)
    want = FM.moe_dropless_mlp(x, wg, wu, wd, idx, gates)
    got = moe_experts_decode(x, wg, wu, wd, idx, gates, interpret=True)
    assert np.abs(np.asarray(got - want)).max() < 1e-4
    assert int(experts_hit(idx, e)) == len(np.unique(np.asarray(idx)))


def test_expert_layer_returns_its_hit_count_and_keeps_none():
    from paddle_tpu.nn.functional import moe as FM
    from paddle_tpu.nn.layer.moe import MoEMLP
    paddle_tpu.seed(3)
    layer = MoEMLP(32, 16, 8, top_k=2, dropless=True)
    layer.eval()
    x = Tensor(jnp.asarray(np.random.default_rng(3).standard_normal(
        (5, 32)), jnp.float32))
    out, hit = layer(x, with_hit=True)
    logits = jnp.einsum("td,de->te", x._value, layer.router_weight._value)
    idx, _gates, _aux = FM.topk_gating_dropless(logits, 2)
    assert int(hit) == len(np.unique(np.asarray(idx)))
    assert np.allclose(np.asarray(out._value), np.asarray(layer(x)._value))
    assert not hasattr(layer, "experts_hit")
    # off the chip the grouped path is the only one; the count belongs to
    # the dropless path
    assert not layer._few_rows(x)
    with pytest.raises(NotImplementedError):
        MoEMLP(32, 16, 8, top_k=2)(x, with_hit=True)


def test_decode_kernel_under_a_selection_equals_the_jnp_attend():
    from paddle_tpu.kernels.paged_attention import (paged_decode_attention,
                                                    select_shape_problems)
    rng = np.random.default_rng(2)
    b, hq, hk, d, page, mp = 3, 4, 2, 128, 16, 5
    assert not select_shape_problems(hk, d, page, jnp.float32)
    assert select_shape_problems(hk, 64, page, jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    kp, vp = (jnp.asarray(rng.standard_normal((b * mp + 1, hk, page, d)),
                          jnp.float32) for _ in range(2))
    bt = jnp.arange(1, b * mp + 1, dtype=jnp.int32).reshape(b, mp)
    lens = jnp.asarray([70, 3, 33], jnp.int32)
    select = jnp.asarray(rng.random((b, mp * page)) < 0.4)
    select = select.at[:, 0].set(True)          # never an empty set
    got = paged_decode_attention(q, kp, vp, bt, lens, interpret=True,
                                 select=select)
    seen = select & (jnp.arange(mp * page)[None] <= lens[:, None])
    want = paged._attend_selected(
        q[:, None], kp, vp, PagedState(bt, lens, jnp.ones_like(lens)),
        seen[:, None])
    assert np.abs(np.asarray(got).reshape(b, -1)
                  - np.asarray(want)[:, 0]).max() < 1e-4


def test_engine_on_the_pallas_paths_equals_the_jnp_engine():
    """The decode kernel under a selection, in interpret mode inside the
    engine's own programs (head width 128, so that a selection can ride
    it; the few-rows expert kernel is taken on a TPU only and is held to
    the grouped path above)."""
    cfg, model, params = make(hidden_size=128, head_dim=128,
                              moe_intermediate_size=128)
    prompts = [tokens(5, 1), tokens(13, 2)]
    got = {}
    for kernel in ("jnp", "pallas"):
        eng = PagedKVEngine(model, max_slots=2, page_size=8, num_pages=17,
                            max_pages_per_slot=8, kernel=kernel)
        assert eng.decode_kernel == kernel
        got[kernel] = eng.generate(prompts, max_new_tokens=10)
        eng.stop()
    assert [list(t) for t in got["pallas"]] == [list(t) for t in got["jnp"]]
    for p, out in zip(prompts, got["pallas"]):
        ids = np.concatenate([p, np.asarray(out[:-1])]).astype(np.int32)
        want = np.asarray(ref.logits(params, ref_cfg(cfg), jnp.asarray(ids)))
        assert (want[len(p) - 1:].argmax(-1) == np.asarray(out)).all()


# -- what the engine learns from the model ---------------------------------

def engine(model, **kw):
    return PagedKVEngine(model, max_slots=2, page_size=PAGE, num_pages=21,
                         max_pages_per_slot=PAGES_PER_SLOT, kernel="jnp",
                         **kw)


def test_engine_carries_the_index_pool_and_counts(tiny):
    cfg, model, params = tiny
    eng = engine(model)
    assert eng.index_dim == cfg.index_head_dim and eng.index_topk == 8
    k, v, ip = eng.pools[0]
    assert len(eng.pools) == cfg.num_hidden_layers
    assert ip.shape == (21, 1, PAGE, cfg.index_head_dim)
    assert eng.kv_bytes_per_slot() == PAGES_PER_SLOT * sum(
        a.size * a.dtype.itemsize // 21 for p in eng.pools for a in p)
    prompts = [tokens(5, 1), tokens(13, 2)]
    outs = eng.generate(prompts, max_new_tokens=9)
    for p, out in zip(prompts, outs):
        ids = np.concatenate([p, np.asarray(out[:-1])]).astype(np.int32)
        want = np.asarray(ref.logits(params, ref_cfg(cfg), jnp.asarray(ids)))
        assert (want[len(p) - 1:].argmax(-1) == np.asarray(out)).all()
    # the first token is the prefill's; the other 8 a slot are decode steps
    assert eng.stats["decode_slot_steps"] == 16
    # a step attends over lens + 1 keys: the 5-token prompt's steps see
    # 6..13 keys (5 over topk = 8), the 13-token prompt's all 8 do
    assert eng.stats["select_engaged_steps"] == 5 + 8
    steps = eng.stats["ticks"] * eng.steps_per_tick
    assert eng.stats["moe_layer_steps"] == steps * cfg.num_hidden_layers
    per_layer_step = eng.stats["moe_experts_hit"] / eng.stats[
        "moe_layer_steps"]
    assert cfg.num_experts_per_tok <= per_layer_step <= min(
        cfg.num_experts, 2 * cfg.num_experts_per_tok)
    eng.stop()


def test_engine_chunks_a_long_prefill_by_its_own_reckoning(tiny,
                                                           monkeypatch):
    cfg, model, _params = tiny
    whole = engine(model)
    # 4 heads x a 40-token window x 4 B: 640 B a prompt token a row
    assert whole._prefill_limit(1) == 1 << 20
    prompts = [tokens(13, 2), tokens(21, 5)]
    want = whole.generate(prompts, max_new_tokens=6)
    whole.stop()
    monkeypatch.setattr(paged, "_PREFILL_SCORE_BYTES", 8 * 640)
    eng = engine(model)
    assert eng._prefill_limit(1) == 8 and eng._prefill_limit(2) == 8
    got = eng.generate(prompts, max_new_tokens=6)
    assert [list(t) for t in got] == [list(t) for t in want]
    # each long prompt alone through the one chunk program
    assert {k[:3] for k in eng._programs if k[0].startswith("prefill")} \
        == {("prefill_chunk", 8, 1)}
    eng.stop()


def test_the_dense_cells_prefill_keeps_its_single_program():
    """16 rows x 32 heads x a 512 bucket x 48 pages of 16 is under the
    budget, so the dense serve cell's programs are what they were."""
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
    paddle_tpu.seed(0)
    model = LlamaForCausalLM(tiny_llama_config(num_attention_heads=32,
                                               num_key_value_heads=32,
                                               hidden_size=64,
                                               num_hidden_layers=1))
    eng = PagedKVEngine(model, max_slots=16, page_size=16, num_pages=769,
                        max_pages_per_slot=48, kernel="jnp")
    assert eng.index_dim == 0 and "decode_slot_steps" not in eng.stats
    assert eng._prefill_limit(16) == 512 and eng._prefill_limit(1) >= 8192
    assert len(eng.pools[0]) == 2
    eng.stop()


@pytest.mark.parametrize("kw,named", [
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"prefix_cache_pages": 4}, "prefix_cache_pages"),
    ({"prefix_cache_pages": 4, "host_tier_bytes": 1 << 20},
     "host_tier_bytes"),
    ({"draft_model": "self"}, "draft_model"),
])
def test_what_assumes_two_pools_refuses_by_name(tiny, kw, named):
    _cfg, model, _params = tiny
    if kw.get("draft_model") == "self":
        kw = {"draft_model": model}
    with pytest.raises(ValueError, match="index pool") as e:
        engine(model, **kw)
    assert named in str(e.value)


def test_a_dense_cache_is_refused_with_the_reason(tiny):
    cfg, model, _params = tiny
    buf = jnp.zeros((1, 16, cfg.num_key_value_heads, cfg.head_dim))
    with pytest.raises(NotImplementedError, match="pages only"):
        model(paddle_tpu.to_tensor(tokens(4)[None]),
              caches=[(Tensor(buf), Tensor(buf))] * cfg.num_hidden_layers,
              cache_index=0)
    with pytest.raises(NotImplementedError, match="forward only"):
        model(paddle_tpu.to_tensor(tokens(4)[None]),
              labels=paddle_tpu.to_tensor(tokens(4)[None]))


def test_the_expert_block_is_the_one_qwen2_moe_uses(tiny):
    from paddle_tpu.models.qwen2_moe import (Qwen2MoeSparseBlock,
                                             tiny_qwen2_moe_config)
    from paddle_tpu.nn.layer.moe import MoEMLP
    _cfg, model, _params = tiny
    block = Qwen2MoeSparseBlock(tiny_qwen2_moe_config(moe_dropless=True))
    assert type(model.model.layers[0].mlp) is MoEMLP is type(block.moe)
    assert model.model.layers[0].mlp.dropless and block.moe.dropless


def test_the_configuration_file_holds_the_published_keys():
    """Every number of the catalog's entry under the same key, the depth
    alone reduced, and the builder's config maps each width across."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "keye-vl-2.0-30b-a3b-lm.json")) as f:
        cfg = json.load(f)
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    assert cfg["reduced"]["num_hidden_layers"]["from"] == 48
    assert cfg["num_hidden_layers"] == cfg["reduced"][
        "num_hidden_layers"]["to"] >= 4
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"]) == (2048, 128, 32, 4)
    assert (cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"]) == (128, 8, 768)
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    from benchmarks.builders import sparse_attn_moe as family
    mc = family.model_config(cfg, 4096)
    assert (mc.index_num_heads, mc.index_head_dim, mc.index_topk) \
        == (16, 64, 2048)
    assert mc.rope_theta == 1e7 and mc.vocab_size == 151936
    layer = sum(family.costs.layer_weights(cfg)[:3]) \
        + 128 * family.costs.layer_weights(cfg)[3]
    total = cfg["num_hidden_layers"] * layer + 2 * 151936 * 2048
    assert abs(2 * total / 1e9 - 10.0) < 0.01      # GB in bf16
