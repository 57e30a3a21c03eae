"""The decoder that generates by diffusion over blocks
(models/block_diffusion_moe.py) against its plain reference
(benchmarks/reference_block_diffusion_moe.py), small, float32, on the CPU:
the model's logits under the mask by blocks; the chunk kernel and the
decode kernel under that mask; the engine (prefill, then block ticks through
the paged cache, on the jnp path and on the interpreted Pallas paths)
against the reference's free-running loop, token for token, for every
P mod 4, truncated last blocks, slots at different phases, chained ticks and
the three unmasking rules; the replay that `benchmarks/serve.py` reads
against the free run's rows; seven planted faults that the same comparison
must refuse; and what the engine refuses by name.
"""
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
from benchmarks import reference_block_diffusion_moe as ref  # noqa: E402
from paddle_tpu import observability  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.inference import PagedKVEngine, paged  # noqa: E402
from paddle_tpu.inference.paged import PagedState  # noqa: E402
from paddle_tpu.jit.functional import state_arrays, state_tensors  # noqa: E402
from paddle_tpu.kernels import paged_attention as pk  # noqa: E402
from paddle_tpu.kernels.prefill_attention import chunk_attention  # noqa: E402
from paddle_tpu.models import block_diffusion_moe as bdm  # noqa: E402
from paddle_tpu.observability import trace as obs_trace  # noqa: E402

TOL = 2e-4                          # float32 against float32 "highest"
MASK = 255


def ref_cfg(c):
    """The keys the reference reads, from the model's config."""
    return {"num_attention_heads": c.num_attention_heads,
            "num_key_value_heads": c.num_key_value_heads,
            "head_dim": c.head_dim, "num_hidden_layers": c.num_hidden_layers,
            "rms_norm_eps": c.rms_norm_eps, "rope_theta": c.rope_theta,
            "num_experts": c.num_experts,
            "num_experts_per_tok": c.num_experts_per_tok,
            "norm_topk_prob": True, "block_length": c.block_length,
            "mask_token_id": c.mask_token_id,
            "generation": {"denoising_steps": c.denoising_steps,
                           "remasking": c.remasking,
                           "confidence_threshold": c.confidence_threshold}}


def make(seed=7, head_std=0.3, **overrides):
    """A tiny model whose rows differ: every masked row of a block has the
    same embedding, so the branches (attention at sharp scores, experts)
    are drawn larger than a token's own row (drawn the other way round, a
    block's four rows predict one token and every confidence ties)."""
    cfg = bdm.tiny_block_diffusion_moe_config(**overrides)
    paddle_tpu.seed(seed)
    model = bdm.BlockDiffusionMoeForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed)
    for n, t in state_tensors(model).items():
        shape = t._value.shape
        if len(shape) >= 2:
            std = 0.1 if n.endswith("embed_tokens.weight") \
                else head_std if n.endswith("lm_head.weight") else 0.3
            t._value = jnp.asarray(rng.normal(0, std, shape), jnp.float32)
        elif n.endswith("q_norm.weight"):
            t._value = jnp.full(shape, 3.0, jnp.float32)
    return cfg, model, state_arrays(model)


@pytest.fixture(scope="module")
def tiny():
    return make(denoising_steps=2)


def tokens(n, seed=3):
    # never the mask id: a test that wants it in a prompt says so
    return np.random.default_rng(seed).integers(0, MASK, size=n).astype(
        np.int32)


def engine(model, kernel="jnp", **kw):
    geo = dict(max_slots=2, page_size=8, num_pages=41, max_pages_per_slot=8,
               steps_per_tick=4)
    geo.update(kw)
    return PagedKVEngine(model, kernel=kernel, **geo)


# -- the layers ------------------------------------------------------------

def test_logits_without_a_cache_match_the_reference(tiny):
    cfg, model, params = tiny
    ids = tokens(14)
    pos = jnp.arange(14)
    got = model(Tensor(jnp.asarray(ids[None])))._value[0]
    want = ref.forward(params, ref_cfg(cfg), ids, pos,
                       ref.block_causal(pos, 4))
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert float(jnp.std(want)) > 1.0          # not a comparison of zeros
    # the mask is an argument: a causal one gives the causal model, which
    # is another model (rows 0-2 of a block lose the keys after them)
    causal = jnp.tril(jnp.ones((14, 14), bool))
    got_c = model(Tensor(jnp.asarray(ids[None])),
                  attn_mask=causal[None])._value[0]
    want_c = ref.forward(params, ref_cfg(cfg), ids, pos, causal)
    assert float(jnp.max(jnp.abs(got_c - want_c))) < TOL
    assert float(jnp.max(jnp.abs(got_c - got))) > 0.1


def _dense(q, k, v, seen):
    """q (b, s, hq, d), k / v (b, hk, L, d), seen (b, s, L) -> (b, s,
    hq * d): softmax attention over the columns `seen` marks."""
    b, s, hq, d = q.shape
    hk = k.shape[1]
    qg = q.reshape(b, s, hk, hq // hk, d).astype(jnp.float32)
    sc = jnp.einsum("bshgd,bhcd->bhgsc", qg, k.astype(jnp.float32)) \
        / np.sqrt(d)
    sc = jnp.where(seen[:, None, None], sc, -1e30)
    out = jnp.einsum("bhgsc,bhcd->bshgd", jax.nn.softmax(sc, -1),
                     v.astype(jnp.float32))
    return out.reshape(b, s, hq * d)


@pytest.mark.parametrize("q_pos,s,block", [(0, 16, 4), (8, 8, 4),
                                           (24, 16, 8), (4, 8, 2)])
def test_chunk_kernel_under_the_mask_by_blocks_equals_dense_attention(
        q_pos, s, block):
    rng = np.random.default_rng(q_pos + s)
    b, hq, hk, d, length = 2, 4, 2, 32, 48
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hk, length, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hk, length, d)), jnp.float32)
    at = jnp.asarray([q_pos, q_pos + block], jnp.int32)
    got = chunk_attention(q, k, v, at, jnp.zeros(b, jnp.int32), block=block,
                          interpret=True)
    pos = at[:, None] + jnp.arange(s)[None]
    seen = jnp.arange(length)[None, None] \
        < ((pos // block + 1) * block)[:, :, None]
    assert float(jnp.max(jnp.abs(got - _dense(q, k, v, seen)))) < 1e-5
    # and it is not the causal kernel
    causal = chunk_attention(q, k, v, at, jnp.zeros(b, jnp.int32),
                             interpret=True)
    assert float(jnp.max(jnp.abs(got - causal))) > 1e-2
    with pytest.raises(ValueError, match="window inside block-causal"):
        chunk_attention(q, k, v, at, at, block=block, window=8,
                        interpret=True)


def test_a_blocks_rows_ride_the_decode_kernel_beside_their_heads():
    """`paged_attention_update(block=)` with s == block: on the Pallas
    scope the rows fold into their kv head's group and the decode kernel's
    length mask is the whole mask; the jnp path masks by blocks; both write
    the rows over what the pages held and agree."""
    rng = np.random.default_rng(5)
    b, blk, hq, hk, d, page, mp = 3, 4, 4, 2, 32, 8, 4
    lens = jnp.asarray([8, 0, 20], jnp.int32)
    bt = jnp.asarray(np.arange(1, b * mp + 1).reshape(b, mp), jnp.int32)
    pools = [jnp.asarray(rng.standard_normal((b * mp + 1, hk, page, d)),
                         jnp.float32) for _ in range(2)]
    q = jnp.asarray(rng.standard_normal((b, blk, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, blk, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, blk, hk, d)), jnp.float32)
    state = PagedState(bt, lens, jnp.asarray([blk, blk, 0], jnp.int32))
    folded = paged._fold_rows(q, hk)
    assert folded.shape == (b, hq * blk, d)
    assert jnp.array_equal(paged._unfold_rows(folded, blk, hk),
                           q.reshape(b, blk, hq * d))
    out_j, (kj, vj) = paged.paged_attention_update(q, k, v, pools, state,
                                                   block=blk)
    with paged.decode_kernel_scope("pallas", interpret=True):
        rows = [p.reshape(pk.pool_rows_shape(b * mp + 1, hk, d, page,
                                             p.dtype)) for p in pools]
        out_p, (kp, vp) = paged.paged_attention_update(q, k, v, rows, state,
                                                       block=blk)
    assert float(jnp.max(jnp.abs(out_j._value - out_p._value))) < 1e-5
    assert jnp.array_equal(kj._value, kp._value.reshape(kj.shape))
    # the live slots' rows landed at lens .. lens + 3, the dead slot's not
    got = pk.pages_by_head(kj._value[bt[0]], hk, d)     # (mp, hk, page, d)
    assert jnp.array_equal(got[1, :, :blk], jnp.swapaxes(k[0], 0, 1))
    assert jnp.array_equal(kj._value[bt[2]], pools[0][bt[2]])
    # every row of slot 0 attends over 12 keys, its own block's included
    kk, vv = (jnp.moveaxis(pk.pages_by_head(p._value[bt], hk, d), 2, 1)
              .reshape(b, hk, mp * page, d) for p in (kj, vj))
    seen = jnp.broadcast_to(jnp.arange(mp * page)[None, None]
                            < (lens + blk)[:, None, None],
                            (b, blk, mp * page))
    assert float(jnp.max(jnp.abs(out_j._value - _dense(q, kk, vv, seen))
                         [:2])) < 1e-5
    with pytest.raises(ValueError, match="plain .k_pool, v_pool. cache"):
        paged.paged_attention_update(q, k, v, pools, state, block=blk,
                                     window=8)


def test_the_unmasking_rules_pick_as_the_references_do():
    rng = np.random.default_rng(11)
    conf = jnp.asarray(rng.uniform(0, 1, (64, 4)), jnp.float32)
    conf = conf.at[:8, 1].set(conf[:8, 2])          # ties
    masked = jnp.asarray(rng.uniform(0, 1, (64, 4)) < 0.7)
    for strategy in bdm.REMASKING:
        for n in (1, 2, 4):
            got = bdm.unmask(conf, masked, n, strategy, 0.6)
            want = ref.unmasked(conf, masked, n, strategy, 0.6)
            assert jnp.array_equal(got, want), (strategy, n)
            assert not jnp.any(got & ~masked)
            took = jnp.sum(got, -1)
            least = jnp.minimum(jnp.sum(masked, -1), n)
            assert jnp.all(took >= least)
            if strategy != "low_confidence_dynamic":
                assert jnp.array_equal(took, least)
    lg = jnp.asarray(rng.standard_normal((5, 4, 256)) * 3, jnp.float32)
    best, prob = bdm.confidence(lg)
    best_r, prob_r = ref._confidence(lg)
    assert jnp.array_equal(best, best_r)
    assert float(jnp.max(jnp.abs(prob - prob_r))) < 1e-6
    assert bdm.REMASKING == ref.STRATEGIES


# -- the engine against the free-running loop --------------------------------

@pytest.mark.parametrize("kernel", ["jnp", "pallas"])
@pytest.mark.parametrize("prompt_len,new", [(8, 10), (9, 7), (10, 12),
                                            (11, 5), (3, 6)])
def test_engine_generates_the_references_tokens(tiny, kernel, prompt_len,
                                                new):
    """Prefill of the prompt's whole blocks, then block ticks through the
    paged cache: every P mod 4 (the prompt's last tokens open the first
    block and are never emitted; 3: no whole block at all), and budgets
    that cut a last block."""
    cfg, model, params = tiny
    prompt = tokens(prompt_len, seed=prompt_len)
    eng = engine(model, kernel)
    got = eng.generate([prompt], max_new_tokens=new)[0]
    assert len(got) == new
    assert got == ref.generate(params, ref_cfg(cfg), prompt, new)
    assert eng.stats["tokens_out"] == new
    blocks = -(-(prompt_len % 4 + new) // 4)
    assert eng.stats["blocks_done"] == eng.stats["block_forwards_store"] \
        == blocks
    assert eng.stats["block_positions_unmasked"] == 4 * blocks \
        - prompt_len % 4


def test_slots_at_different_phases_through_chained_ticks(tiny):
    """Five requests over three slots: admitted and retired in mid-run, the
    survivors' ticks launched from the carry of the tick before; each
    request's tokens are the reference's, whatever ran beside it."""
    cfg, model, params = tiny
    work = [(tokens(p, seed=p), n) for p, n in
            [(13, 25), (8, 6), (18, 14), (7, 11), (12, 3)]]
    eng = engine(model, max_slots=3, num_pages=49)
    reqs = [eng.submit(p, n) for p, n in work]
    eng.run_until_idle()
    for (prompt, new), req in zip(work, reqs):
        assert req.result() == ref.generate(params, ref_cfg(cfg), prompt,
                                            new), (len(prompt), new)
    assert eng.stats["ticks_chained"] > 0
    assert eng.stats["finished"] == 5 and not any(eng._slots)
    assert len(eng._free) == 48 and eng._reserved_unalloc == 0
    # two blocks a tick deliver eight tokens a slot and change no token
    eng8 = engine(model, max_slots=3, num_pages=49, steps_per_tick=8)
    reqs8 = [eng8.submit(p, n) for p, n in work]
    eng8.run_until_idle()
    assert [r.result() for r in reqs8] == [r.result() for r in reqs]
    assert eng8.stats["ticks"] < eng.stats["ticks"]


@pytest.mark.parametrize("steps,remasking", [
    (1, "low_confidence_static"), (4, "low_confidence_static"),
    (2, "sequential"), (4, "sequential")])
def test_schedules_and_rules_generate_the_references_tokens(steps,
                                                            remasking):
    cfg, model, params = make(denoising_steps=steps, remasking=remasking)
    prompt = tokens(10, seed=steps)
    eng = engine(model)
    got = eng.generate([prompt], max_new_tokens=13)[0]
    assert got == ref.generate(params, ref_cfg(cfg), prompt, 13)
    # a slot sits a step out only when nothing is masked: never here but
    # in the first block, which two prompt tokens open, so that its two
    # masked positions take 2 / (4 / steps) steps
    blocks = eng.stats["blocks_done"]
    assert blocks == 4
    assert eng.stats["block_forwards_denoise"] == steps * blocks - (
        steps - -(-2 * steps // 4))


def test_the_dynamic_rule_unmasks_what_clears_the_threshold():
    """A head drawn so sharp that some steps' confidences clear the
    threshold and some do not: blocks settle in fewer forwards than the
    static schedule's, and in more than one."""
    cfg, model, params = make(head_std=2.5, denoising_steps=4,
                              remasking="low_confidence_dynamic",
                              confidence_threshold=0.9)
    prompt = tokens(12, seed=2)
    eng = engine(model)
    got = eng.generate([prompt], max_new_tokens=24)[0]
    assert got == ref.generate(params, ref_cfg(cfg), prompt, 24)
    blocks, forwards = eng.stats["blocks_done"], \
        eng.stats["block_forwards_denoise"]
    assert blocks == 6 and blocks < forwards < 4 * blocks, forwards
    assert eng.stats["block_positions_unmasked"] == 24
    # the static schedule of the same model generates other tokens
    cfg_s, model_s, _ = make(head_std=2.5, denoising_steps=4)
    assert engine(model_s).generate([prompt], max_new_tokens=24)[0] != got


def test_a_prompt_may_hold_the_mask_id(tiny):
    """Which positions are masked is carried as booleans: a prompt whose
    tokens, the ones that open the first block among them, are the mask
    id generates what the reference does."""
    cfg, model, params = tiny
    prompt = tokens(10, seed=4)
    prompt[[2, 5, 9]] = MASK
    got = engine(model).generate([prompt], max_new_tokens=9)[0]
    assert got == ref.generate(params, ref_cfg(cfg), prompt, 9)


def test_eos_ends_a_request_inside_a_block(tiny):
    cfg, model, params = tiny
    prompt = tokens(9, seed=9)
    free = ref.generate(params, ref_cfg(cfg), prompt, 12)
    eos = free[5]
    cut = free[:free.index(eos) + 1]
    eng = engine(model)
    assert eng.generate([prompt], max_new_tokens=12,
                        eos_token_id=eos)[0] == cut
    assert eng.stats["tokens_out"] == len(cut) and not any(eng._slots)


def test_the_replay_gives_the_rows_of_the_free_run(tiny):
    """`logits()` as `benchmarks/serve.py` reads it: row position - 1 of
    the teacher-forced replay is the row that chose the token at that
    position in the free run, for a prompt of whole blocks by default and
    for any prompt when told where it ends."""
    cfg, model, params = tiny
    for plen in (8, 10):
        prompt = tokens(plen, seed=plen)
        rows = {}
        got = ref.generate(params, ref_cfg(cfg), prompt, 11, rows=rows)
        ids = np.concatenate([prompt, got[:-1]]).astype(np.int32)
        replayed = ref.logits(params, ref_cfg(cfg), ids,
                              prompt_tokens=None if plen % 4 == 0 else plen)
        assert replayed.shape == (len(ids), cfg.vocab_size)
        for i, tok in enumerate(got):
            row = replayed[plen - 1 + i]
            assert float(jnp.max(jnp.abs(row - rows[plen + i]))) < TOL
            assert int(jnp.argmax(row)) == tok
    # what serve.check_against_reference makes of it: no gap at all
    lg = np.asarray(replayed[plen - 1:], np.float64)
    assert np.all(lg.max(-1) == lg[np.arange(len(got)), got])


# -- planted faults: each must fail the comparison above ---------------------

def _causal_where(monkeypatch, rows_hit):
    real = paged.paged_attention_update

    def causal(q, k, v, cache, state, block=None, **kw):
        if rows_hit(q.shape[1], block):
            block = None
        return real(q, k, v, cache, state, block=block, **kw)
    monkeypatch.setattr(paged, "paged_attention_update", causal)


def _causal_inside_the_block(monkeypatch):
    _causal_where(monkeypatch, lambda s, b: s == b)


def _causal_prefill(monkeypatch):
    _causal_where(monkeypatch, lambda s, b: s > b)


def _block_forward_where(monkeypatch, live_in):
    """`_block_forward` with the rows that write decided by `live_in(which
    trace of the tick this is, ids, rows_live)`: the first trace is the
    denoising step's, the second the storing forward's."""
    real = PagedKVEngine._block_forward
    traced = []

    def forward(self, ids, lens, rows_live, bt, flat):
        traced.append(1)
        return real(self, ids, lens, live_in(len(traced), ids, rows_live),
                    bt, flat)
    monkeypatch.setattr(PagedKVEngine, "_block_forward", forward)
    return traced


def _store_forward_left_out(monkeypatch):
    return _block_forward_where(
        monkeypatch, lambda nth, ids, live:
        jnp.zeros_like(live) if nth == 2 else live)


def _block_not_rewritten_between_steps(monkeypatch):
    # a step writes only while the whole block is masked: the first
    return _block_forward_where(
        monkeypatch, lambda nth, ids, live:
        live & jnp.all(ids == MASK, -1) if nth == 1 else live)


def _unmasked_position_chosen_again(monkeypatch):
    real = bdm.unmask
    monkeypatch.setattr(
        bdm, "unmask", lambda conf, masked, *how:
        real(conf, jnp.ones_like(masked), *how))


def _partial_block_treated_as_final(monkeypatch):
    monkeypatch.setattr(PagedKVEngine, "_prefill_len",
                        lambda self, req: int(req.prompt.size))


def _confidence_from_the_shifted_position(monkeypatch):
    real = bdm.confidence
    monkeypatch.setattr(bdm, "confidence",
                        lambda lg: real(jnp.roll(lg, 1, axis=-2)))


@pytest.mark.parametrize("plant,prompt_len", [
    (_causal_inside_the_block, 8), (_store_forward_left_out, 8),
    (_block_not_rewritten_between_steps, 8),
    (_unmasked_position_chosen_again, 8), (_causal_prefill, 8),
    (_partial_block_treated_as_final, 10),
    (_confidence_from_the_shifted_position, 8)],
    ids=lambda p: getattr(p, "__name__", str(p)).lstrip("_"))
def test_a_planted_fault_fails_the_comparison(tiny, monkeypatch, plant,
                                              prompt_len):
    cfg, model, params = tiny
    prompt = tokens(prompt_len, seed=prompt_len)
    want = ref.generate(params, ref_cfg(cfg), prompt, 12)
    assert engine(model).generate([prompt], max_new_tokens=12)[0] == want
    traced = plant(monkeypatch)
    got = engine(model).generate([prompt], max_new_tokens=12)[0]
    assert got != want
    if traced is not None:      # the plant found the forward it meant
        assert len(traced) == 2


# -- what the engine learns from the model, and what it refuses --------------

def test_engine_learns_the_block_from_the_config(tiny):
    cfg, model, _ = tiny
    eng = engine(model, "pallas")
    assert eng.block_length == 4 and eng.decode_kernel == "pallas"
    # the decode kernel sees a block's rows beside the heads: 2 query
    # heads a kv head x 4 rows
    assert eng.decode_plan == pk.decode_plan(16, 2, 32, 8, 8, "float32",
                                             slots=2)
    for k in ("block_forwards_denoise", "block_forwards_store",
              "block_positions_unmasked", "blocks_done"):
        assert eng.stats[k] == 0
    src = open(paged.__file__).read()
    for k in ("block_forwards_denoise", "block_forwards_store",
              "block_positions_unmasked", "blocks_done"):
        assert f'"{k}": 0' in src
    with observability.scoped():
        eng.generate([tokens(9)], max_new_tokens=6)
        ticks = [s for s in obs_trace.spans() if s.name == "engine.tick"]
    assert ticks and all(s.attrs["blocks"] == 1 for s in ticks)
    assert eng.stats["moe_layer_steps"] == cfg.num_hidden_layers * (
        eng.stats["ticks"] * 3)
    # a one-token engine has neither the counters nor the attribute
    from paddle_tpu.models.llama import LlamaForCausalLM, tiny_llama_config
    plain = PagedKVEngine(LlamaForCausalLM(tiny_llama_config()),
                          max_slots=2, page_size=8, num_pages=17)
    assert plain.block_length == 0 and "blocks_done" not in plain.stats
    assert "blocks" in obs_trace.SPANS["engine.tick"][1]


@pytest.mark.parametrize("kw,named", [
    ({"prefix_cache_pages": 4}, "prefix_cache_pages"),
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"prefix_cache_pages": 4, "host_tier_bytes": 1 << 20},
     "host_tier_bytes"),
    ({"role": "decode"}, "role='decode'"),
    ({"draft_model": True}, "draft_model"),
    ({"steps_per_tick": 6}, "steps_per_tick"),
    ({"page_size": 6}, "page_size"),
    ({"prefill_chunk": 10}, "prefill_chunk")])
def test_what_assumes_a_token_a_step_is_refused_by_name(tiny, kw, named):
    _cfg, model, _ = tiny
    if kw.get("draft_model"):
        kw = dict(kw, draft_model=model)
    with pytest.raises(ValueError, match="generates by blocks|block_length"
                       ) as e:
        engine(model, **kw)
    assert named in str(e.value)


def test_sampling_and_a_dense_cache_are_refused_with_the_reason(tiny):
    _cfg, model, _ = tiny
    eng = engine(model)
    with pytest.raises(ValueError, match="do_sample is not carried"):
        eng.submit(tokens(8), 4, do_sample=True)
    with pytest.raises(NotImplementedError, match="cached in pages only"):
        model(Tensor(jnp.zeros((1, 4), jnp.int32)),
              caches=[(Tensor(jnp.zeros((1, 8, 2, 32))),) * 2] * 2,
              cache_index=0)
    with pytest.raises(NotImplementedError, match="no noise schedule"):
        model(Tensor(jnp.zeros((1, 4), jnp.int32)),
              labels=Tensor(jnp.zeros((1, 4), jnp.int32)))
    with pytest.raises(ValueError, match="denoising_steps must divide"):
        bdm.tiny_block_diffusion_moe_config(denoising_steps=3)
    with pytest.raises(ValueError, match="remasking must be one of"):
        bdm.tiny_block_diffusion_moe_config(remasking="random")
    with pytest.raises(ValueError, match="is no id of a vocabulary"):
        bdm.tiny_block_diffusion_moe_config(mask_token_id=256)


def test_the_layers_are_the_ones_the_key_selection_model_uses(tiny):
    """Shared, not copied: the projections with their q/k norms, the block
    around them and the expert layer are `sparse_attn_moe`'s; there is no
    indexer."""
    from paddle_tpu.models import sparse_attn_moe as sam
    from paddle_tpu.nn.layer.moe import MoEMLP
    _cfg, model, params = tiny
    layer = model.model.layers[0]
    assert isinstance(layer, sam.SparseAttnMoeDecoderLayer)
    assert isinstance(layer.self_attn, sam.NormedGQA)
    assert isinstance(layer.mlp, MoEMLP) and layer.mlp.dropless
    assert not any("indexer" in n for n in params)
    assert issubclass(sam.IndexedAttention, sam.NormedGQA)


def test_the_configuration_file_holds_the_published_keys():
    import json
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sdar-30b-a3b-chat-7l.json")) as f:
        cfg = json.load(f)
    assert cfg["builder"] == "block_diffusion_moe"
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["vocab_size"],
            cfg["rope_theta"], cfg["max_position_embeddings"]) == (
                2048, 32, 4, 128, 128, 8, 768, 151936, 1000000, 32768)
    assert set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["reduced"]["num_hidden_layers"]["from"] == 48 \
        and cfg["num_hidden_layers"] == 7
    assert cfg["block_length"] == 4 and cfg["mask_token_id"] == 151669
    for key in ("block_length", "mask_token_id", "generation", "mask",
                "qk_norm", "initializer_range"):
        assert key in cfg["assumed"]
    assert "pipeline" in cfg["deployment"] and cfg["draw"]["why"]
