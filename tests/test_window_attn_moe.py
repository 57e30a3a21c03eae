"""The decoder of window and full attention layers with gated attention and
sigmoid-routed experts of which a share is held (models/window_attn_moe.py)
against its plain reference (benchmarks/reference_window_attn_moe.py), small,
float32, on the CPU: the model's logits with and without a cache; prefill
(one program and in chunks) then paged decode under the two page tables, with
contexts that start under the window, cross it and run until every ring has
wrapped twice; the Pallas paths in interpret mode; the eight shares of an
expert layer adding up to the uncut layer; what a slot holds of each table;
six planted faults that the same comparison must refuse; the degenerate
groups; and what the engine learns from the model and what it refuses.
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
from benchmarks import reference_window_attn_moe as ref  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.inference import PagedKVEngine, paged  # noqa: E402
from paddle_tpu.inference.paged import PagedState, ring_pages_for  # noqa: E402
from paddle_tpu.jit.functional import state_arrays  # noqa: E402
from paddle_tpu.kernels.moe_experts import (  # noqa: E402
    experts_hit, held_ids, moe_experts_decode)
from paddle_tpu.models import window_attn_moe as wam  # noqa: E402
from paddle_tpu.models.window_attn_moe import (  # noqa: E402
    FULL, WINDOW, WindowAttnMoeForCausalLM, tiny_window_attn_moe_config)
from paddle_tpu.nn.functional import moe as FM  # noqa: E402
from paddle_tpu.nn.layer.moe import MoEMLP  # noqa: E402

PAGE, CHUNK = 4, 8
TOL = 2e-4                  # float32 against float32 "highest"
HONEST_SD = 2e-3            # the serve comparison on an honest program
FAULT_SD = 0.02             # ... and the least a planted fault must read


def ref_cfg(c):
    """The published keys the reference reads, from the model's config."""
    first, count = c.held_experts or (0, c.num_experts)
    return {"hidden_size": c.hidden_size,
            "num_attention_heads": c.num_attention_heads,
            "num_key_value_heads": c.num_key_value_heads,
            "head_dim": c.head_dim, "num_hidden_layers": c.num_hidden_layers,
            "num_dense_layers": c.num_dense_layers,
            "layer_types": list(c.layer_types),
            "sliding_window": c.sliding_window,
            "rms_norm_eps": c.rms_norm_eps, "rope_theta": c.rope_theta,
            "mup_enabled": c.mup_enabled, "num_experts": count,
            "held_experts_first": first, "router_experts": c.num_experts,
            "num_experts_per_tok": c.num_experts_per_tok,
            "route_norm": c.route_norm, "route_scale": c.route_scale}


def make(**overrides):
    """A tiny model holding experts 2-5 of 8, its routing biases drawn
    (at 0 choosing by score + bias and weighing by score are one thing)
    and its q-norm weight 2 (attention that leans on few keys, so that
    what a window leaves out shows)."""
    cfg = tiny_window_attn_moe_config(**{"held_experts": (2, 4), **overrides})
    paddle_tpu.seed(7)
    model = WindowAttnMoeForCausalLM(cfg)
    model.eval()
    key = jax.random.key(11)
    for i, (name, t) in enumerate(sorted(model.named_parameters())):
        if name.endswith("expert_bias"):
            t._value = 0.2 * jax.random.normal(jax.random.fold_in(key, i),
                                               t.shape)
        elif name.endswith("q_norm.weight"):
            t._value = jnp.full(t.shape, 2.0)
    return cfg, model, state_arrays(model)


@pytest.fixture(scope="module")
def tiny():
    return make()


def engine(model, kernel="jnp", chunk=CHUNK, page=PAGE, **kw):
    args = dict(max_slots=3, page_size=page, num_pages=64,
                max_pages_per_slot=20, steps_per_tick=4, kernel=kernel,
                prefill_chunk=chunk)
    args.update(kw)
    return PagedKVEngine(model, **args)


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, size=n).astype(np.int32) for n in lengths]


def serve_gap(eng, params, cfg, lengths, new):
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


# -- the model against the reference -------------------------------------

def test_logits_without_a_cache_match_the_reference(tiny):
    cfg, model, params = tiny
    ids = prompts_of([40])[0]
    got = np.asarray(model(paddle_tpu.to_tensor(ids[None]))._value)[0]
    want = np.asarray(ref.logits(params, ref_cfg(cfg), jnp.asarray(ids)))
    assert np.abs(got - want).max() < TOL
    # the positions differ: a check that compares tokens has something
    # to see
    assert len(set(want.argmax(-1).tolist())) > 10


def test_logits_with_a_cache_match_the_reference(tiny):
    """The cached forward by hand: a 13-token prefill under two tables,
    then one token a call until the 6-page ring has wrapped twice."""
    cfg, model, params = tiny
    ring = ring_pages_for(cfg.sliding_window, 13, PAGE)
    assert ring == 6
    ids = prompts_of([64], seed=3)[0]
    want = np.asarray(ref.logits(params, ref_cfg(cfg), jnp.asarray(ids)))
    shape = (32, cfg.num_key_value_heads, PAGE, cfg.head_dim)
    flat = [jnp.zeros(shape) for _ in range(2 * cfg.num_hidden_layers)]
    bt = jnp.arange(1, 19, dtype=jnp.int32)[None]           # 18 pages
    rt = jnp.arange(20, 20 + ring, dtype=jnp.int32)[None]

    def call(tokens, at, flat):
        n = tokens.shape[0]
        state = PagedState(bt, at[None], jnp.full((1,), n, jnp.int32), rt)
        pos = Tensor((at + jnp.arange(n, dtype=jnp.int32))[None])
        caches = [(Tensor(flat[2 * i]), Tensor(flat[2 * i + 1]))
                  for i in range(cfg.num_hidden_layers)]
        logits, new, counted = model(
            Tensor(tokens[None]), position_ids=pos, caches=caches,
            cache_index=state, with_counters=True)
        return (logits._value[0, -1], [a._value for kv in new for a in kv],
                {k: jnp.asarray(v) for k, v in counted.items()})
    logits, flat, counted = call(jnp.asarray(ids[:13]), jnp.int32(0), flat)
    assert np.abs(np.asarray(logits) - want[12]).max() < TOL
    assert int(counted["moe_layer_steps"]) == 3
    assert int(counted["moe_pairs_routed"]) == 13 * 2 * 3
    step = jax.jit(call)
    for t in range(13, 64):
        logits, flat, counted = step(jnp.asarray(ids[t:t + 1]),
                                     jnp.int32(t), flat)
        assert np.abs(np.asarray(logits) - want[t]).max() < TOL, t
    assert 0 <= int(counted["moe_pairs_held"]) <= 2 * 3
    assert int(counted["moe_experts_hit"]) <= int(counted["moe_pairs_held"])


# -- through the engine ---------------------------------------------------

@pytest.mark.parametrize("chunk", [CHUNK, None],
                         ids=["chunked", "one-program"])
def test_engine_prefill_then_decode_matches_the_reference(tiny, chunk):
    """Contexts that start under the window (3), at it (9) and over it
    (17, 30), each run 40 tokens on: with 8-token chunks a ring is 5 pages
    of 4, so the longest context goes round it three times; in one program
    (the engine's own limit is far over these prompts) the ring is capped
    at a slot's 20 pages and never wraps."""
    cfg, model, params = tiny
    eng = engine(model, chunk=chunk)
    assert eng._ring.pages_per_slot == (5 if chunk else 20)
    assert eng._ring.layers == [0, 1, 2] and eng._full.layers == [3]
    assert serve_gap(eng, params, cfg, (3, 9, 30, 17), 40) < HONEST_SD
    s = eng.stats
    assert s["decode_slot_steps"] == 4 * 39
    # contexts 3.., 9.., 17.., 30..: a step's keys outgrow 8 from the 6th
    # token of the first on
    assert s["window_engaged_steps"] == 4 * 39 - 5
    assert s["kv_tokens_held"] < s["kv_tokens_flat"]
    assert s["moe_layer_steps"] == 3 * 4 * s["ticks"]
    assert s["moe_pairs_routed"] == 3 * 2 * s["moe_layer_steps"]
    assert 0 < s["moe_pairs_held"] < s["moe_pairs_routed"]
    assert s["moe_experts_hit"] <= s["moe_pairs_held"]
    # every page of both tables is back
    assert len(eng._free) == eng.num_pages - 1
    assert len(eng._ring.free) == eng._ring.num_pages - 1


def test_pallas_paths_in_interpret_mode_match_the_jnp_path():
    """The decode kernel over a ring's view and the Pallas write into a
    ring, interpreted, against the reference and the jnp engine's tokens:
    heads of 128 (a view rides the kernel as a key selection does: its
    score columns have to be tokens in order), 3 query heads a kv head."""
    cfg, model, params = make(head_dim=128, num_attention_heads=6)
    outs = []
    for kernel in ("pallas", "jnp"):
        eng = engine(model, kernel=kernel, page=8)
        assert eng.decode_kernel == kernel
        assert eng._ring.pages_per_slot == 3
        if kernel == "pallas":
            # a prefill's attention goes through the chunk kernel here
            assert eng._chunk_kernel and eng._prefill_limit(1) == 8
            assert serve_gap(eng, params, cfg, (5, 21), 30) < HONEST_SD
            assert eng.stats["kv_write_kernel_ticks"] == eng.stats["ticks"]
        else:
            assert not eng._chunk_kernel
        outs.append(eng.generate(prompts_of((5, 21)), max_new_tokens=30))
    assert outs[0] == outs[1]


def test_a_geometry_whose_view_cannot_ride_the_kernel_is_refused(tiny):
    _cfg, model, _params = tiny
    with pytest.raises(ValueError, match="head_dim % 128"):
        engine(model, kernel="pallas", page=8)


def test_a_slot_holds_its_ring_and_no_more_and_gives_both_tables_back(tiny):
    _cfg, model, _params = tiny
    eng = engine(model)
    ring = eng._ring
    assert ring.pages_per_slot == 5 and ring.num_pages == 3 * 5 + 1
    assert ring.window == 8 and eng._full.window == 0
    reqs = [eng.submit(p, max_new_tokens=n)
            for p, n in zip(prompts_of((30, 3)), (40, 8))]
    seen = set()
    while eng.has_work():
        eng.step()
        for i, slot in enumerate(eng._slots):
            if slot is None:
                assert ring.held(i) == 0 and not eng._bt[i].any()
                continue
            # a ring is never grown past itself; the full table follows
            # the context
            assert -(-min(slot.lens, 20) // PAGE) <= ring.held(i) <= 5
            assert len(slot.pages) >= -(-slot.lens // PAGE)
            seen.add(ring.held(i))
    assert 5 in seen and min(seen) < 5
    assert [len(r.result()) for r in reqs] == [40, 8]
    assert len(eng._free) == eng.num_pages - 1
    assert len(ring.free) == ring.num_pages - 1
    assert not ring.bt.any() and not eng._bt.any()
    # bytes a slot pins: 18 pages would be 20 x 4 layers under one table
    k_and_v = 2 * PAGE * 2 * 32 * 4
    assert eng.kv_bytes_per_slot() == (20 * 1 + 5 * 3) * k_and_v
    # the engine's public account of its two tables, from the real pools
    full, rings = eng.page_groups()
    assert full == {"layers": [3], "window": 0, "pool_pages": eng.num_pages,
                    "pages_per_slot": 20,
                    "pool_bytes": eng.num_pages * k_and_v}
    assert rings == {"layers": [0, 1, 2], "window": 8, "pool_pages": 16,
                     "pages_per_slot": 5, "pool_bytes": 3 * 16 * k_and_v}


@pytest.mark.parametrize("b,s,hq,hk,d,keys,window,q_pos,k_pos", [
    (2, 16, 6, 2, 128, 700, 0, [5, 300], [0, 0]),
    (1, 32, 4, 2, 32, 96, 24, [40], [16]),
    (2, 64, 12, 2, 128, 1200, 512, [0, 900], [0, 300]),
    (1, 8, 2, 2, 64, 40, 8, [3], [0])],
    ids=["causal", "window", "window-blocks-skipped", "one-tile"])
def test_chunk_kernel_matches_dense_attention_under_the_position_rule(
        b, s, hq, hk, d, keys, window, q_pos, k_pos):
    """kernels/prefill_attention.py, interpreted: queries at q_pos + j over
    key columns at k_pos + c, causal and inside the window."""
    from paddle_tpu.kernels.prefill_attention import chunk_attention
    key = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hq, d))
    k = jax.random.normal(jax.random.fold_in(key, 2), (b, hk, keys, d))
    v = jax.random.normal(jax.random.fold_in(key, 3), (b, hk, keys, d))
    qp, kp = jnp.asarray(q_pos, jnp.int32), jnp.asarray(k_pos, jnp.int32)
    at = qp[:, None] + jnp.arange(s)[None]
    col = kp[:, None] + jnp.arange(keys)[None]
    seen = col[:, None, :] <= at[:, :, None]
    if window:
        seen &= col[:, None, :] > at[:, :, None] - window
    with jax.default_matmul_precision("highest"):
        got = chunk_attention(q, k, v, qp, kp, window=window, interpret=True)
        sc = jnp.einsum("bshgd,bhld->bhgsl", q.reshape(b, s, hk, hq // hk, d),
                        k) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen[:, None, None], sc, -1e30), -1)
        want = jnp.einsum("bhgsl,bhld->bshgd", p * seen[:, None, None],
                          v).reshape(b, s, hq * d)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# -- the shares add up ----------------------------------------------------

def test_the_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """An expert layer of 16 experts cut eight ways: the routed parts that
    the eight shares give (the program's `MoEMLP(held=)`, each routing
    over all 16 and computing its own 2), plus the shared expert once,
    equal the reference's uncut layer; and each share equals the
    reference's account of that share."""
    cfg, model, params = make(num_experts=16, held_experts=None,
                              num_hidden_layers=2, num_dense_layers=1,
                              layer_types=[WINDOW, FULL])
    block = model.model.layers[1].mlp
    whole = block.moe
    p = "model.layers.1.mlp."
    rc = ref_cfg(cfg)
    f32 = ref._Params(params)
    x = jax.random.normal(jax.random.key(5), (24, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.expert_layer(x, f32, p, rc, held=(0, 16)))
        shared = np.asarray(ref._swiglu(x, f32, p + "shared_expert.", ref._keep))
        total = np.zeros_like(want)
        for r in range(8):
            share = MoEMLP(cfg.hidden_size, cfg.moe_intermediate_size, 16,
                           top_k=cfg.num_experts_per_tok, dropless=True,
                           score_func="sigmoid", route_scale=cfg.route_scale,
                           expert_bias=True, held=(2 * r, 2))
            share.eval()
            share.router_weight._value = whole.router_weight._value
            share.expert_bias._value = whole.expert_bias._value
            for name in ("experts_gate_weight", "experts_up_weight",
                         "experts_down_weight"):
                getattr(share, name)._value = \
                    getattr(whole, name)._value[2 * r:2 * r + 2]
            part, hit = share(Tensor(x), with_hit=True)
            part = np.asarray(part._value)
            sub = {p + "moe." + n: getattr(share, n)._value
                   for n in ("router_weight", "expert_bias",
                             "experts_gate_weight", "experts_up_weight",
                             "experts_down_weight")}
            mine = np.asarray(ref.routed(x, ref._Params(sub), p + "moe.", rc,
                                         (2 * r, 2)))
            assert np.abs(part - mine).max() < TOL
            assert 0 <= int(hit._value[0]) <= 2
            total += part
        got_whole = np.asarray(block(Tensor(x))._value)
    assert np.abs(total + shared - want).max() < TOL
    assert np.abs(got_whole - want).max() < TOL
    assert np.abs(total).max() > 10 * TOL


def test_router_options_choose_by_score_plus_bias_and_weigh_by_score():
    logits = jax.random.normal(jax.random.key(0), (64, 16))
    bias = 0.5 * jax.random.normal(jax.random.key(1), (16,))
    idx, gates, _aux = FM.topk_gating_dropless(
        logits, 4, score_func="sigmoid", bias=bias, route_norm=True,
        route_scale=2.448)
    s = np.asarray(jax.nn.sigmoid(logits))
    order = np.argsort(-(s + np.asarray(bias)), axis=-1, kind="stable")[:, :4]
    assert (np.sort(np.asarray(idx), -1) == np.sort(order, -1)).all()
    chosen = np.take_along_axis(s, np.asarray(idx), -1)
    want = 2.448 * chosen / chosen.sum(-1, keepdims=True)
    assert np.abs(np.asarray(gates) - want).max() < 1e-6
    # the bias moved some choice, and weighs nothing
    plain = np.argsort(-s, axis=-1, kind="stable")[:, :4]
    assert (np.sort(plain, -1) != np.sort(order, -1)).any()
    # unnormalised and unscaled: the scores themselves
    _i, raw, _a = FM.topk_gating_dropless(logits, 4, score_func="sigmoid",
                                          route_norm=False)
    assert np.abs(np.sort(np.asarray(raw), -1)
                  - np.sort(np.take_along_axis(s, plain, -1), -1)).max() < 1e-6
    # ties go to the lower index
    idx, _g, _a = FM.topk_gating_dropless(jnp.zeros((2, 8)), 3,
                                          score_func="sigmoid")
    assert np.asarray(idx).tolist() == [[0, 1, 2]] * 2
    with pytest.raises(NotImplementedError, match="softmax router"):
        FM.topk_gating_dropless(logits, 4, route_scale=2.0)


def test_the_few_rows_kernel_at_a_share_names_no_expert_held_elsewhere():
    """Interpreted: rows whose choices are partly or wholly elsewhere; the
    kernel equals the grouped path, a row held wholly elsewhere gets
    zeros, and only held experts count as hit."""
    key = jax.random.key(2)
    x = jax.random.normal(key, (5, 128))
    wg, wu = (0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                      (4, 128, 256)) for i in (1, 2))
    wd = 0.1 * jax.random.normal(jax.random.fold_in(key, 3), (4, 256, 128))
    idx = jnp.asarray([[8, 9], [0, 15], [10, 11], [3, 11], [9, 9]], jnp.int32)
    gates = jax.random.uniform(jax.random.fold_in(key, 4), (5, 2))
    local = held_ids(idx, 8, 4)
    assert np.asarray(local).tolist() == [[0, 1], [4, 4], [2, 3], [4, 3],
                                          [1, 1]]
    assert int(experts_hit(local, 4, share=True)) == 4
    assert int(experts_hit(held_ids(idx[1:2], 8, 4), 4, share=True)) == 0
    with jax.default_matmul_precision("highest"):
        got = moe_experts_decode(x, wg, wu, wd, local, gates,
                                 interpret=True, share=True)
        want = FM.moe_dropless_mlp(x, wg, wu, wd, idx, gates, first=8)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL
    assert not np.asarray(got[1]).any() and np.asarray(got[3]).any()


# -- planted faults: the same comparison must refuse each -----------------

def _window_mask_off(monkeypatch, model):
    real = paged._ring_view

    def every_causal_key(state, s, window, page_size):
        table, at, _seen = real(state, s, window, page_size)
        col = jnp.arange(table.shape[1] * page_size)[None, None]
        t = (at[:, None] + jnp.arange(s)[None])[..., None]
        return table, at, col <= t
    monkeypatch.setattr(paged, "_ring_view", every_causal_key)


def _rope_on_the_full_layer(monkeypatch, model):
    model.model.layers[3].self_attn.rope = True


def _bias_in_the_gates(monkeypatch, model):
    real = FM.topk_gating_dropless

    def weighed_by_the_bias(logits, k, bias=None, **kw):
        idx, _gates, aux = real(logits, k, bias=bias, **kw)
        picked = jnp.take_along_axis(
            jax.nn.sigmoid(logits.astype(jnp.float32)) + bias, idx, -1)
        gates = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
        return idx, gates * kw["route_scale"], aux
    monkeypatch.setattr(FM, "topk_gating_dropless", weighed_by_the_bias)


def _output_gate_dropped(monkeypatch, model):
    monkeypatch.setattr(wam.GatedWindowAttention, "_gate",
                        staticmethod(lambda out, g: out))


def _shared_expert_dropped(monkeypatch, model):
    for layer in model.model.layers[1:]:
        w = layer.mlp.shared_expert.down_proj.weight
        monkeypatch.setattr(w, "_value", jnp.zeros_like(w._value))


def _ring_one_page_short(monkeypatch, model):
    real = paged.ring_pages_for
    monkeypatch.setattr(paged, "ring_pages_for",
                        lambda w, n, page: real(w, n, page) - 1)


@pytest.mark.parametrize("plant", [
    _window_mask_off, _rope_on_the_full_layer, _bias_in_the_gates,
    _output_gate_dropped, _shared_expert_dropped, _ring_one_page_short],
    ids=lambda f: f.__name__.strip("_"))
def test_a_planted_fault_fails_the_comparison(monkeypatch, plant):
    """Each fault is planted in the PROGRAM (the engine's trace runs the
    patched function, its model carries the changed attribute or weight);
    the reference reads the honest weights, taken before."""
    cfg, model, params = make()
    params = dict(params)
    plant(monkeypatch, model)
    eng = engine(model)
    if plant is _ring_one_page_short:
        assert eng._ring.pages_per_slot == 4
    assert serve_gap(eng, params, cfg, (30, 17), 24) > FAULT_SD


# -- the degenerate groups ------------------------------------------------

@pytest.mark.parametrize("kinds", [[FULL] * 3, [WINDOW] * 3],
                         ids=["all-full", "all-window"])
def test_a_model_of_one_kind_of_layer_through_the_engine(kinds):
    cfg, model, params = make(num_hidden_layers=3, layer_types=kinds)
    eng = engine(model)
    if kinds[0] == FULL:
        # exactly the engine of a model without windows: one table
        assert eng._ring is None and eng._groups == [eng._full]
        assert "window_engaged_steps" not in eng.stats
        assert eng.pools is eng._full.pools
    else:
        assert eng._full.layers == [] and eng._ring.layers == [0, 1, 2]
        assert eng._full.pools == []
    assert serve_gap(eng, params, cfg, (3, 21), 24) < HONEST_SD
    assert len(eng._free) == eng.num_pages - 1


# -- what the engine learns from the model, and what it refuses -----------

def test_the_ring_follows_the_longest_write_of_one_call(tiny):
    _cfg, model, _params = tiny
    assert ring_pages_for(4096, 1, 16) == 257       # a decode step's view
    assert ring_pages_for(4096, 256, 16) == 273
    assert ring_pages_for(4096, 1024, 16) == 321
    assert ring_pages_for(8, 8, 4) == 5
    # chunks of 12 are prefilled in programs of 16 (the bucket)
    assert engine(model, chunk=12)._ring.pages_per_slot \
        == ring_pages_for(8, 16, PAGE) == 7
    # a ring never needs more than a slot's longest context
    assert engine(model, chunk=128)._ring.pages_per_slot == 20
    eng = engine(model, chunk=None, max_pages_per_slot=6, num_pages=32)
    assert eng._ring.pages_per_slot == 6


def test_the_ring_view_reads_tokens_in_order_and_nothing_outside():
    """Slot 0 at position 37 of a wrapped 5-page ring, slot 1 at 5 (under
    the window): the view names the pages of (t - 8, t] in order."""
    rt = jnp.asarray([[11, 12, 13, 14, 15], [21, 22, 0, 0, 0]], jnp.int32)
    state = PagedState(jnp.zeros((2, 20), jnp.int32),
                       jnp.asarray([37, 5], jnp.int32),
                       jnp.asarray([1, 1], jnp.int32), rt)
    table, at, seen = paged._ring_view(state, 1, 8, PAGE)
    assert table.shape == (2, 3) and seen.shape == (2, 1, 12)
    # positions 30..37 are pages 7, 8, 9 of the context = ring 2, 3, 4
    assert np.asarray(table).tolist() == [[13, 14, 15], [21, 22, 0]]
    assert np.asarray(at).tolist() == [37 - 28, 5]
    assert np.flatnonzero(np.asarray(seen[0, 0])).tolist() == list(range(2, 10))
    assert np.flatnonzero(np.asarray(seen[1, 0])).tolist() == list(range(6))


@pytest.mark.parametrize("kw, why", [
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(prefix_cache_pages=8), "prefix_cache_pages"),
    (dict(prefix_cache_pages=8, host_tier_bytes=1 << 20), "host_tier_bytes"),
    (dict(role="decode", prefix_cache_pages=8), "role='decode'"),
    (dict(draft_model="draft"), "draft_model"),
])
def test_what_assumes_one_table_refuses_a_ring_by_name(tiny, kw, why):
    _cfg, model, _params = tiny
    if "draft_model" in kw:
        kw = dict(draft_model=model, chunk=None)
    with pytest.raises(ValueError, match="second page table") as err:
        engine(model, **kw)
    assert why in str(err.value)


def test_the_window_op_names_what_it_needs(tiny):
    cfg, _model, _params = tiny
    q = jnp.zeros((1, 1, 4, 32))
    k = v = jnp.zeros((1, 1, 2, 32))
    pool = jnp.zeros((4, 2, PAGE, 32))
    state = PagedState(jnp.zeros((1, 4), jnp.int32), jnp.zeros(1, jnp.int32),
                       jnp.ones(1, jnp.int32))
    with pytest.raises(ValueError, match="ring_tables"):
        paged.paged_attention_update(q, k, v, (pool, pool), state, window=8)
    with pytest.raises(ValueError, match="neither scale planes"):
        paged.paged_attention_update(q, k, v, (pool, pool, pool), state,
                                     window=8)
    with pytest.raises(NotImplementedError, match="forward only"):
        WindowAttnMoeForCausalLM(cfg)(paddle_tpu.to_tensor([[1, 2]]),
                                      labels=paddle_tpu.to_tensor([[1, 2]]))
    with pytest.raises(ValueError, match="layer_types must name"):
        tiny_window_attn_moe_config(layer_types=[WINDOW])
    with pytest.raises(NotImplementedError, match="dropless"):
        MoEMLP(8, 8, 4, score_func="sigmoid")
    with pytest.raises(ValueError, match="no range"):
        MoEMLP(8, 8, 4, dropless=True, held=(3, 2))
