"""The decoder-hybrid-decoder of Mamba layers, differential attention in a
window and over everything, and a cross-decoder through gated memory units
(models/ssm_hybrid.py) against its plain reference
(benchmarks/reference_ssm_hybrid.py), small, float32, on the CPU: the
model's logits without a cache; the cached forward by hand, prefill in
chunks that split a page and carry the state, then decode with the rings
wrapped; the engine's prefill and decode on the jnp path and on the
interpreted kernels; the selective scan kernel against a sequential scan;
a padded chunk's state; a dead row's state; six planted faults that the
same comparison must refuse; and what the engine holds and refuses.
"""
import dataclasses
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
from benchmarks import reference_ssm_hybrid as ref  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.inference import PagedKVEngine, paged  # noqa: E402
from paddle_tpu.inference.paged import PagedState, ring_pages_for  # noqa: E402
from paddle_tpu.jit.functional import state_arrays  # noqa: E402
from paddle_tpu.kernels.ssm_scan import (  # noqa: E402
    ssm_scan, ssm_scan_problems, ssm_scan_reference)
from paddle_tpu.models import ssm_hybrid as sh  # noqa: E402
from paddle_tpu.models.ssm_hybrid import (  # noqa: E402
    CROSS, FULL, GMU, MAMBA, WINDOW, SsmHybridForCausalLM,
    tiny_ssm_hybrid_config)

TOL = 2e-4                  # float32 against float32 "highest"
HONEST_SD = 2e-3            # the serve comparison on an honest program
PAGE, CHUNK = 4, 10


def make(**overrides):
    """A tiny model (8 layers of every kind; heads of 16 whose pairs are
    cached as heads of 32), seeded; its lambdas drawn at 0.5 so that a^2
    weighs."""
    cfg = tiny_ssm_hybrid_config(**overrides)
    paddle_tpu.seed(3)
    model = SsmHybridForCausalLM(cfg)
    model.eval()
    key = jax.random.key(5)
    for i, (name, t) in enumerate(sorted(model.named_parameters())):
        if "lambda_" in name:
            t._value = 0.5 * jax.random.normal(jax.random.fold_in(key, i),
                                               t.shape)
    return cfg, model, state_arrays(model)


@pytest.fixture(scope="module")
def tiny():
    return make()


def ref_logits(cfg, params, ids):
    return np.asarray(ref.logits(params, dataclasses.asdict(cfg),
                                 jnp.asarray(ids)))


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, size=n).astype(np.int32) for n in lengths]


def engine(model, kernel="jnp", chunk=CHUNK, page=PAGE, **kw):
    args = dict(max_slots=3, page_size=page, num_pages=64,
                max_pages_per_slot=20, steps_per_tick=4, kernel=kernel,
                prefill_chunk=chunk)
    args.update(kw)
    return PagedKVEngine(model, **args)


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
        lg = ref_logits(cfg, params, ids).astype(np.float64)[len(p) - 1:]
        chosen = lg[np.arange(len(o)), np.asarray(o)]
        worst = max(worst, float(((lg.max(-1) - chosen) / lg.std(-1)).max()))
    return worst


def cached_logits(cfg, model, ids, prompt, chunk, kernel="jnp",
                  page=PAGE):
    """The cached forward by hand, one slot (slot 1 of 2): the prompt in
    chunks of `chunk` (the last padded to `chunk`), then a token a call to
    the end of `ids`; the logits of each call's last valid token, in the
    order of the positions they predict from."""
    e, n, k = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    hk, w = cfg.cache_kv_heads, cfg.cache_head_dim
    ring = ring_pages_for(cfg.sliding_window, chunk, page)
    caches = []
    for kind in cfg.layer_types:
        if kind == MAMBA:
            caches.append((jnp.zeros((2, k - 1, e)), jnp.zeros((2, n, e))))
        elif kind in (WINDOW, FULL):
            caches.append((jnp.zeros((40, hk, page, w)),) * 2)
    bt = jnp.arange(1, 21, dtype=jnp.int32)[None]
    rt = jnp.arange(21, 21 + ring, dtype=jnp.int32)[None]

    def call(tokens, at, nv, flat, slots):
        state = PagedState(bt, at[None], nv[None], rt, slots)
        it = iter(flat)
        with paged.decode_kernel_scope(kernel, interpret=True):
            logits, new = model(Tensor(tokens[None]),
                                caches=[(Tensor(c), Tensor(d))
                                        for c, d in zip(it, it)],
                                cache_index=state)
        return (logits._value[0, -1], [a._value for c in new for a in c])
    def decode(token, at, flat):
        """A decode step: the rows are the slots, slot 0 dead."""
        state = PagedState(jnp.concatenate([bt, bt]),
                           jnp.stack([jnp.int32(0), at]),
                           jnp.asarray([0, 1], jnp.int32),
                           jnp.concatenate([rt, rt]))
        it = iter(flat)
        with paged.decode_kernel_scope(kernel, interpret=True):
            logits, new = model(Tensor(jnp.stack([0, token])[:, None]),
                                caches=[(Tensor(c), Tensor(d))
                                        for c, d in zip(it, it)],
                                cache_index=state)
        return logits._value[1, -1], [a._value for c in new for a in c]

    flat = [a for c in caches for a in c]
    step = jax.jit(call)
    for at in range(0, prompt, chunk):
        take = min(chunk, prompt - at)
        tokens = np.zeros(chunk, np.int32)
        tokens[:take] = ids[at:at + take]
        lg, flat = step(jnp.asarray(tokens), jnp.int32(at), jnp.int32(take),
                        flat, jnp.asarray([1], jnp.int32))
    out = [lg]
    step = jax.jit(decode)
    for t in range(prompt, len(ids)):
        lg, flat = step(jnp.int32(ids[t]), jnp.int32(t), flat)
        out.append(lg)
    return np.stack([np.asarray(o) for o in out])


# -- the model against the reference -------------------------------------

def test_the_layer_kinds_follow_mb_per_layer_and_the_depth():
    assert tiny_ssm_hybrid_config().layer_types == [
        MAMBA, WINDOW, MAMBA, WINDOW, MAMBA, FULL, GMU, CROSS]
    kinds = sh.SsmHybridConfig().layer_types
    assert kinds[:16] == [MAMBA, WINDOW] * 8
    assert kinds[16:18] == [MAMBA, FULL]
    assert kinds[18:] == [GMU, CROSS] * 7
    assert kinds == [{"mamba": MAMBA, "window": WINDOW, "full": FULL,
                      "cross": CROSS, "gmu": GMU}[k]
                     for k in ref.kinds(dataclasses.asdict(
                         sh.SsmHybridConfig()))]


def test_logits_without_a_cache_match_the_reference(tiny):
    cfg, model, params = tiny
    ids = prompts_of([40])[0]
    got = np.asarray(model(paddle_tpu.to_tensor(ids[None]))._value)[0]
    want = ref_logits(cfg, params, ids)
    assert np.abs(got - want).max() < TOL
    # the positions differ: a check that compares tokens has something to
    # see
    assert len(set(want.argmax(-1).tolist())) > 10


@pytest.mark.parametrize("kernel", ["jnp", "pallas"])
def test_logits_with_a_cache_match_the_reference(tiny, kernel):
    """A 21-token prompt in chunks (of 10, which split a page of 4; of 8
    over pages of 16 where the kernels run, interpreted, at heads of 128),
    the last padded, the state carried between them, then a token a call
    until the ring has wrapped (64 tokens)."""
    cfg, model, params = tiny if kernel == "jnp" else make(
        hidden_size=256, intermediate_size=256)
    ids = prompts_of([64], seed=3)[0]
    want = ref_logits(cfg, params, ids)
    chunk, page = (CHUNK, PAGE) if kernel == "jnp" else (8, 16)
    got = cached_logits(cfg, model, ids, 21, chunk, kernel, page)
    if kernel == "jnp":
        assert 21 % chunk and chunk % page
    assert ring_pages_for(cfg.sliding_window, chunk, page) * page < 40
    assert np.abs(got - want[20:]).max() < TOL


# -- through the engine ---------------------------------------------------

def test_engine_prefill_then_decode_matches_the_reference(tiny):
    """Contexts of 3, 9, 17 and 30 tokens, prefilled in chunks of 10 (the
    longest in three programs, a state carried across each), then 40 tokens
    each: the rings of 7 pages of 4 go round more than twice."""
    cfg, model, params = tiny
    eng = engine(model)
    # chunks of 10 ride programs of their bucket, 16
    assert eng._ring.pages_per_slot == ring_pages_for(8, 16, PAGE) == 7
    assert eng._ring.layers == [1, 3] and eng._full.layers == [5]
    assert eng._state.layers == [0, 2, 4]
    assert serve_gap(eng, params, cfg, (3, 9, 30, 17), 40) < HONEST_SD
    s = eng.stats
    # every prompt token through the scan; the cross-decoder ran at each
    # call's last token alone
    assert s["ssm_scan_tokens"] == 3 + 9 + 30 + 17
    assert s["cross_skipped_tokens"] == \
        s["ssm_scan_tokens"] - s["ssm_scan_calls"]
    assert s["ssm_scan_calls"] == 1 + 1 + 3 + 2
    assert len(eng._free) == eng.num_pages - 1
    assert len(eng._ring.free) == eng._ring.num_pages - 1


def test_interpreted_kernels_match_the_jnp_engine_and_the_reference():
    """Heads of 64 whose pairs are cached as heads of 128 (what the ring
    view rides the decode kernel at) and pages of 16: the decode kernel
    over the ring's view and over the full table, the chunk kernel, the
    Pallas write and the scan kernel (chunks of 8 split a page), all
    interpreted, against the reference and the jnp engine's tokens."""
    cfg, model, params = make(hidden_size=256, intermediate_size=256)
    outs = []
    for kernel in ("pallas", "jnp"):
        eng = engine(model, kernel=kernel, chunk=8, page=16)
        assert eng.decode_kernel == kernel
        if kernel == "pallas":
            assert eng._chunk_kernel and eng.kv_write == "pallas"
            assert not ssm_scan_problems(8, cfg.mamba_d_inner,
                                         cfg.mamba_d_state)
            assert serve_gap(eng, params, cfg, (5, 21), 30) < HONEST_SD
        outs.append(eng.generate(prompts_of((5, 21)), max_new_tokens=30))
    assert outs[0] == outs[1]


# -- the scan kernel, and the state a slot keeps ---------------------------

def _scan_inputs(rows, s, e, n, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape),  # noqa: E731
                                   jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                        (rows, s, e))), jnp.float32)
    at = -jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], (1, e))
    return f(rows, s, e), dt, f(rows, n, s), f(rows, n, s), at, f(e), \
        f(rows, n, e)


def test_the_scan_kernel_matches_a_sequential_scan_and_carries_its_state():
    """Interpreted, 2 rows of 48 tokens over 256 channels: in one call, and
    in two calls of 16 and 32 with the first's final state handed to the
    second, against `lax.scan` over the 48."""
    u, dt, bt, ct, at, d, s0 = _scan_inputs(2, 48, 256, 8)
    with jax.default_matmul_precision("highest"):
        want_y, want_s = ssm_scan_reference(u, dt, bt, ct, at, d, s0)
        y, s = ssm_scan(u, dt, bt, ct, at, d, s0, interpret=True)
        assert np.abs(np.asarray(y - want_y)).max() < 1e-5
        assert np.abs(np.asarray(s - want_s)).max() < 1e-5
        y1, s1 = ssm_scan(u[:, :16], dt[:, :16], bt[..., :16], ct[..., :16],
                          at, d, s0, interpret=True)
        y2, s2 = ssm_scan(u[:, 16:], dt[:, 16:], bt[..., 16:], ct[..., 16:],
                          at, d, s1, interpret=True)
    assert np.abs(np.asarray(jnp.concatenate([y1, y2], 1) - want_y)).max() \
        < 1e-5
    assert np.abs(np.asarray(s2 - want_s)).max() < 1e-5
    assert ssm_scan_problems(12, 256, 8) and ssm_scan_problems(16, 200, 8)
    with pytest.raises(ValueError, match="ssm_scan: tokens 12"):
        ssm_scan(u[:, :12], dt[:, :12], bt[..., :12], ct[..., :12], at, d,
                 s0)


@pytest.mark.parametrize("kernel", ["jnp", "pallas"])
def test_a_padded_chunk_leaves_the_state_of_its_last_valid_token(tiny,
                                                                 kernel):
    """11 tokens in a chunk of 16 (5 of padding) against the same 11 in a
    call of 11: the Mamba layers' conv windows and states agree."""
    cfg, model, _params = tiny
    ids = prompts_of([11], seed=4)[0]

    @jax.jit
    def states(tokens, nv):
        e, n, k = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
        caches = []
        for kind in cfg.layer_types:
            if kind == MAMBA:
                caches.append((Tensor(jnp.zeros((1, k - 1, e))),
                               Tensor(jnp.zeros((1, n, e)))))
            elif kind in (WINDOW, FULL):
                pool = jnp.zeros((8, cfg.cache_kv_heads, PAGE,
                                  cfg.cache_head_dim))
                caches.append((Tensor(pool), Tensor(pool)))
        table = jnp.arange(1, 5, dtype=jnp.int32)[None]
        state = PagedState(table, jnp.zeros(1, jnp.int32),
                           jnp.reshape(nv, (1,)), table,
                           jnp.zeros(1, jnp.int32))
        with paged.decode_kernel_scope(kernel, interpret=True):
            _lg, new = model(Tensor(jnp.asarray(tokens)[None]),
                             caches=caches, cache_index=state)
        return [a._value for kind, c in zip(
            [t for t in cfg.layer_types if t in (MAMBA, WINDOW, FULL)], new)
            if kind == MAMBA for a in c]
    padded = states(np.concatenate([ids, np.zeros(5, np.int32)]), 11)
    alone = states(ids, 11)
    padded, alone = ([np.asarray(a) for a in x] for x in (padded, alone))
    assert len(padded) == 2 * 3
    for a, b in zip(padded, alone):
        assert np.abs(a - b).max() < 1e-5
        assert np.abs(a).max() > 0


def test_a_dead_row_of_a_tick_leaves_its_slots_state_unchanged(tiny):
    """The tick program over three slots, one live, one dead after its
    prefill (as in a chained tick after its request ended) and one empty:
    the dead and the empty slot's states come back bit for bit."""
    _cfg, model, _params = tiny
    eng = engine(model)
    eng.generate(prompts_of([9, 14]), max_new_tokens=1)
    # slots 0 and 1 were prefilled, then retired: give them a state of
    # their own to keep
    key = jax.random.key(1)
    eng._state.pools = [tuple(jax.random.normal(jax.random.fold_in(key, i),
                                                a.shape, a.dtype)
                              for i, a in enumerate(grp))
                        for grp in eng._state.pools]
    before = [[np.asarray(a) for a in grp] for grp in eng._state.pools]
    tick = eng._tick_fn(False)
    b = eng.max_slots
    rows = (jnp.asarray([5, 7, 0], jnp.int32), jnp.asarray([9, 14, 0]),
            jnp.asarray([True, False, False]), jnp.asarray([4, 4, 0]))
    bt = jax.tree.map(jnp.asarray, eng._tables())
    _toks, _carry, flat = tick(rows, bt, jnp.full(b, -1, jnp.int32),
                               eng._key_data, np.int32(0),
                               [x for kv in eng.pools for x in kv])
    eng.pools = eng._unflat_pools(flat)
    after = [[np.asarray(a) for a in grp] for grp in eng._state.pools]
    for was, now in zip(before, after):
        for a, b_ in zip(was, now):
            assert (a[1:] == b_[1:]).all()       # dead and empty: unchanged
            assert not (a[0] == b_[0]).all()     # the live slot moved


# -- planted faults: the same comparison must refuse each -----------------

def _state_reset_between_chunks(monkeypatch, model):
    real = paged.slot_state_read

    def fresh(cache, state):
        rows = real(cache, state)
        return rows if state.slots is None else [jnp.zeros_like(r)
                                                 for r in rows]
    monkeypatch.setattr(paged, "slot_state_read", fresh)


def _d_times_u_dropped(monkeypatch, model):
    for layer in model.model.layers:
        if layer.kind == MAMBA:
            monkeypatch.setattr(layer.mixer.D, "_value",
                                jnp.zeros_like(layer.mixer.D._value))


def _memory_from_another_mamba_layer(monkeypatch, model):
    monkeypatch.setattr(sh.SsmHybridConfig, "memory_layer",
                        property(lambda self: self.num_hidden_layers // 2
                                 - self.mb_per_layer))


def _lambda_a2_dropped(monkeypatch, model):
    real = sh.DiffAttention._combine

    def first_map_alone(self, a):
        b, s = a.shape[:2]
        a = a.reshape(b, s, -1, 2, 2 * self.config.head_dim)
        return real(self, a.at[:, :, :, 1].set(0).reshape(b, s, -1))
    monkeypatch.setattr(sh.DiffAttention, "_combine", first_map_alone)


def _window_not_applied(monkeypatch, model):
    real = paged._ring_view

    def every_causal_key(state, s, window, page_size):
        table, at, _seen = real(state, s, window, page_size)
        col = jnp.arange(table.shape[1] * page_size)[None, None]
        t = (at[:, None] + jnp.arange(s)[None])[..., None]
        return table, at, col <= t
    monkeypatch.setattr(paged, "_ring_view", every_causal_key)


def _cross_layers_on_fresh_k_and_v(monkeypatch, model):
    """A cross layer over K and V of its own input, made by the full
    layer's projections, in place of the full layer's pages."""
    full = next(layer.mixer for layer in model.model.layers
                if layer.kind == FULL)
    real = sh.DiffAttention.cached

    def fresh(self, x, cache, cache_index, last=None):
        if self.kind != CROSS:
            return real(self, x, cache, cache_index, last)
        k, v = full.kv(x)
        own = paged.paged_kv_store(k, v, cache, cache_index)
        return real(self, x, own, cache_index, last)
    monkeypatch.setattr(sh.DiffAttention, "cached", fresh)


@pytest.mark.parametrize("plant", [
    _state_reset_between_chunks, _d_times_u_dropped,
    _memory_from_another_mamba_layer, _lambda_a2_dropped,
    _window_not_applied, _cross_layers_on_fresh_k_and_v],
    ids=lambda f: f.__name__.strip("_"))
def test_a_planted_fault_fails_the_comparison(monkeypatch, plant):
    """Each fault is planted in the PROGRAM (its trace runs the patched
    function, its model carries the changed weight); the reference reads
    the honest weights, taken before. The cached forward by hand over a
    21-token prompt in chunks of 10, then 43 decode steps."""
    cfg, model, params = make()
    params = {k: jnp.array(v) for k, v in params.items()}
    ids = prompts_of([64], seed=3)[0]
    want = ref_logits(cfg, params, ids)
    plant(monkeypatch, model)
    got = cached_logits(cfg, model, ids, 21, CHUNK)
    assert np.abs(got - want[20:]).max() > TOL


# -- what the engine holds, and what it refuses ---------------------------

def test_a_slot_pins_its_pages_rings_and_state_from_the_buffers(tiny):
    cfg, model, _params = tiny
    eng = engine(model)
    e, n, k = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    k_and_v = 2 * PAGE * cfg.cache_kv_heads * cfg.cache_head_dim * 4
    state = 3 * ((k - 1) * e + n * e) * 4
    assert eng.kv_bytes_per_slot() == 20 * k_and_v + 2 * 7 * k_and_v + state
    assert state == sum(a.size * a.dtype.itemsize for grp in eng._state.pools
                        for a in grp) // eng.max_slots
    # every cache the programs take, in the layers' order: Mamba, ring,
    # Mamba, ring, Mamba, the full layer's pages; cross and memory units
    # hold nothing
    shapes = [tuple(a.shape) for kv in eng.pools for a in kv]
    assert shapes[:2] == [(3, k - 1, e), (3, n, e)]
    assert shapes[2] == (eng._ring.num_pages, cfg.cache_kv_heads, PAGE,
                         cfg.cache_head_dim)
    assert len(shapes) == 2 * 6
    assert shapes[-1][0] == eng.num_pages
    assert eng._prefill_limit(1) == 1024 and eng._prefill_limit(32) == 32


@pytest.mark.parametrize("kw, why", [
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(prefix_cache_pages=8), "prefix_cache_pages"),
    (dict(prefix_cache_pages=8, host_tier_bytes=1 << 20), "host_tier_bytes"),
    (dict(role="decode", prefix_cache_pages=8), "role='decode'"),
    (dict(draft_model="draft"), "draft_model"),
])
def test_what_assumes_pages_hold_a_slot_refuses_a_state_by_name(tiny, kw,
                                                                 why):
    _cfg, model, _params = tiny
    if "draft_model" in kw:
        kw = dict(draft_model=model, chunk=None)
    with pytest.raises(ValueError, match="fixed-size state a slot") as err:
        engine(model, **kw)
    assert why in str(err.value)


def test_a_block_length_is_refused_by_name(tiny, monkeypatch):
    _cfg, model, _params = tiny
    monkeypatch.setattr(model.config, "block_length", 4, raising=False)
    with pytest.raises(ValueError, match="a block length"):
        engine(model)


def test_what_the_model_does_not_build_is_refused_by_name(tiny):
    cfg, model, _params = tiny
    with pytest.raises(NotImplementedError, match="mb_per_layer=3"):
        tiny_ssm_hybrid_config(mb_per_layer=3)
    with pytest.raises(NotImplementedError, match="tie_word_embeddings"):
        tiny_ssm_hybrid_config(tie_word_embeddings=False)
    with pytest.raises(NotImplementedError, match="forward only"):
        model(paddle_tpu.to_tensor([[1, 2]]),
              labels=paddle_tpu.to_tensor([[1, 2]]))
    with pytest.raises(NotImplementedError, match="PagedKVEngine"):
        model(paddle_tpu.to_tensor([[1, 2]]), caches=[], cache_index=None)
    with pytest.raises(ValueError, match="a window layer's"):
        q = jnp.zeros((1, 1, 4, 32))
        kv = jnp.zeros((1, 1, 2, 32))
        pool = jnp.zeros((4, 2, PAGE, 32))
        paged.paged_attention_update(
            q, kv, kv, (pool, pool),
            PagedState(jnp.zeros((1, 4), jnp.int32), jnp.zeros(1, jnp.int32),
                       jnp.ones(1, jnp.int32)), scale=0.5)
