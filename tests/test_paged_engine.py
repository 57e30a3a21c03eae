"""Continuous-batching paged-KV serving engine (inference/paged.py).

Reference capability: the serving path built on
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu +
launcher-side continuous batching. The load-bearing checks:

- paged attention == dense attention (unit parity on random lens),
- engine tokens == models.generation.generate tokens (greedy, solo),
- a request admitted MID-DECODE of another produces exactly its solo
  tokens (the continuous-batching correctness bar from VERDICT r4 #1),
- pages are recycled across requests and the free list is restored,
- admission control queues what cannot be reserved, never deadlocks,
- the HTTP server streams two concurrent requests through one engine.
"""
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference.paged import (PagedKVEngine, PagedState,
                                        paged_attention_update)
from paddle_tpu.models.llama import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.models.generation import generate


def _model(seed=0):
    paddle_tpu.seed(seed)
    cfg = tiny_llama_config(num_hidden_layers=2, vocab_size=97,
                            hidden_size=32, intermediate_size=64,
                            num_attention_heads=4, num_key_value_heads=2)
    return LlamaForCausalLM(cfg)


def test_paged_attention_matches_dense():
    rng = np.random.default_rng(0)
    b, s, hq, hk, d, ps, npages, mp = 3, 4, 4, 2, 8, 4, 16, 4
    q = rng.normal(size=(b, s, hq, d)).astype(np.float32)
    k = rng.normal(size=(b, s, hk, d)).astype(np.float32)
    v = rng.normal(size=(b, s, hk, d)).astype(np.float32)
    lens = np.array([0, 3, 7], np.int32)
    n_valid = np.array([4, 4, 2], np.int32)
    # pre-populate dense history and the equivalent page pools
    hist_k = rng.normal(size=(b, 16, hk, d)).astype(np.float32)
    hist_v = rng.normal(size=(b, 16, hk, d)).astype(np.float32)
    kp = np.zeros((npages, hk, ps, d), np.float32)
    vp = np.zeros((npages, hk, ps, d), np.float32)
    bt = np.zeros((b, mp), np.int32)
    page = 1
    for i in range(b):
        for j in range(mp):
            bt[i, j] = page
            page += 1
        for pos in range(lens[i]):
            kp[bt[i, pos // ps], :, pos % ps, :] = hist_k[i, pos]
            vp[bt[i, pos // ps], :, pos % ps, :] = hist_v[i, pos]
    state = PagedState(jnp.asarray(bt), jnp.asarray(lens),
                       jnp.asarray(n_valid))
    out, (kp2, vp2) = paged_attention_update(
        Tensor(jnp.asarray(q)), Tensor(jnp.asarray(k)),
        Tensor(jnp.asarray(v)), (Tensor(jnp.asarray(kp)),
                                 Tensor(jnp.asarray(vp))), state)
    out = np.asarray(out._value).reshape(b, s, hq, d)
    # dense oracle per row
    for i in range(b):
        total = lens[i] + s
        keys = np.concatenate([hist_k[i, :lens[i]], k[i]], 0)  # (total,...)
        vals = np.concatenate([hist_v[i, :lens[i]], v[i]], 0)
        keys = np.repeat(keys, hq // hk, axis=1)
        vals = np.repeat(vals, hq // hk, axis=1)
        # rows beyond n_valid are padding by contract (their k/v routes
        # to the trash page, their output is never read)
        for si in range(int(n_valid[i])):
            pos = lens[i] + si
            sc = np.einsum("hd,chd->hc", q[i, si],
                           keys[:pos + 1]) / np.sqrt(d)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            ref = np.einsum("hc,chd->hd", p, vals[:pos + 1])
            np.testing.assert_allclose(out[i, si], ref, rtol=2e-5,
                                       atol=2e-5)
    # writes landed in the right pages (valid ones only)
    kp2 = np.asarray(kp2._value)
    for i in range(b):
        for si in range(int(n_valid[i])):
            pos = lens[i] + si
            np.testing.assert_allclose(
                kp2[bt[i, pos // ps], :, pos % ps, :], k[i, si],
                rtol=1e-6)


def test_paged_attention_update_jits():
    b, s, hq, hk, d, ps, npages, mp = 2, 1, 2, 2, 4, 4, 8, 2
    rng = np.random.default_rng(1)

    @jax.jit
    def step(q, k, v, kp, vp, bt, lens, nv):
        out, (kp2, vp2) = paged_attention_update(
            q, k, v, (kp, vp), PagedState(bt, lens, nv))
        return out._value, kp2._value, vp2._value

    out, kp2, vp2 = step(
        jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32),
        jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32),
        jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32),
        jnp.zeros((npages, hk, ps, d), jnp.float32),
        jnp.zeros((npages, hk, ps, d), jnp.float32),
        jnp.asarray([[1, 2], [3, 4]], jnp.int32),
        jnp.asarray([0, 2], jnp.int32), jnp.asarray([1, 1], jnp.int32))
    assert out.shape == (b, s, hq * d)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.quick
def test_engine_matches_solo_generate():
    model = _model()
    prompts = [[5, 9, 2], [17, 3, 11, 4, 8]]
    solo = [np.asarray(generate(model, np.asarray([p], np.int32),
                                max_new_tokens=7))[0].tolist()[len(p):]
            for p in prompts]
    eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=24,
                        max_pages_per_slot=6, steps_per_tick=3)
    got = eng.generate(prompts, max_new_tokens=7)
    assert got == solo
    assert eng.stats["finished"] == 2
    # every page returned to the free list
    assert len(eng._free) == eng.num_pages - 1
    assert eng._reserved_unalloc == 0


def test_mid_decode_admission_token_parity():
    """The continuous-batching bar: B joins while A is mid-decode; both
    must produce exactly their solo-run tokens."""
    model = _model()
    pa, pb = [5, 9, 2, 14], [17, 3, 11]
    solo_a = np.asarray(generate(model, np.asarray([pa], np.int32),
                                 max_new_tokens=12))[0].tolist()[len(pa):]
    solo_b = np.asarray(generate(model, np.asarray([pb], np.int32),
                                 max_new_tokens=6))[0].tolist()[len(pb):]
    eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=24,
                        max_pages_per_slot=6, steps_per_tick=2)
    ra = eng.submit(pa, max_new_tokens=12)
    eng.step()                     # A prefilled + first decode tick
    eng.step()                     # A decodes alone
    assert 1 <= len(ra.tokens) < 12
    rb = eng.submit(pb, max_new_tokens=6)   # joins mid-decode of A
    eng.run_until_idle()
    assert ra.result() == solo_a
    assert rb.result() == solo_b
    # B really was admitted while A was live (not after)
    assert eng.stats["admitted"] == 2


def test_page_reuse_across_requests():
    model = _model()
    eng = PagedKVEngine(model, max_slots=1, page_size=4, num_pages=8,
                        max_pages_per_slot=4, steps_per_tick=4)
    solo = [np.asarray(generate(model, np.asarray([p], np.int32),
                                max_new_tokens=5))[0].tolist()[len(p):]
            for p in ([1, 2, 3], [40, 41, 42, 43])]
    r1 = eng.submit([1, 2, 3], max_new_tokens=5)
    eng.run_until_idle()
    used_first = eng.stats["admitted"]
    r2 = eng.submit([40, 41, 42, 43], max_new_tokens=5)  # reuses pages
    eng.run_until_idle()
    assert r1.result() == solo[0]
    assert r2.result() == solo[1]
    assert used_first == 1 and eng.stats["admitted"] == 2
    assert len(eng._free) == eng.num_pages - 1


def test_admission_queues_when_full():
    model = _model()
    # pool fits ONE request's reservation at a time
    eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=5,
                        max_pages_per_slot=4, steps_per_tick=2)
    r1 = eng.submit([1, 2, 3], max_new_tokens=8)    # needs 3 pages of 4
    r2 = eng.submit([4, 5, 6], max_new_tokens=8)
    eng.step()
    assert eng.stats["admitted"] == 1               # r2 queued, not dropped
    eng.run_until_idle()
    assert len(r1.result()) == 8 and len(r2.result()) == 8
    assert eng.stats["admitted"] == 2


def test_submit_validation():
    model = _model()
    eng = PagedKVEngine(model, max_slots=1, page_size=4, num_pages=8,
                        max_pages_per_slot=3)
    with pytest.raises(ValueError, match="max_pages_per_slot"):
        eng.submit(list(range(10)), max_new_tokens=8)


def test_submit_without_driver_result_raises_not_hangs():
    """submit() does NOT auto-start the ticker (only stream() does) —
    result()'s stall guard must raise with the fix named instead of
    blocking forever, and the handle stays usable once a real driver
    drains the engine."""
    model = _model()
    eng = PagedKVEngine(model, max_slots=1, page_size=4, num_pages=16,
                        max_pages_per_slot=4)
    r = eng.submit([1, 2, 3], max_new_tokens=2)
    with pytest.raises(RuntimeError, match="run_until_idle"):
        r.result(stall_timeout=0.4)
    eng.run_until_idle()
    assert len(r.result()) == 2


def test_eos_mid_tick_truncates_and_frees():
    model = _model()
    # discover what the model emits, then use its 2nd token as eos
    probe = np.asarray(generate(model, np.asarray([[7, 8]], np.int32),
                                max_new_tokens=6))[0].tolist()[2:]
    eos = probe[1]
    solo = probe[:2]               # tokens up to and including eos
    eng = PagedKVEngine(model, max_slots=1, page_size=4, num_pages=12,
                        max_pages_per_slot=4, steps_per_tick=4)
    r = eng.submit([7, 8], max_new_tokens=6, eos_token_id=eos)
    eng.run_until_idle()
    assert r.result() == solo
    assert len(eng._free) == eng.num_pages - 1


def test_per_slot_sampling_configs_share_one_tick():
    """Greedy and sampled requests ride the same tick program; sampled
    output is valid token ids and seeded-deterministic per engine."""
    model = _model()
    mk = lambda: PagedKVEngine(model, max_slots=2, page_size=4,   # noqa
                               num_pages=24, max_pages_per_slot=6,
                               steps_per_tick=3, seed=11)
    eng = mk()
    rg = eng.submit([5, 9, 2], max_new_tokens=6)
    rs = eng.submit([5, 9, 2], max_new_tokens=6, do_sample=True,
                    temperature=0.8, top_k=20, top_p=0.9)
    eng.run_until_idle()
    solo = np.asarray(generate(model, np.asarray([[5, 9, 2]], np.int32),
                               max_new_tokens=6))[0].tolist()[3:]
    assert rg.result() == solo          # greedy unaffected by neighbor
    toks = rs.result()
    assert len(toks) == 6
    assert all(0 <= t < model.config.vocab_size for t in toks)
    eng2 = mk()
    rg2 = eng2.submit([5, 9, 2], max_new_tokens=6)
    rs2 = eng2.submit([5, 9, 2], max_new_tokens=6, do_sample=True,
                      temperature=0.8, top_k=20, top_p=0.9)
    eng2.run_until_idle()
    assert rs2.result() == toks and rg2.result() == solo


def test_engine_stream_surface():
    """generate_stream-compatible .stream() used by PredictorServer."""
    model = _model()
    eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=24,
                        max_pages_per_slot=6, steps_per_tick=2)
    try:
        solo = np.asarray(generate(model, np.asarray([[5, 9, 2]],
                                                     np.int32),
                                   max_new_tokens=5))[0].tolist()[3:]
        steps = list(eng.stream(np.asarray([[5, 9, 2]], np.int32),
                                max_new_tokens=5))
        assert [int(s[0]) for s in steps] == solo
    finally:
        eng.stop()


def test_http_concurrent_requests_one_engine():
    """Two concurrent HTTP /generate streams join one continuous batch;
    both get their solo-run tokens."""
    import json
    import http.client
    from paddle_tpu.inference.serving import PredictorServer
    model = _model()
    solo = {}
    for name, p in (("a", [5, 9, 2]), ("b", [17, 3, 11, 4])):
        solo[name] = np.asarray(
            generate(model, np.asarray([p], np.int32),
                     max_new_tokens=6))[0].tolist()[len(p):]
    eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=24,
                        max_pages_per_slot=6, steps_per_tick=2)
    srv = PredictorServer(lambda d: d, generator=eng).start()
    try:
        results = {}

        def go(name, ids):
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=120)
            conn.request("POST", "/generate",
                         json.dumps({"ids": [ids],
                                     "max_new_tokens": 6}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            results[name] = json.loads(resp.read())
            conn.close()

        ta = threading.Thread(target=go, args=("a", [5, 9, 2]))
        tb = threading.Thread(target=go, args=("b", [17, 3, 11, 4]))
        ta.start(); tb.start(); ta.join(); tb.join()        # noqa: E702
        assert results["a"]["sequences"][0] == solo["a"]
        assert results["b"]["sequences"][0] == solo["b"]
        # both requests were served; the engine saw them concurrently
        # (ticks overlapped rather than two serial solo runs)
        assert eng.stats["finished"] == 2
    finally:
        srv.stop()
        eng.stop()


def test_cancel_frees_slot_and_pages():
    """Client-disconnect path: cancelling an in-flight request retires
    its slot at the next tick and returns its pages + reservation."""
    model = _model()
    eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=48,
                        max_pages_per_slot=16, steps_per_tick=2)
    r = eng.submit([5, 9, 2], max_new_tokens=50)
    eng.step()
    assert any(eng._slots)
    r.cancel()
    eng.step()
    assert not any(eng._slots)
    assert len(eng._free) == eng.num_pages - 1
    assert eng._reserved_unalloc == 0
    assert eng.stats["cancelled"] == 1
    assert r.done.wait(timeout=5)
    # closing a stream() iterator cancels its requests too
    it = eng.stream(np.asarray([[5, 9, 2]], np.int32), max_new_tokens=50)
    try:
        next(it)
        it.close()
        for _ in range(200):
            if not eng.has_work():
                break
            import time
            time.sleep(0.05)
        assert not eng.has_work()
        assert len(eng._free) == eng.num_pages - 1
    finally:
        eng.stop()


def test_qwen2_moe_serves_through_paged_engine():
    """The MoE flagship rides the same paged path (its attention IS
    LlamaAttention): mid-decode admission token parity holds."""
    from paddle_tpu.models.qwen2_moe import (Qwen2MoeForCausalLM,
                                             tiny_qwen2_moe_config)
    paddle_tpu.seed(1)
    model = Qwen2MoeForCausalLM(tiny_qwen2_moe_config())
    pa, pb = [5, 9, 2], [17, 3, 11, 4]
    solo = {}
    for key, p in (("a", pa), ("b", pb)):
        solo[key] = np.asarray(
            generate(model, np.asarray([p], np.int32),
                     max_new_tokens=5))[0].tolist()[len(p):]
    eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=24,
                        max_pages_per_slot=6, steps_per_tick=2)
    ra = eng.submit(pa, max_new_tokens=5)
    eng.step()
    rb = eng.submit(pb, max_new_tokens=5)    # joins mid-decode of A
    eng.run_until_idle()
    assert ra.result() == solo["a"]
    assert rb.result() == solo["b"]


def test_admission_storm_batched_prefill_parity():
    """Several same-bucket requests admitted in ONE tick prefill as one
    batched program call (r5 storm path) — tokens still exactly match
    solo runs, and the prefill program count shows the batching."""
    model = _model()
    prompts = [[5, 9, 2], [17, 3, 11], [40, 41, 2], [7, 8, 9]]
    solo = [np.asarray(generate(model, np.asarray([p], np.int32),
                                max_new_tokens=5))[0].tolist()[len(p):]
            for p in prompts]
    eng = PagedKVEngine(model, max_slots=4, page_size=4, num_pages=40,
                        max_pages_per_slot=6, steps_per_tick=3)
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run_until_idle()
    for r, want in zip(reqs, solo):
        assert r.result() == want
    assert eng.stats["prefills"] == 4
    # all four prefilled through the ONE batched (bw=max_slots) program
    assert ("prefill", 8, 4) in eng._programs
    assert ("prefill", 8, 1) not in eng._programs


# the width a group of one bucket prefills at (ISSUE 34): the dense serve
# cell's groups (16 slots, buckets 128-512) all run at width 1, as every
# group of 16 slots does; the storm tests' shapes (short rows, 4 slots)
# stay on the padded program, which the chip read cheaper than a pair
# alone up to 32 tokens at 4 slots; a bucket's groups take one width
# whatever their number of rows
_WIDTHS = (
    [(n, ppad, 16, 1) for n in (2, 3, 8, 16) for ppad in (128, 256, 512)]
    + [(1, 8, 4, 1), (1, 512, 16, 1), (2, 4096, 16, 1),
       (4, 8, 4, 4),            # test_admission_storm_batched_prefill_parity
       (3, 8, 4, 4),            # test_chunked_prefill_storm_lockstep
       (2, 8, 4, 4), (2, 16, 4, 4), (4, 32, 4, 4), (2, 64, 4, 1),
       (3, 64, 4, 1), (2, 64, 2, 2), (2, 128, 2, 1), (3, 32, 3, 3),
       (2, 16, 6, 6), (2, 8, 7, 1), (8, 8, 8, 1), (2, 8, 16, 1),
       (16, 8, 16, 1), (16, 64, 16, 1)])


@pytest.mark.parametrize("n,ppad,max_slots,want", _WIDTHS)
def test_prefill_width_rule(n, ppad, max_slots, want):
    """One rule from what the engine can see: the padded program only
    where it costs less than two of its rows alone, a program costing
    max(balance point, its tokens) and the padded one a constant times
    its rows squared beside."""
    from paddle_tpu.inference.paged import prefill_width
    assert prefill_width(n, ppad, max_slots) == want


def _prefill_spans():
    from paddle_tpu.observability import trace
    return [s.attrs for s in trace.spans() if s.name == "engine.prefill"]


@pytest.mark.parametrize("n", [2, 3])
def test_same_bucket_long_rows_prefill_split_parity(n):
    """Rows of one bucket long enough that the program padded to
    max_slots rows would cost more than they do run at width 1, back to
    back under one span: the tokens of solo runs, no padded program, no
    padding."""
    from paddle_tpu import observability
    model = _model()
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(1, 90, m)) for m in (40, 64, 33)[:n]]
    solo = [np.asarray(generate(model, np.asarray([p], np.int32),
                                max_new_tokens=5))[0].tolist()[len(p):]
            for p in prompts]
    eng = PagedKVEngine(model, max_slots=4, page_size=8, num_pages=80,
                        max_pages_per_slot=9, steps_per_tick=3)
    with observability.scoped():
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_idle()
        spans = _prefill_spans()
    for r, want in zip(reqs, solo):
        assert r.result() == want
    assert ("prefill", 64, 1) in eng._programs
    assert ("prefill", 64, 4) not in eng._programs
    assert {k: eng.stats[k] for k in (
        "prefills", "prefill_rows_run", "prefill_rows_padded",
        "prefill_rows_split")} == {
            "prefills": n, "prefill_rows_run": n,
            "prefill_rows_padded": 0, "prefill_rows_split": n}
    assert spans == [{"bucket": 64, "rows": n, "group": 1, "chunks": 1,
                      "calls": n}]


@pytest.mark.parametrize("n", [2, 3])
def test_warm_tails_of_split_rows_still_pad(n):
    """The same prompts after a prefix-cache hit: what is left to
    prefill is a short tail a row, and short tails ride one padded
    program as before — the tokens of solo runs either way."""
    from paddle_tpu import observability
    model = _model()
    rng = np.random.RandomState(6)
    prefix = list(rng.randint(1, 90, 40))           # five full pages of 8
    prompts = [prefix + list(rng.randint(1, 90, m)) for m in (3, 5, 2)[:n]]
    solo = [np.asarray(generate(model, np.asarray([p], np.int32),
                                max_new_tokens=5))[0].tolist()[len(p):]
            for p in prompts]
    eng = PagedKVEngine(model, max_slots=4, page_size=8, num_pages=80,
                        max_pages_per_slot=9, steps_per_tick=3,
                        prefix_cache_pages=16)
    eng.generate([prefix + [7]], max_new_tokens=1)  # cold, alone: width 1
    s0 = dict(eng.stats)
    with observability.scoped():
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_idle()
        spans = _prefill_spans()
    for r, want in zip(reqs, solo):
        assert r.result() == want
    assert eng.stats["prefix_hits"] - s0["prefix_hits"] == n
    assert ("prefill", 8, 4) in eng._programs
    assert ("prefill", 8, 1) not in eng._programs
    assert {k: eng.stats[k] - s0[k] for k in (
        "prefills", "prefill_rows_run", "prefill_rows_padded",
        "prefill_rows_split")} == {
            "prefills": n, "prefill_rows_run": 4,
            "prefill_rows_padded": 4 - n, "prefill_rows_split": 0}
    assert spans == [{"bucket": 8, "rows": n, "group": 4, "chunks": 1,
                      "calls": 1}]


def test_chunked_prefill_long_prompt_parity():
    """prefill_chunk: a prompt longer than the chunk streams through
    the ONE chunk-sized program (appending at lens>0 — the reference's
    chunked-prefill contract); tokens exactly match the solo run, and
    no whole-prompt bucket program is ever compiled."""
    model = _model()
    prompt = list(np.random.RandomState(3).randint(1, 90, 19))
    solo = np.asarray(generate(model, np.asarray([prompt], np.int32),
                               max_new_tokens=6))[0].tolist()[19:]
    eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=40,
                        max_pages_per_slot=10, steps_per_tick=3,
                        prefill_chunk=8)
    r = eng.submit(prompt, max_new_tokens=6)
    # a short co-traveller still uses the bucketed path
    r2 = eng.submit([5, 9, 2], max_new_tokens=4)
    eng.run_until_idle()
    assert r.result() == solo
    solo2 = np.asarray(generate(model, np.asarray([[5, 9, 2]], np.int32),
                                max_new_tokens=4))[0].tolist()[3:]
    assert r2.result() == solo2
    keys = sorted(k for k in eng._programs if k[0].startswith("prefill"))
    assert ("prefill_chunk", 8, 1) in keys
    assert not any(k[0] == "prefill" and k[1] >= 19 for k in keys), keys


def test_chunked_prefill_storm_lockstep():
    """A storm of DIFFERENT-length long prompts prefills in lockstep
    rounds through one (chunk, max_slots) program — token parity exact
    for every request."""
    model = _model()
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(1, 90, n)) for n in (13, 21, 9)]
    solo = [np.asarray(generate(model, np.asarray([p], np.int32),
                                max_new_tokens=4))[0].tolist()[len(p):]
            for p in prompts]
    eng = PagedKVEngine(model, max_slots=4, page_size=4, num_pages=60,
                        max_pages_per_slot=8, steps_per_tick=3,
                        prefill_chunk=8)
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run_until_idle()
    for r, want in zip(reqs, solo):
        assert r.result() == want
    assert ("prefill_chunk", 8, 4) in eng._programs


def test_chunked_prefill_split_rows_parity():
    """Long prompts whose lockstep rounds padded to max_slots rows would
    compute more than the rows alone: each row's rounds at width 1, one
    row's after another's, one span and one wait — exact token parity."""
    from paddle_tpu import observability
    model = _model()
    rng = np.random.RandomState(12)
    prompts = [list(rng.randint(1, 90, m)) for m in (100, 70)]
    solo = [np.asarray(generate(model, np.asarray([p], np.int32),
                                max_new_tokens=4))[0].tolist()[len(p):]
            for p in prompts]
    eng = PagedKVEngine(model, max_slots=8, page_size=8, num_pages=120,
                        max_pages_per_slot=14, steps_per_tick=3,
                        prefill_chunk=64)
    with observability.scoped():
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run_until_idle()
        spans = _prefill_spans()
    for r, want in zip(reqs, solo):
        assert r.result() == want
    assert ("prefill_chunk", 64, 1) in eng._programs
    assert ("prefill_chunk", 64, 8) not in eng._programs
    assert (eng.stats["prefill_rows_run"], eng.stats["prefill_rows_padded"],
            eng.stats["prefill_rows_split"]) == (4, 0, 2)
    assert spans == [{"bucket": 64, "rows": 2, "group": 1, "chunks": 2,
                      "calls": 4}]


def test_rows_whose_scores_would_not_fit_share_one_wait(monkeypatch):
    """Where the scores of max_slots rows of a bucket would not fit one
    program the rows run at width 1 whatever the cost rule says, through
    the same back-to-back path: one span, exact parity."""
    from paddle_tpu import observability
    from paddle_tpu.inference import paged
    model = _model()
    rng = np.random.RandomState(13)
    prompts = [list(rng.randint(1, 90, m)) for m in (11, 14)]
    solo = [np.asarray(generate(model, np.asarray([p], np.int32),
                                max_new_tokens=4))[0].tolist()[len(p):]
            for p in prompts]
    eng = PagedKVEngine(model, max_slots=2, page_size=8, num_pages=80,
                        max_pages_per_slot=9, steps_per_tick=3)
    # 4 heads x 4 B x a 72-token window: one row may hold 16 tokens of
    # scores, two rows the least bucket
    monkeypatch.setattr(paged, "_PREFILL_SCORE_BYTES", 16 * 4 * 4 * 72)
    assert eng._prefill_limit(1) == 16 and eng._prefill_limit(2) == 8
    assert paged.prefill_width(2, 16, 2) == 2
    with observability.scoped():
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run_until_idle()
        spans = _prefill_spans()
    for r, want in zip(reqs, solo):
        assert r.result() == want
    assert ("prefill", 16, 2) not in eng._programs
    assert eng.stats["prefill_rows_split"] == 2
    assert eng.stats["prefill_rows_padded"] == 0
    assert spans == [{"bucket": 16, "rows": 2, "group": 1, "chunks": 1,
                      "calls": 2}]


def test_speculative_paged_lossless_parity():
    """Greedy speculative decoding composed with the paged engine: a
    draft model proposes, ONE target verify per tick accepts the
    longest matching prefix — output tokens are EXACTLY the solo target
    tokens (losslessness), including mid-decode admission. The best
    draft is the target itself: acceptance is then total."""
    model = _model()
    paddle_tpu.seed(5)
    from paddle_tpu.models.llama import LlamaForCausalLM
    draft = LlamaForCausalLM(model.config)          # independent weights
    pa, pb = [5, 9, 2, 14], [17, 3, 11]
    solo = {}
    for key, p, m in (("a", pa, 9), ("b", pb, 6)):
        solo[key] = np.asarray(
            generate(model, np.asarray([p], np.int32),
                     max_new_tokens=m))[0].tolist()[len(p):]
    eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=40,
                        max_pages_per_slot=8, steps_per_tick=3,
                        draft_model=draft, spec_tokens=3)
    ra = eng.submit(pa, max_new_tokens=9)
    eng.step()
    rb = eng.submit(pb, max_new_tokens=6)   # joins mid-decode of A
    eng.run_until_idle()
    assert ra.result() == solo["a"]
    assert rb.result() == solo["b"]
    assert eng.stats["spec_ticks"] > 0
    assert 0 <= eng.stats["spec_accepted"] <= eng.stats["spec_proposed"]

    # perfect draft (the target itself) accepts every proposal
    eng2 = PagedKVEngine(model, max_slots=1, page_size=4, num_pages=40,
                        max_pages_per_slot=8, draft_model=model,
                        spec_tokens=3)
    r = eng2.submit(pa, max_new_tokens=9)
    eng2.run_until_idle()
    assert r.result() == solo["a"]
    assert eng2.stats["spec_accepted"] == eng2.stats["spec_proposed"]


def test_speculative_mixed_regimes_one_tick():
    """Greedy and sampled slots ride the SAME spec tick (r5: sampled
    slots no longer force a fallback): greedy output stays exactly the
    solo run, sampled output is valid, and every tick speculates."""
    model = _model()
    paddle_tpu.seed(5)
    from paddle_tpu.models.llama import LlamaForCausalLM
    draft = LlamaForCausalLM(model.config)
    eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=40,
                        max_pages_per_slot=8, draft_model=draft,
                        spec_tokens=3, seed=7)
    rg = eng.submit([5, 9, 2], max_new_tokens=5)
    rs = eng.submit([5, 9, 2], max_new_tokens=5, do_sample=True,
                    temperature=0.9, top_k=30)
    eng.run_until_idle()
    solo = np.asarray(generate(model, np.asarray([[5, 9, 2]], np.int32),
                               max_new_tokens=5))[0].tolist()[3:]
    assert rg.result() == solo
    toks = rs.result()
    assert len(toks) == 5
    assert all(0 <= x < model.config.vocab_size for x in toks)
    assert eng.stats["spec_ticks"] == eng.stats["ticks"]


def test_speculative_sampled_matches_target_distribution():
    """Leviathan correctness on the paged path: over many keys, the
    first emitted token's marginal must equal the target's processed
    softmax at that position — REGARDLESS of the draft (rejection
    sampling is exactly-correcting). Program-level: one compiled spec
    tick, many keys."""
    import jax
    import jax.numpy as jnp
    model = _model()
    paddle_tpu.seed(13)
    from paddle_tpu.models.llama import LlamaForCausalLM
    draft = LlamaForCausalLM(model.config)
    eng = PagedKVEngine(model, max_slots=1, page_size=4, num_pages=24,
                        max_pages_per_slot=10, draft_model=draft,
                        spec_tokens=3, seed=0)
    r = eng.submit([5, 9, 2], max_new_tokens=30, do_sample=True,
                   temperature=0.8, top_k=0, top_p=1.0)
    eng._admit()                       # prefill only; no tick yet
    a = eng._slot_arrays([0])
    fn = eng._spec_tick_fn(True)
    tflat = [x for kv in eng.pools for x in kv]
    dflat = [x for kv in eng.draft_pools for x in kv]

    # target reference distribution at the first decode position
    from paddle_tpu.inference.paged import (PagedState,
                                            _process_logits_rowwise)
    from paddle_tpu.core.tensor import Tensor
    state = PagedState(jnp.asarray(eng._bt), jnp.asarray(a["lens"]),
                       jnp.asarray(a["active"]).astype(jnp.int32))
    logits, _ = model(Tensor(jnp.asarray(a["tok"])[:, None]),
                      caches=eng._layer_caches(tflat),
                      position_ids=Tensor(jnp.asarray(a["lens"])[:, None]),
                      cache_index=state)
    want = np.asarray(jax.nn.softmax(_process_logits_rowwise(
        logits._value[:, -1], jnp.asarray(a["temp"]),
        jnp.asarray(a["topk"]), jnp.asarray(a["topp"])), axis=-1))[0]

    trials = 400
    donated = jax.default_backend() != "cpu"   # mirror the engine gate
    counts = np.zeros(model.config.vocab_size)
    for s in range(trials):
        key = jax.random.key(1000 + s)
        tf = [jnp.copy(x) for x in tflat] if donated else list(tflat)
        df = [jnp.copy(x) for x in dflat] if donated else list(dflat)
        out, n_emit, _, _, _ = fn(
            jnp.asarray(a["tok"]), jnp.asarray(a["lens"]),
            jnp.asarray(a["active"]), jnp.asarray(eng._bt),
            jax.random.key_data(key), jnp.asarray(a["temp"]),
            jnp.asarray(a["topk"]), jnp.asarray(a["topp"]),
            jnp.asarray(a["wants"]), tf, df)
        counts[int(np.asarray(out)[0, 0])] += 1
    freq = counts / trials
    tv = 0.5 * np.abs(freq - want).sum()
    # TV distance bound: sampling noise ~ sqrt(V/AN) scale; 400 trials
    # over ~97 tokens -> bound 0.25 comfortably separates correct
    # rejection sampling from e.g. always-emitting the draft sample
    assert tv < 0.25, tv


# -- overload control (ISSUE 2: bounded admission + deadlines) --------------

def test_engine_sheds_when_pending_bounded():
    import time
    from paddle_tpu.inference.overload import EngineOverloaded
    eng = PagedKVEngine(_model(), max_slots=1, page_size=4, num_pages=9,
                        steps_per_tick=2, max_pending=0)
    r1 = eng.submit([1, 2, 3], max_new_tokens=4)    # admissible right now
    with pytest.raises(EngineOverloaded) as ei:
        eng.submit([1, 2, 3], max_new_tokens=4)     # queued behind r1
    assert ei.value.retry_after is not None
    assert eng.stats["overloaded"] == 1
    # the shed is a queue-state rejection, not a permanent one: once
    # the queue clears (r1 cancelled + reaped) admission works again
    r1.cancel()
    eng.step()
    assert eng.stats["cancelled"] == 1
    r3 = eng.submit([1, 2, 3], max_new_tokens=4)
    r3.cancel()
    eng.step()


def test_engine_submit_deadline_expiry():
    import time
    from paddle_tpu.inference.overload import Deadline, DeadlineExceeded
    eng = PagedKVEngine(_model(), max_slots=1, page_size=4, num_pages=9,
                        steps_per_tick=2)
    # already-dead budget: rejected at submit, nothing enqueued
    with pytest.raises(DeadlineExceeded):
        eng.submit([1, 2], max_new_tokens=2,
                   deadline=Deadline(time.monotonic() - 1.0))
    assert not eng.has_work()
    # expires while queued: the next tick fails it WITHOUT a prefill
    r = eng.submit([1, 2], max_new_tokens=2,
                   deadline=Deadline.after_ms(1))
    time.sleep(0.02)
    eng.step()
    with pytest.raises(DeadlineExceeded):
        r.result()
    assert eng.stats["expired"] == 1
    assert eng.stats["prefills"] == 0   # no slot/compile spent on it
    assert not eng.has_work()


def test_engine_stream_deadline_threads_through():
    import time
    from paddle_tpu.inference.overload import Deadline, DeadlineExceeded
    eng = PagedKVEngine(_model(), max_slots=1, page_size=4, num_pages=9,
                        steps_per_tick=2)
    it = eng.stream(np.asarray([[1, 2]], np.int32), max_new_tokens=2,
                    deadline=Deadline(time.monotonic() - 1.0))
    with pytest.raises(DeadlineExceeded):
        next(it)
    eng.stop()


def test_stream_partial_admission_failure_cancels_submitted_rows():
    """A non-overload failure on a later row (per-row page-count
    validation) must cancel the rows already admitted — they would
    otherwise keep decoding to max_new_tokens for a caller that
    already got the exception."""
    import time
    eng = PagedKVEngine(_model(), max_slots=2, page_size=4, num_pages=16,
                        max_pages_per_slot=3, steps_per_tick=2)
    ids = np.tile(np.arange(1, 11, dtype=np.int32), (2, 1))
    mask = np.ones_like(ids, bool)
    mask[0, 2:] = False     # row 0: 2 tokens + 8 new -> fits (3 pages)
    #                         row 1: 10 tokens + 8 new -> needs 5 > 3
    it = eng.stream(ids, max_new_tokens=8, attention_mask=mask)
    try:
        with pytest.raises(ValueError, match="max_pages_per_slot"):
            next(it)
        for _ in range(200):
            if not eng.has_work():
                break
            time.sleep(0.05)
        assert not eng.has_work()
        assert eng.stats["cancelled"] == 1
        # same steady state the cancel-frees test pins: at most the
        # retired slot's residual page stays out of the pool
        assert len(eng._free) >= eng.num_pages - 1
        assert eng._reserved_unalloc == 0
    finally:
        eng.stop()


# -- Pallas decode kernel + int8 KV (ISSUE 6) -------------------------------

def test_pallas_kernel_greedy_parity_vs_jnp():
    """The acceptance bar: kernel="pallas" (interpret on CPU) produces
    EXACTLY the jnp path's tokens at f32 — including a request that
    joins mid-decode of another."""
    model = _model()
    pa, pb = [5, 9, 2, 14], [17, 3, 11]
    mk = lambda kern: PagedKVEngine(                       # noqa: E731
        model, max_slots=2, page_size=4, num_pages=24,
        max_pages_per_slot=6, steps_per_tick=2, kernel=kern)
    ej, ep = mk("jnp"), mk("pallas")
    assert ej.decode_kernel == "jnp"
    assert ep.decode_kernel == "pallas"
    results = {}
    for name, eng in (("jnp", ej), ("pallas", ep)):
        ra = eng.submit(pa, max_new_tokens=10)
        eng.step()
        rb = eng.submit(pb, max_new_tokens=6)    # joins mid-decode
        eng.run_until_idle()
        results[name] = (ra.result(), rb.result())
    assert results["pallas"] == results["jnp"]
    solo_a = np.asarray(generate(model, np.asarray([pa], np.int32),
                                 max_new_tokens=10))[0].tolist()[len(pa):]
    assert results["pallas"][0] == solo_a


_STORED = {
    # a request joins mid-decode; heads of 8 at pages of 4: two heads a
    # sublane tile of rows
    "join_mid_decode": dict(),
    # heads of 64 at pages of 16: two tokens a 128-lane row
    "folded_rows": dict(wide=True, page_size=16, num_pages=12,
                        max_pages_per_slot=4),
    # the second request of a prompt shares its pages and prefills a tail
    "prefix_hit": dict(prefix_cache_pages=8, repeat=True),
    # draft steps write one token, the verify pass four from inside a page
    "speculative": dict(draft=True, spec_tokens=3),
    # chunks after the first start where the one before ended
    "chunked_prefill": dict(prefill_chunk=3),
}


@pytest.mark.parametrize("case", sorted(_STORED))
def test_pallas_engine_writes_pools_stored_as_rows(case):
    """An engine whose decode attends through the kernel keeps K and V
    as the kernel's rows and writes them through `paged_kv_write`
    (interpreted here): its greedy tokens are the jnp engine's, whose
    pools are by heads and written by XLA's scatter."""
    from paddle_tpu.kernels.paged_attention import pool_rows_shape
    kw = dict(_STORED[case])
    wide, draft, repeat = (kw.pop(k, False)
                           for k in ("wide", "draft", "repeat"))
    model = _model()
    if wide:
        paddle_tpu.seed(0)
        model = LlamaForCausalLM(tiny_llama_config(
            num_hidden_layers=2, vocab_size=97, hidden_size=128,
            intermediate_size=64, num_attention_heads=2,
            num_key_value_heads=2))
    if draft:
        paddle_tpu.seed(5)
        kw["draft_model"] = LlamaForCausalLM(model.config)
    geo = dict(max_slots=2, page_size=4, num_pages=24,
               max_pages_per_slot=6, steps_per_tick=2)
    geo.update(kw)
    pa, pb = [5, 9, 2, 14, 21, 7, 30, 4, 11], [17, 3, 11]
    results = {}
    for kern in ("jnp", "pallas"):
        eng = PagedKVEngine(model, kernel=kern, **geo)
        ra = eng.submit(pa, max_new_tokens=7)
        eng.step()
        rb = eng.submit(pa if repeat else pb, max_new_tokens=5)
        eng.run_until_idle()
        results[kern] = (ra.result(), rb.result())
        cfg = model.config
        hk = cfg.num_key_value_heads
        hd = cfg.hidden_size // cfg.num_attention_heads
        by_head = (eng.num_pages, hk, eng.page_size, hd)
        if kern == "jnp":
            assert eng.pools[0][0].shape == by_head
            assert eng.stats["kv_write_kernel_ticks"] == 0
            continue
        rows = pool_rows_shape(eng.num_pages, hk, hd, eng.page_size,
                               eng.pools[0][0].dtype)
        assert rows != by_head
        assert all(a.shape == rows for kv in eng.pools for a in kv)
        if draft:
            assert all(a.shape == rows for kv in eng.draft_pools
                       for a in kv)
            assert eng.stats["spec_ticks"] > 0
        if repeat:
            assert eng.stats["prefix_hits"] == 1
        assert eng.stats["kv_write_kernel_ticks"] == eng.stats["ticks"] > 0
    assert results["pallas"] == results["jnp"]
    assert len(results["jnp"][0]) == 7 and len(results["jnp"][1]) == 5


def test_pallas_kernel_long_generation_page_soak():
    """Long-generation parity soak: lens crosses >= 3 page boundaries
    (prompt 3 + 18 new = 21 positions over page_size-4 pages = 6
    pages); kernel and jnp paths stay token-identical the whole way."""
    model = _model()
    prompt = [5, 9, 2]
    outs = {}
    for kern in ("jnp", "pallas"):
        eng = PagedKVEngine(model, max_slots=1, page_size=4,
                            num_pages=16, max_pages_per_slot=6,
                            steps_per_tick=3, kernel=kern)
        outs[kern] = eng.generate([prompt], max_new_tokens=18)[0]
        assert len(eng._free) == eng.num_pages - 1
    assert outs["pallas"] == outs["jnp"]
    assert len(outs["pallas"]) == 18
    solo = np.asarray(generate(model, np.asarray([prompt], np.int32),
                               max_new_tokens=18))[0].tolist()[3:]
    assert outs["pallas"] == solo


def test_pallas_kernel_mixed_sampling_tick():
    """Greedy + sampled slots share one kernel-path tick: the greedy
    row is untouched by its sampling neighbor and still matches the
    solo run; sampled output replays per engine seed."""
    model = _model()
    mk = lambda: PagedKVEngine(model, max_slots=2, page_size=4,  # noqa
                               num_pages=24, max_pages_per_slot=6,
                               steps_per_tick=3, seed=11,
                               kernel="pallas")
    eng = mk()
    rg = eng.submit([5, 9, 2], max_new_tokens=6)
    rs = eng.submit([5, 9, 2], max_new_tokens=6, do_sample=True,
                    temperature=0.8, top_k=20, top_p=0.9)
    eng.run_until_idle()
    solo = np.asarray(generate(model, np.asarray([[5, 9, 2]], np.int32),
                               max_new_tokens=6))[0].tolist()[3:]
    assert rg.result() == solo
    toks = rs.result()
    assert len(toks) == 6
    assert all(0 <= t < model.config.vocab_size for t in toks)
    eng2 = mk()
    rg2 = eng2.submit([5, 9, 2], max_new_tokens=6)
    rs2 = eng2.submit([5, 9, 2], max_new_tokens=6, do_sample=True,
                      temperature=0.8, top_k=20, top_p=0.9)
    eng2.run_until_idle()
    assert rs2.result() == toks and rg2.result() == solo


def test_pallas_kernel_speculative_parity():
    """Speculative decoding rides the kernel path for its s=1 draft
    steps (the g+1-row verify stays jnp): output is still EXACTLY the
    solo target tokens."""
    model = _model()
    paddle_tpu.seed(5)
    from paddle_tpu.models.llama import LlamaForCausalLM
    draft = LlamaForCausalLM(model.config)
    pa = [5, 9, 2, 14]
    solo = np.asarray(generate(model, np.asarray([pa], np.int32),
                               max_new_tokens=9))[0].tolist()[len(pa):]
    eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=40,
                        max_pages_per_slot=8, steps_per_tick=3,
                        draft_model=draft, spec_tokens=3,
                        kernel="pallas")
    r = eng.submit(pa, max_new_tokens=9)
    eng.run_until_idle()
    assert r.result() == solo
    assert eng.stats["spec_ticks"] > 0


def test_int8_kv_greedy_deterministic_replay():
    """int8 KV pools: generation is deterministic across same-seed
    engines (the quantize-at-scatter path has no hidden state), tokens
    are valid ids, and pages recycle cleanly."""
    model = _model()
    prompts = [[5, 9, 2], [17, 3, 11, 4]]
    mk = lambda: PagedKVEngine(model, max_slots=2, page_size=4,  # noqa
                               num_pages=24, max_pages_per_slot=6,
                               steps_per_tick=3, kernel="pallas",
                               kv_dtype="int8")
    e1, e2 = mk(), mk()
    g1 = e1.generate(prompts, max_new_tokens=10)
    g2 = e2.generate(prompts, max_new_tokens=10)
    assert g1 == g2
    assert all(0 <= t < model.config.vocab_size for r in g1 for t in r)
    assert len(e1._free) == e1.num_pages - 1
    # kernel and jnp attends agree on the SAME quantized pools too
    e3 = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=24,
                       max_pages_per_slot=6, steps_per_tick=3,
                       kernel="jnp", kv_dtype="int8")
    assert e3.generate(prompts, max_new_tokens=10) == g1


def test_int8_kv_with_speculative_draft():
    """int8 KV composes with speculative decoding: the draft rides its
    own arity-4 (k, v, k_scale, v_scale) pools through the spec tick,
    retire zeroes BOTH models' scale planes, output is valid and
    replays deterministically across same-seed engines."""
    model = _model()
    paddle_tpu.seed(5)
    from paddle_tpu.models.llama import LlamaForCausalLM
    draft = LlamaForCausalLM(model.config)
    mk = lambda: PagedKVEngine(model, max_slots=2, page_size=4,  # noqa
                               num_pages=40, max_pages_per_slot=8,
                               steps_per_tick=3, draft_model=draft,
                               spec_tokens=3, kernel="pallas",
                               kv_dtype="int8", seed=7)
    e1, e2 = mk(), mk()
    assert len(e1.draft_pools[0]) == 4
    g1 = e1.generate([[5, 9, 2, 14]], max_new_tokens=8)
    assert e1.stats["spec_ticks"] > 0
    assert len(g1[0]) == 8
    assert all(0 <= t < model.config.vocab_size for t in g1[0])
    assert e2.generate([[5, 9, 2, 14]], max_new_tokens=8) == g1
    # every ALLOCATABLE page's scales reset by retire; row 0 is the
    # trash page — the spec verify deliberately routes past-budget
    # writes there (always masked on read), so its scale may be >0
    for pools in (e1.pools, e1.draft_pools):
        for _kp, _vp, ks, vs in pools:
            assert float(jnp.abs(ks[1:]).sum()) == 0.0
            assert float(jnp.abs(vs[1:]).sum()) == 0.0


def test_int8_kv_scales_reset_on_page_recycle():
    """Quant scales only grow at scatter time (scatter-max), so retire
    must zero the freed pages' scale rows — otherwise a recycled page
    quantizes its next tenant with the largest magnitude any PREVIOUS
    tenant wrote and precision ratchets away over server lifetime.
    Behavioral pin: a fresh engine and one that already served (and
    retired) a request produce identical tokens for the same request."""
    model = _model()
    mk = lambda: PagedKVEngine(model, max_slots=1, page_size=4,  # noqa
                               num_pages=12, max_pages_per_slot=4,
                               steps_per_tick=3, kernel="pallas",
                               kv_dtype="int8")
    used, fresh = mk(), mk()
    r1 = used.generate([[40, 41, 42, 43]], max_new_tokens=6)
    # every allocatable page's scale row is back to zero after the
    # retire (row 0 is the trash page — excluded, see the spec test)
    for kp, vp, ks, vs in used.pools:
        assert float(jnp.abs(ks[1:]).sum()) == 0.0
        assert float(jnp.abs(vs[1:]).sum()) == 0.0
    g_used = used.generate([[5, 9, 2]], max_new_tokens=8)
    g_fresh = fresh.generate([[5, 9, 2]], max_new_tokens=8)
    assert g_used == g_fresh


def test_int8_kv_sampling_matches_target_distribution():
    """TV-distance pin for int8-KV sampling (the speculative tick's
    statistical-pin pattern): over many keys, the first sampled
    token's marginal must match the processed softmax of the model
    evaluated on the SAME int8 caches — quantization shifts the
    logits, but sampling on top of them must stay unbiased."""
    model = _model()
    eng = PagedKVEngine(model, max_slots=1, page_size=4, num_pages=24,
                        max_pages_per_slot=10, steps_per_tick=1,
                        kernel="pallas", kv_dtype="int8", seed=0)
    r = eng.submit([5, 9, 2], max_new_tokens=30, do_sample=True,
                   temperature=0.8, top_k=0, top_p=1.0)
    eng._admit()                       # prefill only; no tick yet
    a = eng._slot_arrays([0])
    fn = eng._tick_fn(True)
    flat = [x for kv in eng.pools for x in kv]

    from paddle_tpu.inference.paged import (PagedState,
                                            _process_logits_rowwise)
    state = PagedState(jnp.asarray(eng._bt), jnp.asarray(a["lens"]),
                       jnp.asarray(a["active"]).astype(jnp.int32))
    logits, _ = model(Tensor(jnp.asarray(a["tok"])[:, None]),
                      caches=eng._layer_caches(flat),
                      position_ids=Tensor(jnp.asarray(a["lens"])[:, None]),
                      cache_index=state)
    want = np.asarray(jax.nn.softmax(_process_logits_rowwise(
        logits._value[:, -1], jnp.asarray(a["temp"]),
        jnp.asarray(a["topk"]), jnp.asarray(a["topp"])), axis=-1))[0]

    trials = 400
    counts = np.zeros(model.config.vocab_size)
    rows = tuple(jnp.asarray(a[k]) for k in
                 ("tok", "lens", "active", "limit"))
    args_fixed = (rows, jnp.asarray(eng._bt), jnp.asarray(a["eos"]))
    sample_args = (jnp.asarray(a["temp"]), jnp.asarray(a["topk"]),
                   jnp.asarray(a["topp"]), jnp.asarray(a["wants"]))
    donated = jax.default_backend() != "cpu"   # mirror the engine gate
    for s in range(trials):
        key = jax.random.key(1000 + s)
        fl = [jnp.copy(x) for x in flat] if donated else list(flat)
        toks, _, _ = fn(*args_fixed, jax.random.key_data(key),
                        np.int32(0), *sample_args, fl)
        counts[int(np.asarray(toks)[0, 0])] += 1
    tv = 0.5 * np.abs(counts / trials - want).sum()
    # same bound as the speculative pin: sampling noise at 400 trials
    # over ~97 tokens comfortably separates unbiased sampling from
    # e.g. sampling the UNquantized distribution's argmax region
    assert tv < 0.25, tv


def test_kv_dtype_int8_halves_bytes_per_slot():
    """kv_dtype honored end-to-end: the exported bytes/slot figure
    comes from the real buffer dtypes — int8 pools (plus their f32
    scale planes) cost at most ~0.57x the bf16 figure here (tiny dims;
    the scale overhead vanishes at production page_size x head_dim)."""
    from paddle_tpu.observability.metrics import MetricsRegistry
    model = _model()
    mk = lambda kd: PagedKVEngine(model, max_slots=2,       # noqa
                                  page_size=4, num_pages=24,
                                  max_pages_per_slot=6, kv_dtype=kd)
    bf16, int8 = mk("bf16"), mk("int8")
    assert int8.kv_bytes_per_slot() <= 0.6 * bf16.kv_bytes_per_slot()
    assert int8.pools[0][0].dtype == jnp.int8
    assert int8.pools[0][2].dtype == jnp.float32
    assert str(bf16.pools[0][0].dtype) == "bfloat16"
    reg = MetricsRegistry()
    int8.export_metrics(reg)
    assert reg.gauge("inference.kv.bytes_per_slot").value() \
        == int8.kv_bytes_per_slot()


def test_engine_kernel_config_validation():
    model = _model()
    with pytest.raises(ValueError, match="kernel"):
        PagedKVEngine(model, kernel="bogus")
    with pytest.raises(ValueError, match="kv_dtype"):
        PagedKVEngine(model, kv_dtype="fp4")
    # auto on CPU stays on the jnp path (interpret mode is a parity
    # tool, not a fast path)
    eng = PagedKVEngine(model, max_slots=1, page_size=4, num_pages=16)
    assert eng.decode_kernel == "jnp"


def test_decode_kernel_tick_counter():
    """inference.decode.kernel counts ticks by attend path when
    observability is enabled."""
    from paddle_tpu import observability
    model = _model()
    with observability.scoped() as reg:
        eng = PagedKVEngine(model, max_slots=1, page_size=4,
                            num_pages=16, max_pages_per_slot=4,
                            steps_per_tick=2, kernel="pallas")
        eng.generate([[5, 9, 2]], max_new_tokens=4)
        assert reg.counter("inference.decode.kernel").value(
            path="pallas") >= 1
        assert reg.counter("inference.decode.kernel").value(
            path="jnp") == 0
        # the write follows the attend
        assert reg.counter("inference.kv_write.kernel").value(
            path="pallas") == eng.stats["kv_write_kernel_ticks"] >= 1
        assert reg.counter("inference.kv_write.kernel").value(
            path="xla") == 0


@pytest.mark.parametrize("kernel,kv_dtype", [
    ("pallas", None), ("pallas", "int8"), ("jnp", None)])
def test_engine_reports_its_decode_plan(kernel, kv_dtype):
    """engine.decode_plan is the kernel's own account of one call at
    this geometry (heads and pages a grid step, grid, VMEM bytes), the
    gauge engine.decode_grid_steps its step count; both are counts from
    shapes, so a CPU box reports them."""
    from paddle_tpu.kernels.paged_attention import decode_plan
    from paddle_tpu.observability.metrics import MetricsRegistry
    model = _model()
    cfg = model.config
    eng = PagedKVEngine(model, max_slots=3, page_size=4, num_pages=32,
                        max_pages_per_slot=7, steps_per_tick=2,
                        kernel=kernel, kv_dtype=kv_dtype)
    reg = MetricsRegistry()
    eng.export_metrics(reg)
    if kernel == "jnp":
        assert eng.decode_plan is None
        assert reg.gauge("engine.decode_grid_steps").value() == 0
        return
    hk = cfg.num_key_value_heads or cfg.num_attention_heads
    plan = eng.decode_plan
    assert plan == decode_plan(
        cfg.num_attention_heads, hk,
        cfg.hidden_size // cfg.num_attention_heads, 4, 7,
        eng.pools[0][0].dtype, slots=3)
    assert plan.grid[0] == 3 and hk % plan.heads == 0
    assert plan.grid_steps == plan.grid[0] * plan.grid[1] * plan.grid[2]
    # fewer, larger steps than one (head, page) tile each
    assert plan.grid_steps < 3 * hk * 7
    assert reg.gauge("engine.decode_grid_steps").value() == plan.grid_steps


@pytest.mark.quick
def test_engine_export_metrics():
    """export_metrics publishes the stats dict as catalogued gauges
    (the /metrics integration PredictorServer scrapes)."""
    from paddle_tpu.observability.metrics import MetricsRegistry
    model = _model()
    eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=24,
                        max_pages_per_slot=6, steps_per_tick=3)
    eng.generate([[5, 9, 2]], max_new_tokens=4)
    reg = MetricsRegistry()
    eng.export_metrics(reg)
    assert reg.gauge("engine.finished").value() == 1
    assert reg.gauge("engine.ticks").value() >= 1
    assert reg.gauge("engine.tokens_out").value() >= 4
    assert reg.gauge("engine.pending").value() == 0
    assert "paddle_tpu_engine_finished 1" in reg.prometheus_text()


# -- one decode tick in flight (ISSUE 32) -----------------------------------

def _drive(eng, ahead, events=()):
    """Run the engine dry one scheduler iteration at a time: as the loops
    that own it do (`ahead`: one tick left in flight between two
    iterations) or by plain step() calls. `events` maps an iteration's
    index to a callable run before it."""
    events, k = dict(events), 0
    while eng.has_work() or events:
        if k in events:
            events.pop(k)(eng)
        eng._step(ahead=True) if ahead else eng.step()
        k += 1
        assert k < 400


_PROMPTS = ([5, 9, 2, 14], [17, 3, 11], [7, 8], [21, 4, 6, 13, 2])
_FLYING = {
    # four requests over two slots: first, chained and post-admission ticks
    "greedy": dict(),
    "sampled": dict(submit=dict(do_sample=True, temperature=0.9, top_k=20)),
    # request 0 ends on its eos inside a tick that others outlive, after
    # the next tick was launched with it live
    "eos_mid_tick": dict(eos=5),
    # budgets that end on a tick's first and second step
    "budget_mid_tick": dict(budgets=(5, 9, 12, 6)),
    # every request in a slot from the start, so ticks chain at once
    "submit_in_flight": dict(slots=3, late=2),
    "cancel_in_flight": dict(slots=3, late=4, cancel=2,
                             budgets=(20, 20, 20, 8)),
    "pallas": dict(engine=dict(kernel="pallas")),
    "int8": dict(engine=dict(kernel="pallas", kv_dtype="int8")),
    "draft": dict(draft=True),
}


@pytest.mark.parametrize("case", sorted(_FLYING))
def test_tick_in_flight_emits_the_synchronous_orders_tokens(case):
    """The loop that keeps one decode tick in flight emits, request by
    request, exactly what a loop of plain step() calls emits on a twin
    engine (same seed, same script), and launches every tick through the
    one tick program."""
    kw = _FLYING[case]
    model = _model()
    draft = None
    if kw.get("draft"):
        paddle_tpu.seed(5)
        draft = LlamaForCausalLM(model.config)
    budgets = kw.get("budgets", (11, 6, 14, 9))
    submit = dict(kw.get("submit", {}))
    eos = None
    if "eos" in kw:
        solo = np.asarray(generate(
            model, np.asarray([_PROMPTS[0]], np.int32),
            max_new_tokens=budgets[0]))[0].tolist()[len(_PROMPTS[0]):]
        eos = solo[kw["eos"]]
    seen = {}

    def run(ahead):
        eng = PagedKVEngine(
            model, max_slots=kw.get("slots", 2), page_size=4, num_pages=64,
            max_pages_per_slot=8, steps_per_tick=3, seed=7,
            draft_model=draft, spec_tokens=3, **kw.get("engine", {}))
        upfront = len(_PROMPTS) - ("late" in kw)
        reqs = [eng.submit(p, b, eos_token_id=eos if i == 0 else None,
                           **submit)
                for i, (p, b) in enumerate(zip(_PROMPTS[:upfront], budgets))]
        events = {}
        if "late" in kw:
            def late(eng):
                if ahead:       # the tick before is still on the device
                    assert eng._flying is not None
                    seen["chained_at_submit"] = eng.stats["ticks_chained"]
                reqs.append(eng.submit(_PROMPTS[-1], budgets[-1], **submit))

            def landed(eng):
                if ahead:
                    # the iteration between landed the tick in flight and
                    # admitted nothing: the request joins after the landing
                    assert eng._flying is None
                    assert eng.stats["admitted"] == upfront
                    assert eng.stats["ticks_chained"] \
                        == seen["chained_at_submit"]
            events[kw["late"]] = late
            events[kw["late"] + 1] = landed
        if "cancel" in kw:
            def cancel(eng):
                if ahead:
                    assert eng._flying is not None
                seen[ahead] = len(reqs[1].tokens)
                reqs[1].cancel()
            events[kw["cancel"]] = cancel
        _drive(eng, ahead, events)
        return eng, reqs

    flying, got = run(ahead=True)
    twin, want = run(ahead=False)
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert all(r.done.is_set() and r.error is None for r in got)
    if eos is not None:
        assert got[0].tokens[-1] == eos and len(got[0].tokens) < budgets[0]
        assert (len(got[0].tokens) - 1) % 3 != 0       # inside a tick
    if "cancel" in kw:
        # the rows of the tick that flew under the cancel were dropped,
        # and the late request decoded over the pages it gave back
        assert len(got[1].tokens) == seen[True] == seen[False] < budgets[1]
        assert flying.stats["cancelled"] == 1
    # nothing left on the device, every page back
    assert flying._flying is None and not flying.has_work()
    assert len(flying._free) == flying.num_pages - 1
    assert flying._reserved_unalloc == 0
    assert twin.stats["ticks_chained"] == 0
    if draft is not None:
        assert flying.stats["ticks_chained"] == 0       # depth stays 0
        assert flying.stats["ticks"] == twin.stats["ticks"]
        return
    assert 0 < flying.stats["ticks_chained"] < flying.stats["ticks"]
    assert flying.stats["kv_write_kernel_ticks"] == (
        flying.stats["ticks"] if flying.kv_write == "pallas" else 0)
    # first, chained and post-admission ticks: one program, traced once
    for eng in (flying, twin):
        ticks = [k for k in eng._programs if k[0] == "tick"]
        assert ticks == [("tick", bool(submit))]
        assert eng._programs[ticks[0]].func._cache_size() == 1
    if "late" not in kw:
        # the step keys: a tick's index goes to a tick that decoded
        # something (a request that came under a tick in flight joins a
        # tick later than in the twin, and the counts part there)
        assert flying._tick_count == twin._tick_count


def _two_long(eng, new=9):
    return [eng.submit(p, new) for p in _PROMPTS[:2]]


def test_tick_in_flight_counters_and_tick_log():
    """A scripted run with nothing pending: every tick but the one
    launched from the host's rows is chained, each iteration lands one
    tick and logs the TICK_PHASES in order."""
    from paddle_tpu.inference.paged import TICK_PHASES
    eng = PagedKVEngine(_model(), max_slots=2, page_size=4, num_pages=24,
                        max_pages_per_slot=6, steps_per_tick=2)
    reqs = _two_long(eng)       # 1 token from the prefill, 8 from 4 ticks
    chained = []
    while eng.has_work():
        eng._step(ahead=True)
        chained.append(eng._flying is not None)
    # the last tick but one would find every slot ended by its budget
    assert chained == [True, True, True, False]
    assert eng.stats["ticks"] == 4 and eng.stats["ticks_chained"] == 3
    assert all(len(r.tokens) == 9 for r in reqs)
    rows = list(eng.tick_log)
    assert len(rows) == 4
    live, prefills = 2 + len(TICK_PHASES), 3 + len(TICK_PHASES)
    for row in rows:
        # ..., live slots, prefills, the ticker's CPU over host phases
        assert len(row) == 2 + len(TICK_PHASES) + 3 and row[live] == 2
        assert all(p >= 0.0 for p in row[2:2 + len(TICK_PHASES)])
        assert row[2 + TICK_PHASES.index("readback")] > 0.0
        assert row[-1] >= 0.0
    assert [r[prefills] for r in rows] == [2, 0, 0, 0]
    assert [r[0] for r in rows] == [1, 2, 3, 4]         # one seq a tick
    s = eng.stats
    assert s["tick_host_s"] + s["readback_s"] + s["prefill_s"] \
        == pytest.approx(s["tick_wall_s"], rel=0.05)


def test_step_returns_with_nothing_in_flight():
    model = _model()
    solo = [np.asarray(generate(model, np.asarray([p], np.int32),
                                max_new_tokens=9))[0].tolist()[len(p):]
            for p in _PROMPTS[:2]]
    eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=24,
                        max_pages_per_slot=6, steps_per_tick=2)
    reqs = _two_long(eng)
    assert eng._step(ahead=True) and eng._flying is not None
    ticks = eng.stats["ticks"]
    assert eng.step() is True           # lands it, launches nothing
    assert eng._flying is None and eng.stats["ticks"] == ticks + 1
    assert eng.step() is True and eng._flying is None
    eng.run_until_idle()
    assert [r.result() for r in reqs] == solo


def test_a_wholly_dead_tick_hands_its_index_on():
    """Every slot ends on its eos inside tick N with N+1 launched: N+1
    decodes nothing, `has_work()` holds until it has landed, and the next
    request's sampled tokens are those of an engine that never launched
    ahead."""
    model = _model()
    solo = np.asarray(generate(model, np.asarray([_PROMPTS[0]], np.int32),
                               max_new_tokens=12))[0].tolist()[4:]

    def run(ahead):
        eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=24,
                            max_pages_per_slot=6, steps_per_tick=2, seed=3)
        first = eng.submit(_PROMPTS[0], 12, eos_token_id=solo[5])
        _drive(eng, ahead)
        second = eng.submit(_PROMPTS[1], 8, do_sample=True, temperature=0.9)
        _drive(eng, ahead)
        return eng, first.tokens, second.tokens

    flying, *got = run(True)
    twin, *want = run(False)
    assert got == want and got[0] == solo[:6]
    # the dead tick ran on the device and is counted; its index is not
    assert flying.stats["ticks"] == twin.stats["ticks"] + 1
    assert flying._tick_count == twin._tick_count


class _Boom:
    def __array__(self, *a, **k):
        raise RuntimeError("chip fell over")


def test_error_from_a_tick_in_flight_fails_every_waiter():
    eng = PagedKVEngine(_model(), max_slots=2, page_size=4, num_pages=24,
                        max_pages_per_slot=6, steps_per_tick=2)
    reqs = _two_long(eng, new=18)
    assert eng._step(ahead=True) and eng._flying is not None
    reqs.append(eng.submit(_PROMPTS[2], 4))         # still queued
    eng._flying = eng._flying._replace(toks=_Boom())
    with pytest.raises(RuntimeError, match="chip fell over"):
        eng._ticker_loop()
    for r in reqs:
        assert r.done.is_set() and isinstance(r.error, RuntimeError)
        with pytest.raises(RuntimeError, match="chip fell over"):
            r.result()
    assert eng._flying is None and not eng.has_work()
    assert not any(eng._slots)
    assert len(eng._free) == eng.num_pages - 1       # pages returned
    assert eng._reserved_unalloc == 0


def test_stop_lands_the_tick_in_flight_and_joins():
    model = _model()
    solo = [np.asarray(generate(model, np.asarray([p], np.int32),
                                max_new_tokens=40))[0].tolist()[len(p):]
            for p in _PROMPTS[:2]]
    eng = PagedKVEngine(model, max_slots=2, page_size=4, num_pages=64,
                        max_pages_per_slot=12, steps_per_tick=2)
    reqs = _two_long(eng, new=40)
    eng.start()
    import time
    give_up = time.monotonic() + 60
    while eng.stats["ticks_chained"] < 2 and time.monotonic() < give_up:
        time.sleep(0.002)
    eng.stop()
    assert not eng._ticker.is_alive() and eng._flying is None
    # what was on the device reached the streams: nothing lost, and a
    # later driver goes on from there
    assert sum(len(r.tokens) for r in reqs) == eng.stats["tokens_out"]
    eng.run_until_idle()
    assert [r.result() for r in reqs] == solo
