"""ISSUE 20 — disaggregated prefill/decode pools with prefix-keyed KV
page handoff (inference/disagg.py + engine/serving/router wiring).

The load-bearing scenarios:

- the bundle wire format round-trips BYTE-identically (bf16 via
  ml_dtypes, int8 payloads with their f32 scale rows, nullable draft
  mirrors) and rejects malformed blobs;
- engine-level handoff is exactly lossless: a role="prefill" engine
  prefills + exports, a role="decode" engine imports + decodes, and
  the tokens equal the monolithic engine's greedy output on BOTH
  attend paths (jnp and interpret-Pallas) with int8 KV — including
  byte-identical quant scale rows across the two engines' pools and
  a settled refcount ledger after import;
- chain-key dedup: re-importing resident pages moves nothing;
- the HandoffArbiter grants transfer slots in weighted-fair virtual-
  finish-time order (a heavier tenant jumps a storming tenant's
  backlog) and times out into "proceed unarbitrated", never "drop";
- the two-hop HTTP path: the router learns roles from probed /stats,
  routes hop 1 to the prefill pool and hop 2 to the decode pool with
  the chain keys as an internal header, the decode replica pulls only
  missing pages over /kv/pull, and a warm decode replica transfers
  nothing on the repeat;
- chaos `disagg.transfer.fail` at rate 1.0: every concurrent request
  still completes with the RIGHT tokens via local decode on the warm
  prefill replica (slower, never wrong), zero hangs;
- the `inference.disagg.*` / `router.disagg.*` metric families are
  catalogued both directions (house AST pin).

Engines run the same tiny deterministic llama tier-1 uses everywhere.
"""
import ast
import json
import os
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import observability as obs
from paddle_tpu.distributed import chaos
from paddle_tpu.inference.disagg import (DisaggStats, HandoffArbiter,
                                         PageBundleEntry, pack_bundle,
                                         unpack_bundle)
from paddle_tpu.inference.paged import PagedKVEngine
from paddle_tpu.inference.prefix import chain_keys
from paddle_tpu.inference.router import ReplicaRouter
from paddle_tpu.inference.serving import PredictorServer
from paddle_tpu.inference.tenancy import TenantPolicy, TenantTable
from paddle_tpu.models.generation import generate
from paddle_tpu.models.llama import LlamaForCausalLM, tiny_llama_config

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.usefixtures("no_leaked_threads")

from conftest import wait_for as _wait_for  # noqa: E402

_MODEL = None
PREFIX = [5, 9, 2, 14, 17, 3, 11, 4]          # 2 full pages of 4


def _model(seed=0):
    global _MODEL
    if _MODEL is None:
        paddle_tpu.seed(seed)
        cfg = tiny_llama_config(num_hidden_layers=2, vocab_size=97,
                                hidden_size=32, intermediate_size=64,
                                num_attention_heads=4,
                                num_key_value_heads=2)
        _MODEL = LlamaForCausalLM(cfg)
    return _MODEL


def _solo(model, prompt, n):
    return np.asarray(generate(
        model, np.asarray([prompt], np.int32),
        max_new_tokens=n))[0].tolist()[len(prompt):]


def _ledger_settled(eng):
    cached = set(eng.prefix_cache.pages())
    assert set(eng._page_refs) == cached
    assert eng._cached_pages == cached
    assert eng._reclaimable == len(cached)
    assert len(eng._free) == eng.num_pages - 1 - len(cached)


# -- bundle wire format ------------------------------------------------------

def test_bundle_roundtrip_byte_identity():
    """pack -> unpack reproduces every array bit-for-bit: bf16 KV,
    int8 KV with f32 scale rows, present and absent draft mirrors,
    multiple entries in order."""
    import ml_dtypes
    rng = np.random.RandomState(0)
    bf16 = rng.randn(4, 2, 8).astype(ml_dtypes.bfloat16)
    i8 = rng.randint(-128, 128, (4, 2, 8)).astype(np.int8)
    scale = rng.rand(4, 2).astype(np.float32)
    e1 = PageBundleEntry("k1", [(i8, i8 * 2, scale, scale + 1.0)],
                         draft=[(i8 * 3, i8, scale, scale)])
    e2 = PageBundleEntry("k2", [(bf16, bf16 + 1)])
    raw = pack_bundle([e1, e2])
    out = unpack_bundle(raw)
    assert [o.key for o in out] == ["k1", "k2"]
    assert out[1].draft is None
    for orig, got in ((e1, out[0]), (e2, out[1])):
        for g_orig, g_got in zip(orig.layers, got.layers):
            for a, b in zip(g_orig, g_got):
                assert a.dtype == b.dtype and a.shape == b.shape
                assert a.tobytes() == b.tobytes()
    for a, b in zip(e1.draft[0], out[0].draft[0]):
        assert a.tobytes() == b.tobytes()
    assert out[0].nbytes == e1.nbytes
    # malformed blobs are typed errors, not crashes
    with pytest.raises(ValueError):
        unpack_bundle(b"nope" + raw)
    with pytest.raises(ValueError):
        unpack_bundle(raw[:len(raw) - 8])


# -- engine-level handoff ----------------------------------------------------

@pytest.mark.parametrize("kernel", ["jnp", "pallas"])
def test_engine_handoff_greedy_parity_int8(kernel):
    """The acceptance bar: export -> pack -> unpack -> stage -> import
    -> decode reproduces EXACTLY the monolithic engine's greedy tokens
    with int8 KV on both attend paths; the imported pages' int8 quant
    scale rows are byte-identical across the two engines' pools; the
    decode engine's refcount ledger settles; re-importing resident
    pages dedups to zero work."""
    model = _model()
    kw = dict(max_slots=2, page_size=4, num_pages=32,
              max_pages_per_slot=8, steps_per_tick=2,
              prefix_cache_pages=8, kv_dtype="int8", kernel=kernel)
    prompt = PREFIX + [21, 22, 23]
    mono = PagedKVEngine(model, **kw)
    want = mono.generate([prompt], max_new_tokens=6)[0]
    mono.stop()

    pre = PagedKVEngine(model, role="prefill",
                        host_tier_bytes=1 << 20, **kw)
    dec = PagedKVEngine(model, role="decode", **kw)
    try:
        # hop 1: the prefill phase (serving clamps to one token)
        pre.generate([prompt], max_new_tokens=1)
        keys = chain_keys(prompt, 4)
        entries = pre.export_pages(keys)
        assert [e.key for e in entries] == keys and len(keys) == 2
        raw = pack_bundle(entries)
        # hop 2: a cold decode replica misses everything
        assert dec.disagg_missing(keys) == keys
        dec.stage_import(unpack_bundle(raw))
        toks = dec.generate([prompt], max_new_tokens=6)[0]
        assert toks == want
        snap = dec.disagg.snapshot()
        assert snap["imported_pages"] == 2
        assert snap["imported_bytes"] > 0
        # the imported pages ARE the prefill replica's pages: every
        # pool plane (k, v, k_scale, v_scale) byte-identical
        for key in keys:
            p_pre = pre.prefix_cache.get(key)
            p_dec = dec.prefix_cache.get(key)
            assert p_pre is not None and p_dec is not None
            for gp, gd in zip(pre.pools, dec.pools):
                assert len(gp) == 4          # int8 arity
                for a, b in zip(gp, gd):
                    assert np.asarray(a[p_pre]).tobytes() == \
                        np.asarray(b[p_dec]).tobytes()
        _ledger_settled(dec)
        # warm repeat: nothing is missing, a re-staged bundle dedups
        assert dec.disagg_missing(keys) == []
        dec.stage_import(unpack_bundle(raw))
        dec.generate([[1, 2, 3]], max_new_tokens=1)   # drains staged
        snap = dec.disagg.snapshot()
        assert snap["imported_pages"] == 2            # unchanged
        assert snap["dedup_skipped_pages"] == 2
        _ledger_settled(dec)
    finally:
        pre.stop()
        dec.stop()


@pytest.mark.parametrize("pre_kernel,dec_kernel", [
    ("pallas", "jnp"), ("jnp", "pallas"), ("pallas", "pallas")])
def test_engines_with_different_kernels_exchange_pages(pre_kernel,
                                                       dec_kernel):
    """A Pallas engine stores K and V as its kernel's rows; a page that
    leaves it is (kv_heads, page_size, head_dim) all the same, so a jnp
    engine imports it (and the other way round) and decodes the
    monolithic engine's tokens."""
    model = _model()
    kw = dict(max_slots=2, page_size=4, num_pages=32,
              max_pages_per_slot=8, steps_per_tick=2,
              prefix_cache_pages=8)
    prompt = PREFIX + [21, 22, 23]
    mono = PagedKVEngine(model, kernel="jnp", **kw)
    want = mono.generate([prompt], max_new_tokens=6)[0]
    mono.stop()
    pre = PagedKVEngine(model, role="prefill", host_tier_bytes=1 << 20,
                        kernel=pre_kernel, **kw)
    dec = PagedKVEngine(model, role="decode", kernel=dec_kernel, **kw)
    try:
        stored = {"pallas": (32, 1, 8, 8), "jnp": (32, 2, 4, 8)}
        assert pre.pools[0][0].shape == stored[pre_kernel]
        assert dec.pools[0][0].shape == stored[dec_kernel]
        pre.generate([prompt], max_new_tokens=1)
        keys = chain_keys(prompt, 4)
        entries = pre.export_pages(keys)
        assert [e.key for e in entries] == keys
        for e in entries:
            assert all(a.shape == (2, 4, 8) for kv in e.layers for a in kv)
        dec.stage_import(unpack_bundle(pack_bundle(entries)))
        assert dec.generate([prompt], max_new_tokens=6)[0] == want
        assert dec.disagg.snapshot()["imported_pages"] == 2
        for key in keys:
            for gp, gd in zip(pre.pools, dec.pools):
                for a, b in zip(gp, gd):
                    assert np.asarray(a[pre.prefix_cache.get(key)]) \
                        .tobytes() == np.asarray(
                            b[dec.prefix_cache.get(key)]).tobytes()
        _ledger_settled(dec)
    finally:
        pre.stop()
        dec.stop()


def test_role_validation_and_stats_block():
    model = _model()
    with pytest.raises(ValueError):
        PagedKVEngine(model, max_slots=1, page_size=4, num_pages=16,
                      role="prefill")          # needs a host tier
    with pytest.raises(ValueError):
        PagedKVEngine(model, max_slots=1, page_size=4, num_pages=16,
                      role="decode")           # needs a prefix cache
    with pytest.raises(ValueError):
        PagedKVEngine(model, max_slots=1, page_size=4, num_pages=16,
                      role="router")
    eng = PagedKVEngine(model, max_slots=1, page_size=4, num_pages=16)
    try:
        assert eng.disagg_stats()["role"] == "both"
        assert eng.export_pages(["x"]) == []   # no tier: nothing out
        assert eng.disagg_missing(["x"]) == ["x"]
        with pytest.raises(RuntimeError):
            eng.stage_import([PageBundleEntry(
                "x", [(np.zeros((1,), np.int8),)])])
    finally:
        eng.stop()


# -- the handoff arbiter -----------------------------------------------------

def test_arbiter_weighted_fair_grant_order():
    """WFQ over the transfer path: with a storm tenant's backlog
    queued, a heavier late arrival is granted FIRST (lower virtual
    finish time); a timeout yields False (proceed unarbitrated) and
    never wedges the queue."""
    table = TenantTable([TenantPolicy("storm", weight=1.0),
                         TenantPolicy("vip", weight=4.0)])
    arb = HandoffArbiter(table, max_concurrent=1)
    assert arb.acquire(None)                 # hold the only slot
    order, threads = [], []

    def waiter(tenant):
        assert arb.acquire(tenant, timeout=10.0)
        order.append(tenant)
        arb.release()

    for t in ("storm", "storm", "storm", "vip"):
        th = threading.Thread(target=waiter, args=(t,), daemon=True)
        th.start()
        threads.append(th)
        _wait_for(lambda n=len(threads):
                  arb.snapshot()["waiting"] == n,
                  what="waiter enqueued")
    # a full queue + held slot: timing out returns False, not a drop
    assert arb.acquire("late", timeout=0.05) is False
    arb.release()                            # open the floodgate
    for th in threads:
        th.join(timeout=10)
        assert not th.is_alive()
    assert order == ["vip", "storm", "storm", "storm"]
    snap = arb.snapshot()
    assert snap["active"] == 0 and snap["waiting"] == 0
    assert snap["granted"] == 5
    with pytest.raises(ValueError):
        HandoffArbiter(max_concurrent=0)
    # the slot() context reports held=False after timeout but still
    # lets the caller proceed (and must not release what it never had)
    arb2 = HandoffArbiter(max_concurrent=1)
    assert arb2.acquire(None)
    with arb2.slot(None, timeout=0.05) as held:
        assert held is False
    arb2.release()
    with arb2.slot(None) as held:
        assert held is True


# -- the two-hop HTTP path ---------------------------------------------------

def _pooled_fleet(model, **kw):
    pre = PagedKVEngine(model, role="prefill",
                        host_tier_bytes=1 << 20, **kw)
    dec = PagedKVEngine(model, role="decode", **kw)
    s0 = PredictorServer(lambda x: {"y": np.zeros((1, 1))},
                         model_name="r0", generator=pre).start()
    s1 = PredictorServer(lambda x: {"y": np.zeros((1, 1))},
                         model_name="r1", generator=dec).start()
    pairs = [("r0", f"127.0.0.1:{s0.port}"),
             ("r1", f"127.0.0.1:{s1.port}")]
    return pre, dec, [s0, s1], pairs


def _gen(port, ids, n):
    body = json.dumps({"ids": ids, "max_new_tokens": n}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return (json.loads(resp.read())["sequences"][0],
                resp.headers.get("X-Routed-To"))


def test_router_two_hop_handoff_and_warm_dedup():
    """The wired protocol end to end: probe learns roles from /stats,
    hop 1 prefills on the prefill pool, hop 2 decodes on the decode
    pool after pulling the pages over /kv/pull — output equals the
    solo greedy run; the warm repeat pulls NOTHING (chain-key dedup);
    /stats and /debug/replicas carry the new surfaces."""
    model = _model()
    kw = dict(max_slots=2, page_size=4, num_pages=32,
              max_pages_per_slot=8, steps_per_tick=2,
              prefix_cache_pages=8)
    prompt = PREFIX + [21, 22, 23]
    want = _solo(model, prompt, 4)
    pre, dec, servers, pairs = _pooled_fleet(model, **kw)
    router = ReplicaRouter(pairs, prefix_page_size=4)
    router.probe_all()
    router.start(probe=False)
    try:
        rows = {r["id"]: r for r in
                router.debug_replicas()["replicas"]}
        assert rows["r0"]["role"] == "prefill"
        assert rows["r1"]["role"] == "decode"
        toks, routed = _gen(router.port, prompt, 4)
        assert routed == "r1" and toks == want
        assert router.metrics.counter(
            "router.disagg.handoffs").value() == 1
        assert pre.disagg.snapshot()["handoff_pages"] == 2
        snap = dec.disagg.snapshot()
        assert snap["pulled_pages"] == 2
        assert snap["imported_pages"] == 2
        # warm repeat: decode replica already holds both pages
        toks, routed = _gen(router.port, prompt, 4)
        assert routed == "r1" and toks == want
        snap = dec.disagg.snapshot()
        assert snap["pulled_pages"] == 2          # no second pull
        assert snap["dedup_skipped_pages"] >= 2
        # surfaces: serving /stats disagg block + arbiter, router
        # pools summary, the status tool's handoff line
        with urllib.request.urlopen(
                f"http://127.0.0.1:{servers[1].port}/stats",
                timeout=30) as resp:
            st = json.loads(resp.read())
        assert st["disagg"]["role"] == "decode"
        assert st["disagg"]["arbiter"]["granted"] >= 1
        view = router.debug_replicas()
        assert view["summary"]["pools"] == {"prefill": 1, "decode": 1}
        router.probe_all()                    # refresh last_stats
        from tools.router_status import render
        out = render(router.debug_replicas())
        assert "role" in out and "prefill" in out
        assert "handoff:" in out and "bytes exported" in out
    finally:
        router.stop()
        for s in servers:
            s.stop()
        pre.stop()
        dec.stop()


def test_chaos_transfer_fail_degrades_to_local_decode():
    """`disagg.transfer.fail` at rate 1.0: the handoff is abandoned
    and every concurrent request decodes LOCALLY on the warm prefill
    replica — all complete with the exact solo tokens, zero hangs,
    and the fallback counter names the reason."""
    model = _model()
    kw = dict(max_slots=2, page_size=4, num_pages=32,
              max_pages_per_slot=8, steps_per_tick=2,
              prefix_cache_pages=8)
    prompts = [PREFIX + [30 + i] for i in range(4)]
    want = {i: _solo(model, p, 3) for i, p in enumerate(prompts)}
    pre, dec, servers, pairs = _pooled_fleet(model, **kw)
    router = ReplicaRouter(pairs, prefix_page_size=4)
    router.probe_all()
    router.start(probe=False)
    results, errs = {}, []

    def run(i):
        try:
            results[i] = _gen(router.port, prompts[i], 3)
        except Exception as e:  # noqa: BLE001 — the assert is below
            errs.append((i, repr(e)))

    try:
        with chaos.scoped(rates={"disagg.transfer.fail": 1.0}):
            threads = [threading.Thread(target=run, args=(i,),
                                        daemon=True)
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
                assert not t.is_alive(), "request hung"
        assert not errs, errs
        for i, (toks, routed) in results.items():
            assert toks == want[i], i
            assert routed == "r0"            # local decode, warm side
        assert dec.disagg.snapshot()["pulled_pages"] == 0
        c = router.metrics.counter("router.disagg.fallbacks")
        assert c.value(reason="transfer_fail") == len(prompts)
        assert router.metrics.counter(
            "router.disagg.handoffs").value() == 0
    finally:
        router.stop()
        for s in servers:
            s.stop()
        pre.stop()
        dec.stop()


def test_pull_failure_degrades_to_cold_local_prefill():
    """A decode replica whose /kv/pull fetch fails (dead peer) counts
    a pull failure and still serves the request — cold prefill locally,
    same tokens."""
    model = _model()
    dec = PagedKVEngine(model, role="decode", max_slots=2, page_size=4,
                        num_pages=32, max_pages_per_slot=8,
                        steps_per_tick=2, prefix_cache_pages=8)
    server = PredictorServer(lambda x: {"y": np.zeros((1, 1))},
                             generator=dec).start()
    prompt = PREFIX + [21]
    want = _solo(model, prompt, 3)
    try:
        keys = ",".join(chain_keys(prompt, 4))
        body = json.dumps({"ids": prompt, "max_new_tokens": 3}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/generate", data=body,
            headers={"Content-Type": "application/json",
                     "X-Disagg-KV-From": "127.0.0.1:1",   # dead peer
                     "X-Disagg-Keys": keys})
        with urllib.request.urlopen(req, timeout=30) as resp:
            got = json.loads(resp.read())["sequences"][0]
        assert got == want
        assert dec.disagg.snapshot()["pull_failures"] == 1
    finally:
        server.stop()
        dec.stop()


# -- catalogue pins ----------------------------------------------------------

def test_disagg_metrics_catalogued_both_directions():
    """House pattern: every disagg metric literal in disagg.py and
    router.py is catalogued, and both new families are exactly the
    catalogued names; the chaos sites are registered in POINTS."""
    from paddle_tpu.observability.metrics import METRICS
    seen = set()
    for rel in (("paddle_tpu", "inference", "disagg.py"),
                ("paddle_tpu", "inference", "router.py")):
        src = os.path.join(_ROOT, *rel)
        for node in ast.walk(ast.parse(open(src).read())):
            if isinstance(node, ast.Call) and node.args \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("inc", "observe",
                                           "set_gauge"):
                arg = node.args[0]
                if not (isinstance(arg, ast.Constant)
                        and isinstance(arg.value, str)):
                    continue        # router.py has name-typed helpers
                if arg.value.startswith(("inference.disagg.",
                                         "router.disagg.")):
                    assert arg.value in METRICS, arg.value
                    seen.add(arg.value)
    assert {n for n in METRICS
            if n.startswith("inference.disagg.")} == {
        "inference.disagg.handoff_pages",
        "inference.disagg.handoff_bytes",
        "inference.disagg.imported_pages",
        "inference.disagg.imported_bytes",
        "inference.disagg.dedup_skipped_pages",
        "inference.disagg.transfer_seconds",
        "inference.disagg.pull_failures"}
    assert {n for n in METRICS
            if n.startswith("router.disagg.")} == {
        "router.disagg.handoffs", "router.disagg.fallbacks"}
    assert METRICS["inference.disagg.transfer_seconds"][0] == \
        "histogram"
    recorded = {n for n in seen
                if n.startswith("inference.disagg.")}
    assert recorded == {n for n in METRICS
                        if n.startswith("inference.disagg.")}
    assert "disagg.transfer.fail" in chaos.POINTS
    assert "disagg.transfer.delay" in chaos.POINTS


def test_disagg_stats_observability_literal_sites():
    """With observability on, the stats object actually records into
    the registry (the catalogue pin above only proves literals
    exist)."""
    obs.REGISTRY.reset()
    obs.enable()
    try:
        d = DisaggStats("prefill")
        d.note_export(2, 100)
        d.note_pull(1, 50, 0.01, skipped=1)
        d.note_imported(1, 40)
        d.note_pull_failure()
        assert obs.REGISTRY.counter(
            "inference.disagg.handoff_pages").value() == 2
        assert obs.REGISTRY.counter(
            "inference.disagg.dedup_skipped_pages").value() == 1
        assert obs.REGISTRY.counter(
            "inference.disagg.pull_failures").value() == 1
    finally:
        obs.disable()
        obs.REGISTRY.reset()
