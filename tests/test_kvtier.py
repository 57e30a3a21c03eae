"""ISSUE 18 — tiered KV: host-RAM prefix spill with restore-on-hit
plus session suspend/resume (inference/kvtier.py + the engine/serving/
router wiring).

The load-bearing pins:

- restore-on-hit is BIT-EXACT: a prompt whose prefix pages were
  evicted to the host tier generates exactly the solo/device-warm
  tokens, on both attend paths (jnp and interpret-Pallas) and for
  int8 pools — where the quant scale rows must survive the round
  trip byte-identically (the frozen-scale invariant crosses the
  PCIe boundary);
- the page ledger (`_page_refs`/`_cached_pages`/`_reclaimable`/free
  list) settles exactly after spill/restore cycles, and
  `admission_headroom()` stays truthful — restoring never changes
  what admission can promise;
- a session's turn keeps its FULL pages (prompt + generated) keyed
  in the device cache; a long-idle session suspends (pages spill,
  HBM frees) and its next turn resumes with exact token parity
  against an unsuspended session AND the solo oracle;
- chaos `kvtier.spill.fail` degrades to plain eviction: the next hit
  is cold, never wrong; `kvtier.restore.delay` slows but never
  corrupts a restore;
- a tier at byte budget sheds host LRU entries and never starves
  admission;
- the fleet surface: /stats carries the `kvtier` block,
  /debug/replicas rows carry `kvtier_hit_rate`, tools/router_status
  renders the column, the `inference.kvtier.*` family is catalogued
  both directions, and both chaos sites are registered.
"""
import ast
import json
import os
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.distributed import chaos
from paddle_tpu.inference.kvtier import HostKVTier
from paddle_tpu.inference.paged import PagedKVEngine
from paddle_tpu.inference.prefix import chain_keys
from paddle_tpu.inference.router import ReplicaRouter
from paddle_tpu.inference.serving import PredictorServer
from paddle_tpu.models.generation import generate
from paddle_tpu.models.llama import LlamaForCausalLM, tiny_llama_config

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.usefixtures("no_leaked_threads")


_MODEL = None

PREFIX = [5, 9, 2, 14, 17, 3, 11, 4]             # 2 full pages of 4


def _model(seed=0):
    """One shared read-only model (deterministic weights); engines
    compile their own programs anyway."""
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


def _mk(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 32)
    kw.setdefault("max_pages_per_slot", 8)
    kw.setdefault("steps_per_tick", 2)
    kw.setdefault("prefix_cache_pages", 4)
    kw.setdefault("host_tier_bytes", 1 << 20)
    return PagedKVEngine(model, **kw)


def _evict_prefix(eng, keys, rng):
    """Churn the device cache with distinct prompts until none of
    `keys` is device-resident (each eviction spills), then drain the
    spill worker so the tier population is deterministic."""
    vocab = 97
    while any(k in eng.prefix_cache for k in keys):
        p = list(rng.randint(1, vocab, 9))
        eng.generate([p], max_new_tokens=2)
    assert eng.host_tier.flush()


def _ledger_settled(eng):
    cached_now = set(eng.prefix_cache.pages())
    assert set(eng._page_refs) == cached_now
    assert eng._cached_pages == cached_now
    assert eng._reclaimable == len(cached_now)
    assert len(eng._free) == eng.num_pages - 1 - len(cached_now)


# -- the tier itself ---------------------------------------------------------

def test_host_tier_unit():
    """Byte-budgeted LRU under the spill worker: commit order, budget
    eviction, leading-run match semantics, counters."""
    page = [(np.ones((2, 4, 8), np.float32),) * 2]     # 256B per array
    nbytes = 2 * page[0][0].nbytes
    tier = HostKVTier(budget_bytes=3 * nbytes)
    try:
        for k in ("a", "b", "c"):
            tier.spill(k, page)
        assert tier.flush()
        assert len(tier) == 3
        # leading-run semantics: a gap truncates
        assert [k for k, _e in tier.match_run(["a", "b"])] == ["a", "b"]
        assert tier.match_run(["x", "a"]) == []
        # "c" is now LRU (a/b touched); a 4th entry evicts it
        tier.spill("d", page)
        assert tier.flush()
        snap = tier.snapshot()
        assert snap["host_pages"] == 3 and snap["evictions"] == 1
        assert not tier.has("c") and tier.has("d")
        assert snap["host_bytes"] <= snap["budget_bytes"]
        assert snap["spilled_pages"] == 4
        assert snap["spill_bytes"] == 4 * nbytes
        # re-spilling a resident key replaces, never double-counts bytes
        tier.spill("d", page)
        assert tier.flush()
        assert tier.snapshot()["host_bytes"] == 3 * nbytes
        tier.discard("d")
        assert len(tier) == 2
    finally:
        tier.stop()
    with pytest.raises(ValueError):
        HostKVTier(0)


# -- restore-on-hit parity (the tentpole correctness bar) --------------------

@pytest.mark.parametrize("kernel", ["jnp", "pallas"])
def test_spill_restore_exact_parity(kernel):
    """Evict a cached prefix to the host tier, then resubmit: the
    prefix comes back through one H2D upload, prefill runs only the
    tail (same program as a device-warm hit), and the tokens are
    exactly the solo AND device-warm sequences."""
    model = _model()
    pa = PREFIX + [21, 22, 23]
    eng = _mk(model, kernel=kernel)
    keys = chain_keys(PREFIX, 4)
    r1 = eng.submit(pa, max_new_tokens=8)
    eng.run_until_idle()
    warm = eng.submit(pa, max_new_tokens=8)       # device-warm baseline
    eng.run_until_idle()
    assert r1.result() == _solo(model, pa, 8)
    assert warm.result() == r1.result()

    rng = np.random.RandomState(0)
    _evict_prefix(eng, keys, rng)
    assert eng.host_tier.snapshot()["host_pages"] >= 2

    pre = eng.host_tier.snapshot()
    r2 = eng.submit(pa, max_new_tokens=8)
    eng.step()
    eng.run_until_idle()
    snap = eng.host_tier.snapshot()
    assert snap["restored_pages"] - pre["restored_pages"] == 2
    assert snap["restore_bytes"] > pre["restore_bytes"]
    assert snap["hits"] == pre["hits"] + 1
    assert r2.result() == r1.result()             # exact, restored
    # a restored prefix is a warm hit: the tail-only bucket ran
    assert ("prefill", 8, 1) in eng._programs
    # the restored keys are device-resident again (re-eviction needs
    # no new D2H: the host copy stayed)
    assert all(k in eng.prefix_cache for k in keys)
    assert all(eng.host_tier.has(k) for k in keys)
    _ledger_settled(eng)
    eng.stop()


def test_int8_scales_survive_round_trip():
    """int8 pools spill their per-page quant scale rows alongside the
    payload: restored page bytes (k/v int8 AND f32 scales) are
    IDENTICAL to the pre-spill device content, and a used engine
    stays token-equal to a fresh one."""
    model = _model()
    mk = lambda: _mk(model, kv_dtype="int8")      # noqa: E731
    pa = PREFIX + [21, 22]
    keys = chain_keys(PREFIX, 4)
    used = mk()
    out1 = used.generate([pa], max_new_tokens=5)[0]
    pages0 = used.prefix_cache.match(keys)
    before = [[np.asarray(a[p]) for grp in used.pools for a in grp]
              for p in pages0]
    rng = np.random.RandomState(1)
    _evict_prefix(used, keys, rng)
    out2 = used.generate([pa], max_new_tokens=5)[0]   # restored run
    assert used.host_tier.snapshot()["restored_pages"] >= 2
    pages1 = used.prefix_cache.match(keys)
    after = [[np.asarray(a[p]) for grp in used.pools for a in grp]
             for p in pages1]
    for b_arrs, a_arrs in zip(before, after):
        for b, a in zip(b_arrs, a_arrs):
            np.testing.assert_array_equal(b, a)
    fresh = mk()
    assert out2 == out1 == fresh.generate([pa], max_new_tokens=5)[0]
    used.stop()
    fresh.stop()


# -- sessions ----------------------------------------------------------------

def test_session_retention_warm_second_turn():
    """A finished turn with a session id keeps prompt AND generated
    pages keyed: the next turn's prompt (which replays them verbatim)
    warm-hits past the generated text and stays exact."""
    model = _model()
    eng = _mk(model, num_pages=64, max_pages_per_slot=16,
              prefix_cache_pages=16)
    rng = np.random.RandomState(2)
    turn1 = list(rng.randint(1, 97, 11))
    r1 = eng.submit(np.asarray(turn1, np.int32), max_new_tokens=8,
                    session="s1")
    eng.run_until_idle()
    out1 = r1.result()
    rec = eng._sessions["s1"]
    # committed tokens = 11 + 8 - 1 (the final emitted token's KV was
    # never fed back) -> 4 full pages keyed, generated pages included
    assert len(rec["keys"]) == 4 and not rec["suspended"]
    turn2 = turn1 + out1 + list(rng.randint(1, 97, 5))
    r2 = eng.submit(np.asarray(turn2, np.int32), max_new_tokens=6,
                    session="s1")
    eng.run_until_idle()
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["prefix_pages_shared"] >= 4
    assert r2.result() == _solo(model, turn2, 6)
    eng.stop()


def test_suspend_resume_token_parity():
    """The acceptance pin: a suspended session's round trip (idle ->
    pages spill, HBM freed -> next turn restores) produces exactly
    the tokens of an unsuspended session engine and the solo oracle,
    and the suspends/resumes counters tell the story."""
    model = _model()
    rng = np.random.RandomState(3)
    turn1 = list(rng.randint(1, 97, 11))

    def two_turns(eng, suspend):
        r1 = eng.submit(np.asarray(turn1, np.int32), max_new_tokens=8,
                        session="s1")
        eng.run_until_idle()
        out1 = r1.result()
        if suspend:
            time.sleep(0.05)
            eng.step()                      # the sweep fires
            assert eng.host_tier.flush()
            snap = eng.kvtier_stats()
            assert snap["suspends"] == 1
            assert snap["host_pages"] >= 3
            assert len(eng.prefix_cache) == 0       # device side freed
            assert len(eng._free) == eng.num_pages - 1
            assert eng._sessions["s1"]["suspended"]
        turn2 = turn1 + out1 + list(np.random.RandomState(4)
                                    .randint(1, 97, 5))
        r2 = eng.submit(np.asarray(turn2, np.int32), max_new_tokens=6,
                        session="s1")
        eng.run_until_idle()
        return out1, r2.result(), turn2

    ea = _mk(model, num_pages=64, max_pages_per_slot=16,
             prefix_cache_pages=16, suspend_after_s=0.02)
    o1a, o2a, turn2 = two_turns(ea, suspend=True)
    snap = ea.kvtier_stats()
    assert snap["resumes"] == 1 and snap["restored_pages"] >= 3
    assert not ea._sessions["s1"]["suspended"]

    eb = _mk(model, num_pages=64, max_pages_per_slot=16,
             prefix_cache_pages=16)
    o1b, o2b, _ = two_turns(eb, suspend=False)
    assert (o1a, o2a) == (o1b, o2b)
    assert o2a == _solo(model, turn2, 6)
    _ledger_settled(ea)
    ea.stop()
    eb.stop()


# -- chaos degradation -------------------------------------------------------

def test_spill_fail_chaos_degrades_to_plain_eviction():
    """With `kvtier.spill.fail` at rate 1.0 every capture is dropped:
    eviction destroys the page like a tierless engine, the tier stays
    empty, and the resubmitted prompt is COLD but still exact."""
    model = _model()
    eng = _mk(model)
    pa = PREFIX + [21, 22, 23]
    keys = chain_keys(PREFIX, 4)
    solo = _solo(model, pa, 8)
    rng = np.random.RandomState(5)
    with chaos.scoped(rates={"kvtier.spill.fail": 1.0}):
        assert eng.generate([pa], max_new_tokens=8)[0] == solo
        _evict_prefix(eng, keys, rng)
    snap = eng.kvtier_stats()
    assert snap["host_pages"] == 0 and snap["spilled_pages"] == 0
    assert snap["spill_skipped"] >= 2
    pre_misses = eng.stats["prefix_misses"]
    assert eng.generate([pa], max_new_tokens=8)[0] == solo
    assert eng.stats["prefix_misses"] == pre_misses + 1   # cold again
    assert eng.kvtier_stats()["restored_pages"] == 0
    eng.stop()


def test_restore_delay_chaos_slows_but_never_corrupts():
    model = _model()
    eng = _mk(model)
    pa = PREFIX + [21]
    solo = _solo(model, pa, 6)
    keys = chain_keys(PREFIX, 4)
    assert eng.generate([pa], max_new_tokens=6)[0] == solo
    _evict_prefix(eng, keys, np.random.RandomState(6))
    with chaos.scoped(rates={"kvtier.restore.delay": 1.0},
                      delay_ms=30.0):
        t0 = time.perf_counter()
        assert eng.generate([pa], max_new_tokens=6)[0] == solo
        assert time.perf_counter() - t0 >= 0.03
    assert eng.kvtier_stats()["restored_pages"] >= 2
    eng.stop()


def test_kvtier_chaos_sites_registered():
    assert "kvtier.spill.fail" in chaos.POINTS
    assert "kvtier.restore.delay" in chaos.POINTS


# -- budget / admission safety -----------------------------------------------

def test_admission_not_starved_with_tier_at_budget():
    """A tier whose byte budget holds ~1 page sheds host LRU entries
    while the engine churns; admission keeps its headroom guarantee
    and the ledger settles."""
    model = _model()
    # one page = 2 layers x (k + v) x (2, 4, 8) f32 = 1024 bytes
    eng = _mk(model, max_slots=1, num_pages=8, max_pages_per_slot=7,
              prefix_cache_pages=6, host_tier_bytes=1024)
    pa = list(range(1, 9)) + [40]
    eng.generate([pa], max_new_tokens=3)
    assert len(eng.prefix_cache) == 2
    pb = [60 + i for i in range(12)]              # fits only by evicting
    assert eng.generate([pb], max_new_tokens=12)[0] \
        == _solo(model, pb, 12)
    pc = [30 + i for i in range(12)]              # evicts pb's pages too
    assert eng.generate([pc], max_new_tokens=12)[0] \
        == _solo(model, pc, 12)
    assert eng.stats["prefix_evictions"] >= 2
    assert eng.host_tier.flush()
    snap = eng.kvtier_stats()
    assert snap["spilled_pages"] >= 2
    assert snap["host_bytes"] <= snap["budget_bytes"]
    assert snap["host_pages"] <= 1
    assert snap["evictions"] >= 1                 # budget shed host LRU
    _ledger_settled(eng)
    eng.stop()


def test_tier_disabled_default_and_validation():
    model = _model()
    eng = PagedKVEngine(model, max_slots=1, page_size=4, num_pages=16)
    assert eng.host_tier is None and eng.kvtier_stats() is None
    # a session id without a prefix cache is inert, never an error
    r = eng.submit(PREFIX + [1], max_new_tokens=2, session="s")
    eng.run_until_idle()
    r.result()
    assert eng._sessions == {}
    with pytest.raises(ValueError):
        PagedKVEngine(model, max_slots=1, page_size=4, num_pages=16,
                      host_tier_bytes=-1)
    with pytest.raises(ValueError):
        # tier without a prefix cache: nothing to key pages by
        PagedKVEngine(model, max_slots=1, page_size=4, num_pages=16,
                      host_tier_bytes=1 << 20)
    with pytest.raises(ValueError):
        PagedKVEngine(model, max_slots=1, page_size=4, num_pages=16,
                      prefix_cache_pages=4, suspend_after_s=1.0)


# -- catalogue / fleet surfaces ----------------------------------------------

def test_kvtier_metrics_catalogued_both_directions():
    """House pattern: every inference.kvtier.* instrument literal in
    kvtier.py/paged.py is catalogued, and every catalogued name has a
    literal call site."""
    from paddle_tpu.observability.metrics import METRICS
    seen = set()
    for rel in (("paddle_tpu", "inference", "kvtier.py"),
                ("paddle_tpu", "inference", "paged.py")):
        src = os.path.join(_ROOT, *rel)
        for node in ast.walk(ast.parse(open(src).read())):
            if isinstance(node, ast.Call) and node.args \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("inc", "observe",
                                           "set_gauge"):
                arg = node.args[0]
                assert isinstance(arg, ast.Constant) and \
                    isinstance(arg.value, str), \
                    f"non-literal metric name at {rel[-1]}:{node.lineno}"
                assert arg.value in METRICS, arg.value
                seen.add(arg.value)
    family = {n for n in METRICS if n.startswith("inference.kvtier.")}
    assert family == {"inference.kvtier.spilled_pages",
                      "inference.kvtier.restored_pages",
                      "inference.kvtier.spill_bytes",
                      "inference.kvtier.restore_bytes",
                      "inference.kvtier.host_pages",
                      "inference.kvtier.suspends",
                      "inference.kvtier.resumes"}
    missing = family - seen
    assert not missing, f"catalogued but never recorded: {missing}"
    assert METRICS["inference.kvtier.host_pages"][0] == "gauge"


def test_serving_stats_carries_kvtier_block():
    model = _model()
    eng = _mk(model)
    keys = chain_keys(PREFIX, 4)
    eng.generate([PREFIX + [21]], max_new_tokens=2)
    _evict_prefix(eng, keys, np.random.RandomState(7))
    eng.generate([PREFIX + [31]], max_new_tokens=2)   # restore hit
    server = PredictorServer(lambda x: {"y": np.zeros((1, 1))},
                             generator=eng).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/stats",
                timeout=30) as resp:
            st = json.loads(resp.read())
        kt = st["kvtier"]
        assert kt["enabled"] is True
        assert kt["restored_pages"] >= 2
        assert kt["spilled_pages"] >= 2
        assert kt["hits"] >= 1 and kt["lookups"] >= 1
        assert kt["budget_bytes"] == 1 << 20
    finally:
        server.stop()
    # a tierless engine adds no block
    s2 = PredictorServer(lambda x: {"y": np.zeros((1, 1))},
                         generator=PagedKVEngine(
                             model, max_slots=1, page_size=4,
                             num_pages=16))
    try:
        assert "kvtier" not in s2.stats()
    finally:
        s2.stop()


def test_serving_generate_forwards_session():
    """The HTTP surface: a /generate body carrying `session` reaches
    the engine's session bookkeeping (retention visible after the
    request drains)."""
    model = _model()
    eng = _mk(model)
    server = PredictorServer(lambda x: {"y": np.zeros((1, 1))},
                             generator=eng).start()
    try:
        body = json.dumps({"ids": PREFIX + [21, 22, 23],
                           "max_new_tokens": 4,
                           "session": "conv-7"}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            resp.read()
        assert "conv-7" in eng._sessions
    finally:
        server.stop()
        eng.stop()      # /generate started the engine's ticker


class _Tok:
    """Minimal /generate backend reporting fixed prefix/kvtier stats."""

    concurrent_safe = False

    def __init__(self, kvtier_stats=None):
        self._kt = kvtier_stats

    def stream(self, ids, **kw):
        def gen():
            yield np.asarray([7])
        return gen()

    def kvtier_stats(self):
        return self._kt


def test_debug_replicas_kvtier_hit_rate_and_status_render():
    """The fleet-operator satellite: /debug/replicas rows carry the
    probed host-tier hit rate next to prefix_hit_rate, and
    tools/router_status renders the column — so device-hit, tier-hit,
    and cold traffic are distinguishable per replica."""
    kt = {"enabled": True, "hits": 3, "lookups": 4, "hit_rate": 0.75,
          "host_pages": 5, "spilled_pages": 9, "restored_pages": 3}
    servers = [PredictorServer(
        lambda x: {"y": np.zeros((1, 1))}, model_name=f"r{i}",
        generator=_Tok(kt if i == 0 else None)).start()
        for i in range(2)]
    pairs = [(f"r{i}", f"127.0.0.1:{s.port}")
             for i, s in enumerate(servers)]
    router = ReplicaRouter(pairs, prefix_page_size=4)
    router.probe_all()
    try:
        rows = {r["id"]: r for r in
                router.debug_replicas()["replicas"]}
        assert rows["r0"]["kvtier_hit_rate"] == 0.75
        assert rows["r1"]["kvtier_hit_rate"] is None
        from tools.router_status import render
        out = render(router.debug_replicas())
        assert "tier_hit" in out and "0.75" in out
    finally:
        router.stop()
        for s in servers:
            s.stop()


# -- draft-mirror shedding (ISSUE 20 satellite) ------------------------------

def test_draft_mirrors_shed_before_whole_entries():
    """Under host budget pressure the tier sheds draft-model mirrors
    (oldest first) BEFORE evicting any whole entry: losing a draft
    only costs speculation acceptance on a later restore (the target
    model still verifies, outputs stay exact), while losing an entry
    costs a full prefill."""
    page = [(np.ones((2, 4, 8), np.float32),) * 2]      # 512B
    draft = [(np.ones((2, 4, 8), np.float32),) * 2]     # +512B
    nb = 512
    tier = HostKVTier(budget_bytes=3 * nb)
    try:
        tier.spill("a", page, draft=draft)
        assert tier.flush()
        assert tier.snapshot()["host_bytes"] == 2 * nb
        # b pushes past budget: a's DRAFT goes, both entries stay
        tier.spill("b", page, draft=draft)
        assert tier.flush()
        snap = tier.snapshot()
        assert snap["draft_dropped"] == 1 and snap["evictions"] == 0
        assert snap["host_pages"] == 2
        (_, ea), (_, eb) = tier.match_run(["a", "b"])
        assert ea.draft is None and eb.draft is not None
        # c (draftless) pushes again: b's draft goes next, still no
        # whole-entry eviction
        tier.spill("c", page)
        assert tier.flush()
        snap = tier.snapshot()
        assert snap["draft_dropped"] == 2 and snap["evictions"] == 0
        assert snap["host_pages"] == 3
        assert eb.draft is None
        # d: no drafts left to shed — NOW plain LRU eviction resumes
        tier.spill("d", page)
        assert tier.flush()
        snap = tier.snapshot()
        assert snap["draft_dropped"] == 2 and snap["evictions"] == 1
        assert snap["host_pages"] == 3
        assert snap["host_bytes"] <= snap["budget_bytes"]
    finally:
        tier.stop()


def test_restore_with_stripped_draft_stays_exact():
    """The correctness half of draft shedding: a restore whose entry
    lost its draft mirror zero-fills the draft pools and the
    speculative engine's output is STILL the exact greedy sequence —
    the target model verifies every proposal, so missing draft KV can
    only reduce acceptance, never change tokens."""
    model = _model()
    paddle_tpu.seed(5)
    draft = LlamaForCausalLM(model.config)
    eng = _mk(model, draft_model=draft, spec_tokens=3,
              num_pages=48, max_pages_per_slot=8, steps_per_tick=3)
    pa = PREFIX + [21]
    want = _solo(model, pa, 6)
    assert eng.generate([pa], max_new_tokens=6)[0] == want
    keys = chain_keys(PREFIX, 4)
    _evict_prefix(eng, keys, np.random.RandomState(3))
    # shed every draft mirror, as budget pressure would (accounting
    # kept coherent under the tier's own lock)
    t = eng.host_tier
    with t._cond:
        for e in t._entries.values():
            if e.draft is not None:
                d = sum(a.nbytes for grp in e.draft for a in grp)
                e.draft = None
                e.nbytes -= d
                t._bytes -= d
                t._drafts -= 1
    pre = t.snapshot()["restored_pages"]
    assert eng.generate([pa], max_new_tokens=6)[0] == want
    assert t.snapshot()["restored_pages"] - pre >= 2
    assert eng.stats["spec_ticks"] > 0
    _ledger_settled(eng)
    eng.stop()
