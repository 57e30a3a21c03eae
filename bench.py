"""Benchmark: Llama pretraining step throughput on the attached chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Metric is tokens/sec/chip on a Llama-1B-class pretrain step (fwd+bwd+Adam,
bf16 compute, fp32 master weights, recompute on) — the single-chip proxy
for BASELINE.json's north star (Llama-3-8B >=40% MFU on v5p-64).
vs_baseline = measured MFU / 0.40 (the north-star MFU target; the reference
repo publishes no absolute numbers — BASELINE.md).

Needs a TPU: without one it exits non-zero (a CPU rate is never printed
under a device metric's name). An `extra.*` block that raises is printed
as {"error": ...} and the process then exits non-zero. Due to be replaced
by the cell benchmark (ROADMAP S0); the quickest on-chip proof that the
system still starts is chip_smoke.py.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np


def _peak_flops(dev) -> float:
    """bf16 peak of `dev` from the one peaks table (device/peaks.py);
    an unknown device_kind raises."""
    from paddle_tpu.device.peaks import peaks_for_kind
    return peaks_for_kind(dev.device_kind).bf16_flops


def _decode_bench(on_tpu):
    """Serving decode microbench: aggregate tok/s and KV bytes/slot at
    a fixed slot count, for the jnp attend path, the Pallas
    paged-decode kernel (interpret mode off-TPU — a parity/coverage
    config there, a perf config on real chips), and the kernel with
    int8 KV pools. The measured run executes under a scoped
    observability enable, so the request-tracing layer
    (observability/requests.py) records per-request TTFT and
    inter-token latency; their p50/p95/p99 ride each row (the
    user-felt serving SLOs next to the aggregate throughput).
    Returns a list of row dicts for the BENCH json."""
    import time

    import paddle_tpu
    from paddle_tpu import observability
    from paddle_tpu.inference.paged import PagedKVEngine
    from paddle_tpu.models.llama import LlamaForCausalLM, LlamaConfig, \
        tiny_llama_config

    paddle_tpu.seed(0)
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=1024,
            rope_theta=10000.0, seq_length=1024)
        # page_size 32: the int8 row's (page_size, d) k/v block must
        # tile the int8 Mosaic sublane minimum of 32 when compiled
        slots, page_size, num_pages, max_new = 8, 32, 256, 64
    else:
        cfg = tiny_llama_config(num_hidden_layers=2, vocab_size=128,
                                hidden_size=64, intermediate_size=128,
                                num_attention_heads=4,
                                num_key_value_heads=2)
        slots, page_size, num_pages, max_new = 4, 8, 64, 16
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, cfg.vocab_size, 12))
               for _ in range(slots)]

    rows = []
    for label, kernel, kv_dtype in (
            ("jnp", "jnp", "bf16"),
            ("pallas", "pallas", "bf16"),
            ("pallas+int8", "pallas", "int8")):
        eng = PagedKVEngine(
            model, max_slots=slots, page_size=page_size,
            num_pages=num_pages, steps_per_tick=4, kernel=kernel,
            kv_dtype=kv_dtype)
        eng.generate(prompts, max_new_tokens=2)      # compile warmup
        base_tokens = eng.stats["tokens_out"]
        with observability.scoped(reset=True) as reg:
            t0 = time.perf_counter()
            eng.generate(prompts, max_new_tokens=max_new)
            dt = time.perf_counter() - t0

        def _pcts(name):
            h = reg.histogram(name)
            if h.count() == 0:
                return None
            return {f"p{p}": round(h.percentile(p) * 1000.0, 3)
                    for p in (50, 95, 99)}

        rows.append({
            "path": label,
            "tokens_per_sec": round(
                (eng.stats["tokens_out"] - base_tokens) / dt, 2),
            "kv_bytes_per_slot": eng.kv_bytes_per_slot(),
            "slots": slots,
            "ttft_ms": _pcts("request.ttft.seconds"),
            "itl_ms": _pcts("request.itl.seconds"),
        })
    return rows


def _prefix_bench():
    """Prefix-cache payoff (ISSUE 11): a shared-system-prompt serving
    workload — K requests carrying one common multi-page prefix with
    distinct tails — run twice through the SAME engine: cold (the
    `prefix.cache.bypass` chaos site forces every lookup to miss, so
    every request prefills its whole prompt) and warm (cache on: each
    request prefills only its uncached tail). Reports prompt tokens
    admitted per second of prefill wall time for both passes, the
    warm-pass hit rate, and pages shared — the claim is warm >= 2x
    cold, because prefill work drops from O(prompt) to O(tail).
    Compiles are excluded by running both modes once before timing."""
    import time

    import paddle_tpu
    from paddle_tpu.distributed import chaos
    from paddle_tpu.inference.paged import PagedKVEngine
    from paddle_tpu.models.llama import LlamaForCausalLM, \
        tiny_llama_config

    paddle_tpu.seed(0)
    cfg = tiny_llama_config(num_hidden_layers=2, vocab_size=128,
                            hidden_size=64, intermediate_size=128,
                            num_attention_heads=4,
                            num_key_value_heads=2)
    model = LlamaForCausalLM(cfg)
    page_size, prefix_pages, k_req = 16, 2, 6
    rng = np.random.RandomState(0)
    prefix = list(rng.randint(1, cfg.vocab_size,
                              prefix_pages * page_size))
    prompts = [prefix + list(rng.randint(1, cfg.vocab_size, 8))
               for _ in range(k_req)]
    eng = PagedKVEngine(model, max_slots=4, page_size=page_size,
                        num_pages=128, steps_per_tick=2,
                        prefix_cache_pages=32)
    tokens = sum(len(p) for p in prompts)

    def run_pass(bypass):
        s0 = dict(eng.stats)
        if bypass:
            with chaos.scoped(rates={"prefix.cache.bypass": 1.0}):
                t0 = time.perf_counter()
                eng.generate(prompts, max_new_tokens=2)
                dt = time.perf_counter() - t0
        else:
            t0 = time.perf_counter()
            eng.generate(prompts, max_new_tokens=2)
            dt = time.perf_counter() - t0
        return dt, {k: eng.stats[k] - s0[k]
                    for k in ("prefill_s", "prefix_hits",
                              "prefix_misses", "prefix_pages_shared")}

    run_pass(True)      # warmup: compiles the full-prompt bucket,
    run_pass(False)     # seeds the cache + compiles the tail bucket
    _dt, cold = run_pass(True)
    _dt, warm = run_pass(False)
    cold_tps = tokens / max(cold["prefill_s"], 1e-9)
    warm_tps = tokens / max(warm["prefill_s"], 1e-9)
    denom = warm["prefix_hits"] + warm["prefix_misses"]
    return {
        "requests": k_req,
        "page_size": page_size,
        "prefix_tokens": prefix_pages * page_size,
        "prompt_tokens": tokens,
        "cold_prefill_tokens_per_sec": round(cold_tps, 2),
        "warm_prefill_tokens_per_sec": round(warm_tps, 2),
        "warm_vs_cold": round(warm_tps / max(cold_tps, 1e-9), 3),
        "hit_rate": round(warm["prefix_hits"] / denom, 4) if denom
        else 0.0,
        "pages_shared": warm["prefix_pages_shared"],
        "cached_pages": len(eng.prefix_cache),
    }


def _kvtier_bench():
    """Tiered-KV payoff (ISSUE 18), two numbers the acceptance gate
    names: (1) restore-hit prefill tokens/sec vs cold — K requests
    sharing a multi-page prefix whose pages were EVICTED to the host
    tier run against K never-seen prompts of identical shape (same
    compile buckets, so only the prefill work differs: a restore is
    O(tail) + one H2D batch, cold is O(prompt)); (2) the
    suspend/resume round trip — one session's turn, an idle window
    that spills its pages and frees HBM, then the next turn restored
    from host. Compiles are excluded by a warmup pass of both
    buckets."""
    import time

    import paddle_tpu
    from paddle_tpu.inference.paged import PagedKVEngine
    from paddle_tpu.models.llama import LlamaForCausalLM, \
        tiny_llama_config

    paddle_tpu.seed(0)
    cfg = tiny_llama_config(num_hidden_layers=2, vocab_size=128,
                            hidden_size=64, intermediate_size=128,
                            num_attention_heads=4,
                            num_key_value_heads=2)
    model = LlamaForCausalLM(cfg)
    page_size, prefix_pages, k_req = 16, 4, 4
    rng = np.random.RandomState(0)
    prefix = list(rng.randint(1, cfg.vocab_size,
                              prefix_pages * page_size))
    tails = [list(rng.randint(1, cfg.vocab_size, 8))
             for _ in range(k_req)]

    def fresh(n):
        return [list(rng.randint(1, cfg.vocab_size, len(prefix) + 8))
                for _ in range(n)]

    def fresh_tails(n):
        return [list(rng.randint(1, cfg.vocab_size, 8))
                for _ in range(n)]

    eng = PagedKVEngine(model, max_slots=4, page_size=page_size,
                        num_pages=128, steps_per_tick=2,
                        prefix_cache_pages=prefix_pages + 2,
                        host_tier_bytes=64 << 20)
    tokens = k_req * (len(prefix) + 8)

    from paddle_tpu.inference.prefix import chain_keys
    prefix_keys = chain_keys(prefix, page_size)

    def run_pass(prompts):
        s0 = eng.stats["prefill_s"]
        eng.generate(prompts, max_new_tokens=2)
        return eng.stats["prefill_s"] - s0

    def evict_device_cache():
        # distinct same-shape prompts churn the small device cache
        # until the prefix keys are gone (each eviction spills)
        while any(k in eng.prefix_cache for k in prefix_keys):
            run_pass(fresh(2))
        eng.host_tier.flush()

    # warmup compiles every (bucket, batch-width) the measured passes
    # use: full-prompt bucket at width k (cold pass), then — with the
    # prefix cached by the first group — the tail bucket at width k
    # (restore pass runs the same warm prefill)
    run_pass([prefix + t for t in tails])
    run_pass([prefix + t for t in fresh_tails(k_req)])
    evict_device_cache()

    cold_s = run_pass(fresh(k_req))
    evict_device_cache()
    pre = eng.host_tier.snapshot()
    restore_s = run_pass([prefix + t for t in tails])
    snap = eng.host_tier.snapshot()
    dlk = snap["lookups"] - pre["lookups"]
    pass_hit_rate = (round((snap["hits"] - pre["hits"]) / dlk, 4)
                     if dlk else 0.0)
    cold_tps = tokens / max(cold_s, 1e-9)
    restore_tps = tokens / max(restore_s, 1e-9)
    eng.stop()

    # suspend/resume round trip on a fresh session engine
    eng2 = PagedKVEngine(model, max_slots=4, page_size=page_size,
                         num_pages=128, steps_per_tick=2,
                         prefix_cache_pages=32,
                         host_tier_bytes=64 << 20,
                         suspend_after_s=0.01)
    def turn_pair(session):
        t1 = list(rng.randint(1, cfg.vocab_size, 40))
        r = eng2.submit(np.asarray(t1, np.int32), max_new_tokens=8,
                        session=session)
        eng2.run_until_idle()
        return t1, r.result()

    # warmup pair: compiles the turn-1 bucket and the warm turn-2 tail
    # bucket so the measured round trip times transfers, not XLA
    w1, wout = turn_pair("warmup")
    w2 = w1 + wout + list(rng.randint(1, cfg.vocab_size, 8))
    eng2.submit(np.asarray(w2, np.int32), max_new_tokens=2,
                session="warmup")
    eng2.run_until_idle()

    turn1, out1 = turn_pair("bench")
    time.sleep(0.02)
    t0 = time.perf_counter()
    eng2.step()                     # sweep spills the idle session
    eng2.host_tier.flush()
    suspend_ms = (time.perf_counter() - t0) * 1e3
    turn2 = turn1 + out1 + list(rng.randint(1, cfg.vocab_size, 8))
    t0 = time.perf_counter()
    r2 = eng2.submit(np.asarray(turn2, np.int32), max_new_tokens=2,
                     session="bench")
    eng2.run_until_idle()
    r2.result()
    resume_ms = (time.perf_counter() - t0) * 1e3
    snap2 = eng2.host_tier.snapshot()
    eng2.stop()

    return {
        "requests": k_req,
        "prefix_tokens": prefix_pages * page_size,
        "prompt_tokens": tokens,
        "cold_prefill_tokens_per_sec": round(cold_tps, 2),
        "restore_prefill_tokens_per_sec": round(restore_tps, 2),
        "restore_vs_cold": round(restore_tps / max(cold_tps, 1e-9), 3),
        "tier_hit_rate": pass_hit_rate,
        "tier_hit_rate_lifetime": snap["hit_rate"],
        "restored_pages": snap["restored_pages"],
        "spilled_pages": snap["spilled_pages"],
        "spill_bytes": snap["spill_bytes"],
        "suspend_ms": round(suspend_ms, 2),
        "resume_roundtrip_ms": round(resume_ms, 2),
        "suspends": snap2["suspends"],
        "resumes": snap2["resumes"],
    }


def _disagg_bench():
    """Disaggregated prefill/decode payoff (ISSUE 20): the SAME
    shared-prefix workload run monolithic (one engine does both
    phases) and pooled (a role="prefill" engine prefills + exports,
    a role="decode" engine imports + decodes, page bundles moving
    through the pack/unpack wire format). Three claims, reported as
    numbers: (1) pooled output is EXACTLY the monolithic tokens
    (handoff is lossless); (2) chain-key dedup + int8 pools cut the
    bytes moved >= 2x vs a naive bf16 full-page transfer (shared
    prefix pages move once, not once per request; int8+scales is
    ~0.52x bf16); (3) the per-request handoff cost in ms (the TTFT
    tax the decode pool pays for never running prefill). Compiles
    excluded by a warmup pass through both engines."""
    import time

    import paddle_tpu
    from paddle_tpu.inference.disagg import pack_bundle, unpack_bundle
    from paddle_tpu.inference.paged import PagedKVEngine
    from paddle_tpu.inference.prefix import chain_keys
    from paddle_tpu.models.llama import LlamaForCausalLM, \
        tiny_llama_config

    paddle_tpu.seed(0)
    cfg = tiny_llama_config(num_hidden_layers=2, vocab_size=128,
                            hidden_size=64, intermediate_size=128,
                            num_attention_heads=4,
                            num_key_value_heads=2)
    model = LlamaForCausalLM(cfg)
    page_size, k_req, new_toks = 16, 4, 8
    rng = np.random.RandomState(0)
    prefix = list(rng.randint(1, cfg.vocab_size, 2 * page_size))
    # each request: 2 shared prefix pages + 1 unique full page + tail
    prompts = [prefix + list(rng.randint(1, cfg.vocab_size,
                                         page_size + 4))
               for _ in range(k_req)]
    kw = dict(max_slots=4, page_size=page_size, num_pages=64,
              steps_per_tick=2, prefix_cache_pages=16,
              kv_dtype="int8")

    # warmup prompt: same shape as the workload, sharing the prefix
    # but not any measured unique page — compiles the full-prompt AND
    # warm-tail buckets in every engine before timing starts
    warm = prefix + list(rng.randint(1, cfg.vocab_size, page_size + 4))

    mono = PagedKVEngine(model, **kw)
    mono.generate([prompts[0]], max_new_tokens=2)        # full bucket
    mono.generate([warm], max_new_tokens=2)              # tail bucket
    t0 = time.perf_counter()
    want = mono.generate(prompts, max_new_tokens=new_toks)
    mono_s = time.perf_counter() - t0
    mono.stop()

    pre = PagedKVEngine(model, role="prefill",
                        host_tier_bytes=64 << 20, **kw)
    dec = PagedKVEngine(model, role="decode", **kw)
    pre.generate([prompts[0]], max_new_tokens=1)         # warmup
    pre.generate([warm], max_new_tokens=1)
    dec.generate([prompts[0]], max_new_tokens=2)
    dec.generate([warm], max_new_tokens=2)
    # naive baseline: every full page of every request ships as bf16
    # k+v (2 bytes/elem), no dedup — what a handoff without chain
    # keys or quantization would move
    elems_per_page = (cfg.num_hidden_layers * 2 * page_size
                      * cfg.num_key_value_heads
                      * (cfg.hidden_size // cfg.num_attention_heads))
    pages_total = sum(len(p) // page_size for p in prompts)
    naive_bytes = pages_total * elems_per_page * 2
    moved_bytes = moved_pages = dedup_pages = 0
    handoff_ms = []
    got = []
    t0 = time.perf_counter()
    for p in prompts:
        pre.generate([p], max_new_tokens=1)              # hop 1
        keys = chain_keys(p, page_size)
        h0 = time.perf_counter()
        missing = dec.disagg_missing(keys)
        dedup_pages += len(keys) - len(missing)
        ents = [e for e in pre.export_pages(keys)
                if e.key in set(missing)]
        raw = pack_bundle(ents)
        dec.stage_import(unpack_bundle(raw))
        handoff_ms.append((time.perf_counter() - h0) * 1e3)
        moved_bytes += len(raw)
        moved_pages += len(ents)
        got.append(dec.generate([p],                     # hop 2
                                max_new_tokens=new_toks)[0])
    pooled_s = time.perf_counter() - t0
    parity = got == want
    pre.stop()
    dec.stop()

    toks = k_req * new_toks
    return {
        "requests": k_req,
        "prompt_pages": pages_total,
        "parity": parity,
        "mono_tokens_per_sec": round(toks / max(mono_s, 1e-9), 2),
        "pooled_tokens_per_sec": round(toks / max(pooled_s, 1e-9), 2),
        "handoff_ms_mean": round(sum(handoff_ms) / len(handoff_ms), 3),
        "moved_pages": moved_pages,
        "moved_bytes": moved_bytes,
        "naive_bf16_bytes": naive_bytes,
        "bytes_reduction": round(naive_bytes / max(moved_bytes, 1), 3),
        "dedup_skipped_pages": dedup_pages,
    }


def _tenant_bench():
    """Multi-tenant QoS payoff (ISSUE 13): a saturated two-tenant
    workload — `prod` (weight 3) and `batch` (weight 1) each submit
    more requests than the engine has slots — through ONE engine with
    a TenantTable. Reports the decode slot-tick split (the claim:
    ~3:1 by weight, from the engine's own per-tenant counters), the
    admission interleave, and the per-tenant queue-wait means: the
    weighted-fair pick turns the old FIFO pot-luck into a policy
    number. Pure host-side scheduling on the same tiny model the
    prefix bench uses; compiles excluded by a warmup pass."""
    import time

    import paddle_tpu
    from paddle_tpu.inference.paged import PagedKVEngine
    from paddle_tpu.inference.tenancy import TenantPolicy, TenantTable
    from paddle_tpu.models.llama import LlamaForCausalLM, \
        tiny_llama_config

    paddle_tpu.seed(0)
    cfg = tiny_llama_config(num_hidden_layers=2, vocab_size=128,
                            hidden_size=64, intermediate_size=128,
                            num_attention_heads=4,
                            num_key_value_heads=2)
    model = LlamaForCausalLM(cfg)
    table = TenantTable([TenantPolicy("prod", weight=3.0),
                         TenantPolicy("batch", weight=1.0)])
    eng = PagedKVEngine(model, max_slots=2, page_size=16,
                        num_pages=128, steps_per_tick=2,
                        tenancy=table)
    rng = np.random.RandomState(0)

    def submit_all(n_per_tenant, max_new):
        reqs = []
        for _ in range(n_per_tenant):
            for t in ("prod", "batch"):
                reqs.append(eng.submit(
                    list(rng.randint(1, cfg.vocab_size, 8)),
                    max_new_tokens=max_new, tenant=t))
        return reqs

    warm = submit_all(1, 2)         # warmup: compiles
    eng.run_until_idle()
    for r in warm:
        r.result()
    base = {k: dict(v) for k, v in eng.tenant_snapshot().items()}
    reqs = submit_all(8, 8)
    # the weighted split only exists while BOTH tenants are
    # backlogged (a drained workload equalizes lifetime totals):
    # snapshot slot shares the moment one side's backlog empties
    t0 = time.perf_counter()
    saturated = None
    while eng.has_work():
        eng.step()
        snap = eng.tenant_snapshot()
        if saturated is None and (snap["prod"]["pending"] == 0
                                  or snap["batch"]["pending"] == 0):
            saturated = {
                t: snap[t]["slot_ticks"]
                - base.get(t, {}).get("slot_ticks", 0)
                for t in ("prod", "batch")}
    dt = time.perf_counter() - t0
    for r in reqs:
        r.result()
    snap = eng.tenant_snapshot()

    def delta(t, k):
        return snap[t][k] - base.get(t, {}).get(k, 0)

    sat = saturated or {"prod": 0, "batch": 0}
    return {
        "requests_per_tenant": 8,
        "weights": {"prod": 3.0, "batch": 1.0},
        "wall_s": round(dt, 3),
        "saturated_slot_ticks": sat,
        "saturated_share_ratio": round(
            sat["prod"] / max(sat["batch"], 1), 3),
        "admitted": {"prod": delta("prod", "admitted"),
                     "batch": delta("batch", "admitted")},
    }


def _train_breakdown(on_tpu):
    """Fused-vs-dense loss-path A/B (ISSUE 14) on the SAME model
    config: two fresh same-seed models — one with the blockwise CE
    (`loss_chunk`) + fused norm/rope train path, one on the dense
    logits path (`loss_chunk=0`) — each driven through a Trainer for a
    few timed steps. Reports tokens/sec and the peak logits-path bytes
    per path (dense materializes [B*S, V]; blockwise peaks at
    O(chunk x V)), the loss delta (the parity evidence), and the
    phase-attributed step seconds from `Trainer.measure_phase_seconds`
    read back out of the new `train.phase.seconds` instruments — so
    the bench JSON says WHY the train metric moved."""
    import time

    import paddle_tpu
    import paddle_tpu.optimizer as opt
    from paddle_tpu import observability
    from paddle_tpu.kernels.blockwise_ce import dense_logits_bytes, \
        logits_bytes_saved
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM, \
        tiny_llama_config
    from paddle_tpu.parallel import Trainer, TrainStepConfig

    if on_tpu:
        base = dict(vocab_size=32000, hidden_size=1024,
                    intermediate_size=2816, num_hidden_layers=4,
                    num_attention_heads=16, num_key_value_heads=4,
                    max_position_embeddings=1024, rope_theta=10000.0,
                    seq_length=1024)
        make_cfg = lambda **kw: LlamaConfig(**base, **kw)  # noqa: E731
        batch_b, seq, steps, chunk = 4, 1024, 6, 512
        compute_dtype = "bfloat16"
    else:
        make_cfg = lambda **kw: tiny_llama_config(  # noqa: E731
            vocab_size=512, num_hidden_layers=2, hidden_size=64,
            intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, **kw)
        batch_b, seq, steps, chunk = 4, 32, 4, 16
        compute_dtype = None

    rng = np.random.RandomState(0)
    ids = rng.randint(0, int(make_cfg().vocab_size),
                      (batch_b, seq)).astype(np.int32)
    item = 2 if compute_dtype == "bfloat16" else 4
    rows_out = []
    for label, overrides in (
            ("dense", {}),
            ("fused", dict(loss_chunk=chunk, fused_norm=True,
                           fused_rope=True))):
        paddle_tpu.seed(0)
        cfg = make_cfg(**overrides)
        model = LlamaForCausalLM(cfg)
        optimizer = opt.AdamW(learning_rate=1e-4,
                              parameters=model.parameters(),
                              weight_decay=0.01)
        trainer = Trainer(model, optimizer, config=TrainStepConfig(
            compute_dtype=compute_dtype))
        batch = {"input_ids": ids, "labels": ids}
        # first-step loss is pre-update on identical seeds: THE parity
        # number (later steps drift as rounding feeds AdamW)
        loss_step1 = float(trainer.step(batch))   # warm + compile
        t0 = time.perf_counter()
        for _ in range(steps):
            loss_t = trainer.step(batch)
        loss = float(loss_t)
        dt = time.perf_counter() - t0
        with observability.scoped(reset=True) as reg:
            trainer.measure_phase_seconds(batch, iters=2)
            h = reg.histogram("train.phase.seconds")
            phases = {}
            for ph in ("fwd", "bwd", "optimizer"):
                cell = h.labeled().get((("phase", ph),))
                phases[ph] = round(cell.sum / max(cell.count, 1), 6) \
                    if cell else None
        n_rows = batch_b * seq
        dense_bytes = dense_logits_bytes(n_rows, cfg.vocab_size, item)
        peak = dense_bytes if not cfg.loss_chunk else \
            dense_bytes - logits_bytes_saved(
                n_rows, cfg.vocab_size, cfg.loss_chunk,
                cfg.loss_vocab_block, item)
        rows_out.append({
            "path": label,
            "loss_chunk": cfg.loss_chunk,
            "tokens_per_sec": round(batch_b * seq * steps / dt, 2),
            "loss_step1": round(loss_step1, 6),
            "loss": round(loss, 6),
            "peak_logits_bytes": int(peak),
            "phase_seconds": phases,
        })
    d, f = rows_out
    return {
        "batch": batch_b, "seq": seq, "steps": steps,
        "vocab_size": int(make_cfg().vocab_size),
        "rows": rows_out,
        "fused_vs_dense_tokens_per_sec": round(
            f["tokens_per_sec"] / max(d["tokens_per_sec"], 1e-9), 4),
        "loss_step1_delta": round(abs(f["loss_step1"]
                                      - d["loss_step1"]), 8),
        "logits_bytes_saved": int(d["peak_logits_bytes"]
                                  - f["peak_logits_bytes"]),
    }


def _overlap_ab():
    """Decomposed-FSDP-collective A/B (ISSUE 19) on a dp x fsdp mesh:
    two fresh same-seed models through the SAME Trainer config/batch —
    one on XLA-propagated collectives, one with the chunked ppermute
    rings (`overlap_fsdp`) — reporting tokens/s, MFU, the first-step
    loss delta (parity evidence) and the overlap fraction + per-phase
    comm seconds from `measure_phase_seconds`'s comm-attribution
    twins. Requires >= 2 jax devices."""
    import time

    import jax
    import paddle_tpu
    import paddle_tpu.optimizer as opt
    from paddle_tpu import observability
    from paddle_tpu.distributed import init_mesh
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM, \
        tiny_llama_config
    from paddle_tpu.parallel import Trainer, TrainStepConfig, \
        llama_sharding_plan

    devs = jax.devices()
    n = len(devs)
    if n < 2:
        raise RuntimeError(f"overlap A/B needs >= 2 devices (got {n})")
    on_tpu = devs[0].platform == "tpu"
    fsdp = 4 if n % 4 == 0 else 2
    dp = max(1, n // fsdp)
    mesh = init_mesh({"dp": dp, "fsdp": fsdp})
    if on_tpu:
        make_cfg = lambda: LlamaConfig(  # noqa: E731
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=4, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=1024,
            rope_theta=10000.0, seq_length=1024)
        batch_b, seq, steps, chunks = 4 * dp * fsdp, 1024, 6, 4
        compute_dtype = "bfloat16"
    else:
        make_cfg = lambda: tiny_llama_config(  # noqa: E731
            vocab_size=512, num_hidden_layers=2, hidden_size=256,
            intermediate_size=512, num_attention_heads=4,
            num_key_value_heads=2, seq_length=64)
        batch_b, seq, steps, chunks = dp * fsdp, 64, 8, 2
        compute_dtype = None

    rng = np.random.RandomState(0)
    ids = rng.randint(0, int(make_cfg().vocab_size),
                      (batch_b, seq)).astype(np.int32)
    rows_out = []
    frac = comm = None
    for label, overlap in (("propagated", False), ("overlapped", True)):
        paddle_tpu.seed(0)
        cfg = make_cfg()
        model = LlamaForCausalLM(cfg)
        optimizer = opt.AdamW(learning_rate=1e-4,
                              parameters=model.parameters(),
                              weight_decay=0.01)
        trainer = Trainer(
            model, optimizer, mesh=mesh,
            plan=llama_sharding_plan(mesh.jax_mesh.axis_names),
            config=TrainStepConfig(compute_dtype=compute_dtype,
                                   overlap_fsdp=overlap,
                                   overlap_chunks=chunks))
        batch = {"input_ids": ids, "labels": ids}
        loss_step1 = float(trainer.step(batch))   # warm + compile
        t0 = time.perf_counter()
        for _ in range(steps):
            loss_t = trainer.step(batch)
        loss = float(loss_t)
        dt = time.perf_counter() - t0
        toks = batch_b * seq * steps / dt
        n_params = sum(int(np.prod(v.shape))
                       for v in trainer.params.values())
        mfu = (6.0 * n_params * toks / (_peak_flops(devs[0]) * n)
               if on_tpu else 0.0)
        row = {"path": label,
               "tokens_per_sec": round(toks, 2),
               "mfu": round(mfu, 4),
               "loss_step1": round(loss_step1, 6),
               "loss": round(loss, 6)}
        if overlap:
            with observability.scoped(reset=True) as reg:
                phases = trainer.measure_phase_seconds(batch, iters=2)
            frac = phases.get("overlap_fraction")
            comm = {"fwd": round(phases.get("fwd_comm", 0.0), 6),
                    "bwd": round(phases.get("bwd_comm", 0.0), 6)}
            row["overlap_fraction"] = (round(frac, 4)
                                       if frac is not None else None)
            row["comm_seconds"] = comm
        rows_out.append(row)
    p, o = rows_out
    return {
        "mesh": {"dp": dp, "fsdp": fsdp},
        "batch": batch_b, "seq": seq, "steps": steps, "chunks": chunks,
        "rows": rows_out,
        "overlapped_vs_propagated_tokens_per_sec": round(
            o["tokens_per_sec"] / max(p["tokens_per_sec"], 1e-9), 4),
        "overlap_fraction": (round(frac, 4)
                             if frac is not None else None),
        "loss_step1_delta": round(abs(o["loss_step1"]
                                      - p["loss_step1"]), 8),
    }


def _overlap_bench():
    """`extra.overlap` entry: the A/B needs an fsdp axis, so it runs
    when this process sees >= 2 chips and says so when it does not."""
    import jax
    if len(jax.devices()) < 2:
        return {"skipped": "one device: no fsdp axis to A/B"}
    return _overlap_ab()


def _fleet_bench(trainer, batch, steps):
    """Heartbeat-publisher overhead (ISSUE 9): the SAME compiled step
    run with observability on, first without the fleet plane, then
    with a FleetHeartbeat publishing into a local TCPStore at an
    aggressively short interval. Reports both tokens/sec numbers and
    the delta — the acceptance claim is that the train metric is
    unchanged with the plane enabled. Also scans the aggregator once
    so the row carries the straggler view a healthy single-rank fleet
    produces (none)."""
    import time

    from paddle_tpu import observability
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.observability.fleet import FleetAggregator

    tokens = 1
    for v in batch.values():
        tokens = int(np.asarray(v).shape[0]) * int(np.asarray(v).shape[1])
        break

    def _run(n):
        t0 = time.perf_counter()
        loss = None
        for _ in range(n):
            loss = trainer.step(batch)
        float(loss)                     # close the dispatch chain
        return time.perf_counter() - t0

    interval = 0.05         # 20 Hz — 40x production cadence (2 s), so
    #                         the measured delta bounds the real cost
    with observability.scoped(reset=True):
        _run(1)                         # warm (telemetry path traced)
        base_dt = _run(steps)
        store = TCPStore(is_master=True, world_size=1)
        try:
            hb = trainer.fleet_heartbeat(store, 0, 1, interval=interval)
            try:
                plane_dt = _run(steps)
            finally:
                hb.stop()
            view = FleetAggregator(store, 1, stale_after_s=60.0).scan()
        finally:
            store.close()
    off = tokens * steps / base_dt
    on = tokens * steps / plane_dt
    return {
        "steps": steps,
        "interval_s": interval,
        "tokens_per_sec_plane_off": round(off, 2),
        "tokens_per_sec_plane_on": round(on, 2),
        "overhead_pct": round((plane_dt - base_dt) / base_dt * 100.0, 2),
        "beats": hb.beats,
        "stragglers": view["summary"]["stragglers"],
    }


def _sentry_bench(on_tpu):
    """Training-sentry cost/benefit (ISSUE 17). (a) Sentry overhead on
    the SAME compiled step (`TrainStepConfig(health_probe=True)`
    built once): a plain step loop vs the loop with the sentry's
    host plane per step — probe readback, EWMA fold, loss-cap staging
    — the acceptance claim is <1% (`overhead_pct`). Primary number:
    the added host segments timed directly inside the on-arm loop
    (`host_us_per_step` over the undisturbed step time), which
    excludes machine noise on the big step in the middle. The
    end-to-end interleaved A/B rides along as `ab_delta_pct` with an
    off-vs-off `aa_floor_pct` control — the delta this machine
    reports when there is NO difference, the error bar on the A/B.
    The compile-level cost of the probe itself (plain config vs
    health_probe config, a second compiled program with the grad-norm
    reduction and param-tree update gate) is `probe_compile_delta_pct`.
    (b) Time-to-recover: a rollback-policy sentried run with one
    injected NaN step (chaos `train.grad.nan`), reporting the
    checkpoint-restore seconds and the whole run's wall time — what
    one numerical fault actually costs end to end."""
    import shutil
    import tempfile
    import time

    import paddle_tpu
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import chaos
    from paddle_tpu.distributed.sentry import SentryConfig, TrainingSentry
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.parallel import Trainer, TrainStepConfig

    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=4,
                          num_attention_heads=16, num_key_value_heads=4,
                          max_position_embeddings=1024,
                          rope_theta=10000.0, seq_length=1024)
        batch_b, seq, steps, compute_dtype = 4, 1024, 8, "bfloat16"
    else:
        # NOT tiny_llama_config: the cost under test is a fixed ~40us
        # of host work per step, so the step must be big enough
        # (~120ms here) that sub-1% deltas resolve above this
        # machine's scheduler noise — on a 7ms tiny step the A/A
        # floor alone exceeds 1%
        cfg = LlamaConfig(vocab_size=1024, hidden_size=256,
                          intermediate_size=704, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=128,
                          rope_theta=10000.0, seq_length=128)
        batch_b, seq, steps, compute_dtype = 4, 128, 8, None

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch_b, seq)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}

    def make(probe):
        paddle_tpu.seed(0)
        m = LlamaForCausalLM(cfg)
        o = opt.AdamW(learning_rate=1e-4, parameters=m.parameters())
        return Trainer(m, o, config=TrainStepConfig(
            compute_dtype=compute_dtype, health_probe=probe))

    def timed(t, n):
        float(t.step(batch))            # warm + compile
        t0 = time.perf_counter()
        loss = None
        for _ in range(n):
            loss = t.step(batch)
        float(loss)                     # close the dispatch chain
        return time.perf_counter() - t0

    # interleave the A/B arms in short blocks so machine drift lands
    # on both equally; per-arm totals stay small because the big step
    # (not sample count) is what buys resolution here
    ab_block = 4 if on_tpu else 6
    ab_rounds = 2 if on_tpu else 4
    ab_steps = ab_block * ab_rounds
    plain_dt = timed(make(False), ab_steps)
    probed = make(True)
    float(probed.step(batch))           # warm + compile

    def run_off(n):
        # reads the loss per step like any loop that logs it — the
        # sentry's contract is no sync BEYOND that read
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            float(probed.step(batch))
            ts.append(time.perf_counter() - t0)
        return ts

    # ONE long-lived sentry across every on-arm rep: a fresh detector
    # re-warms its EWMA and restages the loss cap while it settles,
    # which is a startup transient — the claim under test is the
    # steady-state per-step cost
    s_on = TrainingSentry(SentryConfig(policy="skip", warmup_steps=4))
    on_i = [0]
    host_us = []    # the sentry's ADDED segments, timed directly

    def run_on(n):
        # the host plane run() performs per healthy step: cap staging,
        # probe readback, EWMA fold. Each added segment is also timed
        # on its own — the step+loss-sync in the middle is exactly the
        # off-arm body, so (t1-t0)+(t3-t2) is the sentry's cost with
        # machine noise on the big step excluded
        ts = []
        for _ in range(n):
            i = on_i[0]
            on_i[0] += 1
            t0 = time.perf_counter()
            probed.set_loss_cap(s_on.loss_cap())
            t1 = time.perf_counter()
            loss = float(np.asarray(probed.step(batch)._value))
            t2 = time.perf_counter()
            gn, ap = np.asarray(probed.last_probe).tolist()
            s_on.observe_step(i, i, loss, gn, ap > 0.0)
            t3 = time.perf_counter()
            host_us.append(((t1 - t0) + (t3 - t2)) * 1e6)
            ts.append(t3 - t0)
        return ts

    # same compiled step, sentry off vs on; interleaved arms (drift
    # hits both equally) and a LOW per-step quantile over all reps:
    # scheduler noise is one-sided (delays only add), so the 2nd
    # percentile tracks the undisturbed step where rep wall clocks
    # accumulate every disturbance. A third off-arm pass rides along
    # as an A/A control — `aa_floor_pct` is what this machine reports
    # when there is NO difference, the error bar on `overhead_pct`
    offs, ons, offs2 = [], [], []
    for _ in range(ab_rounds):
        offs.extend(run_off(ab_block))
        ons.extend(run_on(ab_block))
        offs2.extend(run_off(ab_block))
    p2 = lambda ts: float(np.percentile(ts, 2))
    base_step = p2(offs + offs2)
    base_dt = base_step * ab_steps
    sentry_dt = p2(ons) * ab_steps
    aa_floor = abs(p2(offs2) - p2(offs)) / p2(offs) * 100.0
    host_step_us = float(np.median(host_us))
    tokens = batch_b * seq

    # (b) one injected NaN at step 0 under the rollback policy: the
    # sentry restores the (bootstrap) promoted checkpoint and finishes
    ckdir = tempfile.mkdtemp(prefix="sentry-bench-")
    trainer = make(True)
    # compile outside the timed run, under a zero-cap chaos scope: the
    # poison input only exists in the compiled step when the site is
    # armed at trace time, and cap 0 means this warm step never fires
    with chaos.scoped(seed=7, rates={"train.grad.nan": (1.0, 0)}):
        float(trainer.step(batch))
    restore = {}
    orig_load = trainer.load_checkpoint

    def timed_load(path):
        t0 = time.perf_counter()
        orig_load(path)
        restore["seconds"] = time.perf_counter() - t0
    trainer.load_checkpoint = timed_load

    sentry = TrainingSentry(SentryConfig(policy="rollback",
                                         warmup_steps=4,
                                         promote_after=2))
    t0 = time.perf_counter()
    with chaos.scoped(seed=7, rates={"train.grad.nan": (1.0, 1)}):
        out = sentry.run(trainer, lambda c: batch, steps, ckdir,
                         checkpoint_interval=max(2, steps // 4))
    run_dt = time.perf_counter() - t0
    shutil.rmtree(ckdir, ignore_errors=True)

    return {
        "steps": steps,
        "tokens_per_sec_sentry_off": round(
            tokens * ab_steps / base_dt, 2),
        "tokens_per_sec_sentry_on": round(
            tokens * ab_steps / sentry_dt, 2),
        "overhead_pct": round(
            host_step_us / (base_step * 1e6) * 100.0, 3),
        "host_us_per_step": round(host_step_us, 1),
        "ab_delta_pct": round(
            (sentry_dt - base_dt) / base_dt * 100.0, 2),
        "aa_floor_pct": round(aa_floor, 2),
        "probe_compile_delta_pct": round(
            (base_dt - plain_dt) / plain_dt * 100.0, 2),
        "recover": {"rollbacks": out["rollbacks"],
                    "triggers": out["triggers"],
                    "restore_seconds": round(
                        restore.get("seconds", 0.0), 4),
                    "run_seconds": round(run_dt, 3),
                    "promoted_step": out["promoted_step"]},
    }


def _router_bench():
    """Router hop overhead (ISSUE 10): the SAME /predict workload
    measured direct-to-replica and through a 2-replica ReplicaRouter
    on localhost — the p50/p95 delta is the latency one routing hop
    adds (connect + pick + relay), the number a fleet deployment pays
    per request for health-aware failover. Stdlib + a trivial
    dict->dict predictor: no jax, no chip."""
    import json as _json
    import time
    import urllib.request

    from paddle_tpu.inference.router import ReplicaRouter
    from paddle_tpu.inference.serving import PredictorServer

    def pred(inputs):
        return {"y": np.asarray([[1.0]], np.float32)}

    servers = [PredictorServer(pred).start() for _ in range(2)]
    router = ReplicaRouter(
        [f"127.0.0.1:{s.port}" for s in servers]).start()
    try:
        body = _json.dumps({"inputs": {"x": [[1.0, 2.0]]}}).encode()

        def once(port):
            t0 = time.perf_counter()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/predict", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                resp.read()
            return (time.perf_counter() - t0) * 1000.0

        n = 50
        for _ in range(5):                  # warm both paths
            once(servers[0].port)
            once(router.port)
        direct = sorted(once(servers[0].port) for _ in range(n))
        routed = sorted(once(router.port) for _ in range(n))

        def pct(xs, p):
            return xs[min(len(xs) - 1, int(round(p / 100.0
                                                 * (len(xs) - 1))))]

        out = {"requests": n, "replicas": len(servers)}
        for name, xs in (("direct_ms", direct),
                         ("via_router_ms", routed)):
            out[name] = {f"p{p}": round(pct(xs, p), 3)
                         for p in (50, 95)}
        out["added_ms"] = {
            f"p{p}": round(pct(routed, p) - pct(direct, p), 3)
            for p in (50, 95)}
        return out
    finally:
        router.stop()
        for s in servers:
            s.stop()


def _autopilot_bench():
    """Fleet-autopilot control-loop latency (ISSUE 16): how long the
    supervisor takes to put a killed replica back in rotation, how
    long a scale-out lags its trigger, and what a 2-replica rolling
    weight swap costs in wall time and failed requests (the headline
    number: 0). Stdlib + a trivial predictor: no jax, no chip."""
    import json as _json
    import threading
    import time
    import urllib.request

    from paddle_tpu.inference.autopilot import (Autoscaler,
                                                InProcessLauncher,
                                                ReplicaSupervisor,
                                                RolloutController)
    from paddle_tpu.inference.router import ReplicaRouter
    from paddle_tpu.inference.serving import PredictorServer

    def pred(inputs):
        return {"y": np.asarray([[1.0]], np.float32)}

    router = ReplicaRouter()
    launcher = InProcessLauncher(
        lambda slot, version: PredictorServer(
            pred, model_name=f"{slot}@{version}"))
    sup = ReplicaSupervisor(router, launcher, ready_timeout_s=10.0)

    def pump(cond, timeout=15.0):
        end = time.perf_counter() + timeout
        while time.perf_counter() < end:
            router.probe_all()
            sup.tick()
            if cond():
                return True
            time.sleep(0.005)
        return False

    try:
        for i in range(2):
            sup.add_slot(f"r{i}", version="v1")
        router.start(probe=False)
        pump(lambda: router.in_rotation_count() == 2)

        # restart-to-ready: kill r1, measure until back in rotation
        launcher.server("r1").stop()
        t0 = time.perf_counter()
        ok = pump(lambda: sup.slot_state("r1") == "serving")
        restart_s = time.perf_counter() - t0 if ok else None

        # scale-out lag: trigger to new-slot-serving
        asc = Autoscaler(router, sup, max_replicas=3, burn_ticks=1,
                         cooldown_s=0.0,
                         signals=lambda: {"ttft_p95_s": None,
                                          "queue_depth": 1e9,
                                          "shed_rate": 0.0})
        t0 = time.perf_counter()
        asc.tick()
        ok = pump(lambda: sup.slot_state("auto-1") == "serving")
        scale_s = time.perf_counter() - t0 if ok else None
        sup.remove_slot("auto-1")

        # rolling swap under live traffic: duration + failed requests
        body = _json.dumps({"inputs": {"x": [[1.0, 2.0]]}}).encode()
        codes, stop = [], threading.Event()

        def traffic():
            while not stop.is_set():
                req = urllib.request.Request(
                    f"http://127.0.0.1:{router.port}/predict",
                    data=body,
                    headers={"Content-Type": "application/json"})
                try:
                    with urllib.request.urlopen(req, timeout=30) as r:
                        r.read()
                        codes.append(r.status)
                except urllib.error.HTTPError as e:
                    codes.append(e.code)
                except Exception:   # noqa: BLE001 — a hang/reset is a failure to count
                    codes.append(-1)
                time.sleep(0.002)

        th = threading.Thread(target=traffic, daemon=True)
        rc = RolloutController(
            router, sup, step_timeout_s=15.0,
            probe_fn=lambda: (router.probe_all(), sup.tick()))
        th.start()
        t0 = time.perf_counter()
        completed = rc.run("v2")
        rollout_s = time.perf_counter() - t0
        stop.set()
        th.join(timeout=30)
        return {
            "restart_to_ready_s": (round(restart_s, 3)
                                   if restart_s is not None else None),
            "scale_out_lag_s": (round(scale_s, 3)
                                if scale_s is not None else None),
            "rollout_duration_s": round(rollout_s, 3),
            "rollout_completed": bool(completed),
            "rollout_requests": len(codes),
            "rollout_failed_requests": sum(1 for c in codes
                                           if c != 200),
        }
    finally:
        for name in list(sup.slot_names()):
            sup.remove_slot(name)
        router.stop()


def main():
    import jax
    import paddle_tpu
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import LlamaForCausalLM, LlamaConfig
    from paddle_tpu.core import compile_cache, jax_compat
    from paddle_tpu.models.llama import flops_per_token
    from paddle_tpu.parallel import Trainer, TrainStepConfig

    if not jax_compat.on_tpu():
        raise SystemExit(
            f"bench.py needs a TPU: jax reports platform "
            f"{jax.devices()[0].platform!r}. A CPU rate is not a device "
            f"metric; tests and drives on the CPU are tests/ and the "
            f"verify skill.")
    compile_cache.ensure()
    dev = jax.devices()[0]
    peak = _peak_flops(dev)     # unknown device_kind: raises here
    # size the model to the chip: params * 14B (bf16 w + fp32 master +
    # adam m,v) must leave headroom for activations (remat on)
    hbm = int(dev.memory_stats()["bytes_limit"])
    if hbm > 2.5e10:  # v5p/v4-class (95G/32G): TinyLlama-1.1B
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=22, num_attention_heads=32,
            num_key_value_heads=4, max_position_embeddings=2048,
            rope_theta=10000.0, seq_length=2048, recompute=True,
            use_flash_attention=True,
            # blockwise CE (ISSUE 14): the [B*S, 32000] logits no
            # longer cap the batch; PT_BENCH_LOSS_CHUNK=0 reverts
            loss_chunk=int(os.environ.get("PT_BENCH_LOSS_CHUNK",
                                          512)))
        batch, seq, steps = 8, 2048, 10
    else:            # 16G-class chip (v5e/v6e): ~400M params
        # measured on v5e: activations for this size fit without
        # remat, and skipping the recompute pass is ~10% faster
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1280, intermediate_size=3584,
            num_hidden_layers=16, num_attention_heads=20,
            num_key_value_heads=4, max_position_embeddings=2048,
            rope_theta=10000.0, seq_length=2048, recompute=False,
            use_flash_attention=True,
            # ffn fusion measured SLOWER here (split defeats the
            # swiglu epilogue fusion); qkv fusion is neutral-positive
            fuse_attention_qkv=True, fuse_attention_ffn=False,
            loss_chunk=int(os.environ.get("PT_BENCH_LOSS_CHUNK",
                                          512)))
        # batch history: b6 > b4 after the fused CE freed the ~1GB
        # f32 log-softmax residual (r2); b7 > b6 after the in-kernel
        # delta + transposed-lse kernels freed the (b,h,sq,8) f32
        # arrays (r4; b8 measured neutral, no longer thrashing)
        batch, seq, steps = int(os.environ.get("PT_BENCH_BATCH", 7)), \
            2048, 10

    paddle_tpu.seed(0)
    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(), weight_decay=0.01)
    trainer = Trainer(model, optimizer,
                      config=TrainStepConfig(compute_dtype="bfloat16"))

    import itertools
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    # HOST batch fed through the sharding-aware device prefetcher
    # (trainer.data_iter -> io/prefetch.py): H2D happens on the prefetch
    # thread overlapped with the previous step's compute, and step()
    # sees already-placed arrays — the measured loop is the overlapped
    # zero-device_put path real input pipelines take (for a synthetic
    # in-memory batch this can only tie the old pre-staged-array loop,
    # never beat it; the win is that the benchmark now measures the
    # production path)
    data = {"input_ids": ids, "labels": ids}
    it = trainer.data_iter(itertools.repeat(data, steps + 1), depth=3)

    # warmup + compile, drained before the clock starts
    jax.block_until_ready(trainer.step(next(it))._value)

    t0 = time.perf_counter()
    for b in it:
        loss = trainer.step(b)
    # sync: the last step's outputs close the chain
    jax.block_until_ready(loss._value)
    dt = time.perf_counter() - t0
    it.close()

    tokens_per_sec = batch * seq * steps / dt
    ftok = flops_per_token(cfg, seq)
    # recompute replays each layer's forward once: ~8N/token instead of 6N
    if cfg.recompute:
        ftok = ftok * 8.0 / 6.0
    mfu = tokens_per_sec * ftok / peak

    # serving decode microbench (ISSUE 6): the perf trajectory now
    # carries aggregate decode tok/s and KV bytes/slot per attend path
    try:
        decode = _decode_bench(on_tpu=True)
    except Exception as e:           # noqa: BLE001 — never sink the
        decode = {"error": f"{type(e).__name__}: {e}"}  # train metric

    # fleet heartbeat-publisher overhead (ISSUE 9)
    try:
        fleet = _fleet_bench(trainer, data, steps)
    except Exception as e:           # noqa: BLE001 — never sink the
        fleet = {"error": f"{type(e).__name__}: {e}"}   # train metric

    # replica-router hop overhead (ISSUE 10)
    try:
        router = _router_bench()
    except Exception as e:           # noqa: BLE001 — never sink the
        router = {"error": f"{type(e).__name__}: {e}"}  # train metric

    # prefix-cache cold-vs-warm prefill payoff (ISSUE 11)
    try:
        prefix = _prefix_bench()
    except Exception as e:           # noqa: BLE001 — never sink the
        prefix = {"error": f"{type(e).__name__}: {e}"}  # train metric

    # host-tier restore-vs-cold prefill + suspend/resume (ISSUE 18)
    try:
        kvtier = _kvtier_bench()
    except Exception as e:           # noqa: BLE001 — never sink the
        kvtier = {"error": f"{type(e).__name__}: {e}"}  # train metric

    # multi-tenant weighted-fair slot split (ISSUE 13)
    try:
        tenant = _tenant_bench()
    except Exception as e:           # noqa: BLE001 — never sink the
        tenant = {"error": f"{type(e).__name__}: {e}"}  # train metric

    # disaggregated prefill/decode handoff A/B (ISSUE 20)
    try:
        disagg = _disagg_bench()
    except Exception as e:           # noqa: BLE001 — never sink the
        disagg = {"error": f"{type(e).__name__}: {e}"}  # train metric

    # fused-vs-dense train loss path + phase attribution (ISSUE 14)
    try:
        train_breakdown = _train_breakdown(on_tpu=True)
    except Exception as e:           # noqa: BLE001 — never sink the
        train_breakdown = {"error": f"{type(e).__name__}: {e}"}

    # decomposed-FSDP-collective overlap A/B (ISSUE 19)
    try:
        overlap = _overlap_bench()
    except Exception as e:           # noqa: BLE001 — never sink the
        overlap = {"error": f"{type(e).__name__}: {e}"}

    # fleet-autopilot control-loop latency (ISSUE 16)
    try:
        autopilot = _autopilot_bench()
    except Exception as e:           # noqa: BLE001 — never sink the
        autopilot = {"error": f"{type(e).__name__}: {e}"}

    # training-sentry probe overhead + time-to-recover (ISSUE 17)
    try:
        sentry = _sentry_bench(on_tpu=True)
    except Exception as e:           # noqa: BLE001 — never sink the
        sentry = {"error": f"{type(e).__name__}: {e}"}

    extras = {"decode": decode, "fleet": fleet, "router": router,
              "prefix": prefix, "kvtier": kvtier,
              "tenant": tenant, "disagg": disagg,
              "train_breakdown": train_breakdown,
              "overlap": overlap,
              "autopilot": autopilot, "sentry": sentry}
    print(json.dumps({
        "metric": "llama1b_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"mfu": round(mfu, 4),
                  "loss": round(float(loss), 4),
                  "platform": dev.platform,
                  "device": dev.device_kind,
                  "device_count": len(jax.devices()),
                  "batch": batch, "seq": seq, "steps": steps,
                  **extras},
    }))
    broken = sorted(k for k, v in extras.items()
                    if isinstance(v, dict) and "error" in v)
    if broken:
        # printed above, but a broken sub-bench is a failed run
        raise SystemExit(f"bench.py: extra blocks failed: {broken}")


if __name__ == "__main__":
    main()
