"""Continuous-batching paged-KV serving benchmark (real TPU chip).

Run on the chip, one process:
    python tools/serving_bench.py [n_requests] [prompt_len] [new_tokens]

Measures aggregate decode throughput of the PagedKVEngine
(inference/paged.py) serving `n_requests` requests through
`max_slots=8` decode slots — requests join mid-decode as earlier ones
finish, which is the capability the r4 fixed-batch number (380.6 tok/s
aggregate, BASELINE.md "BATCHED serving") could not exercise: there, 8
streams had to start and finish together.

Model = the serving config BASELINE.md's latency table uses
(8L/1024h bf16 Llama). Decode runs steps_per_tick steps per host round
trip (the same amortization as tokens_per_fetch=32 in gen_bench).

Protocol: all requests submitted up front (a closed-loop saturation
test); engine drains them; aggregate tok/s = total generated tokens /
wall time after the compile warmup. A heterogeneous variant staggers
budgets so slots retire early and refill mid-decode.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.inference.paged import PagedKVEngine
from paddle_tpu.models import LlamaForCausalLM
from paddle_tpu.models.llama import tiny_llama_config

n_req = int(sys.argv[1]) if len(sys.argv) > 1 else 16
s = int(sys.argv[2]) if len(sys.argv) > 2 else 512
new = int(sys.argv[3]) if len(sys.argv) > 3 else 64

paddle.seed(0)
cfg = tiny_llama_config(num_hidden_layers=8, hidden_size=1024,
                        intermediate_size=2816, num_attention_heads=16,
                        num_key_value_heads=8, vocab_size=16384,
                        max_position_embeddings=s + new, seq_length=s)
model = LlamaForCausalLM(cfg)
model.eval()
model = paddle.amp.decorate(models=model, level="O2", dtype="bfloat16")

PAGE = 64
pages_per_req = -(-(s + new) // PAGE)
eng = PagedKVEngine(model, max_slots=8, page_size=PAGE,
                    num_pages=8 * pages_per_req + 1,
                    max_pages_per_slot=pages_per_req,
                    steps_per_tick=16)
rng = np.random.RandomState(0)
prompts = [rng.randint(0, cfg.vocab_size, (s,)).astype("int32")
           for _ in range(n_req)]

# warm: compile BOTH prefill widths (single + storm) and the tick
t0 = time.perf_counter()
r = eng.submit(prompts[0], max_new_tokens=new)
eng.step()
print(f"single prefill + tick compiled: {time.perf_counter()-t0:.1f}s",
      flush=True)
eng.run_until_idle()
r.result()
storm = [eng.submit(p, max_new_tokens=2) for p in prompts[:8]]
eng.run_until_idle()              # compiles the batched (bw=8) prefill
for rr in storm:
    rr.result()
print(f"warm (incl. storm prefill) done: {time.perf_counter()-t0:.1f}s",
      flush=True)
warm_pf, warm_tk = eng.stats["prefill_s"], eng.stats["tick_s"]

# measured: saturate 8 slots from a 16-deep queue; finishing requests
# free their slot and the queue refills it mid-decode of the others
t0 = time.perf_counter()
reqs = [eng.submit(p, max_new_tokens=new) for p in prompts]
eng.run_until_idle()
dt = time.perf_counter() - t0
total = sum(len(r.result()) for r in reqs)
pf = eng.stats["prefill_s"] - warm_pf
tk = eng.stats["tick_s"] - warm_tk
print(f"continuous batching: {n_req} reqs x {new} tok (b8 slots, "
      f"s{s}): {total} tokens in {dt:.2f}s = "
      f"{total / dt:.1f} tok/s aggregate | ticks={eng.stats['ticks']} "
      f"prefills={eng.stats['prefills']} | prefill {pf:.2f}s, decode "
      f"ticks {tk:.2f}s -> decode-phase "
      f"{(total - n_req) / tk:.1f} tok/s "
      f"({total - n_req} tick tokens)")

# heterogeneous budgets: half the requests are short (16 tokens), so
# slots retire early and refill mid-decode — the admission-latency
# shape fixed-batch serving cannot express
eng2 = PagedKVEngine(model, max_slots=8, page_size=PAGE,
                     num_pages=8 * pages_per_req + 1,
                     max_pages_per_slot=pages_per_req,
                     steps_per_tick=16)
r0 = eng2.submit(prompts[0], max_new_tokens=new)
eng2.run_until_idle()          # warm this engine's programs
storm2 = [eng2.submit(p, max_new_tokens=2) for p in prompts[:8]]
eng2.run_until_idle()
warm2 = dict(eng2.stats)          # snapshot: report the measured phase only
budgets = [16 if i % 2 else new for i in range(n_req)]
t0 = time.perf_counter()
reqs = [eng2.submit(p, max_new_tokens=m)
        for p, m in zip(prompts, budgets)]
eng2.run_until_idle()
dt = time.perf_counter() - t0
total = sum(len(r.result()) for r in reqs)
print(f"heterogeneous budgets: {total} tokens in {dt:.2f}s = "
      f"{total / dt:.1f} tok/s aggregate | admitted="
      f"{eng2.stats['admitted'] - warm2['admitted']} "
      f"ticks={eng2.stats['ticks'] - warm2['ticks']}")

# overload probe: with a bounded pending queue, a burst beyond the
# bound sheds a typed EngineOverloaded (what the HTTP tier maps to a
# retryable 503) instead of queueing unboundedly
from paddle_tpu.inference.overload import EngineOverloaded
eng2.max_pending = 2
admitted, shed = [], 0
for p in prompts:
    try:
        admitted.append(eng2.submit(p, max_new_tokens=8))
    except EngineOverloaded:
        shed += 1
eng2.run_until_idle()
for r in admitted:
    r.result()
print(f"overload probe (max_pending=2): {len(admitted)} admitted, "
      f"{shed} shed | engine counters: "
      f"overloaded={eng2.stats['overloaded']} "
      f"expired={eng2.stats['expired']}")
