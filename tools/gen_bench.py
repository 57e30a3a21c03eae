"""KV-cache decode benchmark on the attached TPU chip.

Run on the chip, one process:
    python tools/gen_bench.py [batch] [prompt_len] [new_tokens]
Measures, for an 8L/1024h bf16 Llama (the serving config BASELINE.md's
latency table uses): prefill latency, per-token decode latency, and
decode throughput through models.generation's jitted prefill/decode
steps. The per-token loop fetches every token id to the host (streaming
and eos early-exit need it), so its latency includes one host sync per
token; the tokens_per_fetch block below amortizes that. Not measured on
the present machine."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import LlamaForCausalLM
from paddle_tpu.models.llama import tiny_llama_config
from paddle_tpu.models.generation import generate_stream

b = int(sys.argv[1]) if len(sys.argv) > 1 else 1
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
ids = np.random.RandomState(0).randint(0, cfg.vocab_size,
                                       (b, s)).astype("int32")

# warm (compile prefill + decode) — SAME max_new_tokens as the measured
# pass: the cache buffer shape is s+new, so a different warm length
# would leave the measured pass recompiling both programs
t0 = time.perf_counter()
for i, tok in enumerate(generate_stream(model, ids, max_new_tokens=new)):
    if i == 0:
        print(f"compile+first-token: {time.perf_counter()-t0:.1f}s",
              flush=True)
    if i == 1:
        print(f"decode compiled at {time.perf_counter()-t0:.1f}s",
              flush=True)
        break

# measured pass
t0 = time.perf_counter()
times = []
for tok in generate_stream(model, ids, max_new_tokens=new):
    times.append(time.perf_counter())
prefill_ms = (times[0] - t0) * 1e3
decode = np.diff(np.array(times)) * 1e3
print(f"b{b} s{s}: prefill {prefill_ms:.1f} ms | decode p50 "
      f"{np.percentile(decode, 50):.1f} ms/tok, p90 "
      f"{np.percentile(decode, 90):.1f} | throughput "
      f"{b * len(decode) / (times[-1] - times[0]):.1f} tok/s "
      f"({len(decode)} steps)")

# device-side block decode (r4): tokens_per_fetch=N runs N decode steps
# in ONE lax.while_loop program per host round trip, so the per-token
# host sync amortizes N-fold and the number reflects chip decode rate.
for tpf in (32,):
    # warm the block program
    for _ in generate_stream(model, ids, max_new_tokens=new,
                             tokens_per_fetch=tpf):
        pass
    t0 = time.perf_counter()
    n = 0
    for tok in generate_stream(model, ids, max_new_tokens=new,
                               tokens_per_fetch=tpf):
        n += 1
    dt = time.perf_counter() - t0
    # the first token comes from prefill; the block path covers the rest
    print(f"b{b} s{s} tokens_per_fetch={tpf}: {b * n / dt:.1f} tok/s "
          f"end-to-end incl prefill | {(dt) * 1e3 / n:.2f} ms/tok avg "
          f"({n} tokens)")
