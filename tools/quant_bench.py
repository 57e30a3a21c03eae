"""int8-vs-bf16 inference benchmark on the attached TPU chip (VERDICT r2
item 3 evidence). Run on the chip, one process:
    python tools/quant_bench.py
Measures a 12-layer/1024-hidden Llama forward, bf16 weights vs PTQ
int8 (W8A8: s8 x s8 -> s32 dot_general + fused dequant epilogue).
Builders' account, taken before PR 1 and not reproduced on the present
machine (v5e 16G, b4 s1024): bf16 40.6 ms, int8 35.0 ms = 1.16x.
Matmul micro (4096^3, chained): bf16 118.6 TF/s, int8 128.3 TOP/s =
1.08x."""
import os
import sys
import time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.models import LlamaForCausalLM
from paddle_tpu.models.llama import tiny_llama_config
from paddle_tpu.quantization import (PTQ, QuantConfig, HistObserver,
                                     AbsMaxChannelWiseWeightObserver,
                                     QuantizedLinear, QuantizedConv2D)

import paddle_tpu.tensor as T


def _bench_conv():
    """int8 conv stack vs bf16 (QuantizedConv2D W8A8 path): 8x
    Conv2D(256,256,3x3) at 56x56 b8 NCHW — ~237 GFLOP/forward."""
    from paddle_tpu import nn
    paddle.seed(0)
    layers = []
    for _ in range(8):
        layers += [nn.Conv2D(256, 256, 3, padding=1), nn.ReLU()]
    model = nn.Sequential(*layers)
    model.eval()
    model = paddle.amp.decorate(models=model, level="O2", dtype="bfloat16")
    rng = np.random.RandomState(0)
    calib = [rng.randn(2, 256, 56, 56).astype("float32") * 0.5
             for _ in range(3)]
    q = PTQ(QuantConfig(activation=HistObserver(percent=0.9999),
                        weight=AbsMaxChannelWiseWeightObserver()))
    qmodel = q.quantize(model)
    for c in calib:
        qmodel(paddle.cast(paddle.to_tensor(c), "bfloat16"))
    int8_model = q.convert(qmodel, execute="int8")
    n8 = sum(isinstance(l, QuantizedConv2D) for l in int8_model.sublayers())
    print("int8 convs:", n8, flush=True)
    x = rng.randn(8, 256, 56, 56).astype("float32") * 0.5

    def bench(m, reps=20):
        sf = paddle.jit.to_static(m)
        xt = paddle.cast(paddle.to_tensor(x), "bfloat16")
        with paddle.no_grad():
            first = sf(xt).numpy()
            float(T.sum(sf(xt)))
            t0 = time.perf_counter()
            for _ in range(reps):
                out = sf(xt)
            float(T.sum(out))
        return (time.perf_counter() - t0) / reps, first

    tb, rf = bench(model)
    ti, ri = bench(int8_model)
    rel = np.abs(ri.astype(np.float32) - rf.astype(np.float32)).mean() \
        / (np.abs(rf.astype(np.float32)).mean() or 1.0)
    gflop = 2 * 8 * 8 * 56 * 56 * 256 * 256 * 9 / 1e9
    print(f"bf16 conv fwd: {tb*1e3:.2f} ms ({gflop/tb/1e3:.1f} TF/s) | "
          f"int8: {ti*1e3:.2f} ms ({gflop/ti/1e3:.1f} TOP/s) | "
          f"speedup {tb/ti:.2f}x | rel-err {rel:.4f}")


if len(sys.argv) > 1 and sys.argv[1] == "conv":
    _bench_conv()
    sys.exit(0)

paddle.seed(0)
cfg = tiny_llama_config(num_hidden_layers=12, hidden_size=1024,
                        intermediate_size=2816, num_attention_heads=16,
                        num_key_value_heads=8, vocab_size=16384,
                        seq_length=1024)
model = LlamaForCausalLM(cfg)
model.eval()
# bf16 baseline (the deployment dtype)
model = paddle.amp.decorate(models=model, level="O2", dtype="bfloat16")
rng = np.random.RandomState(0)
calib = [rng.randint(0, cfg.vocab_size, (2, 128)).astype("int32")
         for _ in range(3)]
q = PTQ(QuantConfig(activation=HistObserver(percent=0.9999),
                    weight=AbsMaxChannelWiseWeightObserver()))
qmodel = q.quantize(model)
for ids in calib:
    qmodel(paddle.to_tensor(ids))
int8_model = q.convert(qmodel, execute="int8")
del qmodel
n8 = sum(isinstance(l, QuantizedLinear) for l in int8_model.sublayers())
print("int8 linears:", n8, flush=True)

x = rng.randint(0, cfg.vocab_size, (4, 1024)).astype("int32")

import paddle_tpu.tensor as T

def bench(m, reps=15):
    sf = paddle.jit.to_static(m)
    xt = paddle.to_tensor(x)
    with paddle.no_grad():
        first = sf(xt).numpy()         # sync + compile (fetch once)
        float(T.sum(sf(xt)))           # warm the scalar-fetch path
        t0 = time.perf_counter()
        for _ in range(reps):
            out = sf(xt)
        float(T.sum(out))              # sync on a scalar, not 268MB
    return (time.perf_counter() - t0) / reps, first

tb, lf = bench(model)
ti, li = bench(int8_model)
agree = (li.argmax(-1) == lf.argmax(-1)).mean()
print(f"bf16 forward: {tb*1e3:.2f} ms | int8 forward: {ti*1e3:.2f} ms | "
      f"speedup {tb/ti:.2f}x | top1-agree {agree:.3f}")
