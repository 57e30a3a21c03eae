"""Secondary model-family benchmarks on the attached TPU chip: the
long-context Llama ladder (S=8k/16k/32k b1, remat, streamed-kv flash
kernels), Qwen2-MoE expert-parallel-shaped train step, and a DiT
forward+backward — the BASELINE.md tracking-table rows beyond the
headline bench.py metric. Run single-process under the default env:
    python tools/model_bench.py [long|moe|dit|all]
Sync discipline per BASELINE.md: fetch the scalar loss, never
block_until_ready.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def _measure_steps(trainer, batch, steps=6, repeats=5):
    """Median-of-`repeats` timed windows of `steps` in-jit steps each
    (VERDICT r3 item 6: one window is one sample; the median over
    several amortized windows plus a reported band is the protocol).
    Returns (median_dt, loss, spread)
    where spread = (max-min)/median over the windows."""
    import statistics
    import jax
    import jax.numpy as jnp
    # pre-stage the batch on device ONCE: a numpy batch pays a
    # host->device transfer every step, which dominates sub-100ms steps
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    jax.block_until_ready(trainer.step(batch)._value)  # compile + sync
    times = []
    loss = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = trainer.step(batch)
        loss = float(loss)                     # sync closes the chain
        times.append((time.perf_counter() - t0) / steps)
    med = statistics.median(times)
    spread = (max(times) - min(times)) / med if med else 0.0
    return med, loss, spread


def bench_long_context():
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import LlamaForCausalLM, LlamaConfig
    from paddle_tpu.parallel import Trainer, TrainStepConfig

    rng = np.random.RandomState(0)
    for S in (8192, 16384, 32768):
        paddle.seed(0)
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1280, intermediate_size=3584,
            num_hidden_layers=16, num_attention_heads=20,
            num_key_value_heads=4, max_position_embeddings=S,
            rope_theta=10000.0, seq_length=S, recompute=True,
            use_flash_attention=True)
        model = LlamaForCausalLM(cfg)
        optimizer = opt.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())
        tr = Trainer(model, optimizer,
                     config=TrainStepConfig(compute_dtype="bfloat16"))
        ids = rng.randint(0, cfg.vocab_size, (1, S)).astype(np.int32)
        dt, loss, sp = _measure_steps(tr, {"input_ids": ids,
                                           "labels": ids})
        print(f"long-context S={S}: {S/dt:,.0f} tok/s/chip "
              f"({dt*1e3:.0f} ms/step, spread {sp:.1%}, "
              f"loss {loss:.3f})", flush=True)
        del tr, model, optimizer


def bench_moe():
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.qwen2_moe import (Qwen2MoeForCausalLM,
                                             tiny_qwen2_moe_config)
    from paddle_tpu.parallel import Trainer, TrainStepConfig

    rng = np.random.RandomState(0)
    paddle.seed(0)
    cfg = tiny_qwen2_moe_config(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        moe_intermediate_size=1408, num_hidden_layers=8,
        num_attention_heads=16, num_key_value_heads=4, num_experts=8,
        num_experts_per_tok=2, seq_length=2048,
        max_position_embeddings=2048, use_flash_attention=True,
        shared_expert_intermediate_size=1408)
    B, S = 4, 2048
    ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    for variant in ("capacity", "dropless"):
        paddle.seed(0)
        cfg.moe_dropless = variant == "dropless"
        model = Qwen2MoeForCausalLM(cfg)
        optimizer = opt.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())
        tr = Trainer(model, optimizer,
                     config=TrainStepConfig(compute_dtype="bfloat16"))
        dt, loss, sp = _measure_steps(tr, {"input_ids": ids,
                                           "labels": ids})
        print(f"qwen2-moe[{variant}] b{B} s{S}: {B*S/dt:,.0f} "
              f"tok/s/chip ({dt*1e3:.0f} ms/step, spread {sp:.1%}, "
              f"loss {loss:.3f})", flush=True)
        del tr, model, optimizer


def bench_dit():
    import paddle_tpu as paddle
    import paddle_tpu.tensor as T

    rng = np.random.RandomState(0)
    paddle.seed(0)
    from paddle_tpu.models import dit
    # DiT-S/2 on 32x32x4 latents, class-conditional (r1/r2 protocol)
    cfg = dit.DiTConfig(input_size=32, patch_size=2, in_channels=4,
                        hidden_size=384, num_layers=12,
                        num_attention_heads=6, num_classes=1000)
    model = dit.DiT(cfg)
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit.functional import functional_call
    from paddle_tpu.parallel import Trainer, TrainStepConfig
    optimizer = opt.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())

    def loss_fn(m, params_c, targs):
        x = T.cast(targs["x"], "bfloat16")     # match compute dtype
        out = functional_call(m, params_c, x, targs["t"], targs["y"])
        return T.mean(T.cast(out, "float32") ** 2)

    tr = Trainer(model, optimizer, loss_fn=loss_fn,
                 config=TrainStepConfig(compute_dtype="bfloat16"))
    # b64 = the BASELINE.md figure (b8 is launch-bound, b128 spills)
    B = int(os.environ.get("PT_DIT_BATCH", "64"))
    batch = {"x": rng.randn(B, 4, 32, 32).astype("float32"),
             "t": rng.randint(0, 1000, (B,)).astype(np.int32),
             "y": rng.randint(0, 1000, (B,)).astype(np.int32)}
    dt, loss, sp = _measure_steps(tr, batch, steps=30, repeats=5)
    print(f"dit-s/2 b{B}: {B/dt:,.0f} imgs/s fwd+bwd+Adam "
          f"({dt*1e3:.1f} ms/step, spread {sp:.1%}, loss {loss:.4f})",
          flush=True)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("long", "all"):
        bench_long_context()
    if which in ("moe", "all"):
        bench_moe()
    if which in ("dit", "all"):
        bench_dit()
