#!/usr/bin/env python3
"""Does a train cell's `correct` see what it claims to? Run on the chip.

    python3 benchmarks/prove_check.py --workload <train cell> --seed <n>

Runs the cell's first step and `--probes` probe steps once, and holds their
losses against three references: the honest one, one that leaves out the
last layer, and one whose matrices went through float8 (e4m3, scaled per
matrix). The last two stand for a step that drops work or computes in a
lower precision: by the tolerances of the traffic file they must fail, the
honest one must pass, and the honest errors show how much room bf16 needs.
The tolerances are set from this table (PERF.md); it is not part of a run.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--probes", type=int, default=32)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from benchmarks import run, train
    cell = run.load_cell(args.workload, args.rehearse)
    os.environ["PADDLE_TPU_AUTOTUNE"] = "0"
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit.functional import state_arrays
    from paddle_tpu.parallel import Trainer, TrainStepConfig
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        raise SystemExit("no accelerator")

    cfg, traffic, builder = cell["config"], cell["traffic"], cell["builder"]
    model = builder.build(cfg, args.seed, dtype="float32", seq=traffic["seq"],
                          settings=traffic["model_settings"])
    ids = next(train.batches(traffic, cfg["vocab_size"], args.seed))["input_ids"]
    params = state_arrays(model)

    def float8(p):
        if p.ndim < 2:
            return p
        scale = jnp.max(jnp.abs(p)) / 448.0
        return (p / scale).astype(jnp.float8_e4m3fn).astype(p.dtype) * scale

    references = {
        "honest": train.reference_losses(builder, params, cfg, ids),
        "last layer left out": train.reference_losses(
            builder, params,
            dict(cfg, num_hidden_layers=cfg["num_hidden_layers"] - 1), ids),
        "float8 matrices": train.reference_losses(
            builder, {n: float8(p) for n, p in params.items()}, cfg, ids),
    }

    o = traffic["optimizer"]
    trainer = Trainer(model, opt.AdamW(learning_rate=o["learning_rate"],
                                       parameters=model.parameters(),
                                       weight_decay=o["weight_decay"]),
                      config=TrainStepConfig(compute_dtype="bfloat16"))
    trainer.set_lr_scale(0.0)
    probes = train.probe_batches(ids, args.probes, args.seed)
    loss0 = float(trainer.step({"input_ids": ids, "labels": ids}))
    got = [float(trainer.step(batch)) for _row, _t, batch in probes]

    check, n = traffic["check"], traffic["check"]["probes"]
    print("tolerances", json.dumps({k: check[k] for k in
                                    ("mean_loss_rel", "position_loss_abs")}),
          f"| a run takes {n} probes, this table {len(probes)}")
    for name, ref in references.items():
        err = [abs(g - ref[row, t]) for g, (row, t, _b) in zip(got, probes)]
        rel = abs(loss0 - ref.mean()) / ref.mean()
        # every block of n probes is what one run would have seen
        blocks = [max(err[i:i + n]) for i in range(0, len(err) - n + 1, n)]
        passes = sum(1 for m in blocks if m <= check["position_loss_abs"]
                     and rel <= check["mean_loss_rel"])
        print(f"{name:22s} mean loss rel {rel:.2e} | single positions: max "
              f"{max(err):.5f} rms "
              f"{math.sqrt(sum(e * e for e in err) / len(err)):.5f} median "
              f"{sorted(err)[len(err) // 2]:.5f} nats | max over each "
              f"{n}: {[round(float(m), 5) for m in blocks]} | passes "
              f"{passes} of {len(blocks)}")


if __name__ == "__main__":
    main()
