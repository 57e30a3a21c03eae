"""Traffic kind `train`: the program's `Trainer` fed through its own
prefetcher, measured in readings of a few steps each.

A reading is `steps_per_reading` steps dispatched back to back; the clock is
read when the last of them has finished on the device, with the next
`dispatch_ahead` steps already queued, so the device never waits for the
host between readings. The end-to-end rate is all the tokens of the window's
readings over all their time: whole readings, so that a step more or less
at the window's edge does not move it. The median reading is kept beside it;
`step.stall_share` is their distance, what stalls cost the window.

`correct` holds the step's own losses against the plain reference before
the window. A mean over 8,188 token losses hides almost any fault (at random
weights it is ln V + sigma^2 / 2 for almost any network), so besides the
mean of the first batch the loss of single positions is compared: a probe
step sees the first batch with every label but one ignored, and its loss is
that position's. Probes and the first step run at learning rate 0
(`Trainer.set_lr_scale`, an input of the program), so all of them are at the
seed's weights and one reference pass serves them all.
"""
from __future__ import annotations

import collections
import math
import statistics
import time

import numpy as np


def batches(traffic, vocab, seed):
    """A fixed pool of seeded batches, cycled for ever."""
    rng = np.random.default_rng(seed)
    pool = [rng.integers(0, vocab, (traffic["batch"], traffic["seq"]),
                         dtype=np.int32)
            for _ in range(traffic["distinct_batches"])]
    i = 0
    while True:
        ids = pool[i % len(pool)]
        yield {"input_ids": ids, "labels": ids}
        i += 1


def reference_losses(builder, params, cfg, ids):
    """(B, S-1) next-token losses of the batch by the plain reference, one
    sequence at a time (one program, float32 logits of one sequence)."""
    import jax
    fn = jax.jit(lambda p, row: builder.reference.next_token_losses(
        p, cfg, row))
    return np.stack([np.asarray(fn(params, ids[b]), np.float64)
                     for b in range(ids.shape[0])])


def probe_batches(ids, n, seed):
    """n probes [(row, position, batch)]: the batch is `ids` with every
    label ignored but the one position t scores, so the step's loss is
    -log p(ids[row, t+1] | ids[row, :t+1]) alone. Rows take turns and the
    positions are spread over the sequence, one in each n-th of it."""
    rng = np.random.default_rng(seed)
    b, s = ids.shape
    out = []
    for k in range(n):
        row = k % b
        t = int(rng.integers(k * (s - 1) // n, (k + 1) * (s - 1) // n))
        labels = np.full_like(ids, -100)
        labels[row, t + 1] = ids[row, t + 1]
        out.append((row, t, {"input_ids": ids, "labels": labels}))
    return out


def run(cell, args, clock, clog, log):
    import jax
    from jax.profiler import TraceAnnotation

    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit.functional import state_arrays
    from paddle_tpu.parallel import Trainer, TrainStepConfig

    cfg, traffic, builder = cell["config"], cell["traffic"], cell["builder"]
    chips, check = cell["chips"], traffic["check"]
    model = builder.build(cfg, args.seed, dtype="float32", seq=traffic["seq"],
                          settings=traffic["model_settings"])
    clock.mark("build")

    source = batches(traffic, cfg["vocab_size"], args.seed)
    first = next(source)
    want = reference_losses(builder, state_arrays(model), cfg,
                            first["input_ids"])
    probes = probe_batches(first["input_ids"], check["probes"], args.seed)
    clock.mark("reference")

    o = traffic["optimizer"]
    optimizer = opt.AdamW(learning_rate=o["learning_rate"],
                          parameters=model.parameters(),
                          weight_decay=o["weight_decay"])
    trainer = Trainer(model, optimizer,
                      config=TrainStepConfig(compute_dtype="bfloat16"))
    clock.mark("trainer")

    def feed():
        yield first
        for _row, _t, batch in probes:
            yield batch
        yield from source
    it = trainer.data_iter(feed(), depth=traffic["prefetch_depth"])
    per, ahead = traffic["steps_per_reading"], traffic["dispatch_ahead"]
    tokens_per_step = traffic["batch"] * traffic["seq"]
    try:
        placed = next(it)       # as the prefetcher lays it out
        trainer.set_lr_scale(0.0)
        loss0 = trainer.step(placed)
        jax.block_until_ready(loss0._value)
        clock.mark("compile_or_load")
        traces0 = trainer._trace_count()
        probed = [trainer.step(next(it)) for _ in probes]
        trainer.set_lr_scale(1.0)
        last = probed[-1]
        for _ in range(traffic["warm_steps"]):
            last = trainer.step(next(it))
        jax.block_until_ready(last._value)
        clock.mark("probes_and_warmup")
        setup_s = clock.total()

        # -- the measured window ----------------------------------------
        mark = clog.mark()
        # what the family counts itself, read as the window opens and closes
        counters = getattr(builder, "counters", lambda _trainer: {})
        counted0 = counters(trainer)
        losses, marks, readings = [], collections.deque(), []
        wait_s, n, traced = 0.0, 0, False
        t0 = t_prev = time.perf_counter()
        while True:
            with TraceAnnotation("bench.next_batch"):
                t = time.perf_counter()
                batch = next(it)
                wait_s += time.perf_counter() - t
            with TraceAnnotation("bench.dispatch"):
                loss = trainer.step(batch)
            n += 1
            losses.append(loss)
            if n % per == 0:
                marks.append((n, loss))
            if not marks or n - marks[0][0] < ahead:
                continue
            with TraceAnnotation("bench.sync"):
                jax.block_until_ready(marks.popleft()[1]._value)
            now = time.perf_counter()
            readings.append(now - t_prev)
            t_prev = now
            if now - t0 >= args.seconds:
                break
            if args.trace and not traced and now - t0 >= args.seconds / 3:
                jax.block_until_ready(losses[-1]._value)
                cell["tracer"].record(
                    lambda: _traced_steps(trainer, it, traffic, losses))
                traced = True
                marks.clear()
                n = 0
                t_prev = time.perf_counter()
        done = len(readings) * per
        jax.block_until_ready(losses[-1]._value)
        counted = {k: v - counted0.get(k, 0)
                   for k, v in counters(trainer).items()}
        compiled_in_window = clog.since(mark)["programs"]
    finally:
        it.close()

    values = [float(x) for x in losses]
    spent = sum(readings)
    rate = done * tokens_per_step / chips / spent
    median = per * tokens_per_step / chips / statistics.median(readings)
    got = float(loss0)
    rel = abs(got - want.mean()) / want.mean()
    probe_err = [abs(float(x) - want[row, t])
                 for x, (row, t, _b) in zip(probed, probes)]
    bad = sum(1 for x in values if not math.isfinite(x))
    retraced = trainer._trace_count() - traces0
    log("[window]", {"readings": len(readings), "steps_per_reading": per,
                     "tokens_per_s_per_chip": rate,
                     "median_reading_tokens_per_s_per_chip": median,
                     "window_s": spent, "input_wait_s": wait_s,
                     "compiled_in_window": compiled_in_window})
    log(f"[correct] loss0 {got:.6f} reference {want.mean():.6f} rel "
        f"{rel:.2e} (tolerance {check['mean_loss_rel']}); {len(probes)} "
        f"single positions, |loss - reference| max {max(probe_err):.5f} "
        f"rms {math.sqrt(sum(e * e for e in probe_err) / len(probes)):.5f} "
        f"nats (tolerance {check['position_loss_abs']}); {len(values)} "
        f"losses, last {values[-1]:.4f}, non-finite {bad}; retraced "
        f"{retraced}")
    window = dict(counted, rate=rate, median_reading_rate=median,
                  window_s=spent, input_wait_s=wait_s)
    if args.trace:
        # the same program as the step's, so it comes from the cache
        ma = trainer.lower(placed).compile().memory_analysis()
        window["compiled_bytes"] = (
            ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    return {
        "correct": bool(rel <= check["mean_loss_rel"]
                        and max(probe_err) <= check["position_loss_abs"]
                        and bad == 0 and retraced == 0
                        and compiled_in_window == 0),
        "attempted": done, "failed": bad, "setup_s": setup_s,
        "end_to_end": {"train_tokens_per_s_per_chip": rate},
        "window": window,
        "compared": {"mean_loss_rel": (rel, check["mean_loss_rel"]),
                     "position_loss_abs": (max(probe_err),
                                           check["position_loss_abs"]),
                     "nonfinite_losses": (bad, 0), "retraced": (retraced, 0),
                     "compiled_in_window": (compiled_in_window, 0)},
    }


def _traced_steps(trainer, it, traffic, losses):
    """What runs under the profiler: a few whole steps, synced at the end."""
    import jax
    from jax.profiler import TraceAnnotation
    for _ in range(traffic["trace_steps"]):
        with TraceAnnotation("bench.next_batch"):
            batch = next(it)
        with TraceAnnotation("bench.dispatch"):
            losses.append(trainer.step(batch))
    with TraceAnnotation("bench.sync"):
        jax.block_until_ready(losses[-1]._value)
