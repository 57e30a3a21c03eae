#!/usr/bin/env python3
"""Does a serve cell's `correct` see what it claims to? For the families
that bring their own builder. Run by hand on the chip (the train cells have
benchmarks/prove_check.py).

    python3 tools/prove_serve_check.py --workload <serve cell> --seed <n> ...

For each seed: the cell's own check (`benchmarks/serve.py
check_against_reference`: one seeded request through the engine, then the
reference's full forward) as the run makes it, and again with a fault
planted in the program. Builder `sparse_attn_moe`: the key selection off
(every causal key attended), the index pool not written on decode, the last
layer's experts skipped (their down projections zero in the engine's view
of the weights). Builder `window_attn_moe`: the window mask off (a window
layer attends over every causal key, as a full layer does), the routing
bias in the gates (it weighs as well as chooses), the last layer's shared
expert dropped (its down projection zero in the engine's view of the
weights). Builder `block_diffusion_moe`: the forward that stores a settled
block left out (its K and V stay the last denoising step's), a prompt
prefilled under the causal mask, the mask inside a block made causal (a
denoising step's rows see no later row of their block), the last layer's
experts skipped; and beside the honest reading how often two confidences
of a step lie within 1 % of each other, by the reference's own float32
confidences over the honest tokens (`close_confidences`: where they do,
bf16 may unmask in another order than the replay).
Last, the honest engine's tokens against the reference computed in float8
(e4m3, scaled per tensor: every matrix, and every value the reference
stores, through its `store`): the nearest precision under the bf16 the
configuration states. Each reading is what the harness's own
comparison makes of it: the max and mean logit gap in sd, and `correct` as
`serve.run` decides it (max <= the traffic file's tolerance). A control
also proves that its fault was planted before its reading counts (the
patched function ran inside the engine's programs; the engine's view of
the weights holds the zeros; the reference's matrices changed) and says
how many of the engine's tokens it changed. The tolerance is set from this
table (PERF.md); it is not part of a run.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import gc
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", default=[0],
                    help="seeds that take every control")
    ap.add_argument("--honest", type=int, nargs="*", default=[],
                    help="further seeds that take the honest reading alone")
    ap.add_argument("--readings", default=None,
                    help="comma-separated readings a --seed takes beside the "
                         "honest one (default: all): float8_reference, a "
                         "fault's name")
    ap.add_argument("--draw", default=None,
                    help="a JSON object read in the place of the "
                         "configuration's `draw` (to try one before it is "
                         "written into the file)")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from benchmarks import run, serve
    cell = run.load_cell(args.workload, args.rehearse)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference import PagedKVEngine, paged
    from paddle_tpu.jit.functional import state_tensors
    from paddle_tpu.nn.functional import moe as FM
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        raise SystemExit("no accelerator")
    cfg, traffic, builder = cell["config"], cell["traffic"], cell["builder"]
    geo = traffic["engine"]
    tol = traffic["check"]["tolerance_sd"]
    if args.draw is not None:
        cfg["draw"] = json.loads(args.draw)
        print("[draw]", json.dumps(cfg["draw"]), flush=True)
    wanted = args.readings.split(",") if args.readings else None

    def asked(name):
        return wanted is None or name in wanted

    @contextlib.contextmanager
    def patched(obj, name, new):
        old = getattr(obj, name)
        setattr(obj, name, new)
        try:
            yield
        finally:
            setattr(obj, name, old)

    calls = collections.Counter()   # how often a planted function ran

    def selection_off():
        def every_causal_key(scores, causal, k):
            calls["selection_off"] += 1
            return causal
        return patched(paged, "select_top", every_causal_key)

    def index_pool_stale():
        real = paged._attend_indexed

        def stale(q, k, v, cache, state, index):
            out, new = real(q, k, v, cache, state, index)
            if q.shape[1] > 1:
                return out, new
            calls["index_pool_stale_on_decode"] += 1
            return out, (*new[:2], cache[2])
        return patched(paged, "_attend_indexed", stale)

    def window_mask_off():
        """Every window layer attends over every causal key, as a full
        layer does: a decode step takes the whole ring as its view, in
        order from position 0, and a prefill call's kernel is given no
        window. That is the fault only while the ring still holds every
        key, i.e. while the checked context is no longer than a ring
        (`rings_hold_the_context`); the ring's own view with its lower
        edge off would add the at most page_size - 1 keys of the view's
        first page to a window's worth, which is no fault to see."""
        real_chunk = paged.chunk_attention

        def every_causal_key(state, s, window, page_size):
            calls["window_mask_off"] += 1
            rt, lens = paged._val(state.ring_tables), paged._val(state.lens)
            col = jnp.arange(rt.shape[1] * page_size)[None, None]
            t = (lens[:, None] + jnp.arange(s)[None])[..., None]
            return rt, lens, col <= t

        def no_window(*a, window=None, **kw):
            return real_chunk(*a, **kw)
        stack = contextlib.ExitStack()
        stack.enter_context(patched(paged, "_ring_view", every_causal_key))
        stack.enter_context(patched(paged, "chunk_attention", no_window))
        return stack

    def rings_hold_the_context(eng):
        held = min(g["pages_per_slot"] for g in eng.page_groups()
                   if g["window"]) * geo["page_size"]
        checked = traffic["check"]["prompt_tokens"] \
            + traffic["check"]["new_tokens"]
        if checked > held:
            raise RuntimeError(
                f"window_mask_off: the check's {checked} tokens have gone "
                f"round a ring of {held}: the keys outside the window are "
                f"overwritten and the fault cannot be planted this way")

    def bias_in_the_gates():
        real = FM.topk_gating_dropless

        def weighed_by_the_bias(logits, k, bias=None, **kw):
            calls["bias_in_the_gates"] += 1
            idx, _gates, aux = real(logits, k, bias=bias, **kw)
            picked = jnp.take_along_axis(
                jax.nn.sigmoid(logits.astype(jnp.float32))
                + bias.astype(jnp.float32), idx, -1)
            gates = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
            return idx, gates * kw["route_scale"], aux
        return patched(FM, "topk_gating_dropless", weighed_by_the_bias)

    def store_forward_left_out():
        """The tick's second trace of `_block_forward` is the forward that
        stores a settled block (the first is the denoising step's, traced
        once for all steps): it is handed no live row, so it writes
        nothing."""
        real = PagedKVEngine._block_forward

        def unstored(self, ids, lens, rows_live, bt, flat):
            calls["store_forward_left_out"] += 1
            if calls["store_forward_left_out"] % 2 == 0:
                rows_live = jnp.zeros_like(rows_live)
            return real(self, ids, lens, rows_live, bt, flat)
        return patched(PagedKVEngine, "_block_forward", unstored)

    def causal_where(name, rows_hit):
        """`paged_attention_update` loses its `block=` for the calls whose
        rows a slot `rows_hit` takes: those attend causally by position."""
        real = paged.paged_attention_update

        def causal(q, k, v, cache, state, block=None, **kw):
            if rows_hit(q.shape[1], block):
                calls[name] += 1
                block = None
            return real(q, k, v, cache, state, block=block, **kw)
        return patched(paged, "paged_attention_update", causal)

    # by family: the faults planted by a patch of the program (name ->
    # the context that plants it) and the matrix of the last layer whose
    # zeros in the engine's view drop a part of the block
    family = cell["config"]["builder"]
    patches, zeroed = {
        "sparse_attn_moe": (
            {"selection_off": selection_off,
             "index_pool_stale_on_decode": index_pool_stale},
            ("last_experts_skipped",
             lambda m: m.model.layers[-1].mlp.experts_down_weight)),
        "block_diffusion_moe": (
            {"store_forward_left_out": store_forward_left_out,
             "causal_prefill": lambda: causal_where(
                 "causal_prefill", lambda s, b: s > b),
             "causal_inside_the_block": lambda: causal_where(
                 "causal_inside_the_block", lambda s, b: s == b)},
            ("last_experts_skipped",
             lambda m: m.model.layers[-1].mlp.experts_down_weight)),
        "window_attn_moe": (
            {"window_mask_off": window_mask_off,
             "bias_in_the_gates": bias_in_the_gates},
            ("shared_expert_dropped", lambda m: m.model.layers[-1]
             .mlp.shared_expert.down_proj.weight)),
    }[family]

    def engine(model):
        return PagedKVEngine(model, max_slots=geo["max_slots"],
                             page_size=geo["page_size"],
                             num_pages=geo["num_pages"],
                             max_pages_per_slot=geo["max_pages_per_slot"],
                             steps_per_tick=geo["steps_per_tick"],
                             kernel=None)

    def check_then(model, seed, after, planted=lambda eng: None,
                   judge=builder):
        """The check with `after()` run between the engine's request and
        the reference's forward: the engine takes its view of the weights
        at its first program, the reference reads the model's afterwards.
        `planted(eng)` looks at the engine once its request is through."""
        eng = engine(model)
        generate = eng.generate
        seen = {}

        def generate_then(*a, **kw):
            out = generate(*a, **kw)
            seen["tokens"] = [int(t) for t in out[0]]
            planted(eng)
            # the engine's pools, programs and their scratch are gone from
            # the device before anything else is put beside 10 GB of weights
            eng.stop()
            eng.pools, eng._weights = None, None
            eng._programs.clear()
            jax.clear_caches()
            gc.collect()
            after()
            return out
        eng.generate = generate_then
        worst, mean, n = serve.check_against_reference(
            judge, model, cfg, eng, traffic, seed)
        return {"max_sd": worst, "mean_sd": mean, "positions": n,
                # as serve.run decides it, nothing having failed or compiled
                "correct": bool(worst <= tol), "tokens": seen["tokens"]}

    # built once: one program for every seed's replay
    replay_steps = jax.jit(
        lambda p, row: builder.reference.replay(p, cfg, row))

    def close_confidences(model, seed, tokens):
        """Of the blocks the check generated, the share in which the
        reference's own confidences of a step lie within 1 % of each other
        where the order decides something: the last position a step
        unmasks against the first it leaves masked."""
        import numpy as np
        from paddle_tpu.jit.functional import state_arrays
        c = traffic["check"]
        prompt = np.random.default_rng(seed + 1).integers(
            1, cfg["vocab_size"], size=c["prompt_tokens"])
        ids = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
        _rows, steps = replay_steps(state_arrays(model), ids)
        first = c["prompt_tokens"] // cfg["block_length"]
        per_step = cfg["block_length"] // len(steps)
        close = blocks = 0
        for conf, masked in steps:
            conf = np.where(np.asarray(masked), np.asarray(conf), -np.inf)
            ranked = -np.sort(-conf[first:], axis=-1)
            decides = np.isfinite(ranked[:, per_step]) \
                if per_step < ranked.shape[1] else np.zeros(len(ranked), bool)
            a, b = ranked[decides, per_step - 1], ranked[decides, per_step]
            close += int(np.sum((a - b) <= 0.01 * a))
            blocks += int(np.sum(decides))
        return {"steps_that_choose": blocks, "within_1_percent": close}

    def check(model, seed, planted=lambda eng: None):
        return check_then(model, seed, lambda: None, planted)

    def to_float8(x):
        """x as float8 holds it (e4m3: 4 bits of exponent, 3 of mantissa),
        scaled per tensor. `reduce_precision` is an operation of its own:
        a narrowing and widening pair of converts may be dropped by the
        compiler as excess precision."""
        scale = jnp.max(jnp.abs(x.astype(jnp.float32))) / 224.0 + 1e-30
        return (jax.lax.reduce_precision(x.astype(jnp.float32) / scale, 4, 3)
                * scale).astype(x.dtype)

    matrix_to_float8 = jax.jit(to_float8)

    # the family's reference with every stored value through float8
    in_float8 = types.SimpleNamespace(reference=types.SimpleNamespace(
        logits=lambda p, c, row: builder.reference.logits(
            p, c, row, store=to_float8)))

    def round_matrices(model):
        moved = 0.0
        for t in state_tensors(model).values():
            if t._value.ndim >= 2:
                rounded = matrix_to_float8(t._value)
                moved += float(jnp.sum(jnp.abs(
                    rounded.astype(jnp.float32)
                    - t._value.astype(jnp.float32))))
                t._value = rounded
        if not moved > 0.0:
            raise RuntimeError("float8 rounding changed no matrix")

    def build(seed):
        model = builder.build(cfg, seed, dtype="bfloat16",
                              seq=traffic["prompt_tokens"][1],
                              settings=traffic["model_settings"])
        model.eval()
        return model

    def release(model):
        """10 GB twice do not fit: let go of a model's weights by hand,
        whatever still holds the model object."""
        for t in state_tensors(model).values():
            t._value = None
        gc.collect()

    def ran(name):
        def planted(eng):
            if not calls[name]:
                raise RuntimeError(f"{name}: the planted function never "
                                   f"ran inside the engine's programs")
            if name == "store_forward_left_out" and calls[name] != 2:
                raise RuntimeError(
                    f"{name}: the tick traced _block_forward "
                    f"{calls[name]} times, not once a denoising step and "
                    f"once to store: which trace stores is not known")
            calls[name] = 0
            if name == "window_mask_off":
                rings_hold_the_context(eng)
        return planted

    table = []
    for seed in args.seed:
        row = {"seed": seed}

        def read(name, reading):
            tokens = reading.pop("tokens")
            if name == "honest":
                row["honest_tokens"] = tokens
            elif "honest_tokens" in row:
                reading["tokens_changed"] = sum(
                    a != b for a, b in zip(tokens, row["honest_tokens"]))
            else:
                row["first_tokens"] = tokens
            row[name] = reading
            print(f"[reading] seed {seed} {name} {json.dumps(reading)}",
                  flush=True)
        # first, while nothing else is on the device: the honest program
        # against a float8 reference; the matrices are rounded in place,
        # one at a time, after the engine's request, and the model is
        # drawn anew afterwards
        if asked("float8_reference"):
            model = build(seed)
            read("float8_reference", check_then(
                model, seed, lambda: round_matrices(model), judge=in_float8))
            release(model)
        model = build(seed)
        read("honest", check(model, seed))
        if family == "block_diffusion_moe":
            row["close_confidences"] = close_confidences(
                model, seed, row["honest_tokens"])
            print(f"[reading] seed {seed} close_confidences "
                  f"{json.dumps(row['close_confidences'])}", flush=True)
        if row.pop("first_tokens", row["honest_tokens"]) \
                != row["honest_tokens"]:
            raise RuntimeError("the honest engine gave other tokens on the "
                               "same seed: the controls compare nothing")
        for fault, plant in patches.items():
            if asked(fault):
                with plant():
                    read(fault, check(model, seed, ran(fault)))
        # a part of the last layer gives nothing: its down projection is
        # zeros while the engine takes its weights, the reference reads
        # the model's own again
        fault, matrix = zeroed
        if asked(fault):
            down = matrix(model)
            name = next(n for n, t in state_tensors(model).items()
                        if t is down)
            kept, down._value = down._value, jnp.zeros_like(down._value)

            def zeros_reached(eng):
                if float(jnp.sum(jnp.abs(eng._weights[0][name]))) != 0.0:
                    raise RuntimeError("the engine's view of the weights "
                                       "does not hold the zeroed matrix")
            read(fault, check_then(
                model, seed, lambda: setattr(down, "_value", kept),
                zeros_reached))
            del kept, down
        del row["honest_tokens"]
        release(model)
        del model
        table.append(row)
        print(json.dumps(row), flush=True)
    for seed in args.honest:
        model = build(seed)
        reading = check(model, seed)
        del reading["tokens"]
        print(f"[reading] seed {seed} honest {json.dumps(reading)}",
              flush=True)
        table.append({"seed": seed, "honest": reading})
        release(model)
        del model
    for name in table[0]:
        if name in ("seed", "close_confidences"):
            continue
        rows = [r for r in table if name in r]
        worst = [r[name]["max_sd"] for r in rows]
        wrong = sum(not r[name]["correct"] for r in rows)
        print(f"{name:28s} max_sd {min(worst):.4f} .. {max(worst):.4f}  "
              f"mean_sd {min(r[name]['mean_sd'] for r in rows):.4f} .. "
              f"{max(r[name]['mean_sd'] for r in rows):.4f}  tolerance "
              f"{tol}: correct false on {wrong} of {len(rows)} seeds")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
