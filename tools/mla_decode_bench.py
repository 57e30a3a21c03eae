#!/usr/bin/env python
"""One layer's latent decode attention (`kernels/mla_decode.py`) at the
geometry of the serve cell `serve.kanana-2-30b-a3b.latent-2k-16k`: 32
slots, 32 heads over one latent row of 512 + 64 values stored at 640
lanes, 36,865 pages of 16 rows and a shuffled block table of 1,152 pages a
slot (the trash page 0 past a slot's last page, as the engine keeps it),
lengths log-uniform over 2,048-18,431 from the seed.

Usage:
  python tools/mla_decode_bench.py [--seed N] [--reps 10]

Prints one JSON line: ms a call (the median of `--reps` readings, each 10
calls dispatched back to back, so the host's dispatch stays off the
device's clock), us a key block of the kernel's loop, the roofline by the
benchmark's own cost of the kernel and peaks of the device
(`benchmarks/builders/latent_attn_moe.py` `mla_decode`: every latent row
read once at 576 values, the queries in bf16 and the output in float32,
or the FLOPs where they take longer) and the largest gap to
`mla_decode_reference`. Off a TPU the kernel runs interpreted at small
widths: a check of the script's logic, whose times are no speed and which
has no roofline.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

# runnable as `python tools/mla_decode_bench.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import costs  # noqa: E402
from benchmarks.builders import latent_attn_moe as family  # noqa: E402
from paddle_tpu.kernels import mla_decode  # noqa: E402

CELL = dict(slots=32, heads=32, rank=512, row=576, width=640, page_size=16,
            num_pages=36865, max_pages=1152, shortest=2048, longest=18431)
SMALL = dict(slots=4, heads=8, rank=128, row=192, width=256, page_size=16,
             num_pages=257, max_pages=64, shortest=64, longest=1023)
_CALLS = 10     # calls a reading
_SCALE = 192 ** -0.5    # (128 + 64) ** -0.5: the query's nope + rope
_reference = jax.jit(mla_decode.mla_decode_reference,
                     static_argnames=("rank", "sm_scale"))


def inputs(geo, seed):
    """(q, pool, block_tables, lens) of one decode step at `geo`, from the
    seed: rows and queries hold zeros past the model's row, as the
    engine's pool and query do."""
    rng = np.random.default_rng(seed)
    lo, hi = np.log(geo["shortest"]), np.log(geo["longest"] + 1)
    lens = np.minimum(np.exp(rng.uniform(lo, hi, geo["slots"])).astype(
        np.int64), geo["longest"])
    ps, mp = geo["page_size"], geo["max_pages"]
    table = 1 + rng.permutation(geo["num_pages"] - 1)[:geo["slots"] * mp]
    table = table.reshape(geo["slots"], mp)
    table = np.where(np.arange(mp)[None, :] > (lens // ps)[:, None], 0,
                     table)
    k_pool, k_q = jax.random.split(jax.random.key(seed % 2 ** 32))
    lanes = jnp.arange(geo["width"]) < geo["row"]
    pool = jnp.where(lanes, 0.5 * jax.random.normal(
        k_pool, (geo["num_pages"], ps, geo["width"]), jnp.float32), 0.0)
    q = jnp.where(lanes, jax.random.normal(
        k_q, (geo["slots"], geo["heads"], geo["width"]), jnp.float32), 0.0)
    return (q.astype(jnp.bfloat16), pool.astype(jnp.bfloat16),
            jnp.asarray(table, jnp.int32), jnp.asarray(lens, jnp.int32))


def least_seconds(geo, lens, device_kind):
    """The benchmark's least time of the call on `device_kind`; None for
    a device without published peaks."""
    if device_kind not in costs.PEAKS:
        return None
    # one layer's call as one decode step, its rows the engine's count
    sizes = {"L": 1, "slots": geo["slots"], "H": geo["heads"],
             "row": geo["row"], "R": geo["rank"], "steps_per_tick": 1}
    window = {"ticks": 1,
              "mla_decode_rows": int(np.sum(np.asarray(lens) + 1))}
    return costs.least_seconds("mla_decode", None, sizes, window,
                               device_kind, own=family.costs)[0]


def bench(fn, geo, seed, reps, block_tokens):
    """Time the jitted `fn(q, pool, block_tables, lens)` at `geo`;
    `block_tokens` is the keys one turn of its loop takes."""
    q, pool, bt, lens = inputs(geo, seed)
    got = fn(q, pool, bt, lens).block_until_ready()
    want = _reference(q, pool, bt, lens, rank=geo["rank"], sm_scale=_SCALE)
    readings = []
    for _ in range(reps):
        t = time.perf_counter()
        for _ in range(_CALLS):
            out = fn(q, pool, bt, lens)
        out.block_until_ready()
        readings.append((time.perf_counter() - t) / _CALLS)
    took = statistics.median(readings)
    blocks = int(np.sum(np.maximum(np.asarray(lens), 0) // block_tokens + 1))
    least = least_seconds(geo, lens, jax.devices()[0].device_kind)
    return {"ms_per_call": took * 1e3, "blocks": blocks,
            "us_per_block": took * 1e6 / blocks,
            "roofline_pct": None if least is None else 100.0 * least / took,
            "max_err": float(jnp.max(jnp.abs(got - want)))}


def kernel(geo, interpret):
    """The tree's kernel, jitted, as `bench` calls it."""
    return jax.jit(functools.partial(
        mla_decode.mla_decode_attention, rank=geo["rank"], sm_scale=_SCALE,
        interpret=interpret))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=4200000001)
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)
    on_tpu = jax.default_backend() == "tpu"
    geo = CELL if on_tpu else SMALL
    ps = geo["page_size"]
    row = bench(kernel(geo, not on_tpu), geo, args.seed, args.reps,
                mla_decode.block_pages(ps, geo["max_pages"]) * ps)
    print(json.dumps({"kernel": "mla_decode", "device":
                      jax.devices()[0].device_kind, "seed": args.seed,
                      **row}), flush=True)


if __name__ == "__main__":
    main()
