"""Pallas routed-experts kernel for a few rows (TPU): the decode step's
expert layer.

A decode step routes a handful of rows (one a slot) to `k` of `E` experts
each, so most experts are hit by no row and those that are see one or two.
The work is reading weights: an expert is three (d x f) matrices, and the
step must read each expert that was hit once and none that was not. The
sort-and-group path (`nn.functional.moe.moe_dropless_mlp`, `ragged_dot`)
is built for thousands of rows an expert; here

- the distinct experts the rows hit are listed outside the kernel (a
  scatter and a stable argsort over E flags) and ride in as a scalar-
  prefetch operand; the grid is (tiles of f, experts hit), its second
  extent a traced value, and the weight blocks' index maps read the list,
  so the pipeline fetches exactly the experts hit, double-buffered, one
  (d, tf) + (d, tf) + (tf, d) tile a step, and no step computes for an
  expert no row chose. Where no row chose a held expert (`share`) the
  grid keeps one step, which zeroes the output under a gate of 0;
- every row meets every listed expert (the rows are few: one small matmul a
  tile), and a row's gate for an expert it did not choose is 0. SwiGLU in
  float32, the products on the MXU in the weights' dtype;
- the output block (rows, d) float32 stays resident across the whole grid
  and takes each expert's gated contribution.

`moe_experts_decode(x, wg, wu, wd, idx, gates)` equals
`moe_dropless_mlp(x, wg, wu, wd, idx, gates)` (tests/test_sparse_attn_moe.py
holds them together in interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.jax_compat import tpu_compiler_params

__all__ = ["moe_experts_decode", "moe_decode_problems", "experts_hit",
           "held_ids"]

# rows up to which the kernel is taken. Every row meets every listed
# expert, so past the rows at which every expert is hit the work grows
# with rows x experts where the grouped path's grows with rows. Measured
# on a v5e at 128 experts of 2048 x 768 in bf16, 8 a row, one layer, ms
# a call, this kernel / the grouped path through the grouped kernel
# (kernels/moe_grouped.py) / through ragged_dot (PERF.md section 6, PR
# 37): 128 rows 1.66 / 1.78 / 4.47, 256: 1.76 / 1.88 / 4.58, 320: 2.14 /
# 1.95 / 4.64, 384: 2.54 / 1.98 / 4.67, 512: 3.36 / 2.06 / 4.81, 1,024:
# 6.60 / 2.44 / 5.27; the crossover lies between 256 and 320 (PR 29,
# against ragged_dot alone: 8 rows 1.31 / 1.65, 32: 2.18 / 3.52)
MAX_ROWS = 256
# what the double-buffered weight tiles of a step may take of VMEM: under
# the 16 MiB a v5e kernel is scoped to by default, with room for x, the
# gates, the output block and the float32 temporaries
_WEIGHT_VMEM = 10 * 2 ** 20


def _f_tile(d, f, itemsize):
    """The widest tile of f (a multiple of 128 that divides f) whose three
    weight blocks fit `_WEIGHT_VMEM` twice over; None if none does."""
    for n in range(1, f // 128 + 1):
        tf = f // n
        if f % n == 0 and tf % 128 == 0 \
                and 2 * 3 * d * tf * itemsize <= _WEIGHT_VMEM:
            return tf
    return None


def moe_decode_problems(rows, d, f, dtype):
    """Reasons these shapes cannot take the kernel; empty = supported."""
    problems = []
    if rows > MAX_ROWS:
        problems.append(f"more than {MAX_ROWS} rows (got {rows})")
    if d % 128 or f % 128:
        problems.append(f"hidden and expert widths must be multiples of "
                        f"128 lanes (got d={d}, f={f})")
    elif _f_tile(d, f, jnp.dtype(dtype).itemsize) is None:
        problems.append(f"no tile of f={f} fits VMEM at d={d}")
    return problems


def held_ids(idx, first, num_held):
    """The rows' choices as ids among the `num_held` experts held here,
    those from `first` on of all the router scores; a choice held
    elsewhere becomes `num_held`, one past the last, which a scatter
    with mode="drop" leaves out."""
    local = idx.astype(jnp.int32) - first
    return jnp.where((local >= 0) & (local < num_held), local, num_held)


def experts_hit(idx, num_experts, share=False):
    """How many distinct experts the rows' choices name: what a step must
    read of the expert weights. idx (T, k) int -> int32 scalar. `share`:
    idx is `held_ids`' and names experts held elsewhere by `num_experts`,
    which count for nothing."""
    hit = jnp.zeros((num_experts,), jnp.int32).at[idx.reshape(-1)].set(
        1, **({"mode": "drop"} if share else {}))
    return jnp.sum(hit)


def _kernel(ids_ref, x_ref, wg_ref, wu_ref, wd_ref, g_ref, o_ref):
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    # said outright: an ambient default of "highest" is no precision of a
    # bf16 product (Mosaic refuses it)
    prec = (jax.lax.Precision.DEFAULT
            if x.dtype in (jnp.bfloat16, jnp.float16)
            else jax.lax.Precision.HIGHEST)
    dot = functools.partial(jnp.dot, precision=prec,
                            preferred_element_type=jnp.float32)
    a, b = dot(x, wg_ref[0]), dot(x, wu_ref[0])
    act = (a * jax.nn.sigmoid(a) * b).astype(x.dtype)
    o_ref[...] += g_ref[0][:, :1] * dot(act, wd_ref[0])


def moe_experts_decode(x, wg, wu, wd, idx, gates, *, interpret=False,
                       share=False):
    """x (T, d), T <= MAX_ROWS; wg, wu (E, d, f); wd (E, f, d); idx, gates
    (T, k): each row's experts and their weights. Returns (T, d) in
    x.dtype: sum_j gates[t, j] * expert_{idx[t, j]}(x[t]).

    `share`: the E experts are a share of those the rows chose among and
    idx is `held_ids`': a choice of value E is of an expert held
    elsewhere, adds nothing and names no expert, so a row whose choices
    are all elsewhere costs nothing."""
    t, d = x.shape
    e, _, f = wg.shape
    problems = moe_decode_problems(t, d, f, wg.dtype)
    if problems and not interpret:
        raise ValueError("moe_experts_decode: " + "; ".join(problems))
    # forward only (a decode step): under an eager op's vjp the operands
    # carry no tangent, so the call needs no differentiation rule
    x, wg, wu, wd, gates = (jax.lax.stop_gradient(a)
                            for a in (x, wg, wu, wd, gates))
    return _call(x, wg, wu, wd, idx.astype(jnp.int32),
                 gates.astype(jnp.float32), interpret=interpret,
                 **({"share": True} if share else {}))


# jitted on its own: a model calls this once a layer, and a caller's trace
# then holds ONE traced and lowered kernel (kernels/paged_attention.py)
@functools.partial(jax.jit, static_argnames=("interpret", "share"))
def _call(x, wg, wu, wd, idx, gates, *, interpret, share=False):
    t, d = x.shape
    e, _, f = wg.shape
    k = idx.shape[1]
    tf = _f_tile(d, f, jnp.dtype(wg.dtype).itemsize) or f
    m = min(e, t * k)                   # the list's length
    rows = -(-t // 16) * 16             # whole sublane tiles of bf16

    # the distinct experts hit first, ascending (`share`: a choice of
    # value e is held elsewhere and left out)
    drop = {"mode": "drop"} if share else {}
    dense = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], idx].add(gates, **drop)         # (T, E)
    hit = jnp.zeros((e,), jnp.int32).at[idx.reshape(-1)].set(1, **drop)
    n = jnp.sum(hit)
    ids = jnp.argsort(1 - hit, stable=True).astype(jnp.int32)[:m]
    # each entry's gate column, broadcast over a lane tile: an expert no
    # row chose has a column of 0
    g = jnp.pad(dense.T[ids], ((0, 0), (0, rows - t)))           # (m, rows)
    g = jnp.broadcast_to(g[:, :, None], (m, rows, 128))
    xp = jnp.pad(x.astype(wg.dtype), ((0, rows - t), (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        # the tiles of f outside, the experts hit inside, as many as there
        # are; one step at least, so that the output block is zeroed
        grid=(f // tf, jnp.maximum(n, 1)),
        in_specs=[
            pl.BlockSpec((rows, d), lambda j, i, ids: (0, 0)),
            pl.BlockSpec((1, d, tf), lambda j, i, ids: (ids[i], 0, j)),
            pl.BlockSpec((1, d, tf), lambda j, i, ids: (ids[i], 0, j)),
            pl.BlockSpec((1, tf, d), lambda j, i, ids: (ids[i], j, 0)),
            pl.BlockSpec((1, rows, 128), lambda j, i, ids: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda j, i, ids: (0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        # the output block is an accumulator across both axes
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_experts_decode",
    )(ids, xp, wg, wu, wd, g)
    return out[:t].astype(x.dtype)
