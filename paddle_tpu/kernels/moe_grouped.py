"""Pallas grouped matmul for routed experts over many rows (TPU): a
prefill chunk's expert layer.

A chunk of T tokens routes T x k (row, expert) pairs, sorted by expert,
so that expert i owns the contiguous rows [offsets[i], offsets[i + 1]).
XLA's grouped matmul (`jax.lax.ragged_dot`) tiles those rows by 512: at
the doc-QA chunk (1,024 tokens x 8 over 128 experts, ~64 rows an expert)
it computes about 143 (tile, expert) visits x 512 rows, 8.9 times the
rows that are there. Here, after the scheme of jax's bundled megablox
`gmm`:

- the metadata (`make_group_metadata`: each visit's group and m-tile,
  the number of visits) is computed outside the kernel and rides in as
  scalar-prefetch operands; the grid's first axis is the number of
  visits, a traced value, so tiles with no rows of a held expert (those
  `moe_held_experts` leaves past the held groups) are never visited;
- the row tile `tm` fits the rows an expert gets (`grouped_plan`);
- one call is the whole SwiGLU: gate and up over the expert's (d, tn)
  tiles, rounded to the weights' dtype, silu in float32, the product in
  the weights' dtype against the (tn, d) tile of down, accumulated in
  float32 over the tiles of f: the precision of the three ragged_dots.
  Where one tile holds all of f, consecutive visits of one expert keep
  their block indices and the pipeline fetches that expert once;
- a visit stores only its group's rows of the (tm, d) output block,
  which stays resident while consecutive visits share its m-tile. Rows
  that belong to no group are left as they were: the caller's.

`moe_grouped(x, wg, wu, wd, group_sizes)` equals `ragged_swiglu` of
`nn.functional.moe` on every row of a group (tests/test_moe_grouped.py
holds them together in interpret mode).
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from paddle_tpu.core.jax_compat import tpu_compiler_params

__all__ = ["moe_grouped", "grouped_plan", "grouped_problems",
           "GroupedPlan", "recording"]

# what the tiling rule weighs a plan by: a v5e's bf16 peak, its HBM
# bandwidth, and what a grid step costs on its own
_PEAK_FLOPS = 197e12
_HBM_BYTES_S = 819e9
_STEP_S = 0.35e-6
_ROW_TILES = (128, 256, 512)
# what the double-buffered weight tiles of a step may take of VMEM (a
# v5e core has 128 MiB; the limit is stated per call, `_vmem_limit`)
_WEIGHT_VMEM = 40 * 2 ** 20


class GroupedPlan(NamedTuple):
    """How one call engages: `m` rows (pairs, padded to whole tiles of
    `tm` inside), `e` experts, a (tm, d) tile of rows against (d, tn)
    tiles of gate and up (`tk` = d: the contraction held whole) and
    (tn, d) tiles of down, and at most `visits` (m-tile, expert) visits,
    the grid's first axis."""
    m: int
    e: int
    tm: int
    tk: int
    tn: int
    visits: int


def _f_tile(d, f, itemsize):
    """The widest tile of f (a multiple of 128 that divides f) whose
    three weight blocks fit `_WEIGHT_VMEM` twice over; None if none
    does."""
    for n in range(1, f // 128 + 1):
        tn = f // n
        if f % n == 0 and tn % 128 == 0 \
                and 2 * 3 * d * tn * itemsize <= _WEIGHT_VMEM:
            return tn
    return None


def grouped_problems(d, f, dtype):
    """Reasons these widths cannot take the kernel; empty = supported."""
    if d % 128 or f % 128:
        return [f"hidden and expert widths must be multiples of 128 lanes "
                f"(got d={d}, f={f})"]
    if _f_tile(d, f, jnp.dtype(dtype).itemsize) is None:
        return [f"no tile of f={f} fits VMEM at d={d}"]
    return []


def grouped_plan(m, e, d, f, dtype, routed=None):
    """The tiles of a call over `m` sorted rows and `e` experts of (d, f),
    chosen from the shapes alone.

    The rows an expert gets are m / E on average, with E the experts the
    rows were routed over (`routed`; `e` where the weights are all of
    them, more where they are a share and the rows of the others are
    sorted past the held groups, never visited). With n = m e / E rows
    on held experts, a tile of tm rows is visited once by each group it
    holds rows of: at most ceil(n / tm) + e - 1 visits, each computing
    tm rows of 2 x 3 d f FLOPs. The weights are read once an expert
    where one tile holds all of f (consecutive visits of an expert keep
    their blocks), else once a visit. The rule takes the tm of 128, 256,
    512 that minimises max(padded FLOPs at the MXU's peak, weight bytes
    at HBM's peak) + a grid step's cost a visit, the larger on a tie.

    At the serve cells' chunks (bf16): the doc-QA cell's 1,024 tokens x
    8 of 128 experts of 2048 x 768 (64 rows an expert): at 512, 143
    visits are 3.51 ms of FLOPs, what XLA's 512-row tiles compute; at
    128, 191 visits are 1.17 ms of FLOPs under 1.47 ms of bytes (128 x
    9.44 MB): tm 128. The gen-1k cell's 2,048 tokens (128 rows an
    expert): 255 visits at 128 are 1.56 ms against 2.34 at 256: tm 128.
    The mixed cell's 4,096 tokens x 4 on 32 held of 256 experts of 3072
    x 3072 (64 held rows an expert; 56.6 MB an expert needs tiles of f,
    so the weights are read a visit): 47 visits at 128 read 2.66 GB
    (3.25 ms), 39 at 256 compute 2.87 ms and read 2.70: tm 256.
    Measured on a v5e (the layer's experts, dispatch and combine
    included, ms at tm 128 / 256 / 512 against ragged_dot; PERF.md
    section 6, PR 37): doc-QA 2.44 / 2.44 / 4.02 against 5.28, gen-1k
    3.10 / 3.08 / 4.62 against 6.23, mixed 5.81 / 5.27 / 8.66 against
    8.79."""
    itemsize = jnp.dtype(dtype).itemsize
    tn = _f_tile(d, f, itemsize)
    if tn is None:
        raise ValueError("moe_grouped: " + "; ".join(
            grouped_problems(d, f, dtype)))
    held = m * e // (routed or e)
    expert_bytes = 3 * d * f * itemsize

    def cost(tm):
        visits = -(-held // tm) + e - 1
        reads = e if tn == f else visits
        return (max(visits * tm * 6 * d * f / _PEAK_FLOPS,
                    reads * expert_bytes / _HBM_BYTES_S)
                + visits * _STEP_S)

    tm = min(_ROW_TILES, key=lambda tm: (cost(tm), -tm))
    tiles_m = -(-m // tm)
    return GroupedPlan(m, e, tm, d, tn, tiles_m + e - 1)


_recorder = threading.local()


@contextlib.contextmanager
def recording(plans):
    """Collect into the list `plans` the plan of every call traced inside
    this scope (the list is emptied first: a program traced again records
    anew). The serving engine wraps each program's trace in one, so that
    it can count a program's calls of the kernel."""
    prev = getattr(_recorder, "plans", None)
    del plans[:]
    _recorder.plans = plans
    try:
        yield plans
    finally:
        _recorder.plans = prev


def _kernel(offsets_ref, gids_ref, mtiles_ref, x_ref, wg_ref, wu_ref,
            wd_ref, o_ref, acc_ref):
    v, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    # said outright: an ambient default of "highest" is no precision of a
    # bf16 product (Mosaic refuses it)
    prec = (jax.lax.Precision.DEFAULT
            if x.dtype in (jnp.bfloat16, jnp.float16)
            else jax.lax.Precision.HIGHEST)
    dot = functools.partial(jnp.dot, precision=prec,
                            preferred_element_type=jnp.float32)
    # gate and up rounded to the weights' dtype as ragged_dot's outputs
    # are, silu in float32, the product in that dtype: the grouped path's
    # precision (`ragged_swiglu`), so that a prefill's logits are what
    # they were
    a, b = (dot(x, w[0]).astype(x.dtype) for w in (wg_ref, wu_ref))
    a32 = a.astype(jnp.float32)
    act = (a32 * jax.nn.sigmoid(a32)).astype(x.dtype) * b
    acc_ref[...] += dot(act, wd_ref[0])

    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        # this visit's group's rows of the tile; the others keep what an
        # earlier visit of the same tile stored
        g = gids_ref[v]
        tm = acc_ref.shape[0]
        row = mtiles_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        o_ref[...] = jnp.where(mine, acc_ref[...],
                               o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)


def moe_grouped(x, wg, wu, wd, group_sizes, *, routed=None,
                interpret=False):
    """x (m, d): rows sorted by expert, group_sizes (E,) int: expert i
    owns the next group_sizes[i] rows; wg, wu (E, d, f); wd (E, f, d).
    Returns (m, d) in x.dtype: silu(x wg[i]) * (x wu[i]) wd[i] on each
    row of group i. Rows past the last group are left undefined (the
    caller's: `moe_held_experts` sets them to zero). `routed`: the
    experts the rows were routed over, where these E are a share of
    them (`grouped_plan`). Forward only."""
    m, d = x.shape
    e, _, f = wg.shape
    plan = grouped_plan(m, e, d, f, wg.dtype, routed)
    plans = getattr(_recorder, "plans", None)
    if plans is not None:
        plans.append(plan)
    # forward only (a prefill): under an eager op's vjp the operands
    # carry no tangent, so the call needs no differentiation rule
    x, wg, wu, wd = (jax.lax.stop_gradient(a) for a in (x, wg, wu, wd))
    return _call(x, wg.astype(x.dtype), wu.astype(x.dtype),
                 wd.astype(x.dtype), group_sizes.astype(jnp.int32),
                 tm=plan.tm, tn=plan.tn, interpret=interpret)


def _vmem_limit(tm, d, tn, itemsize):
    """The scoped-VMEM limit a call states: the pipelined blocks twice
    (rows, the three weight tiles, the output tile), the float32
    accumulator and the (tm, tn) float32 temporaries, plus a quarter."""
    need = (2 * (2 * tm * d + 3 * d * tn) * itemsize
            + tm * d * 4 + 4 * tm * tn * 4)
    return int(need * 1.25)


# jitted on its own: a model calls this once a layer, and a caller's trace
# then holds ONE traced and lowered kernel (kernels/moe_experts.py)
@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def _call(x, wg, wu, wd, group_sizes, *, tm, tn, interpret):
    m, d = x.shape
    e, _, f = wg.shape
    mp = -(-m // tm) * tm
    xp = jnp.pad(x, ((0, mp - m), (0, 0)))
    meta, visits = make_group_metadata(
        group_sizes=group_sizes, m=mp, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=e,
        visit_empty_groups=False)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # the visits outside, the tiles of f inside: the accumulator
        # takes one visit's tiles in turn
        grid=(visits, f // tn),
        in_specs=[
            pl.BlockSpec((tm, d), lambda v, j, off, g, mt: (mt[v], 0)),
            pl.BlockSpec((1, d, tn), lambda v, j, off, g, mt: (g[v], 0, j)),
            pl.BlockSpec((1, d, tn), lambda v, j, off, g, mt: (g[v], 0, j)),
            pl.BlockSpec((1, tn, d), lambda v, j, off, g, mt: (g[v], j, 0)),
        ],
        out_specs=pl.BlockSpec((tm, d), lambda v, j, off, g, mt: (mt[v], 0)),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, d), x.dtype),
        # a tile's output block is revisited by consecutive visits
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tm, d, tn,
                                         jnp.dtype(x.dtype).itemsize)),
        interpret=interpret,
        name="moe_grouped",
    )(*meta, xp, wg, wu, wd)
    return out[:m]
