"""Pallas paged-attention decode kernel (TPU), with int8 KV dequant, and
the Pallas write of a call's new K and V into the pools it reads.

The serving hot path: PagedKVEngine's decode tick attends ONE query row
per slot over that slot's whole paged KV window. The jnp path in
inference/paged.py gathers every slot's full page window into a dense
(b, hk, L, d) array, repeats it across query heads for GQA, and runs a
dense masked softmax — O(window) HBM gather traffic plus hq/hk x
materialization per layer per decode step. This kernel is the
vLLM-PagedAttention-style replacement (Kwon et al., SOSP'23; same
capability as the reference's block_multi_head_attention_kernel.cu
decode branch):

- the page pools (num_pages, hk, page_size, d) stay in HBM
  (`memory_space=HBM`); the grid is (slot, head block, page block) and
  one step takes EVERY kv head of the head block over a block of pages
  (128 keys): the body reads the block table (a scalar-prefetch
  operand, SMEM-resident before the body runs) and copies exactly the
  pages the slot owns, one `make_async_copy` a page for all heads at
  once, into one of two VMEM windows while the step before computes on
  the other — across slots too, so only a call's first window is
  waited for. `decode_plan` derives heads a step, pages a step and the
  VMEM bytes from the shapes under one fixed budget; there is no knob;
- a page is read as rows of whole 128-lane tiles (`_packing`): where
  d < 128, `fold` = 128 / d tokens lie side by side in a row, and where
  a head's rows do not fill the pool dtype's sublane tile, `pack` heads
  share one. Mosaic slices a ref only along whole tiles, so this is
  what lets a copy address one page; it also leaves no padding in VMEM
  and gives the MXU 128 lanes to contract over. The q tile carries one
  row per (packed head, folded token, query head): the query in that
  token's lanes, zeros elsewhere, so a single dot batched over head
  groups scores every row against its own tokens, and a mask drops the
  columns of the other packed heads;
- GQA is handled by the same head-fold trick as flash_attention.py:
  the g = hq//hk query heads sharing a kv head ride the rows of ONE q
  tile, so k/v pages are streamed once per kv head instead of
  materializing jnp.repeat'ed copies;
- softmax is the online accumulator from the flash kernels (base-2
  exponentials, log2e folded into the q scale once), one per row of
  the q tile, carried across the page-block axis in the resident
  output blocks (numerator, running max, running sum); the caller
  merges the `fold` rows of a query head, which is exact. Pages past
  the slot's length are never copied, and a block wholly past it is
  one bare skipped step;
- int8 KV pools dequantize INSIDE the K-loop: int8 -> f32 in register,
  the dot, and the per-page-per-head f32 scale applied to the score
  columns after it (K) and to the probabilities' columns before theirs
  (V: a block holds several pages, so the scale is per column), so the
  bf16/f32 pool is never materialized in HBM — the quant_matmul.py
  lesson applied to KV.

Which shape a pool has where. The documented shape of a pool, and the
one every page has outside an engine, is by heads: `(num_pages, hk,
page_size, d)`. The kernel reads a pool as rows, `pool_rows_shape`:
`(num_pages, hk / pack, pack * page_size / fold, fold * d)`, the same
bytes in the same order. On the chip the two differ wherever d < 128 (a
64-wide minor dimension is padded to 128 lanes), so a reshape between
them copies the pool, and XLA's scatter of a step's tokens wants a
third layout of its own: a pool that XLA wrote and this kernel read was
copied four times a decode step. So an engine that decodes through this
kernel STORES its plain (bf16 / f32) K and V pools as rows, and
`paged_kv_write` below writes a call's tokens into them through a
Pallas call that aliases them: one grid step a block of touched pages,
each copied whole into VMEM, merged with the new tokens under a mask
and copied back, double-buffered as the decode kernel is. No XLA op
touches such a pool, so none relays it. `paged_decode_attention` takes
either shape (`kv_heads` says a pool stored as rows; one by heads is
reshaped, which costs the copy); `pages_by_head` is the view back for
what gathers pages (prefill, verify) or hands one out. int8 pools with
their scale planes stay by heads and under XLA's scatter (their write
rescales whole pages).

Masking contract: query position per slot is `lens[i]` (the new token's
k/v is already scattered at that position), so column c is visible iff
c <= lens[i]. Unallocated / partial pages therefore never contribute.

Runs under `interpret=True` on CPU (tier-1 exercises exact greedy
parity vs the jnp path this way); on real TPUs the compiled kernel is
the decode hot loop.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.jax_compat import tpu_compiler_params

__all__ = ["paged_decode_attention", "paged_kv_write", "pool_rows_shape",
           "pages_by_head", "page_size_of", "decode_shape_problems",
           "check_decode_shapes", "select_shape_problems", "decode_plan",
           "DecodePlan"]

_NEG_INF = -1e30
_LOG2E = 1.4426950408889634


def _prec(dtype):
    return (jax.lax.Precision.DEFAULT
            if dtype in (jnp.bfloat16, jnp.float16)
            else jax.lax.Precision.HIGHEST)


# Mosaic minimum sublane tile by element size: int8 (32, 128),
# bf16/f16 (16, 128), f32 (8, 128) — the (page_size, d) k/v block's
# sublane dim must tile it when compiled for a real TPU
_MIN_SUBLANE = {1: 32, 2: 16, 4: 8}


def _sublane(dtype):
    return _MIN_SUBLANE.get(jnp.dtype(dtype).itemsize, 8)


def _round_up(x, n):
    return -(-x // n) * n


def decode_shape_problems(hq, hk, d, page_size, interpret=False,
                          kv_dtype=None):
    """Reasons this (hq, hk, d, page_size) geometry cannot take the
    Pallas decode kernel; empty list = supported. Mirrors
    `_ring_flash_plan`'s role for ring attention: the AUTO path gates
    on this, the forced path turns the reasons into a ValueError.
    `kv_dtype` is the POOL dtype (the sublane tile is dtype-dependent:
    int8 pools need page_size % 32, bf16 % 16, f32 % 8)."""
    problems = []
    if hk <= 0 or hq % hk != 0:
        problems.append(f"q heads must be a multiple of kv heads "
                        f"(hq={hq}, hk={hk})")
    if not interpret:
        # compiled Mosaic copies a page as whole (sublane, 128) tiles;
        # interpret mode (CPU tier-1) has no tiling constraint
        dt = jnp.dtype(kv_dtype if kv_dtype is not None
                       else jnp.float32)
        sub = _sublane(dt)
        if d % 8 != 0:
            problems.append(f"head_dim % 8 == 0 required on TPU "
                            f"(got d={d})")
        if page_size % sub != 0:
            problems.append(f"page_size % {sub} == 0 required on TPU "
                            f"for {dt.name} pools (got "
                            f"page_size={page_size})")
        fold, pack = _packing(max(hk, 1), d, page_size, dt)
        if fold * d % 128 != 0:
            problems.append(f"head_dim must be a multiple of 128, or "
                            f"divide it with page_size % (128 / "
                            f"head_dim) == 0, on TPU (got d={d}, "
                            f"page_size={page_size})")
        elif pack * (page_size // fold) % sub != 0:
            problems.append(f"kv heads must pack whole {dt.name} "
                            f"sublane tiles of {sub} rows on TPU (got "
                            f"hk={hk} heads of {page_size // fold} "
                            f"rows a page)")
    return problems


def select_shape_problems(hk, d, page_size, kv_dtype):
    """Reasons a key selection cannot ride this geometry's kernel: its
    score columns have to be tokens in order, so no folded tokens and no
    packed heads (`_packing`)."""
    fold, pack = _packing(hk, d, page_size, kv_dtype)
    if (fold, pack) == (1, 1):
        return []
    return [f"a key selection needs head_dim % 128 == 0 and a page of "
            f"whole {jnp.dtype(kv_dtype).name} sublane tiles (got d={d}, "
            f"page_size={page_size}: {fold} tokens a row, {pack} heads a "
            f"tile)"]


def check_decode_shapes(hq, hk, d, page_size, interpret=False,
                        kv_dtype=None):
    """Raise a descriptive ValueError naming every misaligned dim when
    the kernel cannot run (same contract as
    `ring_attention_local(use_flash=True)`); no-op when supported."""
    problems = decode_shape_problems(hq, hk, d, page_size, interpret,
                                     kv_dtype)
    if problems:
        raise ValueError(
            "paged_decode_attention: shapes cannot take the Pallas "
            "decode kernel — " + "; ".join(problems)
            + '; use kernel="jnp" for the gather/softmax fallback')


# one step's score columns: a lane tile of tokens, so the online-softmax
# state is touched once per 128 keys and not once per page
_BLOCK_TOKENS = 128
# what a step's buffers may take of VMEM: half the 16 MiB a v5e kernel
# is scoped to by default, so Mosaic's own spills and the pipeline's
# bookkeeping have the other half
_VMEM_BUDGET = 8 * 2 ** 20


class DecodePlan(NamedTuple):
    """How `paged_decode_attention` cuts one call into grid steps, and
    how it reads a page: as rows of whole 128-lane tiles."""
    heads: int          # kv heads a step
    pages: int          # pages of a slot a step
    grid: tuple         # (slots, head blocks, page blocks)
    vmem_bytes: int     # of the step's buffers, tile padding counted
    fold: int           # tokens side by side in one row (d < 128)
    pack: int           # kv heads sharing one sublane tile of rows

    @property
    def grid_steps(self):
        return math.prod(self.grid)


def _packing(hk, d, page_size, kv_dtype):
    """(fold, pack): a head's page is page_size * d contiguous
    elements; read as rows of `fold` tokens (fold * d = 128 lanes where
    d < 128) it has page_size / fold rows, and `pack` heads together
    fill whole sublane tiles of the pool's dtype. Mosaic slices a ref
    only along whole tiles, so this is the shape the copies move."""
    fold = 128 // d if d < 128 and 128 % d == 0 \
        and page_size % (128 // d) == 0 else 1
    sub = _sublane(kv_dtype)
    pack = sub // math.gcd(sub, page_size // fold)
    return fold, (pack if hk % pack == 0 else 1)


def pool_rows_shape(num_pages, hk, d, page_size, kv_dtype):
    """The shape a K or V pool is STORED in where the Pallas calls below
    are the only ones to touch it: `(num_pages, hk / pack, pack *
    page_size / fold, fold * d)`, the rows of `_packing`. It is a
    row-major reshape of `(num_pages, hk, page_size, d)`: a page is the
    same bytes either way (`pages_by_head` is the view back), but on the
    chip a reshape between the two moves data wherever d < 128, so a pool
    is kept in one of them for good."""
    fold, pack = _packing(hk, d, page_size, kv_dtype)
    return (num_pages, hk // pack, pack * page_size // fold, fold * d)


def page_size_of(pool, hk, d):
    """Tokens a page of `pool` holds, in either shape."""
    return math.prod(pool.shape[1:]) // (hk * d)


def pages_by_head(pages, hk, d):
    """Pages cut or gathered from a pool, `(..., *page)` with the page
    in either shape, seen as `(..., hk, page_size, d)`."""
    return pages.reshape(*pages.shape[:-3], hk, -1, d)


def _tile_bytes(rows, cols, dtype):
    """VMEM bytes of a (rows, cols) array: rows padded to the dtype's
    sublane tile, cols to 128 lanes."""
    return _round_up(rows, _sublane(dtype)) * _round_up(cols, 128) \
        * jnp.dtype(dtype).itemsize


def _q_rows(g, fold, pack):
    """Rows of the q tile: one per (packed head, folded token, query
    head of the group), padded to whole f32 sublane tiles."""
    return _round_up(g * fold * pack, 8)


def _step_vmem_bytes(heads, pages, g, d, page_size, kv_dtype, fold, pack):
    """Everything one grid step holds in VMEM for `heads` kv heads of
    `pages` pages: the double-buffered K and V windows, the pipelined q
    block, the resident output blocks (accumulator, running max and
    sum), and the score-sized (int8: also the dequantized window-sized)
    temporaries of the body."""
    f32 = jnp.float32
    gp, lanes = _q_rows(g, fold, pack), fold * d
    rows = pages * pack * page_size // fold
    kv = 2 * 2 * _tile_bytes(rows, lanes, kv_dtype)
    q_out = 2 * 2 * _tile_bytes(gp, lanes, f32) \
        + 2 * 2 * _tile_bytes(gp, 128, f32)
    temps = 2 * _tile_bytes(gp, rows, f32)
    if jnp.dtype(kv_dtype) == jnp.int8:
        temps += 2 * _tile_bytes(rows, lanes, f32)
    return heads // pack * (kv + q_out + temps)


def decode_plan(hq, hk, d, page_size, max_pages, kv_dtype, slots=1):
    """Heads a step, pages a step, grid and VMEM bytes of the decode
    kernel for this geometry — from the shapes alone. A step takes
    `_BLOCK_TOKENS` keys of every kv head; where that does not fit
    `_VMEM_BUDGET` it takes a divisor of the heads, then fewer pages."""
    g = hq // hk
    fold, pack = _packing(hk, d, page_size, kv_dtype)
    pages = max(1, min(_BLOCK_TOKENS // page_size, max_pages))
    while True:
        for heads in range(hk, 0, -pack):
            if hk % heads:
                continue
            need = _step_vmem_bytes(heads, pages, g, d, page_size,
                                    kv_dtype, fold, pack)
            if need <= _VMEM_BUDGET:
                break
        if need <= _VMEM_BUDGET or pages == 1:
            break
        pages //= 2
    return DecodePlan(heads, pages,
                      (slots, hk // heads, -(-max_pages // pages)), need,
                      fold, pack)


def _decode_kernel(bt_ref, lens_ref, *refs, page_size, plan, g, quantized,
                   selected=False):
    """Grid (b, head blocks, page blocks), run in order. Scalar
    prefetch: block tables (b, mp) i32, lens (b,) i32 and, for int8
    pools, the per-slot gathered f32 scales (b, mp, hk) in SMEM
    (gathered from the (num_pages, hk) planes outside the kernel, so
    SMEM use follows the batch and not the pool).

    The pools stay in HBM, seen as (num_pages, hk / pack, rows, lanes):
    `pack` heads' rows of `fold` tokens each (`_packing`). The body
    copies the `pages` pages of this step's block, every head of the
    head block at once, into one of two VMEM windows (groups, pages *
    rows, lanes) while the step before computes on the other. q is the
    (1, groups, gp, lanes) block of one slot: row (a, r, i) holds query
    head i of packed head a in the lanes of folded token r and zeros
    elsewhere, so one dot over all lanes scores it against the tokens
    t = r (mod fold); columns of the other packed heads are masked.
    Each row keeps its own online softmax in the resident output blocks
    (acc, m, l); the caller merges the rows of one query head.

    `selected`: a (1, 1, 1, t) block of a per-slot key selection follows
    q, one float a column of this step's score tile (fold = pack = 1, so
    column c of block j is token j * t + c); a column is seen only where
    it is positive, on top of the length's mask."""
    if quantized:
        ks_ref, vs_ref, *refs = refs
    q_ref, *refs = refs
    if selected:
        sel_ref, *refs = refs
    k_hbm, v_hbm, acc_ref, m_ref, l_ref, kbuf, vbuf, sem, cur_ref = refs
    bi, hi, j = (pl.program_id(a) for a in range(3))
    nb, nh, _ = (pl.num_programs(a) for a in range(3))
    mp = bt_ref.shape[1]
    groups, gp = q_ref.shape[1], q_ref.shape[2]
    pages, fold, pack = plan.pages, plan.fold, plan.pack
    rp = page_size // fold               # rows of one head of one page
    rows = pack * rp                     # rows of one page of a group
    prec = _prec(q_ref.dtype)

    def last_page(b):
        return jnp.clip(jax.lax.div(lens_ref[b], page_size), 0, mp - 1)

    def window(b, h, blk, slot, do):
        """`do` ("start" or "wait") the copies that bring pages [blk *
        pages, ...) of slot b's head block h into window `slot`: only
        the pages the length reaches, so the same count is started and
        waited for."""
        first = blk * pages

        def page(p, carry):
            at = bt_ref[b, first + p]
            row = pl.multiple_of(p * rows, rows)
            for n, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                src = hbm.at[at] if nh == 1 else \
                    hbm.at[at, pl.ds(h * groups, groups)]
                getattr(pltpu.make_async_copy(
                    src, buf.at[slot, :, pl.ds(row, rows)],
                    sem.at[n, slot]), do)()
            return carry

        jax.lax.fori_loop(
            0, jnp.clip(last_page(b) + 1 - first, 0, pages), page, 0)

    pos = lens_ref[bi]                   # query position of this slot
    last = last_page(bi)
    last_blk = jax.lax.div(last, pages)  # block 0 is always computed

    @pl.when((bi == 0) & (hi == 0) & (j == 0))
    def _first():
        # a window's pages past the length are never copied and their
        # probabilities are exactly 0, but 0 * (what VMEM held before
        # the call) may be NaN: from here on it holds zeros or pool data
        vbuf[...] = jnp.zeros_like(vbuf)
        cur_ref[0] = 0
        window(bi, hi, 0, 0, "start")

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= last_blk)
    def _compute():
        cur = cur_ref[0]
        # next window: this slot's next block, or after its last one
        # block 0 of the next (slot, head block) — the grid runs in
        # order, so that step finds its window already on the way
        unit = bi * nh + hi + 1
        ends = j == last_blk
        nxt_b = jnp.where(ends, jnp.minimum(jax.lax.div(unit, nh),
                                            nb - 1), bi)
        nxt_h = jnp.where(ends, jax.lax.rem(unit, nh), hi)
        nxt_blk = jnp.where(ends, 0, j + 1)

        @pl.when(jnp.logical_not(ends) | (unit < nb * nh))
        def _prefetch():
            window(nxt_b, nxt_h, nxt_blk, 1 - cur, "start")

        window(bi, hi, j, cur, "wait")
        cur_ref[0] = 1 - cur

        q = q_ref[0]                                  # (groups, gp, lanes)
        kj = kbuf[cur]                                # (groups, t, lanes)
        vj = vbuf[cur]
        if quantized:
            # fuse-the-convert: int8 -> f32 in REGISTER, dot, then the
            # per-page-per-head scale on the score columns — the
            # dequantized window never exists in HBM
            kj = kj.astype(jnp.float32)
            vj = vj.astype(jnp.float32)
            q = q.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kj, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=prec)                           # (groups, gp, t)
        # which token and which packed head a column is, which a row
        # wants: the same for every group
        t = pages * rows
        row = jax.lax.broadcasted_iota(jnp.int32, (1, gp, t), 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, gp, t), 2)
        div, rem = jax.lax.div, jax.lax.rem  # of non-negative ints
        token = (j * pages + div(col, rows)) * page_size \
            + rem(col, rp) * fold + rem(div(row, g), fold)
        seen = (token <= pos) \
            & (rem(div(col, rp), pack) == div(row, g * fold))
        if selected:
            seen = seen & (sel_ref[0] > 0.0)

        def by_column(sc_ref):
            """(groups, 1, t): the scale of each column's page and
            head, built from SMEM scalars; past the length the last
            page's, never one of a page the slot does not own."""
            unit = div(col[:, :1], rp)                # page, packed head
            out = []
            for gi in range(groups):
                sc = jnp.zeros((1, 1, t), jnp.float32)
                for u in range(pages * pack):
                    pg = jnp.minimum(j * pages + u // pack, last)
                    head = (hi * groups + gi) * pack + u % pack
                    sc = jnp.where(unit == u, sc_ref[bi, pg, head], sc)
                out.append(sc)
            return jnp.concatenate(out, axis=0)

        if quantized:
            s = s * by_column(ks_ref)
        s = jnp.where(seen, s, _NEG_INF)
        m = m_ref[0, :, :, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        l_new = l_ref[0, :, :, :1] * alpha \
            + jnp.sum(p, axis=-1, keepdims=True, dtype=jnp.float32)
        if quantized:
            # the V scale is per column of p, so it rides p into the
            # dot (f32 x f32, as the K scale rides s out of one)
            p = p * by_column(vs_ref)
        pv = jax.lax.dot_general(
            p.astype(vj.dtype), vj, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=prec)                           # (groups, gp, lanes)
        acc_ref[0] = acc_ref[0] * alpha + pv
        m_ref[0] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[0] = jnp.broadcast_to(l_new, l_ref.shape[1:])


def paged_decode_attention(q, k_pool, v_pool, block_tables, lens, *,
                           k_scale=None, v_scale=None, sm_scale=None,
                           interpret=False, select=None, kv_heads=None):
    """One decode step of paged attention for every slot.

    q: (b, hq, d) — one (position-encoded) query row per slot.
    k_pool/v_pool: (num_pages, hk, page_size, d), bf16/f32, or int8
        with `k_scale`/`v_scale` (num_pages, hk) f32 such that
        k ~= k_pool * k_scale[page, head, None, None]. Or, with
        `kv_heads` = hk said, pools stored as rows (`pool_rows_shape`):
        the kernel reads them as they are, where a pool in the first
        shape is reshaped for it (on the chip, at d < 128: copied).
    block_tables: (b, max_pages) int32 — physical page of each logical
        page per slot (engine convention: 0 = never-written trash page
        for unallocated entries; pages past the length are not read).
    lens: (b,) int32 — this query's position (its k/v must already be
        scattered there); columns c <= lens[i] are attended.
    select: optional (b, max_pages * page_size) bool — attend over the
        columns it marks only (a learned key selection), still under
        c <= lens[i]. Needs a geometry whose score columns are tokens in
        order (`decode_plan`: fold = pack = 1, i.e. head_dim a multiple
        of 128 and a page that fills the pool dtype's sublane tile);
        `select_shape_problems` says when it is not.

    Returns (b, hq, d) f32. Shapes must pass `check_decode_shapes`
    (call it, or gate on `decode_shape_problems`, before forcing this
    path — same contract as ring_attention_local(use_flash=True)).
    """
    _, hq, d = q.shape
    hk = kv_heads or k_pool.shape[1]
    page_size = page_size_of(k_pool, hk, d)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if k_pool.dtype == jnp.int8 and (k_scale is None or v_scale is None):
        raise ValueError("int8 pools require k_scale and v_scale "
                         "(num_pages, hk) f32")
    check_decode_shapes(hq, hk, d, page_size, interpret,
                        kv_dtype=k_pool.dtype)
    if select is None:
        return _decode(q, k_pool, v_pool, block_tables, lens, k_scale,
                       v_scale, hk=hk, sm_scale=sm_scale,
                       interpret=interpret)
    problems = select_shape_problems(hk, d, page_size, k_pool.dtype)
    if problems:
        raise ValueError("paged_decode_attention(select=): "
                         + "; ".join(problems))
    return _decode(q, k_pool, v_pool, block_tables, lens, k_scale, v_scale,
                   select, hk=hk, sm_scale=sm_scale, interpret=interpret)


# jitted on its own: a model calls this once a layer, and a caller's
# trace then holds ONE traced and lowered kernel that every layer calls,
# not one a layer (a server's start is mostly tracing its tick)
@functools.partial(jax.jit,
                   static_argnames=("hk", "sm_scale", "interpret"))
def _decode(q, k_pool, v_pool, block_tables, lens, k_scale, v_scale,
            select=None, *, hk, sm_scale, interpret):
    b, hq, d = q.shape
    num_pages = k_pool.shape[0]
    page_size = page_size_of(k_pool, hk, d)
    mp = block_tables.shape[1]
    quantized = k_pool.dtype == jnp.int8
    plan = decode_plan(hq, hk, d, page_size, mp, k_pool.dtype, slots=b)
    fold, pack = plan.fold, plan.pack
    g, lanes = hq // hk, fold * d
    groups = plan.heads // pack
    n = pack * fold * g                  # rows that hold a query
    # padded to whole sublane tiles so the compiled kernel never sees a
    # ragged second-minor dim (padded rows are zeros and are sliced off)
    gp = n if interpret else _q_rows(g, fold, pack)

    # row (a, r, i) of a group's q tile: query head i of packed head a,
    # in the lanes of folded token r. log2e rides the softmax scale into
    # q once, here: exponentials in the body are exp2
    # (flash_attention.py convention)
    qs = (q * jnp.asarray(sm_scale * _LOG2E, q.dtype)).reshape(
        b, hk // pack, pack, 1, g, 1, d)
    qf = (qs * jnp.eye(fold, dtype=q.dtype).reshape(fold, 1, fold, 1)
          ).reshape(b, hk // pack, n, lanes)
    if gp != n:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, gp - n), (0, 0)))

    def rows_of(pool):
        # nothing to do for a pool stored as rows
        return pool.reshape(pool_rows_shape(num_pages, hk, d, page_size,
                                            pool.dtype))

    bt = block_tables.astype(jnp.int32)
    lens = lens.astype(jnp.int32)
    scalar_args = [bt, lens]
    if quantized:
        # gather scales per SLOT here (tiny: (b, mp, hk)) so the SMEM
        # footprint follows the batch, not the pool — pool-wide scale
        # planes would outgrow SMEM at production page counts
        scalar_args += [k_scale[bt].astype(jnp.float32),
                        v_scale[bt].astype(jnp.float32)]

    def block(width):
        return pl.BlockSpec((1, groups, gp, width),
                            lambda bi, hi, j, *_sp: (bi, hi, 0, 0))

    def out(width):
        return jax.ShapeDtypeStruct((b, hk // pack, gp, width),
                                    jnp.float32)

    pool_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    window = (2, groups, plan.pages * pack * page_size // fold, lanes)
    operands, in_specs = [qf], [block(lanes)]
    if select is not None:
        # one float a key, cut into the page blocks of the grid
        t = plan.pages * page_size
        sel = jnp.pad(select.astype(jnp.float32),
                      ((0, 0), (0, plan.grid[2] * t - select.shape[1])))
        operands.append(sel.reshape(b, plan.grid[2], 1, t))
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, t), lambda bi, hi, j, *_sp: (bi, j, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalar_args),
        grid=plan.grid,
        in_specs=in_specs + [pool_spec, pool_spec],
        out_specs=[block(lanes), block(128), block(128)],
        scratch_shapes=[pltpu.VMEM(window, k_pool.dtype),
                        pltpu.VMEM(window, v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size, plan=plan,
                          g=g, quantized=quantized,
                          **({"selected": True} if select is not None
                             else {})),
        grid_spec=grid_spec,
        out_shape=[out(lanes), out(128), out(128)],
        # in order: a step starts the copies the next one waits for
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
        name="paged_attention_decode",
    )(*scalar_args, *operands, rows_of(k_pool), rows_of(v_pool))

    # merge the `fold` partial softmaxes of each query head: row
    # (a, r, i) has its numerator in the lanes of token r
    def per_row(x):
        return x[:, :, :n, 0].reshape(b, hk, fold, g)
    acc = acc[:, :, :n].reshape(b, hk, fold, g, fold, d)
    acc = jnp.stack([acc[:, :, r, :, r] for r in range(fold)], axis=2)
    m, l = per_row(m), per_row(l)
    w = jnp.exp2(m - jnp.max(m, axis=2, keepdims=True))
    o = jnp.sum(acc * w[..., None], axis=2) \
        / jnp.maximum(jnp.sum(l * w, axis=2), 1e-30)[..., None]
    # no column is visible to a negative position
    o = jnp.where((lens >= 0)[:, None, None, None], o, 0.0)
    return o.reshape(b, hq, d)


# -- the write: a call's new tokens into the pages they belong to ----------

# what the write's buffers may take of VMEM. A page of a step costs 8
# page-sized buffers: two windows a pool, and K's and V's block of new
# tokens, which the pipeline double-buffers
_WRITE_VMEM_BUDGET = 4 * 2 ** 20


def _write_kernel(pages_ref, lo_ref, hi_ref, knew_ref, vnew_ref, _k_in,
                  _v_in, k_hbm, v_hbm, kbuf, vbuf, sem, *, num_pages, rp,
                  fold, d):
    """Grid (page blocks,), run in order; a step takes `t` touched pages
    (the first dim of the blocks of new tokens). Scalar prefetch, flat
    (steps * t,) i32: the physical page of each, and the tokens [lo, hi)
    of it that this call writes; a page id past the pool is skipped.

    The pools stay in HBM, stored as rows (`pool_rows_shape`), and are
    the call's outputs, aliased onto its inputs: a step copies its pages
    whole (every head) into one of two VMEM windows, puts the new tokens
    in under a mask (row r, lane l of a page hold token (r mod rp) * fold
    + l // d: `_packing`), and copies them back, while the pages of the
    step after are on their way in and those of the step before on their
    way out. A page is touched by one step of a call: what the engine
    guarantees, since a page being written belongs to one slot."""
    i, n = pl.program_id(0), pl.num_programs(0)
    t = knew_ref.shape[0]
    cur = jax.lax.rem(i, 2)

    def copies(blk, slot, do, back):
        way = 1 if back else 0

        def page(p, carry):
            at = pages_ref[blk * t + p]

            @pl.when(at < num_pages)
            def _():
                for a, (hbm, buf) in enumerate(((k_hbm, kbuf),
                                                (v_hbm, vbuf))):
                    ends = (buf.at[slot, p], hbm.at[at]) if back \
                        else (hbm.at[at], buf.at[slot, p])
                    getattr(pltpu.make_async_copy(
                        *ends, sem.at[way, a, slot]), do)()
            return carry
        jax.lax.fori_loop(0, t, page, 0)

    @pl.when(i == 0)
    def _first():
        copies(0, 0, "start", False)

    @pl.when(i >= 1)
    def _drained():         # the window about to be filled again
        copies(i - 1, 1 - cur, "wait", True)

    @pl.when(i + 1 < n)
    def _prefetch():
        copies(i + 1, 1 - cur, "start", False)

    copies(i, cur, "wait", False)
    shape = kbuf.shape[2:]
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    token = jax.lax.rem(row, rp) * fold + jax.lax.div(lane, d)

    def merge(p, carry):
        at = i * t + p

        @pl.when(pages_ref[at] < num_pages)
        def _():
            new = (token >= lo_ref[at]) & (token < hi_ref[at])
            kbuf[cur, p] = jnp.where(new, knew_ref[p], kbuf[cur, p])
            vbuf[cur, p] = jnp.where(new, vnew_ref[p], vbuf[cur, p])
        return carry
    jax.lax.fori_loop(0, t, merge, 0)
    copies(i, cur, "start", True)

    @pl.when(i == n - 1)
    def _last():
        copies(i, cur, "wait", True)


# jitted on its own, as `_decode` is: one lowered kernel a program
@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_write(k_pool, v_pool, k, v, pages, offsets, *,
                   interpret=False):
    """Write a call's new tokens into K and V pools stored as rows, in
    place: the values `pool.at[page, :, offset, :].set(token)` writes into
    a `(num_pages, hk, page_size, d)` pool, cast to the pool's dtype and
    nothing else.

    k_pool/v_pool: `pool_rows_shape`, bf16 or f32 (an int8 pool's write
        rescales its pages: it keeps XLA's scatter).
    k, v: (b, s, hk, d), a row's tokens at consecutive positions.
    pages, offsets: (b * s,) i32, the physical page and the place in it
        of each token; a page past the pool drops the token, and a row's
        dropped tokens are its last ones (`n_valid`).

    Returns the pools. The call aliases them, so inside a caller's jit
    XLA neither copies a pool nor chooses a layout for it."""
    b, s, hk, d = k.shape
    num_pages = k_pool.shape[0]
    page = k_pool.shape[1:]
    page_size = page_size_of(k_pool, hk, d)
    fold, pack = _packing(hk, d, page_size, k_pool.dtype)
    if page != pool_rows_shape(1, hk, d, page_size, k_pool.dtype)[1:]:
        raise ValueError(f"paged_kv_write: pools of pages {page} are not "
                         f"stored as rows (pool_rows_shape) of {hk} heads "
                         f"of {d}")
    # pages a row can touch from any start, pages a step, steps
    npg = (s + page_size - 2) // page_size + 1
    page_bytes = math.prod(page[:-2]) * _tile_bytes(*page[-2:],
                                                    k_pool.dtype)
    t = max(1, min(b * npg, _WRITE_VMEM_BUDGET // (8 * page_bytes)))
    steps = -(-b * npg // t)

    # page m of a row holds its tokens [m * page_size - o0, + page_size),
    # o0 the first token's place; it is touched where the first of them
    # is a token the call writes
    pages = pages.reshape(b, s).astype(jnp.int32)
    o0 = offsets.reshape(b, s)[:, :1].astype(jnp.int32)
    n_valid = jnp.sum(pages < num_pages, axis=1, keepdims=True)
    first = jnp.arange(npg, dtype=jnp.int32)[None] * page_size - o0
    at = jnp.take_along_axis(pages, jnp.clip(first, 0, s - 1), axis=1)
    at = jnp.where(first < n_valid, at, num_pages)
    lo = jnp.maximum(first, 0) - first
    hi = jnp.minimum(n_valid - first, page_size)

    def flat(x, fill):
        return jnp.pad(x.reshape(-1), (0, steps * t - b * npg),
                       constant_values=fill)

    def staged(x):
        """The new tokens laid out as the pages they go to."""
        if s == 1:
            x = jnp.broadcast_to(x[:, :, None], (b, 1, page_size, hk, d))
        else:
            tok = jnp.arange(npg * page_size, dtype=jnp.int32)[None] - o0
            x = jnp.take_along_axis(
                x, jnp.clip(tok, 0, s - 1)[:, :, None, None], axis=1)
            x = x.reshape(b, npg, page_size, hk, d)
        x = jnp.swapaxes(x, 2, 3).astype(k_pool.dtype)
        x = x.reshape(b * npg, *page)
        return jnp.pad(x, ((0, steps * t - b * npg),) + ((0, 0),) * 3)

    new_spec = pl.BlockSpec((t, *page), lambda i, *_sp: (i, 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    window = (2, t, *page)
    return pl.pallas_call(
        functools.partial(_write_kernel, num_pages=num_pages,
                          rp=page_size // fold, fold=fold, d=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(steps,),
            in_specs=[new_spec, new_spec, pool_spec, pool_spec],
            out_specs=[pool_spec, pool_spec],
            scratch_shapes=[pltpu.VMEM(window, k_pool.dtype),
                            pltpu.VMEM(window, v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2, 2))],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        # operands count from the scalars on
        input_output_aliases={5: 0, 6: 1},
        # in order: a step starts the copies its neighbours wait for
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_kv_write",
    )(flat(at, num_pages), flat(lo, 0), flat(hi, 0), staged(k), staged(v),
      k_pool, v_pool)
