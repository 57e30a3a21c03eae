"""Pallas decode kernel for latent attention (MLA) over a latent paged
cache (TPU).

A model with latent attention (DeepSeek-V2/V3 and the families that copy
its attention) caches ONE row a token a layer: the normed latent c (the
config's `kv_lora_rank` values) and the shared rotary key k_pe
(`qk_rope_head_dim` values) side by side, `width` = rank + rope values.
Every head's key and value are linear in c (k_nope_h = c W_uk,h, v_h = c
W_uv,h), so decode folds W_uk,h into the query and W_uv,h into the output
(the "absorbed" form) and all heads read the same row:

    s_h(j) = (q~_h . c(j) + q_pe_h . k_pe(j)) * scale,   q~_h = W_uk,h q_nope_h
    o~_h = sum_j softmax_j(s_h) c(j)                     (then o_h = o~_h W_uv,h)

i.e. a multi-query attention of H heads whose key is the whole row and
whose value is its first `rank` values. This kernel computes the middle
line for every slot of a decode step:

- the pool (num_pages, page_size, width) stays in HBM; the block table and
  the lengths are scalar-prefetch operands, the table first (what a trace
  reader finds a tick program by: `custom-call(s32[slots, pages], ...`);
- the grid is one step a slot; a step loops over the slot's key blocks of
  `block_pages` pages, as many as its length reaches, so no block past a
  length costs a step. A turn of the loop starts the copies of the next
  window (this slot's next block or, after its last, the next slot's
  first) into one of two VMEM windows, then waits for its own and
  computes on it while those run. The starts are straight-line code, one
  `make_async_copy` for EVERY page of the block, and the wait is one wait
  of the window's whole size: a count of copies fixed at trace time needs
  no scalar loop to start them and no wait a page. So a block reads pages
  past the slot's length too: the block table names the trash page 0
  there (the engine keeps it so), a block past the table's end copies its
  last page again, and those columns are masked by position;
- one dot of the H query rows [q~ | q_pe] against the window's rows
  scores every head (contracting over the whole row), one more against
  the window's first `rank` lanes accumulates the value; an online
  softmax (base-2, log2e folded into the query's scale outside) is kept in
  the output blocks of the slot through its loop.

`mla_decode_attention(q, pool, block_tables, lens, rank=)` returns õ
(b, H, rank) float32; `mla_decode_reference` is the same function in
jax.numpy (the engine's path off the chip, and the tests' yardstick).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.jax_compat import tpu_compiler_params

__all__ = ["mla_decode_attention", "mla_decode_reference",
           "mla_decode_problems", "block_pages", "kernel_row_width"]

_NEG_INF = -1e30
_LOG2E = 1.4426950408889634
# keys a block of the loop takes. A turn costs a fixed ~0.4 us (its
# straight-line starts, its wait, the softmax's reductions) beside ~1 us a
# 512 keys (their copies at 640 lanes, ~1.3 KB a row), and a block copies
# on average half a block of trash pages a slot: 1,024 keys is the better
# of 512 and 1,024 at the serve cell's lengths of 2k-18k (one v5e,
# tools/mla_decode_bench.py), and the two windows take 2.6 MB of VMEM
_BLOCK_TOKENS = 1024


def kernel_row_width(width):
    """The width a latent pool's row is STORED at where this kernel reads
    it: whole 128-lane tiles. Mosaic copies a page only as whole tiles,
    and on the chip a (pages, page_size, 576) pool is laid out at 640
    lanes whatever its shape says, so the kernel's pool says so itself and
    holds zeros past the model's values (the query holds zeros there
    too)."""
    return -(-width // 128) * 128


def block_pages(page_size, max_pages):
    """Pages of one slot a block of the kernel's loop takes:
    `_BLOCK_TOKENS` keys, at most the block table's width."""
    return max(1, min(_BLOCK_TOKENS // page_size, max_pages))


def mla_decode_problems(heads, width, rank, page_size, dtype):
    """Reasons this geometry cannot take the kernel on a TPU; empty list
    = supported."""
    problems = []
    sub = {1: 32, 2: 16, 4: 8}.get(jnp.dtype(dtype).itemsize, 8)
    if page_size % sub:
        problems.append(f"page_size % {sub} == 0 required for "
                        f"{jnp.dtype(dtype).name} pools (got {page_size})")
    if rank % 128 or not rank < width or width % 128:
        problems.append(f"the latent rank must be a multiple of 128 and "
                        f"narrower than the row, the row whole lane "
                        f"tiles (`kernel_row_width`; got rank={rank}, "
                        f"width={width})")
    if heads % 8:
        problems.append(f"heads % 8 == 0 required (got {heads})")
    return problems


def _kernel(bt_ref, lens_ref, q_ref, pool_hbm, acc_ref, m_ref, l_ref, buf,
            sem, cur_ref, *, pages, page_size, rank):
    """Grid (slots,), run in order; a step loops over its slot's key
    blocks, as many as its length reaches."""
    bi, nb = pl.program_id(0), pl.num_programs(0)
    mp = bt_ref.shape[1]
    t = pages * page_size

    def blocks(b):
        """Key blocks of slot b: its positions 0 .. lens[b] (one for a
        slot of negative length, whose columns are all masked)."""
        return jax.lax.div(jnp.maximum(lens_ref[b], 0), t) + 1

    def start(b, blk, slot):
        """Start the copies of block `blk` of slot b into window `slot`:
        every page of the block, straight-line, a page past the block
        table's end as its last one (columns past a slot's length are
        masked, and the table names a page, the trash page at least,
        wherever the slot holds none)."""
        for p in range(pages):
            pltpu.make_async_copy(
                pool_hbm.at[bt_ref[b, jnp.minimum(blk * pages + p, mp - 1)]],
                buf.at[slot, pl.ds(p * page_size, page_size)],
                sem.at[slot]).start()

    def wait(slot):
        """Wait for a window's copies: one wait of the whole window's
        size."""
        pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                              sem.at[slot]).wait()

    @pl.when(bi == 0)
    def _first():
        # a masked column's probability is exactly 0, but 0 x what VMEM
        # held before the call may be NaN. Every window is filled whole by
        # its copies before it is read; the zeros keep that so for a
        # window a future change copies only in part, at one store a call
        buf[...] = jnp.zeros_like(buf)
        cur_ref[0] = 0
        start(0, 0, 0)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    n = blocks(bi)
    pos = lens_ref[bi]
    q = q_ref[0]                                     # (H, width)
    # said outright: an ambient default of "highest" is no precision of a
    # bf16 product (Mosaic refuses it)
    prec = (jax.lax.Precision.DEFAULT if buf.dtype == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)

    def step(j, carry):
        cur = cur_ref[0]
        # the next window: this slot's next block or, after its last, the
        # next slot's first; the grid runs in order, so that one finds its
        # window already on the way
        ends = j + 1 >= n
        nxt = jnp.where(ends, bi + 1, bi)

        @pl.when(nxt < nb)
        def _next():
            start(nxt, jnp.where(ends, 0, j + 1), 1 - cur)

        wait(cur)
        cur_ref[0] = 1 - cur
        kv = buf[cur]                                # (t, width)
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                precision=prec,
                                preferred_element_type=jnp.float32)  # (H, t)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(j * t + col <= pos, s, _NEG_INF)
        m = m_ref[0][:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        l_ref[0] = jnp.broadcast_to(
            l_ref[0][:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape[1:])
        m_ref[0] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        pv = jax.lax.dot_general(p.astype(kv.dtype), kv[:, :rank],
                                 (((1,), (0,)), ((), ())), precision=prec,
                                 preferred_element_type=jnp.float32)
        acc_ref[0] = acc_ref[0] * alpha + pv         # (H, rank)
        return carry

    jax.lax.fori_loop(0, n, step, 0)


def mla_decode_attention(q, pool, block_tables, lens, *, rank, sm_scale,
                         interpret=False):
    """One decode step of latent attention for every slot.

    q: (b, H, width) — each head's [q~ | q_pe], the absorbed query beside
        the rotary one, in the pool's dtype. pool: (num_pages, page_size,
        width), each row [c | k_pe]. block_tables (b, max_pages) int32,
        lens (b,) int32: the query's position, whose row is already in
        the pool; columns c <= lens[i] are attended, a slot of negative
        length attends nothing and gets zeros. `rank`: the values of c,
        the first `rank` of a row, which are the value.

    Returns õ (b, H, rank) float32."""
    b, h, width = q.shape
    problems = mla_decode_problems(h, width, rank, pool.shape[1], pool.dtype)
    if problems and not interpret:
        raise ValueError("mla_decode_attention: " + "; ".join(problems))
    return _call(q, pool, block_tables.astype(jnp.int32),
                 lens.astype(jnp.int32), rank=rank, sm_scale=sm_scale,
                 interpret=interpret)


# jitted on its own: a model calls this once a layer, and a caller's trace
# then holds ONE traced and lowered kernel (kernels/paged_attention.py)
@functools.partial(jax.jit, static_argnames=("rank", "sm_scale",
                                             "interpret"))
def _call(q, pool, bt, lens, *, rank, sm_scale, interpret):
    b, h, width = q.shape
    page_size, mp = pool.shape[1], bt.shape[1]
    pages = block_pages(page_size, mp)
    qs = (q.astype(jnp.float32) * (sm_scale * _LOG2E)).astype(pool.dtype)

    def resident(width):
        return pl.BlockSpec((1, h, width), lambda bi, *_: (bi, 0, 0))

    def out(width):
        return jax.ShapeDtypeStruct((b, h, width), jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[resident(width), pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=[resident(rank), resident(128), resident(128)],
        scratch_shapes=[pltpu.VMEM((2, pages * page_size, width),
                                   pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    acc, _m, l = pl.pallas_call(
        functools.partial(_kernel, pages=pages, page_size=page_size,
                          rank=rank),
        grid_spec=grid_spec,
        out_shape=[out(rank), out(128), out(128)],
        # in order: a step starts the copies the next one waits for
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode",
    )(bt, lens, qs, pool)
    o = acc / jnp.maximum(l[:, :, :1], 1e-30)
    # no column is visible to a negative position
    return jnp.where((lens >= 0)[:, None, None], o, 0.0)


def mla_decode_reference(q, pool, block_tables, lens, *, rank, sm_scale):
    """`mla_decode_attention` in jax.numpy: every slot's pages gathered,
    a masked softmax in float32 over columns c <= lens."""
    b = q.shape[0]
    rows = pool[block_tables].reshape(b, -1, pool.shape[-1])     # (b, L, w)
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("bhw,blw->bhl", q.astype(jnp.float32), rows) * sm_scale
    col = jnp.arange(rows.shape[1], dtype=jnp.int32)
    seen = col[None, None, :] <= lens[:, None, None]
    s = jnp.where(seen, s, _NEG_INF)
    p = jnp.where(seen, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    o = jnp.einsum("bhl,blr->bhr", p, rows[..., :rank])
    return o / jnp.maximum(jnp.sum(p, axis=-1), 1e-30)[..., None]
