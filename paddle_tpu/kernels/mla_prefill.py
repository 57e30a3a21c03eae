"""Pallas prefill attention for latent attention (MLA) in the expanded form
(TPU).

A prefill chunk of a model with latent attention attends over the slot's
latent rows [c | k_pe] (kernels/mla_decode.py) in the EXPANDED form: every
head's key and value are made from the latent, k_nope_h = c W_uk,h and v_h =
c W_uv,h, and

    s_h(t, j) = (q_nope_h(t) . k_nope_h(j) + q_pe_h(t) . k_pe(j)) * scale

under a causal softmax: 320 multiply-adds a head a (query, key) pair at the
published widths, where the absorbed form takes 1,088. The jnp path of
inference/paged.py (`_attend_latent_expanded`) writes each block's float32
scores to HBM and reads them back; this kernel is the flash forward of
kernels/prefill_attention.py with the expansion inside it:

- q_nope, q_pe (b, H, s, .): the chunk's queries, token j of row i at
  position lens[i] + j, the scale (and log2e: base-2 exponentials) folded
  in; the pool (num_pages, page_size, width) stays in HBM, each row [c |
  k_pe | zeros] (`width` whole lane tiles, so the rotary part is the lanes
  past `rank`, and q_pe is padded with zeros to as many); the lengths, the
  valid tokens and the block table are scalar-prefetch operands (the
  table after the lengths: a prefill call is never taken for a tick
  program, which the block table as first operand marks);
- grid (b, H, q blocks, key blocks), the key blocks innermost with the
  softmax state in VMEM scratch. A key block is `block_pages` pages of the
  row; a step that runs copies the pages of the NEXT step that runs (this
  row's next key block or, after the q block's last, the first of the next
  q block, head or row), one `make_async_copy` a page, into one of two
  VMEM windows, waits for its own (one wait of the window's size: every
  block copies all its pages, the table naming the trash page past a
  row's last), expands the block's keys and values through the head's
  W_uk,h and W_uv,h in VMEM and scores it against the q block: no latent
  row is gathered outside the kernel, and no score and no expanded key
  leaves the chip;
- the grid's last extent is a traced value, the key blocks the longest
  row reaches; a key block after a q block's last valid query is skipped
  (the step copies nothing and its body does not run), and only a block
  that holds a key after the q block's first query computes the causal
  mask. A q block wholly past the row's valid tokens is zeros.

`mla_prefill_attention` returns (b, s, H * dv); `expanded_rows` counts the
latent rows its steps expand, the same blocks. Forward only;
`interpret=True` runs it on the CPU (tier-1 holds it to the jnp path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.jax_compat import tpu_compiler_params

__all__ = ["mla_prefill_attention", "mla_prefill_problems", "expanded_rows",
           "block_pages"]

_NEG_INF = -1e30
_LOG2E = 1.4426950408889634
_BLOCK_K = 512          # latent rows a step
_BLOCK_Q = 1024         # queries a q block


def _block_q(s):
    """Tokens a q block: the largest power of two that divides s, at most
    `_BLOCK_Q`, at least 8 (a sublane tile)."""
    bq = 8
    while bq * 2 <= min(s, _BLOCK_Q) and s % (bq * 2) == 0:
        bq *= 2
    return bq


def block_pages(page_size, max_pages):
    """Pages of a row one key block takes: `_BLOCK_K` latent rows, at most
    the block table's width."""
    return max(1, min(_BLOCK_K // page_size, max_pages))


def mla_prefill_problems(s, nope, rope, dv, rank, width, page_size, dtype,
                         interpret=False):
    """Reasons these shapes cannot take the kernel; empty = supported."""
    problems = []
    if s % 8:
        problems.append(f"a chunk of whole sublane tiles (s % 8 == 0, got "
                        f"s={s})")
    if width - rank < rope:
        problems.append(f"a row of {width} values holds no rotary key of "
                        f"{rope} past a latent of {rank}")
    if interpret:
        return problems
    if any(n % 128 for n in (nope, dv, rank, width)):
        problems.append(f"whole lane tiles required on TPU (nope={nope}, "
                        f"dv={dv}, rank={rank}, width={width})")
    sub = {1: 32, 2: 16, 4: 8}.get(jnp.dtype(dtype).itemsize, 8)
    if page_size % sub:
        problems.append(f"page_size % {sub} == 0 required for "
                        f"{jnp.dtype(dtype).name} pools (got {page_size})")
    return problems


def expanded_rows(lens, n_valid, s, max_pages, page_size):
    """Latent rows one layer's kernel call expands (each row's q blocks
    holding a valid query, each the key blocks up to its last valid
    query): what the engine counts as `mla_expand_rows`."""
    bq, bk = _block_q(s), block_pages(page_size, max_pages) * page_size
    length = max_pages * page_size
    total = 0
    for at, nv in zip(np.asarray(lens), np.asarray(n_valid)):
        last_valid = int(at) + max(int(nv), 1) - 1
        for qi in range(s // bq):
            q_lo = int(at) + qi * bq
            if q_lo > last_valid:
                break
            q_hi = min(q_lo + bq - 1, last_valid, length - 1)
            total += (q_hi // bk + 1) * bk
    return total


def _prec(dtype):
    return (jax.lax.Precision.DEFAULT
            if dtype in (jnp.bfloat16, jnp.float16)
            else jax.lax.Precision.HIGHEST)


def _kernel(lens_ref, nv_ref, bt_ref, qn_ref, qp_ref, pool_hbm, wuk_ref,
            wuv_ref, o_ref, buf, sem, cur_ref, m_scr, l_scr, acc_scr, *, bq,
            pages, page_size, rank):
    """Grid (rows, heads, q blocks, key blocks), run in order; a step that
    runs finds its key block in a window its predecessor started."""
    bi, hi, qi, ki = (pl.program_id(i) for i in range(4))
    nb, nh, nq = (pl.num_programs(i) for i in range(3))
    mp = bt_ref.shape[1]
    bk = pages * page_size

    def last_valid(b):
        return lens_ref[b] + jnp.maximum(nv_ref[b], 1) - 1

    def reach(b, q):
        """The last key block q block q of row b needs; -1 for a q block
        wholly past the row's valid tokens."""
        q_lo = lens_ref[b] + q * bq
        q_hi = jnp.minimum(q_lo + bq - 1, last_valid(b))
        return jnp.where(q_lo <= last_valid(b), jax.lax.div(q_hi, bk), -1)

    def start(b, blk, slot):
        """Start the copies of key block `blk` of row b into window `slot`:
        every page of the block, a page past the block table's end as its
        last one (columns past a row's tokens are masked, and the table
        names a page, the trash page at least, wherever it is unused)."""
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

    @pl.when((bi == 0) & (hi == 0) & (qi == 0) & (ki == 0))
    def _first():
        cur_ref[0] = 0
        start(0, 0, 0)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        o_ref[...] = jnp.zeros_like(o_ref)

    q_lo = lens_ref[bi] + qi * bq                   # the block's first query
    k_lo = ki * bk
    kmax = reach(bi, qi)

    def update(ctx, mask):
        prec = _prec(ctx.dtype)
        dot = functools.partial(jax.lax.dot_general, precision=prec,
                                preferred_element_type=jnp.float32)
        c = ctx[:, :rank]
        k_nope = dot(c, wuk_ref[0], (((1,), (0,)), ((), ()))).astype(
            ctx.dtype)                                      # (bk, nope)
        v = dot(c, wuv_ref[0], (((1,), (0,)), ((), ()))).astype(ctx.dtype)
        nt = (((1,), (1,)), ((), ()))
        s = dot(qn_ref[0, 0], k_nope, nt) + dot(qp_ref[0, 0],
                                                ctx[:, rank:], nt)
        if mask:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_lo + col <= q_lo + row, s, _NEG_INF)
        # every query sees key 0, so after the first block m is finite and
        # a masked column's exp2 is exactly 0
        m = m_scr[:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki <= kmax)
    def _step():
        cur = cur_ref[0]
        # the next step that runs: this q block's next key block or, after
        # its last, block 0 of this row for its next head or live q block
        # (q blocks past a dead one are dead), else of the next row
        more = ki < kmax
        stay = (hi + 1 < nh) | ((qi + 1 < nq) & (reach(bi, qi + 1) >= 0))
        nxt = jnp.where(more | stay, bi, bi + 1)

        @pl.when(nxt < nb)
        def _next():
            start(nxt, jnp.where(more, ki + 1, 0), 1 - cur)

        wait(cur)
        cur_ref[0] = 1 - cur
        diagonal = k_lo + bk - 1 > q_lo     # holds a key after the first query

        @pl.when(diagonal)
        def _masked():
            update(buf[cur], True)

        @pl.when(jnp.logical_not(diagonal))
        def _whole():
            update(buf[cur], False)

        @pl.when(ki == kmax)
        def _store():
            o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
                           ).astype(o_ref.dtype)


def mla_prefill_attention(q_nope, q_pe, pool, block_tables, w_uk, w_uv,
                          lens, n_valid, *, sm_scale, interpret=False):
    """q_nope (b, s, H, nope), q_pe (b, s, H, rope): the chunk's queries,
    position-encoded, token j of row i at position lens[i] + j; pool
    (num_pages, page_size, width), each row [c | k_pe | zeros], and
    block_tables (b, max_pages): row i's latent rows, position c at
    offset c % page_size of page block_tables[i, c // page_size] (the
    chunk's own already written); w_uk (rank, H, nope), w_uv (rank, H,
    dv): W_kvb by heads; lens, n_valid (b,) int32. Returns (b, s, H * dv)
    in the pool's dtype: each valid query's causal attention over its
    row's keys, zeros past a row's valid tokens where a whole q block
    lies there."""
    b, s, h, nope = q_nope.shape
    rank, width = w_uk.shape[0], pool.shape[-1]
    problems = mla_prefill_problems(s, nope, q_pe.shape[-1],
                                    w_uv.shape[-1], rank, width,
                                    pool.shape[1], pool.dtype, interpret)
    if problems:
        raise ValueError("mla_prefill_attention: " + "; ".join(problems))
    return _call(q_nope, q_pe, pool, block_tables.astype(jnp.int32), w_uk,
                 w_uv, lens.astype(jnp.int32), n_valid.astype(jnp.int32),
                 sm_scale=float(sm_scale), bq=_block_q(s),
                 pages=block_pages(pool.shape[1], block_tables.shape[1]),
                 interpret=interpret)


# jitted on its own, as the decode kernel is: one lowered kernel a program,
# however many layers call it
@functools.partial(jax.jit, static_argnames=("sm_scale", "bq", "pages",
                                             "interpret"))
def _call(q_nope, q_pe, pool, bt, w_uk, w_uv, lens, n_valid, *, sm_scale,
          bq, pages, interpret):
    b, s, h, nope = q_nope.shape
    rank, _, dv = w_uv.shape
    page_size, width = pool.shape[1], pool.shape[2]
    dt = pool.dtype
    bk = pages * page_size
    nq, nk_max = s // bq, -(-bt.shape[1] // pages)
    scale = sm_scale * _LOG2E

    def heads_first(q, lanes):
        q = (q.astype(jnp.float32) * scale).astype(dt)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, lanes - q.shape[-1])))
        return jnp.transpose(q, (0, 2, 1, 3))               # (b, H, s, .)

    qn = heads_first(q_nope, nope)
    qp = heads_first(q_pe, width - rank)
    wuk = jnp.transpose(w_uk.astype(dt), (1, 0, 2))         # (H, rank, nope)
    wuv = jnp.transpose(w_uv.astype(dt), (1, 0, 2))
    last_valid = lens + jnp.maximum(n_valid, 1) - 1
    nk = jnp.clip(jnp.max(last_valid) // bk + 1, 1, nk_max)

    def q_spec(lanes):
        return pl.BlockSpec((1, 1, bq, lanes),
                            lambda bi, hi, qi, ki, *_: (bi, hi, qi, 0))

    def w_spec(lanes):
        return pl.BlockSpec((1, rank, lanes),
                            lambda bi, hi, qi, ki, *_: (hi, 0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, pages=pages, page_size=page_size,
                          rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h, nq, nk),
            in_specs=[q_spec(nope), q_spec(width - rank),
                      pl.BlockSpec(memory_space=pltpu.HBM),
                      w_spec(nope), w_spec(dv)],
            out_specs=q_spec(dv),
            scratch_shapes=[pltpu.VMEM((2, bk, width), dt),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dv), dt),
        # in order: a step starts the copies the next one waits for
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",) * 4),
        interpret=interpret,
        name="mla_prefill",
    )(lens, n_valid, bt, qn, qp, pool, wuk, wuv)
    return jnp.transpose(out, (0, 2, 1, 3)).reshape(b, s, h * dv)
