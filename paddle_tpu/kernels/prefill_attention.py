"""Pallas attention for a prefill chunk over keys gathered in order (TPU):
many query tokens a slot against that slot's cached keys, under a mask that
is a rule of positions (causal, optionally a window, or causal by blocks),
never a tensor.

The jnp paths of inference/paged.py (`_attend_pages`, `_attend_selected`)
write a chunk's float32 scores to HBM and read them back several times: a
million score elements a token at 4,096 keys x 48 heads x 5 layers is most
of a prefill. This kernel is the flash forward (kernels/flash_attention.py:
online softmax, base-2 exponentials) cut to what a chunk needs:

- q (b, s, hq, d): the chunk's queries, token j of row i at position
  q_pos[i] + j. k, v (b, hk, L, d): the row's keys IN ORDER, column c at
  position k_pos[i] + c (the caller gathers the pages it names: a ring's
  view, or a block table from its start). A query sees the columns whose
  position is at most its own and, with `window`, more than its own less
  the window; with `block`, at most the last position of its own block of
  `block` positions (generation by diffusion over blocks: a query sees
  every key of its own block). Positions ride in as scalar-prefetch
  operands;
- GQA by the head fold: the g = hq / hk query heads of a kv head ride the
  rows of one q tile (g x block_q rows), so a key block is read once a kv
  head;
- grid (b, hk, q blocks, k blocks), the k blocks innermost with the softmax
  state in VMEM scratch. A key block wholly outside what a q block sees
  (after its last query, or before its first query's window) is skipped:
  its index map is clamped into the needed range, so the step refetches
  nothing, and its body does not run. Work and bytes follow the context,
  not the table.

Forward only. `interpret=True` runs it on the CPU (tier-1 holds it to the
jnp path).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.jax_compat import tpu_compiler_params

__all__ = ["chunk_attention", "chunk_attention_problems"]

_NEG_INF = -1e30
_LOG2E = 1.4426950408889634
_BLOCK_K = 512          # keys a step
_Q_ROWS = 1024          # rows of a q tile: query heads of a group x tokens


def _prec(dtype):
    return (jax.lax.Precision.DEFAULT
            if dtype in (jnp.bfloat16, jnp.float16)
            else jax.lax.Precision.HIGHEST)


def _block_q(s, g):
    """Tokens a q tile: the largest power of two that divides s with at
    most `_Q_ROWS` rows of g heads, at least 8 (a sublane tile)."""
    bq = 8
    while bq * 2 <= s and s % (bq * 2) == 0 and g * bq * 2 <= _Q_ROWS:
        bq *= 2
    return bq


def chunk_attention_problems(s, hq, hk, d, interpret=False):
    """Reasons these shapes cannot take the kernel; empty = supported."""
    problems = []
    if hk <= 0 or hq % hk:
        problems.append(f"q heads must be a multiple of kv heads "
                        f"(hq={hq}, hk={hk})")
    if s % 8:
        problems.append(f"a chunk of whole sublane tiles (s % 8 == 0, got "
                        f"s={s})")
    if not interpret and d % 128:
        problems.append(f"head_dim % 128 == 0 required on TPU (got d={d})")
    return problems


def _last_seen(at, block):
    """The last position a query at `at` sees: its own, or with `block` the
    last of its block."""
    if not block:
        return at
    # positions are never negative: the truncating division is the floor
    return (jax.lax.div(at, jnp.int32(block)) + 1) * block - 1


def _kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, bq, bk, nk, window, block):
    bi, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    rows = q_ref.shape[3]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = qpos_ref[bi] + qi * bq            # the tile's first query
    k_lo = kpos_ref[bi] + ki * bk            # the block's first key
    run = k_lo <= _last_seen(q_lo + bq - 1, block)
    if window:
        run = jnp.logical_and(run, k_lo + bk - 1 > q_lo - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0, 0]                                  # (rows, d)
        kj, vj = k_ref[0, 0], v_ref[0, 0]                   # (bk, d)
        prec = _prec(q.dtype)
        s = jax.lax.dot_general(
            q, kj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        # row r is query head r // bq of the group, token r % bq
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
        at = q_lo + jax.lax.rem(row, bq)
        key = k_lo + col
        seen = key <= _last_seen(at, block)
        if window:
            seen = jnp.logical_and(seen, key > at - window)
        s = jnp.where(seen, s, _NEG_INF)
        m = m_scr[:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp2(s - m_new), 0.0)
        alpha = jnp.exp2(m - m_new)
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(vj.dtype), vj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[0, 0, 0] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
                          ).astype(o_ref.dtype)


def chunk_attention(q, k, v, q_pos, k_pos, *, window=0, block=0,
                    sm_scale=None, interpret=False):
    """q (b, s, hq, d); k, v (b, hk, L, d), a row's keys in order; q_pos,
    k_pos (b,) int32, the positions of each row's first query and first
    key column. Returns (b, s, hq * d) in q.dtype: each query's attention
    over the columns at positions (own - window, own] (window 0: every
    position up to its own; `block`: up to the last position of its own
    block of `block`, which no window cuts). A query that sees no column
    gets zeros."""
    b, s, hq, d = q.shape
    hk = k.shape[1]
    problems = chunk_attention_problems(s, hq, hk, d, interpret)
    if problems:
        raise ValueError("chunk_attention: " + "; ".join(problems))
    if window and block:
        raise ValueError("chunk_attention: a window inside block-causal "
                         "attention is not defined")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    return _call(q, k, v, q_pos.astype(jnp.int32), k_pos.astype(jnp.int32),
                 window=int(window or 0), sm_scale=float(sm_scale),
                 block=int(block or 0), interpret=interpret)


# jitted on its own, as the decode kernel is: one lowered kernel a program
# and a window, however many layers call it
@functools.partial(jax.jit,
                   static_argnames=("window", "sm_scale", "interpret",
                                    "block"))
def _call(q, k, v, q_pos, k_pos, *, window, sm_scale, interpret, block):
    b, s, hq, d = q.shape
    hk, length = k.shape[1], k.shape[2]
    g = hq // hk
    bq = _block_q(s, g)
    nq, rows = s // bq, g * bq
    bk = min(_BLOCK_K, -(-length // 128) * 128)
    nk = -(-length // bk)
    if nk * bk != length:
        # columns past the keys stand at positions past every query
        pad = ((0, 0), (0, 0), (0, nk * bk - length), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    # a q tile is (g heads x bq tokens, d): log2e rides the scale into q
    qt = (q * jnp.asarray(sm_scale * _LOG2E, q.dtype)).reshape(
        b, nq, bq, hk, g, d)
    qt = jnp.transpose(qt, (0, 3, 1, 4, 2, 5)).reshape(b, hk, nq, rows, d)

    def needed(bi, qi, ki, qpos, kpos):
        """ki clamped into the key blocks q tile qi sees: a step outside
        them names the block of a step inside, which is not fetched again."""
        q_lo = qpos[bi] + qi * bq
        last = jnp.clip((_last_seen(q_lo + bq - 1, block) - kpos[bi]) // bk,
                        0, nk - 1)
        first = jnp.clip((q_lo - window + 1 - kpos[bi]) // bk, 0, last) \
            if window else 0
        return jnp.clip(ki, first, last)

    q_spec = pl.BlockSpec((1, 1, 1, rows, d),
                          lambda bi, hi, qi, ki, *_sp: (bi, hi, qi, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d), lambda bi, hi, qi, ki, qpos, kpos:
        (bi, hi, needed(bi, qi, ki, qpos, kpos), 0))
    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, nk=nk, window=window,
                          block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hk, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((rows, 128), jnp.float32),
                            pltpu.VMEM((rows, 128), jnp.float32),
                            pltpu.VMEM((rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, hk, nq, rows, d), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="paged_attention_prefill",
    )(q_pos, k_pos, qt, k, v)
    out = out.reshape(b, hk, nq, g, bq, d)
    return jnp.transpose(out, (0, 2, 4, 1, 3, 5)).reshape(b, s, hq * d)

