"""Pallas paged-attention decode kernel (TPU), with int8 KV dequant.

The serving hot path: PagedKVEngine's decode tick attends ONE query row
per slot over that slot's whole paged KV window. The jnp path in
inference/paged.py gathers every slot's full page window into a dense
(b, hk, L, d) array, repeats it across query heads for GQA, and runs a
dense masked softmax — O(window) HBM gather traffic plus hq/hk x
materialization per layer per decode step. This kernel is the
vLLM-PagedAttention-style replacement (Kwon et al., SOSP'23; same
capability as the reference's block_multi_head_attention_kernel.cu
decode branch):

- the page pools (num_pages, hk, page_size, d) stay in HBM; the grid is
  (slot, kv_head, page) and the k/v BlockSpec index_map reads the
  BLOCK TABLE (a scalar-prefetch operand, SMEM-resident before the body
  runs) to DMA exactly the pages the slot owns — no dense gather, no
  copy of anyone else's pages;
- GQA is handled by the same head-fold trick as flash_attention.py:
  the g = hq//hk query heads sharing a kv head ride ONE (g, d) q tile,
  so k/v pages are streamed once per kv head instead of materializing
  jnp.repeat'ed copies;
- softmax is the online accumulator from the flash kernels (base-2
  exponentials, log2e folded into the q scale once), carried in VMEM
  scratch across the page axis; pages past the slot's length are
  skipped via pl.when AND their DMA is elided by clamping the index
  map to the last needed page (the _ki_clamp trick);
- int8 KV pools dequantize INSIDE the K-loop: scores/values are
  computed from the int8 page block and scaled by the per-page-per-head
  f32 scale AFTER the dot (scalar multiply), so the bf16/f32 pool is
  never materialized in HBM — the quant_matmul.py lesson applied to KV.

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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.jax_compat import tpu_compiler_params

__all__ = ["paged_decode_attention", "decode_shape_problems",
           "check_decode_shapes"]

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
        # compiled Mosaic wants tileable (page_size, d) k/v blocks;
        # interpret mode (CPU tier-1) has no tiling constraint
        dt = jnp.dtype(kv_dtype if kv_dtype is not None
                       else jnp.float32)
        sub = _MIN_SUBLANE.get(dt.itemsize, 8)
        if d % 8 != 0:
            problems.append(f"head_dim % 8 == 0 required on TPU "
                            f"(got d={d})")
        if page_size % sub != 0:
            problems.append(f"page_size % {sub} == 0 required on TPU "
                            f"for {dt.name} pools (got "
                            f"page_size={page_size})")
    return problems


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


def _decode_kernel(bt_ref, lens_ref, kscale_ref, vscale_ref,
                   q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                   page_size, sm_scale, quantized):
    """Grid (b, hk, max_pages). Scalar-prefetch refs: block tables
    (b, mp) i32, lens (b,) i32, and — quantized pools only — the
    PER-SLOT gathered f32 scales (b, mp, hk) in SMEM (gathered from
    the (num_pages, hk) planes outside the kernel so SMEM use scales
    with the batch, not the pool). k_ref/v_ref are ONE page block
    (1, 1, page_size, d), DMA'd by the index_map through the block
    table."""
    bi = pl.program_id(0)
    hi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = lens_ref[bi]                   # query position of this slot
    last = pos // page_size              # last page the window touches
    gp, d = q_ref.shape[2], q_ref.shape[3]
    prec = _prec(q_ref.dtype)

    @pl.when(j <= last)
    def _compute():
        # log2e folded into the (gp, d) q tile once; exponentials below
        # are exp2 (flash_attention.py convention)
        q = q_ref[0, 0] * jnp.asarray(sm_scale * _LOG2E, q_ref.dtype)
        kj = k_ref[0, 0]                              # (ps, d)
        vj = v_ref[0, 0]
        if quantized:
            # fuse-the-convert: int8 -> f32 in REGISTER, dot, then one
            # scalar multiply per page block (the per-page-per-head
            # scale) — the dequantized page never exists in HBM
            kj = kj.astype(jnp.float32)
            vj = vj.astype(jnp.float32)
            q = q.astype(jnp.float32)
            s = jax.lax.dot_general(
                q, kj, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=prec) * kscale_ref[bi, j, hi]  # (gp, ps)
        else:
            s = jax.lax.dot_general(
                q, kj, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=prec)                          # (gp, ps)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
            + j * page_size
        s = jnp.where(col <= pos, s, _NEG_INF)
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True,
                                    dtype=jnp.float32)
        pv = jax.lax.dot_general(
            p.astype(vj.dtype), vj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        if quantized:
            pv = pv * vscale_ref[bi, j, hi]
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _store():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[:, :1], 1e-30))


def _decode_kernel_noquant(bt_ref, lens_ref, *rest, **kw):
    """Unquantized pools carry no scale operands: splice None refs into
    _decode_kernel's scale slots."""
    return _decode_kernel(bt_ref, lens_ref, None, None, *rest,
                          quantized=False, **kw)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lens, *,
                           k_scale=None, v_scale=None, sm_scale=None,
                           interpret=False):
    """One decode step of paged attention for every slot.

    q: (b, hq, d) — one (position-encoded) query row per slot.
    k_pool/v_pool: (num_pages, hk, page_size, d), bf16/f32, or int8
        with `k_scale`/`v_scale` (num_pages, hk) f32 such that
        k ~= k_pool * k_scale[page, head, None, None].
    block_tables: (b, max_pages) int32 — physical page of each logical
        page per slot (engine convention: 0 = never-written trash page
        for unallocated entries; those columns are masked anyway).
    lens: (b,) int32 — this query's position (its k/v must already be
        scattered there); columns c <= lens[i] are attended.

    Returns (b, hq, d) f32. Shapes must pass `check_decode_shapes`
    (call it, or gate on `decode_shape_problems`, before forcing this
    path — same contract as ring_attention_local(use_flash=True)).
    """
    b, hq, d = q.shape
    num_pages, hk, page_size, _ = k_pool.shape
    mp = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    quantized = k_pool.dtype == jnp.int8
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError("int8 pools require k_scale and v_scale "
                         "(num_pages, hk) f32")
    check_decode_shapes(hq, hk, d, page_size, interpret,
                        kv_dtype=k_pool.dtype)

    g = hq // hk
    # fold query heads sharing a kv head into the q tile's rows, padded
    # to a full sublane tile so the compiled kernel never sees a g < 8
    # second-minor dim (padded rows are zeros; their output is sliced
    # off — they cost nothing real at these sizes)
    gp = max(8, -(-g // 8) * 8) if not interpret else g
    qf = q.reshape(b, hk, g, d)
    if gp != g:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, gp - g), (0, 0)))

    bt = block_tables.astype(jnp.int32)
    lens = lens.astype(jnp.int32)

    def clamp(j, bt_sp, lens_sp, bi):
        # revisit the last needed page above the window: a repeated
        # block index elides the DMA (flash _ki_clamp trick), and the
        # clamped entry is always an ALLOCATED page of this slot
        return bt_sp[bi, jnp.minimum(j, lens_sp[bi] // page_size)]

    kv_spec = pl.BlockSpec(
        (1, 1, page_size, d),
        lambda bi, hi, j, bt_sp, lens_sp, *_sc: (
            clamp(j, bt_sp, lens_sp, bi), hi, 0, 0))
    q_spec = pl.BlockSpec(
        (1, 1, gp, d),
        lambda bi, hi, j, *_sp: (bi, hi, 0, 0))

    scalar_args = [bt, lens]
    if quantized:
        # gather scales per SLOT here (tiny: (b, mp, hk)) so the SMEM
        # footprint follows the batch, not the pool — pool-wide scale
        # planes would outgrow SMEM at production page counts
        scalar_args += [k_scale[bt].astype(jnp.float32),
                        v_scale[bt].astype(jnp.float32)]
        kernel = functools.partial(_decode_kernel, page_size=page_size,
                                   sm_scale=sm_scale, quantized=True)
    else:
        kernel = functools.partial(_decode_kernel_noquant,
                                   page_size=page_size,
                                   sm_scale=sm_scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalar_args),
        grid=(b, hk, mp),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((gp, 8), jnp.float32),
                        pltpu.VMEM((gp, 8), jnp.float32),
                        pltpu.VMEM((gp, d), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, gp, d), jnp.float32),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention_decode",
    )(*scalar_args, qf, k_pool, v_pool)
    return out[:, :, :g, :].reshape(b, hq, d)
