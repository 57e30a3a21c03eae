"""FlashAttention for TPU (Pallas), forward + backward kernels.

Replaces the reference's vendored FA2 CUDA library (reference:
third_party/flashattn + paddle/phi/kernels/gpu/flash_attn_kernel.cu,
python surface python/paddle/nn/functional/flash_attention.py) with a
TPU-native implementation:

- forward: a Pallas kernel — one grid cell per (batch, head, q-block),
  online-softmax accumulation over k/v blocks streamed through VMEM, MXU
  matmuls in f32 accumulation. Causal cells whose k-block lies entirely
  above the diagonal are skipped via the loop bound. Also emits the
  row logsumexp (LSE) for the backward pass, stored TRANSPOSED as
  (b, h, 8, sq) f32 — full (8,128) tiles; a (sq, 8) layout wastes 15/16
  of every tile's bandwidth on the minor-dim padding (r4 trace).
- backward, small kv (the common training shape after the GQA fold):
  ONE fused Pallas kernel — grid (b, h, q-block), k/v + full-kv f32
  dk/dv scratch VMEM-resident — produces dq, dk and dv from a single
  softmax recompute (_bwd_fused_kernel).
- backward, larger kv: two Pallas kernels in FA2 style —
    dq: grid (b, h, q-block); recompute p from q,k and the saved LSE,
        ds = p * (dO·vT - delta), accumulate dq += ds @ k.
    dkv: grid (b, h, k-block); loop over q-blocks at/below the diagonal,
        dv += p^T·dO and dk += ds^T·q with f32 accumulators carried
        through the loop.
  delta = rowsum(dO * O) is precomputed in XLA (one fused pass).
- CPU fallback (and the bwd-of-bwd path): rematerialising chunked
  attention (lax.scan over k/v blocks with jax.checkpoint) differentiated
  by JAX AD — exact same math with O(S·D) residual memory.

Two kernel layouts per direction, selected by kv size: below
_KV_VMEM_BYTES the whole k/v sits in VMEM per (b, h) (fastest — one
fetch, no per-block grid overhead); above it, 4D-grid variants stream
one k/v block per grid step with the softmax state / accumulators in
VMEM scratch, so single-chip sequence length is bounded by HBM only
(verified: 32K tokens trains on one 16G v5e). Multi-chip long context
goes through ring/context-parallel (distributed/context_parallel.py),
which shards the sequence before the kernel sees it.

Layouts: public entry takes paddle's (batch, seq, heads, head_dim).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from paddle_tpu.core import jax_compat
from paddle_tpu.kernels import sharding as _sharding

_NEG_INF = -1e30
_LOG2E = 1.4426950408889634  # kernels exponentiate in base 2: exp(x) = exp2(x*log2e)
# LSE/delta sublane replication rows in the TRANSPOSED (b, h, rows, sq)
# layout: 8 = the f32 sublane tile, so every (8, 128) tile is fully
# used. (The r1-r3 (b, h, sq, lanes) layout padded the 8- or 128-wide
# minor dim into (8,128) tiles; the r4 trace measured its delta twin
# broadcasting at 33 GB/s — 4.3 ms/step of layout waste.)
_LSE_ROWS = 8

# Block sizes of a shape the autotune table (core/autotune.py) does not
# hold (swept on v5e: (512,512) best in the full train step; larger
# q-blocks win in kernel isolation but lose in context)
_BLOCK_Q = 512
_BLOCK_K = 512
_BLOCK_Q_BWD = 512
_BLOCK_K_BWD = 512
# streamed-kv (long-sequence) kernels want much larger k blocks: fewer
# grid steps and fewer lse/delta re-reads. S=16k b1 on v5e measured
# 9.2k tok/s at bk=512 vs 13.9k at bk=2048.
_BLOCK_K_STREAM = 2048


def _tuned_blocks(which, b, h, sq, sk, d, dtype, causal, seg_len=None):
    """(bq, bk) for the whole-kv kernels from the runtime autotune cache
    (reference: phi/kernels/autotune/cache.h AlgorithmsCache).
    Cached/seeded shapes never sweep; a NEW shape on a real
    TPU is measured once standalone across a NARROW candidate set —
    narrow deliberately: big q-blocks win in kernel isolation but lose
    in the full train step (round-2 sweep), so only in-context-safe
    configs compete — and the winner is persisted to disk."""
    default = ((_BLOCK_Q, _BLOCK_K) if which == "flash_fwd"
               else (_BLOCK_Q_BWD, _BLOCK_K_BWD))
    from paddle_tpu.core import autotune
    dname = {"bfloat16": "bf16", "float32": "f32",
             "float16": "f16"}.get(jnp.dtype(dtype).name,
                                   jnp.dtype(dtype).name)
    key = (f"q{sq}_s{sk}_d{d}_{dname}_c{int(bool(causal))}"
           + ("_g" if seg_len is not None else ""))
    prep: dict = {}

    def measure(cfg):
        import numpy as np
        if not prep:
            rng = np.random.default_rng(0)
            mb, mh = min(b, 2), min(h, 4)
            prep["qkv"] = [
                jnp.asarray(rng.standard_normal((mb, mh, s_, d)), dtype)
                for s_ in (sq, sk, sk)]
            if which == "flash_bwd":
                # explicit blocks: the prep forward must not trigger a
                # nested flash_fwd sweep
                o, lse = _flash_fwd_pallas(
                    *prep["qkv"], causal, 1.0 / math.sqrt(d),
                    block_q=_BLOCK_Q, block_k=_BLOCK_K, stream_kv=False,
                    seg_len=seg_len)
                prep["o"], prep["lse"] = o, lse
                prep["g"] = jnp.asarray(
                    np.random.default_rng(1).standard_normal(o.shape),
                    dtype)
        mq, mk, mv = prep["qkv"]
        if which == "flash_fwd":
            def run():
                return _flash_fwd_pallas(
                    mq, mk, mv, causal, 1.0 / math.sqrt(d),
                    block_q=cfg[0], block_k=cfg[1], stream_kv=False,
                    seg_len=seg_len)[0]
        else:
            def run():
                return _flash_bwd_pallas(
                    mq, mk, mv, prep["o"], prep["lse"], prep["g"],
                    causal, 1.0 / math.sqrt(d), block_q=cfg[0],
                    block_k=cfg[1], stream_kv=False, seg_len=seg_len)[0]
        return autotune.time_fn(run)

    cands = [c for c in ((512, 512), (256, 512), (512, 256), (256, 256))
             if c[0] <= max(sq, 256) and c[1] <= max(sk, 256)
             and (seg_len is None or seg_len % c[0] == 0)]
    bq, bk = autotune.choose(which, key, cands, measure, default)
    return bq, bk


def _prec(dtype):
    """MXU precision: bf16/f16 operands use the native one-pass mode (full
    rate, f32 accumulation); f32 operands keep exact f32. The package-global
    'highest' default would emulate bf16 matmuls in f32 at a fraction of
    the rate."""
    return (jax.lax.Precision.DEFAULT
            if dtype in (jnp.bfloat16, jnp.float16)
            else jax.lax.Precision.HIGHEST)


def _pick_block(seq, target):
    """Largest power-of-two block <= target that divides/covers seq."""
    b = min(target, max(8, 1 << (seq - 1).bit_length()))
    return min(b, target)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                block_k, kv_valid, seg_len=None):
    # lse_ref is None on the inference path (save_lse=False): the LSE
    # write is only needed as the backward's softmax residual.
    # seg_len: GQA fold — the q axis is G concatenated length-seg_len
    # segments (one per q-head sharing this kv head); causal masking is
    # per-segment (row mod seg_len).
    # k arrives pre-transposed as (1, 1, d, sk): the (1),(0) contraction is
    # the fastest Mosaic form for the hot q @ k dot. ((1,),(1,)) also
    # lowers for bf16 — the backward kernels use it (verified on v5e).
    bq, d = q_ref.shape[2], q_ref.shape[3]
    kv_pad = k_ref.shape[3]
    iq = pl.program_id(2)

    # fold log2(e) into the scale once on (bq, d) instead of an extra
    # multiply on every (bq, sk) score: all exponentials below are exp2,
    # and the saved LSE is base-2
    q = (q_ref[0, 0] * jnp.asarray(sm_scale * _LOG2E, q_ref.dtype))
    prec = _prec(q_ref.dtype)

    nk_total = kv_pad // block_k
    if causal:
        # number of k-blocks touching this q-block's (segment-local) rows
        start = iq * bq
        if seg_len is not None:
            start = start % seg_len
        nk = jnp.minimum((start + bq + block_k - 1) // block_k, nk_total)
        # blocks fully below the diagonal (and inside valid kv) need no
        # element mask at all — pure MXU + softmax
        n_full = jnp.minimum(start // block_k, kv_valid // block_k)
    else:
        nk = nk_total
        n_full = kv_valid // block_k

    # with bq == bk, aligned kv and aligned segments, the only masked
    # block is the diagonal one and its causal mask is the STATIC lower
    # triangle — loop-invariant, so Mosaic hoists it out of the masked
    # loop instead of regenerating j-offset iotas per iteration
    static_tri = (causal and bq == block_k and kv_valid % block_k == 0
                  and (seg_len is None or seg_len % block_k == 0))

    def body(j, carry, masked=True):
        m, l, acc = carry
        kj = k_ref[0, 0, :, pl.ds(j * block_k, block_k)]   # (d, bk)
        vj = v_ref[0, 0, pl.ds(j * block_k, block_k), :]   # (bk, d)
        s = jax.lax.dot_general(
            q, kj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec)                              # (bq, bk) f32
        # bf16: the package-global 'highest' would force an f32-contract
        # form Mosaic can't lower; bf16 inputs with f32 accumulation IS
        # the full-rate MXU mode
        if masked and static_tri:
            tri = (jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
                   <= jax.lax.broadcasted_iota(jnp.int32, (bq, block_k),
                                               0))
            s = jnp.where(tri, s, _NEG_INF)
        elif masked:
            col = jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1) \
                + j * block_k
            valid = col < kv_valid
            if causal:
                row = jax.lax.broadcasted_iota(jnp.int32, (bq, block_k),
                                               0) + start
                valid = jnp.logical_and(valid, col <= row)
            s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True,
                                    dtype=jnp.float32)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(vj.dtype), vj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    carry = jax.lax.fori_loop(
        0, n_full, functools.partial(body, masked=False), (m0, l0, acc0))
    m, l, acc = jax.lax.fori_loop(n_full, nk, body, carry)
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    if lse_ref is not None:
        # TRANSPOSED lse store (rows, bq): the old (bq, 8) f32 layout
        # tiled (8,128) wasted 15/16 of every tile's bandwidth (r4
        # trace: its downstream delta twin broadcast ran at 33 GB/s)
        lse_t = (m + jnp.log2(jnp.maximum(l, 1e-30))).T   # (1, bq), base-2
        lse_ref[0, 0] = jnp.broadcast_to(lse_t, (lse_ref.shape[2], bq))


def _fwd_kernel_stream(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                       acc_scr, *, sm_scale, causal, kv_valid, nk_total,
                       seg_len=None):
    """4D-grid forward: grid (b, h, iq, jk) streams one k/v block per step
    with the softmax state in VMEM scratch. Used when whole-k/v no longer
    fits the per-kernel VMEM budget (long sequences); the 3D variant above
    is faster at short kv (k/v fetched once per (b,h), no per-block grid
    overhead)."""
    bq, d = q_ref.shape[2], q_ref.shape[3]
    bk = k_ref.shape[3]
    iq = pl.program_id(2)
    jk = pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = iq * bq
    if seg_len is not None:
        start = start % seg_len
    run = (jk * bk <= start + bq - 1) if causal else True
    full = (jk + 1) * bk <= kv_valid
    if causal:
        full = jnp.logical_and(full, (jk + 1) * bk - 1 <= start)

    prec = _prec(q_ref.dtype)

    def compute(masked):
        q = (q_ref[0, 0] * jnp.asarray(sm_scale * _LOG2E, q_ref.dtype))
        kj = k_ref[0, 0]                                   # (d, bk)
        vj = v_ref[0, 0]                                   # (bk, d)
        s = jax.lax.dot_general(
            q, kj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        if masked:
            col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) \
                + jk * bk
            valid = col < kv_valid
            if causal:
                row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
                    + start
                valid = jnp.logical_and(valid, col <= row)
            s = jnp.where(valid, s, _NEG_INF)
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True,
                                    dtype=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(vj.dtype), vj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(jnp.logical_and(run, full))
    def _unmasked():
        compute(False)

    @pl.when(jnp.logical_and(run, jnp.logical_not(full)))
    def _masked():
        compute(True)

    @pl.when(jk == nk_total - 1)
    def _store():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_t = (m_scr[:, :1] + jnp.log2(l)).T            # (1, bq)
            lse_ref[0, 0] = jnp.broadcast_to(lse_t,
                                             (lse_ref.shape[2], bq))


# whole-k/v per grid cell is faster but caps kv length; beyond this byte
# budget (k+v resident per kernel) the streamed 4D-grid variants kick in.
# 3MB: S=8k (2.1MB k+v at d=64) stays whole-kv, S=16k (4.2MB) streams —
# the whole-kv dq kernel at 16k measured 17.5M scoped vmem (>16M limit)
# inside the full remat train step.
_KV_VMEM_BYTES = 3 * 1024 * 1024


def _stream_block_k(sk, d, itemsize, dtype=None):
    """Streamed-path k-block width: as wide as the tuned/target width
    allows WITHOUT the per-cell resident k+v block pair exceeding the
    same VMEM budget that triggered streaming (a flat 2048 at large d or
    f32 would recreate the whole-kv overflow the budget exists to
    avoid). The target comes from the autotune cache (seeded with the
    round-2 sweep: 2048 at 8k-32k), else _BLOCK_K_STREAM."""
    from paddle_tpu.core import autotune
    name = jnp.dtype(dtype).name if dtype is not None else "bf16"
    name = {"bfloat16": "bf16", "float32": "f32",
            "float16": "f16"}.get(name, name)
    target = autotune.get("flash_stream_bk", f"s{sk}_{name}") \
        or _BLOCK_K_STREAM
    budget_elems = _KV_VMEM_BYTES // (2 * d * itemsize)
    capped = max(512, (budget_elems // 512) * 512)
    return min(int(target), capped, sk)


def _auto_stream_kv(sk_p, d, itemsize):
    """True when whole-k/v per (b, h) would exceed the VMEM budget (k and
    v each sk_p*d elements). Shared by fwd and bwd so both directions
    always pick the same kernel layout."""
    return sk_p * d * 2 * itemsize > _KV_VMEM_BYTES


def _ki_clamp(bq, bk, causal, seg_len):
    """For streamed k/v block index maps: clamp ki to the last block this
    q-row actually needs (causal), so above-diagonal grid steps revisit
    the previous block — Pallas elides the DMA for a repeated index —
    instead of fetching data the kernel body then skips."""
    def clamp(qi, ki):
        if not causal:
            return ki
        start = qi * bq
        if seg_len is not None:
            start = start % seg_len
        return jnp.minimum(ki, (start + bq - 1) // bk)
    return clamp


def _flash_fwd_pallas(q, k, v, causal, sm_scale, block_q=None, block_k=None,
                      interpret=False, save_lse=True, seg_len=None,
                      stream_kv=None):
    """q,k,v: (B, H, S, D) with equal head counts. seg_len: the q axis is
    G concatenated segments of this length (GQA fold; requires block
    alignment — callers gate on it). stream_kv: force (True) / forbid
    (False) the 4D streamed-kv kernel; None = auto by kv size.
    Returns (out (B,H,Sq,D), lse (B,H,8,Sq_pad) f32 TRANSPOSED | None)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    will_stream = (stream_kv if stream_kv is not None
                   else _auto_stream_kv(sk, d, k.dtype.itemsize))
    if block_q is None and block_k is None and not will_stream:
        # streamed shapes skip whole-kv tuning entirely: sweeping the
        # whole-kv kernels at a VMEM-overflowing kv size is exactly what
        # _auto_stream_kv exists to avoid, and the streamed path picks
        # its own bk via _stream_block_k
        tq, tk = _tuned_blocks("flash_fwd", b, h, sq, sk, d, q.dtype,
                               causal, seg_len)
    else:
        tq, tk = block_q or _BLOCK_Q, block_k or _BLOCK_K
    bq = min(tq, sq)
    bk = min(tk, sk)
    if seg_len is not None:
        assert sq % seg_len == 0 and seg_len % bq == 0, (sq, seg_len, bq)
    # pad seqs to block multiples
    sq_p = (sq + bq - 1) // bq * bq
    sk_p = (sk + bk - 1) // bk * bk
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))

    if stream_kv is None:
        stream_kv = _auto_stream_kv(sk_p, d, k.dtype.itemsize)
    if stream_kv and block_k is None:
        bk2 = _stream_block_k(sk, d, k.dtype.itemsize, k.dtype)
        if bk2 > bk:
            bk = bk2
            sk_p = (sk + bk - 1) // bk * bk
            if sk_p != k.shape[2]:
                pad = sk_p - sk
                k = jnp.pad(k[:, :, :sk],
                            ((0, 0), (0, 0), (0, pad), (0, 0)))
                v = jnp.pad(v[:, :, :sk],
                            ((0, 0), (0, 0), (0, pad), (0, 0)))
    kt = jnp.swapaxes(k, 2, 3)   # (b, h, d, sk): XLA fuses the transpose

    if stream_kv:
        kernel = functools.partial(
            _fwd_kernel_stream, sm_scale=sm_scale, causal=causal,
            kv_valid=sk, nk_total=sk_p // bk, seg_len=seg_len)
        qspec = pl.BlockSpec((1, 1, bq, d),
                             lambda bi, hi, qi, ki: (bi, hi, qi, 0))
        grid = (b, h, sq_p // bq, sk_p // bk)
        clamp = _ki_clamp(bq, bk, causal, seg_len)
        in_specs = [
            qspec,
            pl.BlockSpec((1, 1, d, bk),
                         lambda bi, hi, qi, ki: (bi, hi, 0, clamp(qi, ki))),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi, clamp(qi, ki), 0)),
        ]
        lspec = pl.BlockSpec((1, 1, _LSE_ROWS, bq),
                             lambda bi, hi, qi, ki: (bi, hi, 0, qi))
        scratch = [pltpu.VMEM((bq, _LSE_ROWS), jnp.float32),
                   pltpu.VMEM((bq, _LSE_ROWS), jnp.float32),
                   pltpu.VMEM((bq, d), jnp.float32)]
    else:
        # q goes in as (b, h, sq, d) and XLA inserts a relayout copy
        # (~5 ms a step) at the pallas boundary. Handing it over
        # transposed, so that the swapaxes fuses into q's producer and
        # the kernel contracts a transposed lhs, measured 2 % SLOWER
        # than eating the copy on v5e.
        kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale,
                                   causal=causal, block_k=bk, kv_valid=sk,
                                   seg_len=seg_len)
        qspec = ospec = pl.BlockSpec((1, 1, bq, d),
                                     lambda bi, hi, qi: (bi, hi, qi, 0))
        grid = (b, h, sq_p // bq)
        in_specs = [
            qspec,
            pl.BlockSpec((1, 1, d, sk_p),
                         lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, sk_p, d),
                         lambda bi, hi, qi: (bi, hi, 0, 0)),
        ]
        lspec = pl.BlockSpec((1, 1, _LSE_ROWS, bq),
                             lambda bi, hi, qi: (bi, hi, 0, qi))
        scratch = []
    if stream_kv:
        ospec = qspec
    out_specs = [ospec]
    out_shape = [jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype)]
    if save_lse:
        out_specs.append(lspec)
        out_shape.append(
            jax.ShapeDtypeStruct((b, h, _LSE_ROWS, sq_p), jnp.float32))
    else:
        kernel = functools.partial(
            lambda q_ref, k_ref, v_ref, o_ref, *scr, kern: kern(
                q_ref, k_ref, v_ref, o_ref, None, *scr), kern=kernel)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_fwd",
    )(q, kt, v)
    out = outs[0]
    lse = outs[1] if save_lse else None
    return out[:, :, :sq, :], lse


# ---------------------------------------------------------------------------
# Pallas backward kernels (FA2: recompute p from LSE, no O(S^2) residuals)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, sm_scale, causal, block_k, kv_valid, seg_len=None):
    bq, d = q_ref.shape[2], q_ref.shape[3]
    kv_pad = k_ref.shape[2]
    iq = pl.program_id(2)

    q = (q_ref[0, 0] * jnp.asarray(sm_scale * _LOG2E, q_ref.dtype))
    do = do_ref[0, 0]
    lse = lse_ref[0, 0, :1, :].T                   # (bq, 1) f32
    delta = delta_ref[0, 0, :1, :].T               # (bq, 1) f32
    prec = _prec(q_ref.dtype)

    nk_total = kv_pad // block_k
    if causal:
        start = iq * bq
        if seg_len is not None:
            start = start % seg_len
        nk = jnp.minimum((start + bq + block_k - 1) // block_k, nk_total)
        n_full = jnp.minimum(start // block_k, kv_valid // block_k)
    else:
        nk = nk_total
        n_full = kv_valid // block_k

    def body(j, acc, masked=True):
        kj = k_ref[0, 0, pl.ds(j * block_k, block_k), :]   # (bk, d)
        vj = v_ref[0, 0, pl.ds(j * block_k, block_k), :]   # (bk, d)
        s = jax.lax.dot_general(
            q, kj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (bq, bk)
        if masked:
            col = jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1) \
                + j * block_k
            valid = col < kv_valid
            if causal:
                row = jax.lax.broadcasted_iota(jnp.int32, (bq, block_k),
                                               0) + start
                valid = jnp.logical_and(valid, col <= row)
            s = jnp.where(valid, s, _NEG_INF)
        p = jnp.exp2(s - lse)                                   # (bq, bk)
        dp = jax.lax.dot_general(
            do, vj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (bq, bk)
        ds = p * (dp - delta) * sm_scale
        return acc + jax.lax.dot_general(
            ds.astype(kj.dtype), kj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (bq, d)

    acc0 = jnp.zeros((bq, d), jnp.float32)
    acc = jax.lax.fori_loop(0, n_full,
                            functools.partial(body, masked=False), acc0)
    acc = jax.lax.fori_loop(n_full, nk, body, acc)
    dq_ref[0, 0] = acc.astype(dq_ref.dtype)


def _bwd_dq_kernel_stream(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, acc_scr, *, sm_scale, causal, kv_valid,
                          nk_total, seg_len=None):
    """4D-grid dq: grid (b, h, iq, jk) streams one k/v block per step,
    dq accumulates in VMEM scratch (long-kv counterpart of
    _bwd_dq_kernel, same reasoning as _fwd_kernel_stream)."""
    bq, d = q_ref.shape[2], q_ref.shape[3]
    bk = k_ref.shape[2]
    iq = pl.program_id(2)
    jk = pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = iq * bq
    if seg_len is not None:
        start = start % seg_len
    run = (jk * bk <= start + bq - 1) if causal else True
    full = (jk + 1) * bk <= kv_valid
    if causal:
        full = jnp.logical_and(full, (jk + 1) * bk - 1 <= start)

    prec = _prec(q_ref.dtype)

    def compute(masked):
        q = (q_ref[0, 0] * jnp.asarray(sm_scale * _LOG2E, q_ref.dtype))
        do = do_ref[0, 0]
        lse = lse_ref[0, 0, :1, :].T
        delta = delta_ref[0, 0, :1, :].T
        kj = k_ref[0, 0]                                   # (bk, d)
        vj = v_ref[0, 0]                                   # (bk, d)
        s = jax.lax.dot_general(
            q, kj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        if masked:
            col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) \
                + jk * bk
            valid = col < kv_valid
            if causal:
                row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
                    + start
                valid = jnp.logical_and(valid, col <= row)
            s = jnp.where(valid, s, _NEG_INF)
        p = jnp.exp2(s - lse)
        dp = jax.lax.dot_general(
            do, vj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        ds = p * (dp - delta) * sm_scale
        acc_scr[...] += jax.lax.dot_general(
            ds.astype(kj.dtype), kj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)

    @pl.when(jnp.logical_and(run, full))
    def _unmasked():
        compute(False)

    @pl.when(jnp.logical_and(run, jnp.logical_not(full)))
    def _masked():
        compute(True)

    @pl.when(jk == nk_total - 1)
    def _store():
        dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                    nq_total, q_valid, kv_valid, seg_len=None):
    # Grid (b, h, ik, jq): jq (fastest axis) streams q/do/lse/delta blocks
    # while k/v stay resident (same block index => Pallas skips the DMA);
    # dk/dv accumulate in VMEM scratch and store once at the last jq.
    # This keeps per-cell VMEM O(bq + bk) — a flat q stream would need the
    # whole (folded) q/lse/delta per cell and overflows VMEM.
    bk, d = k_ref.shape[2], k_ref.shape[3]
    bq = q_ref.shape[2]
    ik = pl.program_id(2)
    jq = pl.program_id(3)

    @pl.when(jq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # segment-local start row of this q block (GQA fold: causality is per
    # length-seg_len segment)
    start = jq * bq
    if seg_len is not None:
        start = start % seg_len
    run = (start + bq - 1 >= ik * bk) if causal else True
    # cells with every (row, col) pair valid skip the element mask
    full = jnp.logical_and((ik + 1) * bk <= kv_valid,
                           (jq + 1) * bq <= q_valid)
    if causal:
        full = jnp.logical_and(full, (ik + 1) * bk - 1 <= start)

    def _compute(masked):
        # everything in the TRANSPOSED (bk, bq) orientation: sT = k·qT,
        # so dv = pT·do and dk = dsT·q contract directly with no (bq,bk)
        # transposes on the hot path (only the (bq,1) lse/delta vectors
        # get relaid out to (1,bq))
        prec = _prec(q_ref.dtype)
        k = k_ref[0, 0]                                         # (bk, d)
        v = v_ref[0, 0]                                         # (bk, d)
        qj = (q_ref[0, 0]
              * jnp.asarray(sm_scale * _LOG2E, q_ref.dtype))    # (bq, d)
        doj = do_ref[0, 0]                                      # (bq, d)
        lse_t = lse_ref[0, 0, :1, :]                            # (1, bq)
        delta_t = delta_ref[0, 0, :1, :]                        # (1, bq)
        s_t = jax.lax.dot_general(
            k, qj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (bk, bq)
        if masked:
            col = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0) \
                + ik * bk
            row_g = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1) \
                + jq * bq
            valid = jnp.logical_and(col < kv_valid, row_g < q_valid)
            if causal:
                row_c = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1) \
                    + start
                valid = jnp.logical_and(valid, col <= row_c)
            s_t = jnp.where(valid, s_t, _NEG_INF)
        p_t = jnp.exp2(s_t - lse_t)                             # (bk, bq)
        dv_scr[...] += jax.lax.dot_general(
            p_t.astype(doj.dtype), doj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (bk, d)
        dp_t = jax.lax.dot_general(
            v, doj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (bk, bq)
        ds_t = p_t * (dp_t - delta_t) * sm_scale                 # (bk, bq)
        dk_scr[...] += jax.lax.dot_general(
            ds_t.astype(qj.dtype), qj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (bk, d)

    @pl.when(jnp.logical_and(run, full))
    def _compute_unmasked():
        _compute(False)

    @pl.when(jnp.logical_and(run, jnp.logical_not(full)))
    def _compute_masked():
        _compute(True)

    @pl.when(jq == nq_total - 1)
    def _store():
        # undo the sm_scale*log2e folded into qj when accumulating dk
        # (dk = ds^T @ q with q unscaled; qj above was pre-scaled for s)
        dk_ref[0, 0] = (dk_scr[...] / (sm_scale * _LOG2E)).astype(
            dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                      sm_scale, causal, block_k, q_valid, kv_valid,
                      nq_total, seg_len=None):
    """Single-pass FA2 backward: dq, dk and dv from ONE softmax recompute.

    Grid (b, h, jq). Per (b, h): k/v stay VMEM-resident (constant block
    index => one DMA); q/do/o and the transposed (8, bq) lse stream per
    q-block — each block is read exactly once per (b, h) sweep, so this
    costs the same HBM bytes as keeping them resident. delta comes from
    o IN-REGISTER (sum(do*o)), not a materialized array. dq accumulates in
    the fori_loop carry and writes per cell; dk/dv accumulate across the
    whole jq sweep in full-kv f32 scratch and store once at the last jq
    (the dk/dv output block index is constant per (b, h), so Pallas
    flushes it exactly once).

    vs the round-1 dq+dkv kernel pair this halves the softmax recompute
    (the dominant VPU cost: ds is shared by dk AND dq), reads each
    lse/delta element once instead of once per kv block, and needs no
    extra matmul for dq beyond ds_t @ k (ds is already in registers).
    Everything runs in the transposed (bk, bq) orientation so no
    (bq, bk) block ever needs a transpose.
    """
    bq, d = q_ref.shape[2], q_ref.shape[3]
    kv_pad = k_ref.shape[2]
    jq = pl.program_id(2)

    @pl.when(jq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    qj = q_ref[0, 0] * jnp.asarray(sm_scale * _LOG2E, q_ref.dtype)  # (bq,d)
    doj = do_ref[0, 0]                                              # (bq,d)
    lse_t = lse_ref[0, 0, :1, :]                                    # (1,bq)
    # delta = sum(do * o) computed IN-REGISTER from the streamed o
    # block: the old materialized delta was a (b, h, sq, 8) f32 array
    # whose (8,128) tile padding made its broadcast write run at
    # ~33 GB/s — 4.3 ms/step of pure layout waste (r4 trace)
    delta_t = jnp.sum(doj.astype(jnp.float32)
                      * o_ref[0, 0].astype(jnp.float32),
                      axis=-1)[None, :]                             # (1,bq)
    prec = _prec(q_ref.dtype)

    start_g = jq * bq                    # global row (q_valid mask)
    start = start_g % seg_len if seg_len is not None else start_g
    nk_total = kv_pad // block_k
    if causal:
        nk = jnp.minimum((start + bq + block_k - 1) // block_k, nk_total)
        n_full = jnp.minimum(start // block_k, kv_valid // block_k)
    else:
        nk = nk_total
        n_full = kv_valid // block_k
    # rows past q_valid must not contribute to dk/dv: no mask-free blocks
    # unless every row of this q-block is valid
    n_full = jnp.where((jq + 1) * bq <= q_valid, n_full, 0)

    # see _fwd_kernel: on fully-aligned shapes the masked block is the
    # diagonal one with a STATIC (transposed) triangular mask
    static_tri = (causal and bq == block_k and kv_valid % block_k == 0
                  and q_valid % bq == 0
                  and (seg_len is None or seg_len % block_k == 0))

    def body(j, dq_acc, masked=True):
        kj = k_ref[0, 0, pl.ds(j * block_k, block_k), :]   # (bk, d)
        vj = v_ref[0, 0, pl.ds(j * block_k, block_k), :]   # (bk, d)
        s_t = jax.lax.dot_general(
            kj, qj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (bk,bq)
        if masked and static_tri:
            tri_t = (jax.lax.broadcasted_iota(jnp.int32, (block_k, bq), 0)
                     <= jax.lax.broadcasted_iota(jnp.int32, (block_k, bq),
                                                 1))
            s_t = jnp.where(tri_t, s_t, _NEG_INF)
        elif masked:
            col = jax.lax.broadcasted_iota(
                jnp.int32, (block_k, bq), 0) + j * block_k
            row_g = jax.lax.broadcasted_iota(
                jnp.int32, (block_k, bq), 1) + start_g
            valid = jnp.logical_and(col < kv_valid, row_g < q_valid)
            if causal:
                row_c = jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, bq), 1) + start
                valid = jnp.logical_and(valid, col <= row_c)
            s_t = jnp.where(valid, s_t, _NEG_INF)
        p_t = jnp.exp2(s_t - lse_t)                                 # (bk,bq)
        dv_scr[pl.ds(j * block_k, block_k)] += jax.lax.dot_general(
            p_t.astype(doj.dtype), doj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (bk,d)
        dp_t = jax.lax.dot_general(
            vj, doj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (bk,bq)
        ds_t = p_t * (dp_t - delta_t) * sm_scale                 # true ds^T
        ds_lp = ds_t.astype(qj.dtype)
        dk_scr[pl.ds(j * block_k, block_k)] += jax.lax.dot_general(
            ds_lp, qj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (bk,d)
        return dq_acc + jax.lax.dot_general(
            ds_lp, kj, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (bq,d)

    dq0 = jnp.zeros((bq, d), jnp.float32)
    dq_acc = jax.lax.fori_loop(0, n_full,
                               functools.partial(body, masked=False), dq0)
    dq_acc = jax.lax.fori_loop(n_full, nk, body, dq_acc)
    dq_ref[0, 0] = dq_acc.astype(dq_ref.dtype)

    @pl.when(jq == nq_total - 1)
    def _store():
        # dk accumulated against the log2e/sm_scale-prescaled q; undo it
        dk_ref[0, 0] = (dk_scr[...] / (sm_scale * _LOG2E)).astype(
            dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


# fused single-kernel backward needs k+v resident AND full-kv f32 dk/dv
# scratch (2x k+v bytes in f32) in VMEM; above this k+v byte budget fall
# back to the round-1 dq + dkv kernel pair. 1MB measured safe on v5e
# (16MB scoped vmem); 2MB compiled standalone but blew the scoped limit
# inside the full train step at S=8k (co-scheduled ops share VMEM).
_FUSED_KV_BYTES = 1024 * 1024


def _flash_bwd_pallas(q, k, v, o, lse, g, causal, sm_scale,
                      block_q=None, block_k=None, interpret=False,
                      seg_len=None, stream_kv=None, fused=None):
    """FA2 backward. q,k,v,o,g: (B,H,S,D); lse: (B,H,rows,Sq_pad) f32
    TRANSPOSED layout (full (8,128) tiles — see the fwd kernel note)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    will_stream = (stream_kv if stream_kv is not None
                   else _auto_stream_kv(sk, d, k.dtype.itemsize))
    if block_q is None and block_k is None and not will_stream:
        tq, tk = _tuned_blocks("flash_bwd", b, h, sq, sk, d, q.dtype,
                               causal, seg_len)
    else:
        tq, tk = block_q or _BLOCK_Q_BWD, block_k or _BLOCK_K_BWD
    bq = min(tq, sq)
    bk = min(tk, sk)
    if seg_len is not None:
        assert sq % seg_len == 0 and seg_len % bq == 0, (sq, seg_len, bq)
    sq_p = (sq + bq - 1) // bq * bq
    sk_p = (sk + bk - 1) // bk * bk

    # lse was padded with the FORWARD block size; reconcile to ours
    # (padded rows are masked in dkv and sliced off dq, values don't matter)
    if lse.shape[3] > sq_p:
        lse = lse[..., :sq_p]
    elif lse.shape[3] < sq_p:
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, 0),
                            (0, sq_p - lse.shape[3])))
    if sq_p != sq:
        pad = ((0, 0), (0, 0), (0, sq_p - sq), (0, 0))
        q = jnp.pad(q, pad)
        g = jnp.pad(g, pad)
        o = jnp.pad(o, pad)
    if sk_p != sk:
        pad = ((0, 0), (0, 0), (0, sk_p - sk), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)

    if stream_kv is None:
        stream_kv = _auto_stream_kv(sk_p, d, k.dtype.itemsize)
    if stream_kv and block_k is None:
        bk2 = _stream_block_k(sk, d, k.dtype.itemsize, k.dtype)
        if bk2 > bk:
            bk = bk2
            sk_p = (sk + bk - 1) // bk * bk
            if k.shape[2] != sk_p:     # re-pad from the valid prefix
                pad = ((0, 0), (0, 0), (0, sk_p - sk), (0, 0))
                k = jnp.pad(k[:, :, :sk], pad)
                v = jnp.pad(v[:, :, :sk], pad)
    if fused is None:
        fused = (not stream_kv
                 and sk_p * d * 2 * k.dtype.itemsize <= _FUSED_KV_BYTES)
    elif fused and stream_kv:
        raise ValueError(
            "fused=True requires the whole-kv layout but stream_kv "
            "resolved True for this kv size; pass stream_kv=False")

    if fused:
        qspec = pl.BlockSpec((1, 1, bq, d),
                             lambda bi, hi, qi: (bi, hi, qi, 0))
        kres = pl.BlockSpec((1, 1, sk_p, d),
                            lambda bi, hi, qi: (bi, hi, 0, 0))
        # lse/delta stream per q-block: each block is read exactly once
        # per (b, h) sweep, so streaming costs the same HBM bytes as
        # whole-resident, without dynamic sublane slicing in-kernel
        lres = pl.BlockSpec((1, 1, _LSE_ROWS, bq),
                            lambda bi, hi, qi: (bi, hi, 0, qi))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, sm_scale=sm_scale,
                              causal=causal, block_k=bk, q_valid=sq,
                              kv_valid=sk, nq_total=sq_p // bq,
                              seg_len=seg_len),
            grid=(b, h, sq_p // bq),
            in_specs=[qspec, kres, kres, qspec, qspec, lres],
            out_specs=[qspec, kres, kres],
            out_shape=[jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
                       jax.ShapeDtypeStruct((b, h, sk_p, d), k.dtype),
                       jax.ShapeDtypeStruct((b, h, sk_p, d), v.dtype)],
            scratch_shapes=[pltpu.VMEM((sk_p, d), jnp.float32),
                            pltpu.VMEM((sk_p, d), jnp.float32)],
            interpret=interpret,
            name="flash_bwd_fused",
        )(q, k, v, g, o, lse)
        return (dq[:, :, :sq, :], dk[:, :, :sk, :], dv[:, :, :sk, :])

    # non-fused paths (streamed / dq+dkv pair) still consume the
    # materialized lane-broadcast delta (their kernels read it per
    # (q-block, kv-block) pair, where recomputing from o would re-read
    # o once per kv block)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, :, None, :],
                             delta.shape[:2] + (_LSE_ROWS,)
                             + delta.shape[2:])

    if stream_kv:
        clamp = _ki_clamp(bq, bk, causal, seg_len)
        qspec4q = pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0))
        kspec4q = pl.BlockSpec((1, 1, bk, d),
                               lambda bi, hi, qi, ki: (bi, hi,
                                                       clamp(qi, ki), 0))
        lspec4q = pl.BlockSpec((1, 1, _LSE_ROWS, bq),
                               lambda bi, hi, qi, ki: (bi, hi, 0, qi))
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel_stream, sm_scale=sm_scale,
                              causal=causal, kv_valid=sk,
                              nk_total=sk_p // bk, seg_len=seg_len),
            grid=(b, h, sq_p // bq, sk_p // bk),
            in_specs=[qspec4q, kspec4q, kspec4q, qspec4q, lspec4q, lspec4q],
            out_specs=qspec4q,
            out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            interpret=interpret,
            name="flash_bwd_dq_stream",
        )(q, k, v, g, lse, delta)
    else:
        qspec = pl.BlockSpec((1, 1, bq, d),
                             lambda bi, hi, qi: (bi, hi, qi, 0))
        kfull = pl.BlockSpec((1, 1, sk_p, d),
                             lambda bi, hi, qi: (bi, hi, 0, 0))
        lspec = pl.BlockSpec((1, 1, _LSE_ROWS, bq),
                             lambda bi, hi, qi: (bi, hi, 0, qi))
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, sm_scale=sm_scale,
                              causal=causal, block_k=bk, kv_valid=sk,
                              seg_len=seg_len),
            grid=(b, h, sq_p // bq),
            in_specs=[qspec, kfull, kfull, qspec, lspec, lspec],
            out_specs=qspec,
            out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
            interpret=interpret,
            name="flash_bwd_dq",
        )(q, k, v, g, lse, delta)

    nq_total = sq_p // bq
    kspec4 = pl.BlockSpec((1, 1, bk, d),
                          lambda bi, hi, ki, qi: (bi, hi, ki, 0))
    qspec4 = pl.BlockSpec((1, 1, bq, d),
                          lambda bi, hi, ki, qi: (bi, hi, qi, 0))
    lspec4 = pl.BlockSpec((1, 1, _LSE_ROWS, bq),
                          lambda bi, hi, ki, qi: (bi, hi, 0, qi))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          nq_total=nq_total, q_valid=sq, kv_valid=sk,
                          seg_len=seg_len),
        grid=(b, h, sk_p // bk, nq_total),
        in_specs=[qspec4, kspec4, kspec4, qspec4, lspec4, lspec4],
        out_specs=[kspec4, kspec4],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, sk_p, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, g, lse, delta)

    return (dq[:, :, :sq, :], dk[:, :, :sk, :], dv[:, :, :sk, :])


# ---------------------------------------------------------------------------
# Chunked (blockwise) attention in pure jax — CPU fallback path
# ---------------------------------------------------------------------------

def _chunked_attention(q, k, v, causal, sm_scale, block_q=512, block_k=512):
    """(B,H,S,D) exact attention via online softmax over k/v blocks.
    jax.checkpoint per block => O(S·D) residuals under AD."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    sq_p = (sq + bq - 1) // bq * bq
    sk_p = (sk + bk - 1) // bk * bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    nq, nk = sq_p // bq, sk_p // bk

    qb = qp.reshape(b, h, nq, bq, d)
    kb = kp.reshape(b, h, nk, bk, d)
    vb = vp.reshape(b, h, nk, bk, d)

    @jax.checkpoint
    def block(qi, kj, vj, iq, jk):
        prec = _prec(qi.dtype)
        qf = qi * jnp.asarray(sm_scale, qi.dtype)
        s = jnp.einsum("...qd,...kd->...qk", qf, kj,
                       preferred_element_type=jnp.float32,
                       precision=prec)
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + jk * bk
        valid = col < sk
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
            valid = jnp.logical_and(valid, col <= row)
        s = jnp.where(valid, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("...qk,...kd->...qd", p.astype(vj.dtype), vj,
                       preferred_element_type=jnp.float32,
                       precision=prec)
        return m, l, o

    def q_block(iq, qi):
        def kv_step(carry, jk):
            m, l, acc = carry
            mj, lj, oj = block(qi, kb[:, :, jk], vb[:, :, jk], iq, jk)
            m_new = jnp.maximum(m, mj)
            alpha = jnp.exp(m - m_new)
            beta = jnp.exp(mj - m_new)
            l_new = l * alpha + lj * beta
            acc_new = acc * alpha + oj * beta
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, bq, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, bq, 1), jnp.float32)
        a0 = jnp.zeros((b, h, bq, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(nk))
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    outs = jax.lax.map(lambda i: q_block(i, qb[:, :, i]), jnp.arange(nq))
    out = jnp.moveaxis(outs, 0, 2).reshape(b, h, sq_p, d)
    return out[:, :, :sq, :]


# ---------------------------------------------------------------------------
# custom_vjp glue
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, sm_scale, seg_len):
    if jax_compat.on_tpu():
        return _flash_fwd_pallas(q, k, v, causal, sm_scale,
                                 save_lse=False, seg_len=seg_len)[0]
    assert seg_len is None  # the GQA fold is only taken on the TPU path
    return _chunked_attention(q, k, v, causal, sm_scale)


def _flash_fwd_rule(q, k, v, causal, sm_scale, seg_len):
    if jax_compat.on_tpu():
        out, lse = _flash_fwd_pallas(q, k, v, causal, sm_scale,
                                     seg_len=seg_len)
        return out, (q, k, v, out, lse)
    assert seg_len is None
    return _chunked_attention(q, k, v, causal, sm_scale), (q, k, v, None,
                                                          None)


def _flash_bwd_rule(causal, sm_scale, seg_len, res, g):
    q, k, v, o, lse = res
    if lse is not None:
        return _flash_bwd_pallas(q, k, v, o, lse, g, causal, sm_scale,
                                 seg_len=seg_len)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _chunked_attention(q_, k_, v_, causal, sm_scale),
        q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention_bhsd(q, k, v, causal=False, sm_scale=None):
    """(B, H, S, D) entry. GQA: kv head count may divide q head count.

    On TPU, GQA takes the fold path: q (B, G*Hk, S, D) is bitcast to
    (B, Hk, G*S, D) — adjacent q-heads share a kv head — so the kernels
    stream each kv head once instead of G repeated copies, and dk/dv come
    out per-kv-head directly (no XLA group-reduction). Requires the
    segment length S to align with the q block sizes; otherwise falls
    back to jnp.repeat of k/v.

    Under a device mesh the Pallas path runs per shard (batch over
    dp/fsdp, heads over mp — kernels/sharding.py): XLA cannot partition
    the Mosaic call itself.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    local = functools.partial(_flash_local, causal=causal,
                              sm_scale=sm_scale)
    mesh = _sharding.kernel_mesh() if jax_compat.on_tpu() else None
    if mesh is None:
        return local(q, k, v)
    spec = P(_sharding.batch_axes(mesh, q.shape[0]),
             _sharding.head_axis(mesh, q.shape[1], k.shape[1]), None, None)
    return _sharding.per_shard(local, mesh, (spec, spec, spec),
                               spec)(q, k, v)


def _flash_local(q, k, v, causal, sm_scale):
    hq, hk = q.shape[1], k.shape[1]
    if hk != hq:
        rep = hq // hk
        b, _, s, d = q.shape
        bq_f = min(_BLOCK_Q, rep * s)
        bq_b = min(_BLOCK_Q_BWD, rep * s)
        if (jax_compat.on_tpu() and hq % hk == 0 and s % bq_f == 0
                and s % bq_b == 0):
            qf = q.reshape(b, hk, rep * s, d)
            out = _flash(qf, k, v, causal, sm_scale, s)
            return out.reshape(b, hq, s, d)
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return _flash(q, k, v, causal, sm_scale, None)


def flash_attention_bshd(q, k, v, causal=False, sm_scale=None):
    """Paddle layout (B, S, H, D) (reference flash_attention surface)."""
    out = flash_attention_bhsd(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2), causal=causal, sm_scale=sm_scale)
    return jnp.swapaxes(out, 1, 2)
