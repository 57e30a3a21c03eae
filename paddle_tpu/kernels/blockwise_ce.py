"""Blockwise cross-entropy: hidden -> vocab projection fused with
softmax-CE, streamed over row (sequence) chunks and vocab blocks.

The train path's memory cap (BENCH r04-r05): `models/llama.py` reshapes
the lm_head output to `[-1, vocab]` and hands a [B*S, V] logits tensor
to cross_entropy — at Llama-3 vocab (128256) that tensor dwarfs every
activation and bounds the batch size. The reference keeps a hand-written
fusion library for exactly this (paddle/phi/kernels/fusion/gpu/,
fused_linear + softmax-CE epilogues); the TPU-native equivalent is this
module: the final hidden->vocab matmul and the softmax-CE reduction run
chunk by chunk, so neither forward NOR backward ever materializes the
[B*S, V] logits — the flash-attention treatment (recompute from a saved
row statistic) applied to the loss.

Math (identical to nn/functional/loss.py `_ce_mean_fused`, per row):

    lse_i    = logsumexp_v(x_i . W[:, v])
    picked_i = x_i . W[:, labels_i]
    loss     = sum_i valid_i * (lse_i - picked_i) / max(sum valid, 1)

Forward saves ONLY the per-row lse (N f32) + the valid count; backward
recomputes each chunk's logits from (x, W) and emits

    dlogits = (softmax - onehot) * g * valid / count

chunk by chunk, contracting immediately into dx (chunk, D) and a
running f32 dW accumulator — dlogits never exists at [N, V] either.

Two execution paths behind one `custom_vjp` (the paged-attention
pattern):

- **Pallas (TPU)**: grid (row-chunk, vocab-block) kernels; x chunks and
  W blocks stream through VMEM, the online-softmax state (m, l, picked)
  rides VMEM scratch across the vocab axis; backward is a dx kernel
  (vocab-fast grid, dx scratch) + a dW kernel (row-fast grid, (D, bv)
  f32 scratch) — the flash `_bwd_dkv_kernel` shape. Off-TPU a forced
  `kernel="pallas"` runs `interpret=True` (tier-1 parity coverage).
- **jnp (CPU / fallback)**: `jax.lax.scan` over row chunks (optionally
  an inner `fori_loop` over vocab blocks with online max) — the same
  math, same O(chunk x vocab_block) peak intermediate, XLA-fused.

Shape contract mirrors `paged_attention.decode_shape_problems`: the
AUTO path gates on `ce_shape_problems`, a forced "pallas" turns the
reasons into a ValueError naming every misaligned dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from paddle_tpu.core import jax_compat
from paddle_tpu.core.jax_compat import tpu_compiler_params
from paddle_tpu.kernels import sharding as _sharding

__all__ = ["blockwise_ce_loss", "ce_shape_problems", "check_ce_shapes",
           "logits_bytes_saved", "dense_logits_bytes"]

_NEG_INF = -1e30

# Pallas vocab-block default: W block (D, bv) bf16 + the (D, bv) f32 dW
# scratch must co-reside in VMEM (at D=4096, bv=512: 4MB + 8MB — tight
# but inside the 16MB budget with the x chunk)
_BLOCK_V = 512


def _prec(dtype):
    return (jax.lax.Precision.DEFAULT
            if dtype in (jnp.bfloat16, jnp.float16)
            else jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# shape contract (decode_shape_problems style)
# ---------------------------------------------------------------------------

def ce_shape_problems(n, d, v, chunk, vocab_block=0, interpret=False):
    """Reasons this (n, d, v, chunk, vocab_block) geometry cannot take
    the Pallas blockwise-CE kernels; empty list = supported. The AUTO
    path gates on this, the forced path turns the reasons into a
    ValueError (the `check_decode_shapes` contract)."""
    problems = []
    if chunk < 1:
        problems.append(f"chunk must be >= 1 (got {chunk})")
    if vocab_block < 0:
        problems.append(f"vocab_block must be >= 0 (got {vocab_block})")
    if not interpret:
        # compiled Mosaic wants tileable blocks: the x chunk is
        # (chunk, d), the W block (d, bv) — f32/bf16 sublane + 128-lane
        if d % 128 != 0:
            problems.append(f"hidden % 128 == 0 required on TPU "
                            f"(got d={d})")
        if chunk % 8 != 0:
            problems.append(f"chunk % 8 == 0 required on TPU "
                            f"(got chunk={chunk})")
        bv = vocab_block or _BLOCK_V
        if bv % 128 != 0:
            problems.append(f"vocab_block % 128 == 0 required on TPU "
                            f"(got vocab_block={bv})")
    return problems


def check_ce_shapes(n, d, v, chunk, vocab_block=0, interpret=False):
    """Raise a descriptive ValueError naming every misaligned dim when
    the Pallas path cannot run; no-op when supported."""
    problems = ce_shape_problems(n, d, v, chunk, vocab_block, interpret)
    if problems:
        raise ValueError(
            "blockwise_ce_loss: shapes cannot take the Pallas kernels "
            "— " + "; ".join(problems)
            + '; use kernel="jnp" for the lax.scan fallback')


# ---------------------------------------------------------------------------
# memory accounting (telemetry / bench)
# ---------------------------------------------------------------------------

def dense_logits_bytes(n_rows, vocab, itemsize=2):
    """Bytes of the [N, V] logits tensor the dense loss path
    materializes (forward AND as the dlogits cotangent in backward)."""
    return int(n_rows) * int(vocab) * int(itemsize)


def logits_bytes_saved(n_rows, vocab, chunk, vocab_block=0, itemsize=2):
    """Dense-path logits bytes minus the blockwise path's peak
    O(chunk x vocab_block) logits-shaped intermediate — the
    `train.loss.logits_bytes_saved` gauge."""
    if chunk <= 0:
        return 0
    peak = min(int(chunk), int(n_rows)) * (
        min(int(vocab_block), int(vocab)) if vocab_block else int(vocab)
    ) * int(itemsize)
    return max(0, dense_logits_bytes(n_rows, vocab, itemsize) - peak)


# ---------------------------------------------------------------------------
# jnp fallback: lax.scan over row chunks (+ optional vocab fori)
# ---------------------------------------------------------------------------

def _pad_rows(x, labels, chunk, ignore_index):
    """Pad N up to a chunk multiple: zero rows + ignore_index labels
    (padded rows contribute nothing to loss, count, or gradients)."""
    n = x.shape[0]
    n_pad = -(-n // chunk) * chunk
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        labels = jnp.pad(labels, (0, n_pad - n),
                         constant_values=ignore_index)
    return x, labels, n_pad


def _chunk_lse_picked(xc, w, labels_c, vocab_block, v_valid):
    """One row chunk's (lse, picked), both f32 (chunk,). With
    vocab_block > 0 the (chunk, V) logits never exist — an inner
    fori_loop keeps the online max/sum state and streams (chunk, bv)
    score blocks (W pre-padded by the caller when V % bv != 0;
    `v_valid` = the real vocab, padded columns masked)."""
    v = v_valid
    prec = _prec(xc.dtype)
    if not vocab_block:
        s = jax.lax.dot_general(
            xc, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        m = jnp.max(s, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(s - m[:, None]), axis=-1))
        picked = jnp.take_along_axis(
            s, labels_c[:, None].astype(jnp.int32), axis=-1)[:, 0]
        return lse, picked
    bv = vocab_block
    nv = w.shape[1] // bv          # caller padded V to a bv multiple
    c = xc.shape[0]

    def vb_step(j, carry):
        m, l, picked = carry
        wj = jax.lax.dynamic_slice(w, (0, j * bv), (w.shape[0], bv))
        s = jax.lax.dot_general(
            xc, wj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        col = jax.lax.broadcasted_iota(jnp.int32, (c, bv), 1) + j * bv
        s_m = jnp.where(col < v, s, _NEG_INF)     # v = VALID vocab
        m_new = jnp.maximum(m, jnp.max(s_m, axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(s_m - m_new[:, None]), axis=-1)
        picked = picked + jnp.sum(
            jnp.where(col == labels_c[:, None].astype(jnp.int32),
                      s, 0.0), axis=-1)
        return m_new, l, picked

    m0 = jnp.full((c,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((c,), jnp.float32)
    p0 = jnp.zeros((c,), jnp.float32)
    m, l, picked = jax.lax.fori_loop(0, nv, vb_step, (m0, l0, p0))
    return m + jnp.log(jnp.maximum(l, 1e-30)), picked


def _pad_vocab(w, vocab_block):
    if not vocab_block:
        return w
    v = w.shape[1]
    v_pad = -(-v // vocab_block) * vocab_block
    if v_pad != v:
        w = jnp.pad(w, ((0, 0), (0, v_pad - v)))
    return w


def _fwd_jnp(x, w, labels, chunk, vocab_block, ignore_index):
    n = x.shape[0]
    xp, lp, n_pad = _pad_rows(x, labels, chunk, ignore_index)
    wp = _pad_vocab(w, vocab_block)
    nc = n_pad // chunk
    xb = xp.reshape(nc, chunk, x.shape[1])
    lb = lp.reshape(nc, chunk)
    # valid vocab stays w.shape[1]: padded columns are masked inside
    v = w.shape[1]

    def row_step(carry, xl):
        loss_sum, count = carry
        xc, lc = xl
        lse, picked = _chunk_lse_picked(xc, wp, lc, vocab_block, v)
        valid = lc != ignore_index
        loss_sum = loss_sum + jnp.sum(jnp.where(valid, lse - picked, 0.0))
        count = count + jnp.sum(valid.astype(jnp.float32))
        return (loss_sum, count), lse

    (loss_sum, count), lses = jax.lax.scan(
        row_step, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xb, lb))
    count = jnp.maximum(count, 1.0)
    return loss_sum / count, lses, count


def _bwd_jnp(x, w, labels, lses, count, g, chunk, vocab_block,
             ignore_index):
    n, d = x.shape
    v = w.shape[1]
    xp, lp, n_pad = _pad_rows(x, labels, chunk, ignore_index)
    wp = _pad_vocab(w, vocab_block)
    nc = n_pad // chunk
    xb = xp.reshape(nc, chunk, d)
    lb = lp.reshape(nc, chunk)
    prec = _prec(x.dtype)
    gscale = g / count

    def row_step(dw_acc, xl):
        xc, lc, lse_c = xl
        scale = jnp.where(lc != ignore_index, gscale, 0.0)     # (chunk,)
        lab = lc[:, None].astype(jnp.int32)
        if not vocab_block:
            s = jax.lax.dot_general(
                xc, wp, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)
            p = jnp.exp(s - lse_c[:, None])
            onehot = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                      == lab)
            dvals = ((p - onehot.astype(jnp.float32))
                     * scale[:, None]).astype(xc.dtype)
            dx_c = jax.lax.dot_general(
                dvals, wp, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)
            dw_acc = dw_acc + jax.lax.dot_general(
                xc, dvals, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)
            return dw_acc, dx_c
        bv = vocab_block
        nv = wp.shape[1] // bv
        c = xc.shape[0]

        def vb_step(j, carry):
            dx_c, dw_a = carry
            wj = jax.lax.dynamic_slice(wp, (0, j * bv), (d, bv))
            s = jax.lax.dot_general(
                xc, wj, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)
            col = jax.lax.broadcasted_iota(jnp.int32, (c, bv), 1) + j * bv
            p = jnp.where(col < v, jnp.exp(s - lse_c[:, None]), 0.0)
            dvals = ((p - (col == lab).astype(jnp.float32))
                     * scale[:, None]).astype(xc.dtype)
            dx_c = dx_c + jax.lax.dot_general(
                dvals, wj, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)
            dw_j = jax.lax.dot_general(
                xc, dvals, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)
            dw_a = jax.lax.dynamic_update_slice(
                dw_a, jax.lax.dynamic_slice(
                    dw_a, (0, j * bv), (d, bv)) + dw_j, (0, j * bv))
            return dx_c, dw_a

        dx_c, dw_acc = jax.lax.fori_loop(
            0, nv, vb_step, (jnp.zeros((c, d), jnp.float32), dw_acc))
        return dw_acc, dx_c

    dw0 = jnp.zeros((d, wp.shape[1]), jnp.float32)
    dw, dxs = jax.lax.scan(row_step, dw0, (xb, lb, lses))
    dx = dxs.reshape(n_pad, d)[:n].astype(x.dtype)
    return dx, dw[:, :v].astype(w.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _ce_fwd_kernel(x_ref, w_ref, lab_ref, lse_ref, pk_ref,
                   m_scr, l_scr, pk_scr, *, block_v, v_valid, nv):
    """Grid (row-chunk i, vocab-block j), j fastest. x chunk stays
    resident per i (constant block index elides the DMA); W blocks
    stream; the online-softmax state (m, l) and the picked-logit
    accumulator live in VMEM scratch; lse/picked flush at the last j.

    Everything stays 2D in the flash-kernel idiom (no 1D vectors, no
    int relayouts on TPU): labels arrive as an f32 (1, chunk) row —
    exact for any vocab < 2^24 — and transpose like the flash lse."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        pk_scr[...] = jnp.zeros_like(pk_scr)

    c = x_ref.shape[0]
    prec = _prec(x_ref.dtype)
    s = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)  # (c, bv)
    col = (jax.lax.broadcasted_iota(jnp.int32, (c, block_v), 1)
           + j * block_v).astype(jnp.float32)
    s_m = jnp.where(col < v_valid, s, _NEG_INF)
    lab_t = lab_ref[...].T                                # (c, 1) f32
    m = m_scr[:, :1]
    m_new = jnp.maximum(m, jnp.max(s_m, axis=-1, keepdims=True))
    l_scr[...] = (l_scr[...] * jnp.exp(m - m_new)
                  + jnp.sum(jnp.exp(s_m - m_new), axis=-1,
                            keepdims=True))
    pk_scr[...] += jnp.sum(
        jnp.where(col == lab_t, s, 0.0), axis=-1, keepdims=True)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == nv - 1)
    def _store():
        lse = m_scr[:, :1] + jnp.log(jnp.maximum(l_scr[:, :1], 1e-30))
        lse_ref[...] = lse.T                              # (1, chunk)
        pk_ref[...] = pk_scr[:, :1].T


def _ce_dx_kernel(x_ref, w_ref, lab_ref, lse_ref, sc_ref, dx_ref,
                  acc_scr, *, block_v, v_valid, nv):
    """dx: grid (i, j) j fastest; dlogits recomputed per (c, bv) block
    from the saved lse, contracted into the (c, D) dx scratch; store at
    the last j. dlogits never exists beyond one block."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    c = x_ref.shape[0]
    prec = _prec(x_ref.dtype)
    s = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)
    col = (jax.lax.broadcasted_iota(jnp.int32, (c, block_v), 1)
           + j * block_v).astype(jnp.float32)
    lse = lse_ref[...].T                                  # (c, 1)
    p = jnp.where(col < v_valid, jnp.exp(s - lse), 0.0)
    onehot = (col == lab_ref[...].T).astype(jnp.float32)
    dvals = ((p - onehot) * sc_ref[...].T).astype(x_ref.dtype)
    acc_scr[...] += jax.lax.dot_general(
        dvals, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)

    @pl.when(j == nv - 1)
    def _store():
        dx_ref[...] = acc_scr[...].astype(dx_ref.dtype)


def _ce_dw_kernel(x_ref, w_ref, lab_ref, lse_ref, sc_ref, dw_ref,
                  acc_scr, *, block_v, v_valid, nr):
    """dW: grid (j, i) i fastest; the (D, bv) f32 accumulator sweeps
    every row chunk for one W block and flushes once at the last i (the
    flash `_bwd_dkv_kernel` shape)."""
    jv = pl.program_id(0)
    ir = pl.program_id(1)

    @pl.when(ir == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    c = x_ref.shape[0]
    prec = _prec(x_ref.dtype)
    s = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)
    col = (jax.lax.broadcasted_iota(jnp.int32, (c, block_v), 1)
           + jv * block_v).astype(jnp.float32)
    p = jnp.where(col < v_valid, jnp.exp(s - lse_ref[...].T), 0.0)
    onehot = (col == lab_ref[...].T).astype(jnp.float32)
    dvals = ((p - onehot) * sc_ref[...].T).astype(x_ref.dtype)
    acc_scr[...] += jax.lax.dot_general(
        x_ref[...], dvals, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)

    @pl.when(ir == nr - 1)
    def _store():
        dw_ref[...] = acc_scr[...].astype(dw_ref.dtype)


def _compiler_params(chunk, d, bv, itemsize):
    """Mosaic params for the three CE kernels, with the scoped-VMEM
    limit stated: at chunk 512 x hidden 2048 the double-buffered x
    chunk, W block and output block plus the f32 accumulator already
    pass the compiler's 16 MiB default (libtpu refused the dx kernel at
    16.78M), so the limit is computed from the blocks the kernel
    actually holds — pipelined inputs/outputs twice, the f32 scratch,
    and the (chunk, bv) f32 score/probability temporaries — plus a
    quarter of headroom."""
    big = max(chunk * d, d * bv)
    need = (2 * (chunk * d + d * bv + big) * itemsize   # x, W, out blocks
            + big * 4                                   # f32 accumulator
            + 6 * chunk * bv * 4)                       # score temporaries
    return tpu_compiler_params(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=int(need * 1.25))


def _row_spec(chunk, index_map):
    """BlockSpec for one (1, chunk) f32 row of a per-chunk vector
    (labels, lse, picked, scale). The arrays ride as (nc, 1, chunk) with
    the chunk axis squeezed, so the block's last two dims EQUAL the
    array's: a (1, chunk) block of an (nc, chunk) array breaks Mosaic's
    8-sublane block rule."""
    return pl.BlockSpec((None, 1, chunk), index_map)


def _fwd_pallas(x, w, labels, chunk, vocab_block, ignore_index,
                interpret):
    n, d = x.shape
    v = w.shape[1]
    bv = vocab_block or _BLOCK_V
    bv = min(bv, -(-v // 128) * 128) if not interpret else min(bv, v)
    xp, lp, n_pad = _pad_rows(x, labels, chunk, ignore_index)
    v_pad = -(-v // bv) * bv
    wp = jnp.pad(w, ((0, 0), (0, v_pad - v))) if v_pad != v else w
    nc, nv = n_pad // chunk, v_pad // bv
    lab2 = lp.reshape(nc, chunk).astype(jnp.int32)
    # labels ride into the kernel as f32 rows (exact below 2^24): all
    # in-kernel compares stay f32 2D — no int relayouts for Mosaic
    labf = lab2.astype(jnp.float32)
    row = _row_spec(chunk, lambda i, j: (i, 0, 0))

    lse, picked = pl.pallas_call(
        functools.partial(_ce_fwd_kernel, block_v=bv, v_valid=v, nv=nv),
        grid=(nc, nv),
        in_specs=[
            pl.BlockSpec((chunk, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            row,
        ],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((nc, 1, chunk), jnp.float32),
                   jax.ShapeDtypeStruct((nc, 1, chunk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((chunk, 8), jnp.float32),
                        pltpu.VMEM((chunk, 8), jnp.float32),
                        pltpu.VMEM((chunk, 8), jnp.float32)],
        compiler_params=_compiler_params(chunk, d, bv, x.dtype.itemsize),
        interpret=interpret,
        name="blockwise_ce_fwd",
    )(xp.reshape(nc * chunk, d), wp, labf[:, None, :])
    lse, picked = lse[:, 0, :], picked[:, 0, :]
    valid = lab2 != ignore_index
    count = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    loss = jnp.sum(jnp.where(valid, lse - picked, 0.0)) / count
    return loss, lse, count


def _bwd_pallas(x, w, labels, lses, count, g, chunk, vocab_block,
                ignore_index, interpret):
    n, d = x.shape
    v = w.shape[1]
    bv = vocab_block or _BLOCK_V
    bv = min(bv, -(-v // 128) * 128) if not interpret else min(bv, v)
    xp, lp, n_pad = _pad_rows(x, labels, chunk, ignore_index)
    v_pad = -(-v // bv) * bv
    wp = jnp.pad(w, ((0, 0), (0, v_pad - v))) if v_pad != v else w
    nc, nv = n_pad // chunk, v_pad // bv
    lab2 = lp.reshape(nc, chunk).astype(jnp.int32)
    labf = lab2.astype(jnp.float32)
    scale = jnp.where(lab2 != ignore_index, g / count, 0.0).astype(
        jnp.float32)
    x2 = xp.reshape(nc * chunk, d)
    rows = [a[:, None, :] for a in (labf, lses, scale)]
    row = _row_spec(chunk, lambda i, j: (i, 0, 0))

    dx = pl.pallas_call(
        functools.partial(_ce_dx_kernel, block_v=bv, v_valid=v, nv=nv),
        grid=(nc, nv),
        in_specs=[
            pl.BlockSpec((chunk, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            row, row, row,
        ],
        out_specs=pl.BlockSpec((chunk, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((chunk, d), jnp.float32)],
        compiler_params=_compiler_params(chunk, d, bv, x.dtype.itemsize),
        interpret=interpret,
        name="blockwise_ce_dx",
    )(x2, wp, *rows)

    row = _row_spec(chunk, lambda jv, ir: (ir, 0, 0))
    dw = pl.pallas_call(
        functools.partial(_ce_dw_kernel, block_v=bv, v_valid=v, nr=nc),
        grid=(nv, nc),
        in_specs=[
            pl.BlockSpec((chunk, d), lambda jv, ir: (ir, 0)),
            pl.BlockSpec((d, bv), lambda jv, ir: (0, jv)),
            row, row, row,
        ],
        out_specs=pl.BlockSpec((d, bv), lambda jv, ir: (0, jv)),
        out_shape=jax.ShapeDtypeStruct((d, v_pad), w.dtype),
        scratch_shapes=[pltpu.VMEM((d, bv), jnp.float32)],
        compiler_params=_compiler_params(chunk, d, bv, x.dtype.itemsize),
        interpret=interpret,
        name="blockwise_ce_dw",
    )(x2, wp, *rows)
    return dx[:n], dw[:, :v]


# ---------------------------------------------------------------------------
# custom_vjp glue + public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _bce(x, w, labels, chunk, vocab_block, ignore_index, use_pallas,
         interpret):
    loss, _ = _bce_fwd(x, w, labels, chunk, vocab_block, ignore_index,
                       use_pallas, interpret)
    return loss


def _bce_fwd(x, w, labels, chunk, vocab_block, ignore_index, use_pallas,
             interpret):
    if use_pallas:
        loss, lses, count = _fwd_pallas(x, w, labels, chunk, vocab_block,
                                        ignore_index, interpret)
    else:
        loss, lses, count = _fwd_jnp(x, w, labels, chunk, vocab_block,
                                     ignore_index)
    return loss, (x, w, labels, lses, count)


def _bce_bwd(chunk, vocab_block, ignore_index, use_pallas, interpret,
             res, g):
    x, w, labels, lses, count = res
    g = jnp.asarray(g, jnp.float32)
    if use_pallas:
        dx, dw = _bwd_pallas(x, w, labels, lses, count, g, chunk,
                             vocab_block, ignore_index, interpret)
    else:
        dx, dw = _bwd_jnp(x, w, labels, lses, count, g, chunk,
                          vocab_block, ignore_index)
    return dx, dw, None


_bce.defvjp(_bce_fwd, _bce_bwd)


def blockwise_ce_loss(x, w, labels, *, chunk, vocab_block=0,
                      ignore_index=-100, kernel=None, interpret=False):
    """Mean softmax cross-entropy of `x @ w` against int `labels`,
    without materializing the [N, V] logits in forward or backward.

    x: (N, D) hidden rows; w: (D, V) projection (tied-embedding callers
    transpose first); labels: (N,) int, `ignore_index` rows excluded
    from the mean (matching `F.cross_entropy(..., reduction="mean")`).
    chunk: rows per streamed block — the peak logits-shaped
    intermediate is (chunk, vocab_block or V). N not divisible by
    `chunk` and V not divisible by `vocab_block` are padded + masked.

    kernel: None = auto (Pallas on TPU when `ce_shape_problems` is
    empty, the lax.scan fallback otherwise); "pallas" forces the
    kernels (off-TPU via interpret mode — the paged-attention parity
    pattern); "jnp" forces the fallback. Returns a scalar f32 loss;
    differentiable in (x, w) via a custom_vjp that recomputes each
    chunk's logits from the saved row lse.
    """
    if kernel not in (None, "pallas", "jnp"):
        raise ValueError(f"kernel must be None|'pallas'|'jnp', "
                         f"got {kernel!r}")
    if x.ndim != 2 or w.ndim != 2 or labels.ndim != 1:
        raise ValueError(
            f"blockwise_ce_loss wants x (N, D), w (D, V), labels (N,); "
            f"got {x.shape}, {w.shape}, {labels.shape}")
    if x.shape[1] != w.shape[0] or x.shape[0] != labels.shape[0]:
        raise ValueError(
            f"shape mismatch: x {x.shape}, w {w.shape}, "
            f"labels {labels.shape}")
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1 (got {chunk})")
    n, d = x.shape
    v = w.shape[1]
    if kernel == "pallas":
        interpret = interpret or not jax_compat.on_tpu()
        check_ce_shapes(n, d, v, chunk, vocab_block, interpret)
        use_pallas = True
    elif kernel == "jnp":
        use_pallas = False
    else:
        use_pallas = (jax_compat.on_tpu() and not ce_shape_problems(
            n, d, v, chunk, vocab_block, interpret))
    labels = jnp.asarray(labels).astype(jnp.int32)

    def local(xl, wl, ll):
        return _bce(xl, wl, ll, int(chunk), int(vocab_block),
                    int(ignore_index), use_pallas, bool(interpret))

    mesh = _sharding.kernel_mesh() if use_pallas else None
    if mesh is None:
        return local(x, w, labels)
    # rows are independent: each shard takes its slice of them against
    # the whole W and returns (loss sum, valid count); the mean is
    # taken over the sums (kernels/sharding.py)
    rows = _sharding.row_axes(mesh, n)

    def shard(xl, wl, ll):
        cnt = jnp.sum(ll != ignore_index).astype(jnp.float32)
        return (local(xl, wl, ll) * cnt)[None], cnt[None]

    sums, counts = _sharding.per_shard(
        shard, mesh, (P(rows, None), P(None, None), P(rows)),
        (P(rows), P(rows)))(x, w, labels)
    return jnp.sum(sums) / jnp.maximum(jnp.sum(counts), 1.0)
