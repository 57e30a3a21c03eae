"""Context parallelism: ring attention + Ulysses (all-to-all) attention.

The reference has NO ring/Ulysses attention (SURVEY.md §5 long-context:
"No ring attention, no blockwise attention, no Ulysses all-to-all attention
exists in this tree" — verified); it only ships the 'sep' mesh axis +
Megatron-SP scatter/gather utils and leaves attention-side handling to
model code. This module ADDS the capability the north star needs:

- Ulysses: activations arrive seq-sharded over the 'sp' axis; one
  all-to-all turns seq-sharding into head-sharding, full-sequence flash
  attention runs per local head group, a second all-to-all restores
  seq-sharding. Collective volume: 2 x activations over ICI.
- Ring: K/V shards rotate around the 'sp' ring via `ppermute` while each
  device's Q shard accumulates online-softmax partial results — attention
  memory O(S_local^2) never materialises; comm overlaps compute steps.

Both are expressed with `jax.shard_map` over ONLY the 'sp' axis
(axis_names={'sp'}): dp/fsdp/mp stay in GSPMD-auto mode, so these compose
with the rest of the 4D plan inside one jit program.
"""
from __future__ import annotations

import functools
import math
from contextlib import contextmanager

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.core.jax_compat import axis_size, shard_map
from paddle_tpu.kernels.flash_attention import (
    _LSE_ROWS, _NEG_INF, _chunked_attention, flash_attention_bhsd)


# ---------------------------------------------------------------------------
# ring attention core (operates on LOCAL shards inside shard_map)
# ---------------------------------------------------------------------------

def _merge_block(q, kj, vj, m, l, acc, sm_scale, causal, row_off, col_off):
    """Online-softmax merge of one K/V block into the running (m, l, acc).
    q: (B,H,Sq,D); kj/vj: (B,H,Sk,D); offsets are global positions."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * sm_scale,
                   kj.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    if causal:
        sq, sk = q.shape[2], kj.shape[2]
        row = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0) + row_off
        col = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1) + col_off
        s = jnp.where(col <= row, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum(
        "bhqk,bhkd->bhqd", p, vj.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


# -- flash-kernel ring (r5): per-shard Pallas flash + base-2 lse merge ------
# The jnp _merge_block ring materializes the full (S_local, S_shard)
# score matrix per step — ~8x slower than the flash kernel at S=8k
# (round 5, on a side bench since deleted). This path runs the SAME
# Pallas kernels the single-chip flash path uses, merging per-shard partials by their
# base-2 lse; backward is a second ring rotating (k, v, dk, dv)
# together so each shard's grads ride home with it.

_RING_BQ = 512   # pinned blocks: lax.switch branches must agree on the
_RING_BK = 512   # padded lse width, so no per-branch autotune here


def _ring_flash_plan(hq, hk, sq, sk, d):
    """THE fold/flash decision, shared by the wrapper and the local
    entry (they used to re-derive it and drift). Returns None (shapes
    can't take the kernels), ("plain", None), or ("fold", seg_len) —
    seg_len = the local q length; bq = min(_RING_BQ, seg_len), so the
    base alignment check below already covers the folded layout."""
    if not (sq % min(_RING_BQ, sq) == 0 and sk % min(_RING_BK, sk) == 0
            and sq >= 8 and sk >= 8 and d % 8 == 0):
        return None
    if hq == hk:
        return ("plain", None)
    if hq % hk:
        return None
    return ("fold", sq)


def _ring_flash_shapes_ok(q, k):
    return _ring_flash_plan(q.shape[1], k.shape[1], q.shape[2],
                            k.shape[2], q.shape[3]) is not None


def _ring_flash_step_fwd(q, k_cur, v_cur, mode, sm_scale, interpret,
                         seg_len=None):
    """mode: 0 = unmasked shard, 1 = aligned-diagonal (causal), 2 =
    future shard (fully masked -> zero weight). seg_len: GQA fold — q
    carries G concatenated segments of this length (causal masking is
    per-segment, exactly the single-chip fold)."""
    from paddle_tpu.kernels.flash_attention import _flash_fwd_pallas
    bq = min(_RING_BQ, seg_len if seg_len else q.shape[2])
    bk = min(_RING_BK, k_cur.shape[2])

    def run(causal):
        def f():
            return _flash_fwd_pallas(q, k_cur, v_cur, causal, sm_scale,
                                     block_q=bq, block_k=bk,
                                     interpret=interpret,
                                     seg_len=seg_len if causal else None)
        return f

    def skip():
        b, h, sq, d = q.shape
        return (jnp.zeros((b, h, sq, d), q.dtype),
                jnp.full((b, h, _LSE_ROWS, sq), _NEG_INF, jnp.float32))

    return jax.lax.switch(mode, [run(False), run(True), skip])


def _ring_flash_fwd_scan(q, k, v, axis_name, causal, sm_scale,
                         interpret, seg_len=None):
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, j):
        acc, lse_acc, k_cur, v_cur = carry
        src = (idx - j) % n
        if causal:
            mode = jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2))
        else:
            mode = jnp.zeros((), jnp.int32)
        o_j, lse_j = _ring_flash_step_fwd(q, k_cur, v_cur, mode,
                                          sm_scale, interpret, seg_len)
        a = lse_acc[:, :, 0, :sq]                      # (b, h, sq) base-2
        bj = lse_j[:, :, 0, :sq]
        new = jnp.logaddexp2(a, bj)
        w_old = jnp.exp2(a - new)[..., None]
        w_new = jnp.exp2(bj - new)[..., None]
        acc = acc * w_old + o_j.astype(jnp.float32) * w_new
        lse_full = jnp.broadcast_to(new[:, :, None, :],
                                    lse_acc.shape)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (acc, lse_full, k_nxt, v_nxt), None

    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    lse0 = jnp.full((b, h, _LSE_ROWS, sq), _NEG_INF, jnp.float32)
    (acc, lse, _, _), _ = jax.lax.scan(
        step, (acc0, lse0, k, v), jnp.arange(n))
    return acc.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis_name, causal, sm_scale, interpret,
                seg_len=None):
    out, _ = _ring_flash_fwd_scan(q, k, v, axis_name, causal, sm_scale,
                                  interpret, seg_len)
    return out


def _ring_flash_fwd_rule(q, k, v, axis_name, causal, sm_scale,
                         interpret, seg_len=None):
    out, lse = _ring_flash_fwd_scan(q, k, v, axis_name, causal, sm_scale,
                                    interpret, seg_len)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd_rule(axis_name, causal, sm_scale, interpret, seg_len,
                         res, g):
    from paddle_tpu.kernels.flash_attention import _flash_bwd_pallas
    q, k, v, o, lse = res
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    bq = min(_RING_BQ, seg_len if seg_len else q.shape[2])
    bk = min(_RING_BK, k.shape[2])

    def one(mode, k_cur, v_cur):
        def run(cflag):
            def f():
                return _flash_bwd_pallas(
                    q, k_cur, v_cur, o, lse, g, cflag, sm_scale,
                    block_q=bq, block_k=bk, interpret=interpret,
                    seg_len=seg_len if cflag else None)
            return f

        def skip():
            return (jnp.zeros_like(q), jnp.zeros_like(k_cur),
                    jnp.zeros_like(v_cur))

        return jax.lax.switch(mode, [run(False), run(True), skip])

    def step(carry, j):
        dq_acc, k_cur, v_cur, dk_acc, dv_acc = carry
        src = (idx - j) % n
        if causal:
            mode = jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2))
        else:
            mode = jnp.zeros((), jnp.int32)
        dq_j, dk_j, dv_j = one(mode, k_cur, v_cur)
        dq_acc = dq_acc + dq_j.astype(jnp.float32)
        dk_acc = dk_acc + dk_j.astype(jnp.float32)
        dv_acc = dv_acc + dv_j.astype(jnp.float32)
        # rotate the shard AND its grad accumulator together: after the
        # final rotation (n total) both are back at the owner
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
        return (dq_acc, k_cur, v_cur, dk_acc, dv_acc), None

    z = jnp.zeros(q.shape, jnp.float32)
    zk = jnp.zeros(k.shape, jnp.float32)
    (dq, _, _, dk, dv), _ = jax.lax.scan(
        step, (z, k, v, zk, jnp.zeros(v.shape, jnp.float32)),
        jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def ring_attention_local(q, k, v, axis_name, causal=True, sm_scale=None,
                         use_flash=None, interpret=False):
    """Local view: q,k,v (B, H, S_local, D), seq dim sharded over
    `axis_name`. Returns local (B, H, S_local, D). On TPU (or with
    interpret=True) block-aligned shapes take the flash-kernel ring;
    others keep the jnp online-softmax merge."""
    from paddle_tpu.core import jax_compat
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if use_flash is None:
        use_flash = ((jax_compat.on_tpu() or interpret)
                     and _ring_flash_shapes_ok(q, k))
    if use_flash:
        plan = _ring_flash_plan(q.shape[1], k.shape[1], q.shape[2],
                                k.shape[2], q.shape[3])
        if plan is None:
            # only reachable with an explicit use_flash=True (the auto
            # path gates on _ring_flash_shapes_ok): name the misaligned
            # dims instead of dying later on an obscure Pallas shape
            # assert inside the kernel
            hq, hk = q.shape[1], k.shape[1]
            sq, sk, d = q.shape[2], k.shape[2], q.shape[3]
            raise ValueError(
                "ring_attention_local(use_flash=True): shapes cannot "
                "take the flash-kernel ring — requires local seq lens "
                f"divisible by their block (q: {sq} % "
                f"{min(_RING_BQ, sq)} == 0, k: {sk} % "
                f"{min(_RING_BK, sk)} == 0), seq >= 8 (q={sq}, k={sk}), "
                f"head_dim % 8 == 0 (got {d}), and q heads divisible "
                f"by kv heads (hq={hq}, hk={hk}); pass use_flash=False "
                "(or None) for the jnp online-softmax ring")
        if plan[0] == "fold":
            # GQA fold (same trick as flash_attention_bhsd): stream each
            # kv head once and halve the ring's ICI volume vs repeating
            hq, hk = q.shape[1], k.shape[1]
            b_, _, sl, d_ = q.shape
            qf = q.reshape(b_, hk, (hq // hk) * sl, d_)
            out = _ring_flash(qf, k, v, axis_name, causal, sm_scale,
                              interpret, sl)
            return out.reshape(b_, hq, sl, d_)
        return _ring_flash(q, k, v, axis_name, causal, sm_scale,
                           interpret)
    if q.shape[1] != k.shape[1]:     # jnp fallback: materialize GQA
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_loc = q.shape[2]
    b, h, _, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        m, l, acc, k_cur, v_cur = carry
        src = (idx - i) % n          # whose shard we hold this step
        m, l, acc = _merge_block(
            q, k_cur, v_cur, m, l, acc, sm_scale, causal,
            row_off=idx * s_loc, col_off=src * s_loc)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (m, l, acc, k_nxt, v_nxt), None

    m0 = jnp.full((b, h, s_loc, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc, 1), jnp.float32)
    a0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    (m, l, acc, _, _), _ = jax.lax.scan(
        step, (m0, l0, a0, k, v), jnp.arange(n))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ulysses_attention_local(q, k, v, axis_name, causal=True, sm_scale=None):
    """Local view: q (B, S_local, H, D) seq-sharded. All-to-all to
    head-sharding, full-seq attention, all-to-all back (DeepSpeed-Ulysses;
    the reference's 'sep' axis ambition, topology.py:184, realised)."""
    n = axis_size(axis_name)
    hq, hk = q.shape[2], k.shape[2]
    if hk != hq:                      # GQA: repeat kv to q heads first
        k = jnp.repeat(k, hq // hk, axis=2)
        v = jnp.repeat(v, hq // hk, axis=2)
    # (B, S/n, H, D) -> (B, S, H/n, D)
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                            split_axis=2, concat_axis=1, tiled=True)
    qg, kg, vg = a2a(q), a2a(k), a2a(v)
    out = flash_attention_bhsd(
        jnp.swapaxes(qg, 1, 2), jnp.swapaxes(kg, 1, 2),
        jnp.swapaxes(vg, 1, 2), causal=causal, sm_scale=sm_scale)
    out = jnp.swapaxes(out, 1, 2)     # (B, S, H/n, D)
    return jax.lax.all_to_all(out, axis_name=axis_name, split_axis=1,
                              concat_axis=2, tiled=True)


# ---------------------------------------------------------------------------
# global-array wrappers (shard_map over the sp axis only)
# ---------------------------------------------------------------------------

def _attn_specs(mesh, axis):
    """Specs for (B, S, H, D) attention inputs in a full-manual shard_map:
    batch over dp/fsdp, seq over the cp axis, heads over mp. Attention is
    embarrassingly parallel over batch and heads, so full-manual over these
    axes is exact; only `axis` carries collectives."""
    names = mesh.axis_names
    batch = tuple(a for a in ("dp", "fsdp") if a in names)
    heads = "mp" if "mp" in names else None
    return P(batch if batch else None, axis, heads, None)


def ring_attention(q, k, v, mesh=None, axis="sp", causal=True,
                   sm_scale=None):
    """Global arrays (B, S, H, D); seq dim sharded over mesh axis `axis`.
    GQA handled by head repeat."""
    from paddle_tpu.distributed.mesh import ProcessMesh
    if isinstance(mesh, ProcessMesh):
        mesh = mesh.jax_mesh
    # GQA handling lives entirely in ring_attention_local: the flash
    # path folds (halved ring ICI volume), the jnp fallback repeats —
    # the wrapper no longer predicts the local decision (it drifted)

    def local(ql, kl, vl):
        out = ring_attention_local(
            jnp.swapaxes(ql, 1, 2), jnp.swapaxes(kl, 1, 2),
            jnp.swapaxes(vl, 1, 2), axis, causal=causal,
            sm_scale=sm_scale)
        return jnp.swapaxes(out, 1, 2)

    spec = _attn_specs(mesh, axis)
    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


def ulysses_attention(q, k, v, mesh=None, axis="sp", causal=True,
                      sm_scale=None):
    """Global arrays (B, S, H, D); seq dim sharded over mesh axis `axis`."""
    from paddle_tpu.distributed.mesh import ProcessMesh
    if isinstance(mesh, ProcessMesh):
        mesh = mesh.jax_mesh
    hq, hk = q.shape[2], k.shape[2]
    if hk != hq:
        k = jnp.repeat(k, hq // hk, axis=2)
        v = jnp.repeat(v, hq // hk, axis=2)
    local = functools.partial(ulysses_attention_local, axis_name=axis,
                              causal=causal, sm_scale=sm_scale)
    spec = _attn_specs(mesh, axis)
    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# model integration: a context that reroutes sdpa to ring/ulysses
# ---------------------------------------------------------------------------

_cp_state = {"mode": None, "mesh": None, "axis": "sp"}


@contextmanager
def context_parallel_guard(mesh, axis="sp", mode="ring"):
    """Inside this context, nn.functional.scaled_dot_product_attention /
    flash_attention route through ring or Ulysses attention over `axis`."""
    prev = dict(_cp_state)
    _cp_state.update(mode=mode, mesh=mesh, axis=axis)
    try:
        yield
    finally:
        _cp_state.update(prev)


def current_context_parallel():
    return dict(_cp_state) if _cp_state["mode"] else None


def dispatch_context_parallel(q, k, v, causal):
    """Called by the attention ops when a guard is active; q,k,v are raw
    arrays (B, S, H, D)."""
    st = _cp_state
    f = ring_attention if st["mode"] == "ring" else ulysses_attention
    return f(q, k, v, mesh=st["mesh"], axis=st["axis"], causal=causal)
