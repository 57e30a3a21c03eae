"""MoE gating + dispatch, TPU-native.

Reference: python/paddle/incubate/distributed/models/moe/ — MoELayer with
gshard/switch/naive gates (gate/gshard_gate.py, switch_gate.py) dispatching
tokens through MoEScatter/MoEGather PyLayers over the global_scatter /
global_gather all-to-all collective ops
(paddle/fluid/operators/collective/global_scatter_op.cc).

TPU-native: the GShard dense-einsum formulation. Gating produces a combine
tensor (T, E, C) and a boolean dispatch mask; dispatch/return are einsums.
When expert weights are sharded over the mesh's 'ep' axis, XLA partitions
the (E, C, D) expert batch over 'ep' and emits the token all-to-all over
ICI itself — the reference's global_scatter/global_gather pair compiled
from shardings instead of hand-written. Capacity is static (XLA needs
static shapes); overflow tokens are dropped (GShard semantics), which the
aux load-balancing loss drives towards zero.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# version-safe axis_size (the bare jax.lax spelling is version-fragile;
# callers wrapping the ep-local entry points in shard_map should import
# it from paddle_tpu.core.jax_compat too)
from paddle_tpu.core.jax_compat import axis_size


def _one_hot(x, n, dtype=jnp.float32):
    return jax.nn.one_hot(x, n, dtype=dtype)


def top2_gating(logits, capacity_factor=1.25, train=True, rng_key=None):
    """GShard top-2 gating (reference: moe/gate/gshard_gate.py).

    logits: (T, E). Returns (combine (T,E,C), dispatch bool (T,E,C),
    aux_loss scalar)."""
    t, e = logits.shape
    c = max(4, int(math.ceil(2 * t * capacity_factor / e)))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    idx1 = jnp.argmax(probs, axis=-1)                       # (T,)
    mask1 = _one_hot(idx1, e)
    probs2 = probs * (1.0 - mask1)
    idx2 = jnp.argmax(probs2, axis=-1)
    mask2 = _one_hot(idx2, e)

    # load-balancing aux loss (GShard eq.: E * sum(me * ce))
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(mask1, axis=0)
    aux_loss = e * jnp.sum(me * ce)

    # positions within each expert's capacity buffer
    pos1 = (jnp.cumsum(mask1, axis=0) - 1.0) * mask1        # (T,E)
    pos2 = ((jnp.cumsum(mask2, axis=0) - 1.0)
            + jnp.sum(mask1, axis=0, keepdims=True)) * mask2
    keep1 = (pos1 < c) & (mask1 > 0)
    keep2 = (pos2 < c) & (mask2 > 0)
    mask1 = mask1 * keep1
    mask2 = mask2 * keep2

    g1 = jnp.sum(probs * mask1, axis=-1)                    # (T,)
    g2 = jnp.sum(probs * mask2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    p1 = jnp.sum(pos1 * mask1, axis=-1).astype(jnp.int32)   # (T,)
    p2 = jnp.sum(pos2 * mask2, axis=-1).astype(jnp.int32)
    in1 = jnp.sum(mask1, axis=-1) > 0
    in2 = jnp.sum(mask2, axis=-1) > 0

    cap1 = _one_hot(p1, c) * in1[:, None]                   # (T,C)
    cap2 = _one_hot(p2, c) * in2[:, None]
    combine = (g1[:, None, None] * mask1[:, :, None] * cap1[:, None, :]
               + g2[:, None, None] * mask2[:, :, None] * cap2[:, None, :])
    dispatch = combine > 0
    return combine, dispatch, aux_loss


def switch_gating(logits, capacity_factor=1.25, train=True, rng_key=None):
    """Switch-Transformer top-1 gating (reference: moe/gate/switch_gate.py),
    with optional multiplicative jitter during training."""
    t, e = logits.shape
    c = max(4, int(math.ceil(t * capacity_factor / e)))
    if train and rng_key is not None:
        noise = jax.random.uniform(rng_key, logits.shape, jnp.float32,
                                   0.98, 1.02)
        logits = logits * noise
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    mask = _one_hot(idx, e)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(mask, axis=0)
    aux_loss = e * jnp.sum(me * ce)

    pos = (jnp.cumsum(mask, axis=0) - 1.0) * mask
    keep = (pos < c) & (mask > 0)
    mask = mask * keep
    gate = jnp.sum(probs * mask, axis=-1)
    p = jnp.sum(pos * mask, axis=-1).astype(jnp.int32)
    inc = jnp.sum(mask, axis=-1) > 0
    cap = _one_hot(p, c) * inc[:, None]
    combine = gate[:, None, None] * mask[:, :, None] * cap[:, None, :]
    return combine, combine > 0, aux_loss


def moe_dispatch(x, dispatch):
    """x (T,D), dispatch (T,E,C) -> expert inputs (E,C,D)."""
    return jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)


def moe_combine(expert_out, combine):
    """expert_out (E,C,D), combine (T,E,C) -> (T,D)."""
    return jnp.einsum("tec,ecd->td", combine.astype(expert_out.dtype),
                      expert_out)


def topk_gating_dropless(logits, k, score_func="softmax", bias=None,
                         route_norm=True, route_scale=1.0):
    """Dropless top-k gating (MegaBlocks/dMoE semantics; the reference's
    gshard gate at moe/gate/gshard_gate.py drops at capacity — this path
    never drops): every token's top-k experts are honored exactly.

    logits (T, E) -> (expert_idx (T,k) int32, gates (T,k) f32
    renormalized over the top-k, aux_loss scalar). The aux loss keeps
    the GShard form (E * sum(me * ce)) with ce = mean assignment
    fraction over all T*k slots — load balance still matters for
    grouped-matmul efficiency even though nothing is dropped.

    The router's options, as a config states them (the defaults are the
    softmax router above): `score_func` "sigmoid" scores each expert on
    its own; `bias` (E,) is added to the scores to CHOOSE the k experts
    and never weighs them (a load-balancing term that no gradient
    trains); `route_norm` renormalises the chosen scores to sum to 1;
    `route_scale` multiplies the gates. Ties go to the lower index."""
    t, e = logits.shape
    if score_func == "sigmoid":
        probs = jax.nn.sigmoid(logits.astype(jnp.float32))
        picked = probs if bias is None else probs + bias.astype(jnp.float32)
        _, idx = jax.lax.top_k(picked, k)                   # (T, k)
        gates = jnp.take_along_axis(probs, idx, axis=-1)
        if route_norm:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        gates = gates * route_scale
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(_one_hot(idx, e), axis=1), axis=0) / k
        return idx.astype(jnp.int32), gates, e * jnp.sum(me * ce)
    if score_func != "softmax" or bias is not None or not route_norm \
            or route_scale != 1.0:
        raise NotImplementedError(
            f"router options score_func={score_func!r}, bias, "
            f"route_norm={route_norm}, route_scale={route_scale}: the "
            "softmax router is renormalised, unbiased and unscaled")
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                    # (T, k)
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(_one_hot(idx, e), axis=1), axis=0) / k
    aux_loss = e * jnp.sum(me * ce)
    return idx.astype(jnp.int32), gates, aux_loss


def moe_dropless_mlp_ep_local(xt, router_w, wg, wu, wd, k, axis_name,
                              token_axes=(), buffer_rows=None):
    """Expert-parallel dropless dMoE — the per-shard body (runs inside
    shard_map over the `axis_name` ('ep') mesh axis).

    Reference mechanism: global_scatter / global_gather all-to-all
    (python/paddle/distributed/utils/moe_utils.py:20,
    incubate/distributed/models/moe/moe_layer.py:263). TPU-native
    realisation: the ragged (token, expert) pair stream is packed into a
    DENSE-PADDED per-destination buffer and exchanged with
    `lax.all_to_all` (XLA's ragged-all-to-all is not available on every
    backend; dense padding keeps shapes static, which XLA needs anyway).

    xt: (T_local, D) this shard's tokens. router_w: (D, E) replicated.
    wg/wu: (E_local, D, F), wd: (E_local, F, D) — expert dim already
    sharded over `axis_name`. Tokens route by global expert id; shard p
    owns experts [p*E_local, (p+1)*E_local).

    buffer_rows: per-(src, dst) buffer capacity. None (default) =
    T_local*k — the worst case, so NOTHING is ever dropped (true
    dropless at P x memory in the a2a buffers). Smaller values trade
    memory/compute for GShard-style overflow drops (overflowing pairs
    contribute zero, gates NOT renormalized — monitor aux_loss).

    Returns (out (T_local, D), aux_loss scalar pmean'd over
    token_axes + (axis_name,))."""
    t_l, d = xt.shape
    e_l = wg.shape[0]
    p = axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    e = e_l * p
    n = t_l * k
    cbuf = n if buffer_rows is None else int(buffer_rows)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                     # (T_l, k)
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # aux loss over GLOBAL token means (reference computes it on the
    # full batch; local means pmean'd are exact for equal shard sizes)
    red = tuple(token_axes) + (axis_name,)
    me_mean = jax.lax.pmean(jnp.mean(probs, axis=0), red)
    ce_mean = jax.lax.pmean(
        jnp.mean(jnp.sum(_one_hot(idx, e), axis=1), axis=0) / k, red)
    aux = e * jnp.sum(me_mean * ce_mean)

    # ---- pack: sort pairs by global expert id (= by destination, and
    # by expert within destination) into (P, cbuf, D) send buffers ----
    flat_e = idx.reshape(-1).astype(jnp.int32)               # (N,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = jnp.take(flat_e, order)
    sorted_x = jnp.take(xt, order // k, axis=0)              # (N, D)
    dest = sorted_e // e_l                                   # (N,)
    send_counts = jnp.bincount(dest, length=p)
    start = jnp.cumsum(send_counts) - send_counts            # excl. cumsum
    slot = jnp.arange(n, dtype=jnp.int32) - start[dest].astype(jnp.int32)
    send_x = jnp.zeros((p, cbuf, d), xt.dtype).at[dest, slot].set(
        sorted_x, mode="drop")
    send_e = jnp.full((p, cbuf), e, jnp.int32).at[dest, slot].set(
        sorted_e, mode="drop")                               # e = sentinel

    # ---- all-to-all: row block i of the buffer goes to shard i ------
    a2a = lambda a: jax.lax.all_to_all(                      # noqa: E731
        a, axis_name, split_axis=0, concat_axis=0, tiled=True)
    recv_x = a2a(send_x).reshape(p * cbuf, d)
    recv_e = a2a(send_e).reshape(p * cbuf)

    # ---- local ragged grouped matmul over MY experts ----------------
    # received ids are all in [me*e_l, (me+1)*e_l) or the sentinel
    out_recv = moe_held_experts(recv_x, recv_e, wg, wu, wd,
                                me * e_l).reshape(p, cbuf, d)

    # ---- return trip + unpack ---------------------------------------
    back = a2a(out_recv)                                     # (P,cbuf,D)
    val_sorted = back[dest, jnp.clip(slot, 0, cbuf - 1)]
    val_sorted = jnp.where((slot < cbuf)[:, None], val_sorted, 0.0)
    inv = jnp.argsort(order, stable=True)
    out_rows = jnp.take(val_sorted, inv, axis=0).reshape(t_l, k, d)
    out = jnp.sum(gates[..., None].astype(xt.dtype) * out_rows, axis=1)
    return out, aux


def ragged_swiglu(x, wg, wu, wd, group_sizes):
    """silu(x wg[i]) * (x wu[i]) wd[i] on each row of group i, by XLA's
    grouped matmul (`jax.lax.ragged_dot`): x (N, D) sorted by expert,
    group_sizes (E,) int32, wg/wu (E, D, F), wd (E, F, D) -> (N, D),
    of which the rows past the last group are no one's. The grouped
    path's SwiGLU wherever the Pallas kernel (`kernels/moe_grouped.py`
    `moe_grouped`, the same contract) is not taken: off a TPU, under a
    gradient, in the expert-parallel exchange."""
    # said outright for half-width operands: the TPU's grouped matmul
    # refuses them under an ambient "highest"
    prec = (jax.lax.Precision.DEFAULT
            if x.dtype in (jnp.bfloat16, jnp.float16) else None)
    rdot = lambda a, w: jax.lax.ragged_dot(                 # noqa: E731
        a, w.astype(x.dtype), group_sizes, precision=prec)
    a, b = rdot(x, wg), rdot(x, wu)
    act = jax.nn.silu(a.astype(jnp.float32)).astype(x.dtype) * b
    return rdot(act, wd)


def moe_held_experts(rows, ids, wg, wu, wd, first, swiglu=ragged_swiglu):
    """What the experts HELD here give for rows that name their expert by
    its global id: wg/wu (E_held, D, F), wd (E_held, F, D) are experts
    [first, first + E_held) of all; a row whose expert is held elsewhere
    (or the sentinel of an empty buffer place) gives zeros. The local
    half of expert parallelism, with or without the exchange around it:
    `moe_dropless_mlp_ep_local` calls it on the rows it received, a layer
    that holds a share of the experts on one device (`moe_dropless_mlp`,
    `first=`) on its own.

    rows (N, D), ids (N,) int -> (N, D). The rows are sorted by expert,
    those of experts held elsewhere last, behind every group: the grouped
    matmul (`swiglu`: `ragged_swiglu`, or the Pallas kernel with its
    contract) is given the held experts' group sizes alone, and what it
    leaves in the rows past them is set to zero."""
    e_l = wg.shape[0]
    le = ids.astype(jnp.int32) - first
    le = jnp.where((le >= 0) & (le < e_l), le, e_l)
    order = jnp.argsort(le, stable=True)
    rx = jnp.take(rows, order, axis=0)
    sorted_le = jnp.take(le, order)
    group_sizes = jnp.bincount(le, length=e_l + 1).astype(jnp.int32)[:e_l]
    o = jnp.where((sorted_le < e_l)[:, None],
                  swiglu(rx, wg, wu, wd, group_sizes), 0)
    return jnp.take(o, jnp.argsort(order, stable=True), axis=0)


def moe_dropless_mlp(xt, wg, wu, wd, idx, gates, first=None,
                     swiglu=ragged_swiglu):
    """Sort-based grouped-matmul expert MLP with ZERO token drops
    (MegaBlocks-style; TPU-native via jax.lax.ragged_dot — the
    XLA grouped matmul MaxText uses for dMoE — or, where the layer takes
    it, `swiglu` = the Pallas grouped kernel, `kernels/moe_grouped.py`).

    xt (T, D); wg/wu (E, D, F); wd (E, F, D); idx/gates (T, k).
    All shapes static: the T*k (token, expert) pairs are sorted by
    expert id, each expert consumes a contiguous ragged row-group, and
    outputs unsort back to token order. -> (T, D).

    `first`: the weights are a SHARE of the experts `idx` names, those
    from `first` on (`moe_held_experts`): the pairs of experts held
    elsewhere give nothing, and the sum is the part of the layer's
    result that this share gives."""
    t, d = xt.shape
    e = wg.shape[0]
    k = idx.shape[1]
    if first is not None:
        with jax.named_scope("dispatch"):
            pairs_x = jnp.repeat(xt, k, axis=0)             # (T*k, D)
        with jax.named_scope("experts"):
            o = moe_held_experts(pairs_x, idx.reshape(-1), wg, wu, wd,
                                 first, swiglu)
        with jax.named_scope("combine"):
            return jnp.sum(gates[..., None].astype(xt.dtype)
                           * o.reshape(t, k, d), axis=1)
    with jax.named_scope("dispatch"):
        flat_e = idx.reshape(-1)                            # (T*k,)
        order = jnp.argsort(flat_e, stable=True)
        tok_of = order // k
        sorted_x = jnp.take(xt, tok_of, axis=0)             # (T*k, D)
        group_sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)
    with jax.named_scope("experts"):
        o = swiglu(sorted_x, wg, wu, wd, group_sizes)
    with jax.named_scope("combine"):
        inv = jnp.argsort(order, stable=True)
        out_rows = jnp.take(o, inv, axis=0).reshape(t, k, d)
        return jnp.sum(gates[..., None].astype(xt.dtype) * out_rows, axis=1)
