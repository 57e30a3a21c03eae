"""Fused RMSNorm+residual-add and fused RoPE apply (Pallas TPU + jnp).

The other two train-path ops XLA fuses poorly enough to matter at step
scale (ISSUE 14; reference kernels fused_layernorm_kernel.cu rmsnorm
branch and fused_rope under paddle/phi/kernels/fusion/gpu/):

- **RMSNorm + residual**: the decoder block's `h = residual + attn_out;
  normed = rms_norm(h)` chain reads h twice (once for the add's
  consumer, once for the norm's f32 stat pass) and jax AD of the
  unfused chain re-reads everything again backward. Here
  `rms_norm_residual` does the add, the f32 mean-square, and the
  scale-by-weight in ONE pass over x (the residual sum is written in
  the same pass as the norm output), with a `custom_vjp` whose backward
  is the closed-form RMSNorm gradient from the saved per-row rstd —
  one read of (h, g) instead of AD's slice/concat chain.
- **RoPE**: the half-split rotation (`o1 = x1 c - x2 s; o2 = x2 c + x1
  s`) lowers as slice/concat pairs XLA pads into relayout copies.
  `rope_apply` precomputes full-width cos / sign-folded sin tables once
  (tiny: (S, D)) and the kernel does two multiplies + one lane
  rotation per tile; the backward is the INVERSE rotation — the same
  kernel with -sin on the cotangent (the incubate `_apply_rope_neox`
  trick, kept).

Both ops run the Pallas kernels on TPU when their shape contract holds
(`*_shape_problems` — the `decode_shape_problems` style: the AUTO path
gates silently, a forced "pallas" raises naming every misaligned dim)
and fall back to jnp with IDENTICAL math elsewhere, so CPU tier-1
exercises the exact numerics the TPU path ships (plus interpret-mode
kernel parity, the paged-attention pattern).
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

__all__ = ["rms_norm_residual", "rope_apply",
           "norm_shape_problems", "check_norm_shapes",
           "rope_shape_problems", "check_rope_shapes"]

# rows per grid cell (both kernels); padded rows are zeros and sliced off
_BLOCK_ROWS = 256


# ---------------------------------------------------------------------------
# shape contracts
# ---------------------------------------------------------------------------

def norm_shape_problems(d, interpret=False):
    """Reasons the Pallas RMSNorm+residual kernel cannot take a row
    width d; empty = supported."""
    problems = []
    if not interpret and d % 128 != 0:
        problems.append(f"hidden % 128 == 0 required on TPU (got d={d})")
    return problems


def check_norm_shapes(d, interpret=False):
    problems = norm_shape_problems(d, interpret)
    if problems:
        raise ValueError(
            "rms_norm_residual: shapes cannot take the Pallas kernel — "
            + "; ".join(problems)
            + '; use kernel="jnp" for the fused-jnp fallback')


def rope_shape_problems(d, interpret=False):
    """Reasons the Pallas RoPE kernel cannot take head_dim d."""
    problems = []
    if d % 2 != 0:
        problems.append(f"head_dim must be even (got d={d})")
    if not interpret:
        if d % 8 != 0:
            problems.append(f"head_dim % 8 == 0 required on TPU "
                            f"(got d={d})")
    return problems


def check_rope_shapes(d, interpret=False):
    problems = rope_shape_problems(d, interpret)
    if problems:
        raise ValueError(
            "rope_apply: shapes cannot take the Pallas kernel — "
            + "; ".join(problems)
            + '; use kernel="jnp" for the fused-jnp fallback')


# ---------------------------------------------------------------------------
# RMSNorm + residual
# ---------------------------------------------------------------------------

def _rmsn_fwd_math(h, w, eps):
    """Shared forward math — EXACTLY `nn/functional/norm.py _rms_norm`
    (the eager `rms_norm_ref` defop): f32 stats, f32 scale-by-weight,
    cast back. The parity pin in tests depends on this being the same
    expression tree."""
    hf = h.astype(jnp.float32)
    ms = jnp.mean(jnp.square(hf), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    y = (hf * rstd * w.astype(jnp.float32)).astype(h.dtype)
    return y, rstd


def _rmsn_bwd_math(h, w, rstd, gy, gh):
    """Closed-form RMSNorm backward from the saved rstd:
    dh = rstd * (gy*w - xhat * mean(gy*w*xhat)) + gh;  dw = sum gy*xhat.
    One pass over (h, gy) — what jax AD spreads across the rsqrt/mean
    chain re-reads."""
    hf = h.astype(jnp.float32)
    gyf = gy.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    xhat = hf * rstd
    dxhat = gyf * wf
    c = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dh = rstd * (dxhat - xhat * c)
    if gh is not None:
        dh = dh + gh.astype(jnp.float32)
    dw = jnp.sum(gyf * xhat, axis=tuple(range(h.ndim - 1)))
    return dh.astype(h.dtype), dw.astype(w.dtype)


def _rmsn_fwd_kernel(x_ref, res_ref, w_ref, y_ref, h_ref, rstd_ref, *,
                     eps, has_res):
    x = x_ref[...]
    h = x + res_ref[...] if has_res else x
    h_ref[...] = h
    hf = h.astype(jnp.float32)
    ms = jnp.mean(hf * hf, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)                        # (bn, 1)
    # w_ref[...] is the 2D (1, d) row — broadcast, never a 1D vector
    # (the flash-kernel Mosaic idiom)
    y_ref[...] = (hf * rstd
                  * w_ref[...].astype(jnp.float32)).astype(y_ref.dtype)
    # transposed (8, bn) store: full (8, 128) f32 tiles (the flash lse
    # layout lesson)
    rstd_ref[...] = jnp.broadcast_to(rstd.T, rstd_ref.shape)


def _rmsn_fwd_kernel_nores(x_ref, w_ref, y_ref, h_ref, rstd_ref, *, eps):
    return _rmsn_fwd_kernel(x_ref, None, w_ref, y_ref, h_ref, rstd_ref,
                            eps=eps, has_res=False)


def _rmsn_bwd_kernel(h_ref, w_ref, rstd_ref, gy_ref, gh_ref, dh_ref,
                     dwp_ref, *, has_gh):
    hf = h_ref[...].astype(jnp.float32)
    gyf = gy_ref[...].astype(jnp.float32)
    wf = w_ref[...].astype(jnp.float32)                   # (1, d)
    rstd = rstd_ref[:1, :].T                              # (bn, 1)
    xhat = hf * rstd
    dxhat = gyf * wf
    c = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dh = rstd * (dxhat - xhat * c)
    if has_gh:
        dh = dh + gh_ref[...].astype(jnp.float32)
    dh_ref[...] = dh.astype(dh_ref.dtype)
    # per-block dW partial (1, d); summed outside (rows/bn terms)
    dwp_ref[...] = jnp.sum(gyf * xhat, axis=0, keepdims=True)


def _rmsn_fwd_pallas(x2, res2, w, eps, interpret):
    n, d = x2.shape
    bn = min(_BLOCK_ROWS, n)
    n_pad = -(-n // bn) * bn
    pads = ((0, n_pad - n), (0, 0))
    xp = jnp.pad(x2, pads) if n_pad != n else x2
    args = [xp]
    in_specs = [pl.BlockSpec((bn, d), lambda i: (i, 0))]
    if res2 is not None:
        rp = jnp.pad(res2, pads) if n_pad != n else res2
        args.append(rp)
        in_specs.append(pl.BlockSpec((bn, d), lambda i: (i, 0)))
        kernel = functools.partial(_rmsn_fwd_kernel, eps=eps,
                                   has_res=True)
    else:
        kernel = functools.partial(_rmsn_fwd_kernel_nores, eps=eps)
    args.append(w.reshape(1, d))
    in_specs.append(pl.BlockSpec((1, d), lambda i: (0, 0)))
    y, h, rstd_t = pl.pallas_call(
        kernel,
        grid=(n_pad // bn,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                   pl.BlockSpec((bn, d), lambda i: (i, 0)),
                   pl.BlockSpec((8, bn), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((n_pad, d), x2.dtype),
                   jax.ShapeDtypeStruct((n_pad, d), x2.dtype),
                   jax.ShapeDtypeStruct((8, n_pad), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="fused_rmsnorm_fwd",
    )(*args)
    return y[:n], h[:n], rstd_t


def _rmsn_bwd_pallas(h2, w, rstd_t, gy2, gh2, interpret):
    n, d = h2.shape
    bn = min(_BLOCK_ROWS, n)
    n_pad = -(-n // bn) * bn
    pads = ((0, n_pad - n), (0, 0))
    hp = jnp.pad(h2, pads) if n_pad != n else h2
    gyp = jnp.pad(gy2, pads) if n_pad != n else gy2
    args = [hp, w.reshape(1, d), rstd_t]
    in_specs = [pl.BlockSpec((bn, d), lambda i: (i, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),
                pl.BlockSpec((8, bn), lambda i: (0, i))]
    args.append(gyp)
    in_specs.append(pl.BlockSpec((bn, d), lambda i: (i, 0)))
    if gh2 is not None:
        ghp = jnp.pad(gh2, pads) if n_pad != n else gh2
        args.append(ghp)
        in_specs.append(pl.BlockSpec((bn, d), lambda i: (i, 0)))
        kernel = functools.partial(_rmsn_bwd_kernel, has_gh=True)
    else:
        kernel = functools.partial(
            lambda h_ref, w_ref, r_ref, gy_ref, dh_ref, dwp_ref, kern:
            kern(h_ref, w_ref, r_ref, gy_ref, None, dh_ref, dwp_ref),
            kern=functools.partial(_rmsn_bwd_kernel, has_gh=False))
    dh, dwp = pl.pallas_call(
        kernel,
        grid=(n_pad // bn,),
        in_specs=in_specs,
        # the per-block dW partial rides as (blocks, 1, d) with the block
        # axis squeezed: a (1, d) block of a (blocks, d) array breaks
        # Mosaic's 8-sublane block rule
        out_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                   pl.BlockSpec((None, 1, d), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_pad, d), h2.dtype),
                   jax.ShapeDtypeStruct((n_pad // bn, 1, d),
                                        jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="fused_rmsnorm_bwd",
    )(*args)
    return dh[:n], jnp.sum(dwp, axis=(0, 1)).astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rmsn_res(x2, res2, w, eps, use_pallas, interpret):
    y, h, _ = _rmsn_res_fwd_impl(x2, res2, w, eps, use_pallas, interpret)
    return y, h


def _rmsn_res_fwd_impl(x2, res2, w, eps, use_pallas, interpret):
    if use_pallas:
        y, h, rstd_t = _rmsn_fwd_pallas(x2, res2, w, eps, interpret)
        return y, h, rstd_t
    h = x2 + res2
    y, rstd = _rmsn_fwd_math(h, w, eps)
    return y, h, rstd


def _rmsn_res_fwd(x2, res2, w, eps, use_pallas, interpret):
    y, h, rstd = _rmsn_res_fwd_impl(x2, res2, w, eps, use_pallas,
                                    interpret)
    return (y, h), (h, w, rstd)


def _rmsn_res_bwd(eps, use_pallas, interpret, res, g):
    gy, gh = g
    h, w, rstd = res
    if use_pallas:
        dh, dw = _rmsn_bwd_pallas(h, w, rstd, gy, gh, interpret)
    else:
        dh, dw = _rmsn_bwd_math(h, w, rstd, gy, gh)
    return dh, dh, dw


_rmsn_res.defvjp(_rmsn_res_fwd, _rmsn_res_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsn_plain(x2, w, eps, use_pallas, interpret):
    y, _, _ = _rmsn_plain_fwd_impl(x2, w, eps, use_pallas, interpret)
    return y


def _rmsn_plain_fwd_impl(x2, w, eps, use_pallas, interpret):
    if use_pallas:
        return _rmsn_fwd_pallas(x2, None, w, eps, interpret)
    y, rstd = _rmsn_fwd_math(x2, w, eps)
    return y, x2, rstd


def _rmsn_plain_fwd(x2, w, eps, use_pallas, interpret):
    y, h, rstd = _rmsn_plain_fwd_impl(x2, w, eps, use_pallas, interpret)
    return y, (h, w, rstd)


def _rmsn_plain_bwd(eps, use_pallas, interpret, res, gy):
    h, w, rstd = res
    if use_pallas:
        dh, dw = _rmsn_bwd_pallas(h, w, rstd, gy, None, interpret)
    else:
        dh, dw = _rmsn_bwd_math(h, w, rstd, gy, None)
    return dh, dw


_rmsn_plain.defvjp(_rmsn_plain_fwd, _rmsn_plain_bwd)


def rms_norm_residual(x, weight, residual=None, epsilon=1e-6,
                      kernel=None, interpret=False):
    """Fused `h = x + residual; y = rms_norm(h) * weight` in one pass.

    x / residual: (..., d) same shape; weight: (d,). Returns (y, h) —
    both in x's dtype; with residual=None, h IS x (the plain fused
    norm, still one custom_vjp op). Matches the eager `rms_norm_ref`
    defop's numerics exactly (f32 stats, f32 scale, cast back).

    kernel: None = auto (Pallas on TPU when `norm_shape_problems` is
    empty, fused-jnp otherwise); "pallas" forces the kernel (off-TPU
    via interpret mode); "jnp" forces the fallback.
    """
    if kernel not in (None, "pallas", "jnp"):
        raise ValueError(f"kernel must be None|'pallas'|'jnp', "
                         f"got {kernel!r}")
    d = x.shape[-1]
    if weight.shape != (d,):
        raise ValueError(f"weight must be ({d},), got {weight.shape}")
    if residual is not None and residual.shape != x.shape:
        raise ValueError(f"residual shape {residual.shape} != x shape "
                         f"{x.shape}")
    if kernel == "pallas":
        interpret = interpret or not jax_compat.on_tpu()
        check_norm_shapes(d, interpret)
        use_pallas = True
    elif kernel == "jnp":
        use_pallas = False
    else:
        use_pallas = (jax_compat.on_tpu()
                      and not norm_shape_problems(d, interpret))
    lead = x.shape[:-1]
    x2 = x.reshape(-1, d)
    eps = float(epsilon)
    mesh = _sharding.kernel_mesh() if use_pallas else None
    # under a mesh the rows split over its shards (kernels/sharding.py);
    # the weight rides whole, its gradient summed by the shard_map
    rows = P(_sharding.row_axes(mesh, x2.shape[0]), None) \
        if mesh is not None else None
    if residual is None:
        def plain(xl, wl):
            return _rmsn_plain(xl, wl, eps, use_pallas, bool(interpret))
        if mesh is not None:
            plain = _sharding.per_shard(plain, mesh, (rows, P(None)), rows)
        return plain(x2, weight).reshape(lead + (d,)), x

    def fused(xl, rl, wl):
        return _rmsn_res(xl, rl, wl, eps, use_pallas, bool(interpret))
    if mesh is not None:
        fused = _sharding.per_shard(fused, mesh, (rows, rows, P(None)),
                                    (rows, rows))
    y, h = fused(x2, residual.reshape(-1, d), weight)
    return y.reshape(lead + (d,)), h.reshape(lead + (d,))


# ---------------------------------------------------------------------------
# fused RoPE apply
# ---------------------------------------------------------------------------

def _rope_fwd_math(x, cos_f, sin_f):
    """x (n, h, d); cos_f (n, d) full-width cos; sin_f (n, d) = the
    SIGN-FOLDED sin table concat(-sin, sin). out = x*cos + roll(x)*sin
    where roll swaps the halves — identical math to the incubate
    `_rope_neox_raw` half-split form, f32 compute, cast back."""
    d = x.shape[-1]
    d2 = d // 2
    xf = x.astype(jnp.float32)
    rolled = jnp.concatenate([xf[..., d2:], xf[..., :d2]], axis=-1)
    out = (xf * cos_f[:, None, :] + rolled * sin_f[:, None, :])
    return out.astype(x.dtype)


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)              # (bn, h, d)
    d = x.shape[-1]
    d2 = d // 2
    rolled = jnp.concatenate([x[..., d2:], x[..., :d2]], axis=-1)
    cos = cos_ref[...][:, None, :]                  # (bn, 1, d)
    sin = sin_ref[...][:, None, :]
    o_ref[...] = (x * cos + rolled * sin).astype(o_ref.dtype)


def _rope_pallas(x3, cos_f, sin_f, interpret):
    n, h, d = x3.shape
    bn = min(_BLOCK_ROWS, n)
    n_pad = -(-n // bn) * bn
    if n_pad != n:
        x3 = jnp.pad(x3, ((0, n_pad - n), (0, 0), (0, 0)))
        cos_f = jnp.pad(cos_f, ((0, n_pad - n), (0, 0)))
        sin_f = jnp.pad(sin_f, ((0, n_pad - n), (0, 0)))
    out = pl.pallas_call(
        _rope_kernel,
        grid=(n_pad // bn,),
        in_specs=[pl.BlockSpec((bn, h, d), lambda i: (i, 0, 0)),
                  pl.BlockSpec((bn, d), lambda i: (i, 0)),
                  pl.BlockSpec((bn, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bn, h, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, h, d), x3.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="fused_rope",
    )(x3, cos_f, sin_f)
    return out[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rope(x3, cos_f, sin_f, use_pallas, interpret):
    if use_pallas:
        return _rope_pallas(x3, cos_f, sin_f, interpret)
    return _rope_fwd_math(x3, cos_f, sin_f)


def _rope_fwd(x3, cos_f, sin_f, use_pallas, interpret):
    return _rope(x3, cos_f, sin_f, use_pallas, interpret), (cos_f, sin_f)


def _rope_bwd(use_pallas, interpret, res, g):
    cos_f, sin_f = res
    # the backward of a rotation is the INVERSE rotation — the same
    # forward on the cotangent with the angle negated (the incubate
    # _apply_rope_neox trick). Half-split: dx1 = g1 c + g2 s,
    # dx2 = g2 c - g1 s; in the sign-folded full-width form that is
    # exactly sin_f -> -sin_f (concat(-s, s) -> concat(s, -s)).
    sin_b = -sin_f
    if use_pallas:
        dx = _rope_pallas(g, cos_f, sin_b, interpret)
    else:
        dx = _rope_fwd_math(g, cos_f, sin_b)
    return dx, jnp.zeros_like(cos_f), jnp.zeros_like(sin_f)


_rope.defvjp(_rope_fwd, _rope_bwd)


def _cos_sin_rows(positions, d, theta, dtype):
    """Full-width f32 tables per row: cos_f (n, d) = concat(cos, cos),
    sin_f (n, d) = concat(-sin, sin) (the sign fold that turns the
    half-split rotation into mul/roll/mul/add). positions: (n,) i32."""
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq   # (n, d/2)
    cos = jnp.cos(ang)
    sin = jnp.sin(ang)
    cos_f = jnp.concatenate([cos, cos], axis=-1)
    sin_f = jnp.concatenate([-sin, sin], axis=-1)
    return cos_f.astype(dtype), sin_f.astype(dtype)


def rope_apply(x, positions=None, theta=10000.0, kernel=None,
               interpret=False):
    """NeoX/Llama RoPE on x (B, S, H, D) in one fused pass.

    positions: (S,) or (B, S) int positions (None = arange(S)). Exact
    numerics of the incubate `_apply_rope_neox` half-split apply (f32
    compute, cast back); backward is the inverse rotation via
    custom_vjp. kernel: None = auto (Pallas on TPU when
    `rope_shape_problems` is empty), "pallas" forced (interpret
    off-TPU), "jnp" forced.
    """
    if kernel not in (None, "pallas", "jnp"):
        raise ValueError(f"kernel must be None|'pallas'|'jnp', "
                         f"got {kernel!r}")
    b, s, h, d = x.shape
    if d % 2 != 0:
        raise ValueError(f"head_dim must be even (got {d})")
    if kernel == "pallas":
        interpret = interpret or not jax_compat.on_tpu()
        check_rope_shapes(d, interpret)
        use_pallas = True
    elif kernel == "jnp":
        use_pallas = False
    else:
        use_pallas = (jax_compat.on_tpu()
                      and not rope_shape_problems(d, interpret))
    if positions is None:
        pos = jnp.tile(jnp.arange(s, dtype=jnp.int32), b)
    else:
        pos = jnp.asarray(positions).astype(jnp.int32)
        if pos.ndim == 1:
            pos = jnp.tile(pos, b)
        else:
            pos = pos.reshape(-1)
    cos_f, sin_f = _cos_sin_rows(pos, d, float(theta), jnp.float32)
    x3 = x.reshape(b * s, h, d)

    def local(xl, cl, sl):
        return _rope(xl, cl, sl, use_pallas, bool(interpret))
    mesh = _sharding.kernel_mesh() if use_pallas else None
    if mesh is not None:
        # batch-major rows over the batch axes, heads over mp
        # (kernels/sharding.py)
        rows = _sharding.batch_axes(mesh, b)
        xs = P(rows, _sharding.head_axis(mesh, h), None)
        local = _sharding.per_shard(
            local, mesh, (xs, P(rows, None), P(rows, None)), xs)
    return local(x3, cos_f, sin_f).reshape(b, s, h, d)
