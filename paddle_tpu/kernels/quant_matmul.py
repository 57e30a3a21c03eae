"""Fused weight-only-int8 matmul Pallas kernel.

Reference capability: paddle/phi/kernels/weight_quantize_kernel.h +
fusion/gpu/fused_weight_only_linear — the llm.int8-style W8A16 path where
int8 weights are dequantized INSIDE the matmul kernel.

Why a kernel: XLA lowers `qw.astype(bf16) * scale @ x` as a separate
dequant fusion that MATERIALIZES the full bf16 weight in HBM every call
(measured 0.89x vs plain bf16 on v5e — worse than not quantizing).
Fusing the convert+scale into the matmul's K-loop keeps weight traffic
at 1 byte/element, which is the whole point of W8A16 for bandwidth-bound
decode shapes.

Layout: x (M, K) bf16 @ qw (K, N) int8 * scale (N,) f32 -> (M, N).
Grid (M/bm, N/bn, K/bk), K innermost ("arbitrary"), f32 VMEM accumulator,
dequant epilogue applied once at the last K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.jax_compat import tpu_compiler_params

__all__ = ["weight_only_int8_matmul", "pick_block_m"]


def pick_block_m(M: int):
    """Largest VMEM-safe M tile dividing M (None if M doesn't tile —
    callers then take the XLA fallback instead of an unbounded bm=M
    accumulator that blows VMEM for large ragged batch*seq)."""
    for c in (256, 128, 64, 32, 16, 8):
        if M % c == 0:
            return c
    return M if M <= 256 else None


def _kernel(x_ref, qw_ref, s_ref, o_ref, acc_ref, *, nk):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = qw_ref[...].astype(jnp.bfloat16)     # in-register dequant (tile)
    # precision pinned: the package default (FLAGS_matmul_precision
    # "highest") requests f32-emulated bf16 passes Mosaic can't lower
    acc_ref[...] += jax.lax.dot(
        x_ref[...].astype(jnp.bfloat16), w,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        o_ref[...] = (acc_ref[...]
                      * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def weight_only_int8_matmul(x, qw, scale, block_m=None, block_n=512,
                            block_k=512, out_dtype=jnp.bfloat16,
                            interpret=False):
    """x (..., K) bf16/f32 @ int8 qw (K, N), `scale` (N,) f32 already
    divided by the quant bound (i.e. w ~= qw * scale). Shapes must tile:
    K % block_k == 0 and N % block_n == 0 (callers fall back to the XLA
    path otherwise — see QuantizedLinear)."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = qw.shape[1]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(M, K)
    if block_m is None:
        block_m = pick_block_m(M)
        if block_m is None:
            raise ValueError(
                f"M={M} has no tile-able block_m; use the XLA fallback")
    if M % block_m != 0:
        raise ValueError(f"M={M} not divisible by block_m={block_m}")
    bm = block_m
    nk = K // block_k
    grid = (M // bm, N // block_n, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, block_n), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * N * K,
            bytes_accessed=M * K * 2 + K * N + M * N * 2,
            transcendentals=0),
        interpret=interpret,
        name="quant_matmul",
    )(x2, qw, scale.reshape(1, N))
    return out.reshape(lead + (N,))
