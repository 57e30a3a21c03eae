"""Pallas selective scan of a Mamba-1 layer, for a prefill chunk (TPU).

Mamba-1's recurrence over a row's tokens t, E channels and N states:

    s_t = exp(delta_t * A) * s_{t-1} + (delta_t * u_t) (x) B_t
    y_t = s_t . C_t + D * u_t

with A (E, N) per channel AND state, so the recurrence has no matmul form
(Mamba-2's scalar A a head is what makes its chunked dual a matmul). It is
a sequential recurrence on the vector unit, and this kernel runs it so:

- the state of one channel block, (N, block) float32 with the channels on
  the lanes, lives in VMEM scratch for the whole chunk; the grid is rows x
  channel blocks x time blocks, time innermost and in order, so a row's
  state is read from `state` at its first time block and written to the
  final state at its last, and is never in HBM between;
- a time block's u and delta are (tokens, block) tiles, taken 8 tokens (a
  sublane tile) at a time and the 8 steps unrolled; B and C come in
  transposed, (N, tokens), so a token's B_t and C_t are a column of N
  values, picked out of the block by a lane mask (a lane-dynamic slice is
  no Mosaic load) and broadcast along the channels' lanes;
- y is written 8 tokens at a time, in float32.

A token with delta 0 leaves the state exactly as it was (exp(0) = 1 and
its input term is 0): the engine zeroes delta past a row's valid tokens,
so a padded chunk ends in the state of its last valid token.

`ssm_scan(u, delta, bt, ct, at, d, state)` takes u, delta (rows, s, E)
float32, bt, ct (rows, N, s) float32, at = A transposed (N, E) float32, d
(E,) float32 and the initial state (rows, N, E) float32, and returns y
(rows, s, E) float32 and the final state (rows, N, E) float32.
`ssm_scan_reference` is the same function in jax.numpy, a `lax.scan` over
the tokens: the engine's path off the chip and the tests' yardstick.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.jax_compat import tpu_compiler_params

__all__ = ["ssm_scan", "ssm_scan_reference", "ssm_scan_problems"]

# channels a grid step holds: the state is (N, 512) float32, 8 vregs at
# N = 16, carried through the token loop in registers
_BLOCK_CHANNELS = 512
# tokens a grid step takes: u, delta and y tiles of 512 KB each at 512
# channels, double-buffered well inside the scoped VMEM
_BLOCK_TOKENS = 256
_SUB = 8                # tokens a turn of the loop: one sublane tile


def _blocks(s, e):
    return min(_BLOCK_TOKENS, s), min(_BLOCK_CHANNELS, e)


def ssm_scan_problems(s, e, n, interpret=False):
    """Why the kernel cannot take a chunk of `s` tokens over `e` channels
    and `n` states ([] if it can): whole sublane tiles of tokens, whole
    lane tiles of channels, blocks that divide both."""
    bs, be = _blocks(s, e)
    problems = []
    if s % _SUB or s % bs:
        problems.append(f"tokens {s} are no whole {_SUB}-token tiles of "
                        f"{bs}-token blocks")
    if not interpret and (e % 128 or e % be):
        problems.append(f"channels {e} are no whole 128-lane tiles of "
                        f"{be}-channel blocks")
    if n % _SUB and not interpret:
        problems.append(f"states {n} are no whole {_SUB}-sublane tiles")
    return problems


def _kernel(u_ref, dl_ref, bt_ref, ct_ref, at_ref, d_ref, s0_ref,
            y_ref, sf_ref, st_ref, *, tokens):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _first():
        st_ref[...] = s0_ref[0]

    at = at_ref[...]                                    # (N, be)
    dv = d_ref[...]                                     # (1, be)
    bt, ct = bt_ref[0], ct_ref[0]                       # (N, bs)
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUB, at.shape[1]), 0)

    def turn(g, state):
        j0 = pl.multiple_of(g * _SUB, _SUB)
        u8 = u_ref[0, pl.ds(j0, _SUB), :]               # (8, be)
        dl8 = dl_ref[0, pl.ds(j0, _SUB), :]
        y8 = jnp.zeros(u8.shape, jnp.float32)
        for k in range(_SUB):
            dt, u = dl8[k:k + 1], u8[k:k + 1]           # (1, be)
            here = lane == j0 + k
            b = jnp.sum(jnp.where(here, bt, 0.0), axis=1, keepdims=True)
            c = jnp.sum(jnp.where(here, ct, 0.0), axis=1, keepdims=True)
            state = jnp.exp(dt * at) * state + b * (dt * u)
            y = jnp.sum(state * c, axis=0, keepdims=True) + dv * u
            y8 = jnp.where(sub == k, y, y8)
        y_ref[0, pl.ds(j0, _SUB), :] = y8
        return state

    st_ref[...] = jax.lax.fori_loop(0, tokens // _SUB, turn, st_ref[...])

    @pl.when(ti == pl.num_programs(2) - 1)
    def _last():
        sf_ref[0] = st_ref[...]


def ssm_scan(u, delta, bt, ct, at, d, state, *, interpret=False):
    """The selective scan of one chunk for every row (module doc)."""
    rows, s, e = u.shape
    n = at.shape[0]
    problems = ssm_scan_problems(s, e, n, interpret)
    if problems:
        raise ValueError("ssm_scan: " + "; ".join(problems))
    return _call(u, delta, bt, ct, at, d, state, interpret=interpret)


# jitted on its own: a model calls this once a layer, and a caller's trace
# then holds ONE traced and lowered kernel (kernels/paged_attention.py)
@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(u, delta, bt, ct, at, d, state, *, interpret):
    rows, s, e = u.shape
    n = at.shape[0]
    bs, be = _blocks(s, e)
    f32 = jnp.float32
    tiles = pl.BlockSpec((1, bs, be), lambda r, c, t: (r, t, c))
    cols = pl.BlockSpec((1, n, bs), lambda r, c, t: (r, 0, t))
    states = pl.BlockSpec((1, n, be), lambda r, c, t: (r, 0, c))
    y, final = pl.pallas_call(
        functools.partial(_kernel, tokens=bs),
        grid=(rows, e // be, s // bs),
        in_specs=[tiles, tiles, cols, cols,
                  pl.BlockSpec((n, be), lambda r, c, t: (0, c)),
                  pl.BlockSpec((1, be), lambda r, c, t: (0, c)),
                  states],
        out_specs=[tiles, states],
        out_shape=[jax.ShapeDtypeStruct((rows, s, e), f32),
                   jax.ShapeDtypeStruct((rows, n, e), f32)],
        scratch_shapes=[pltpu.VMEM((n, be), f32)],
        # the time axis carries the state in order
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(u.astype(f32), delta.astype(f32), bt.astype(f32), ct.astype(f32),
      at.astype(f32), d.astype(f32).reshape(1, e), state.astype(f32))
    return y, final


def ssm_scan_reference(u, delta, bt, ct, at, d, state):
    """`ssm_scan` in jax.numpy: one `lax.scan` step a token, float32."""
    f32 = jnp.float32
    at, d = at.astype(f32), d.astype(f32)

    def step(st, x):
        u_t, dl_t, b_t, c_t = x                 # (rows, E) x2, (rows, N) x2
        st = jnp.exp(dl_t[:, None, :] * at[None]) * st \
            + b_t[:, :, None] * (dl_t * u_t)[:, None, :]
        y = jnp.sum(st * c_t[:, :, None], axis=1) + d * u_t
        return st, y

    xs = (jnp.swapaxes(u.astype(f32), 0, 1),
          jnp.swapaxes(delta.astype(f32), 0, 1),
          jnp.moveaxis(bt.astype(f32), 2, 0),
          jnp.moveaxis(ct.astype(f32), 2, 0))
    final, y = jax.lax.scan(step, state.astype(f32), xs)
    return jnp.swapaxes(y, 0, 1), final
