"""Continuous-batching paged-KV serving engine, TPU-first.

Reference surface: the reference's production serving path is paged
("block") KV attention — the CUDA kernel
`paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu`
driven through
`python/paddle/incubate/nn/functional/block_multihead_attention.py`,
with launcher-side batching and block-table bookkeeping. This module is
the TPU-native redesign of that serving path (the eager
`incubate.nn.functional.block_multihead_attention` op keeps the
reference's op-level API contract; THIS engine is what actually serves):

- KV pages live in device pools `(num_pages, kv_heads, page_size,
  head_dim)` per layer; block tables are DEVICE int32 inputs. The whole
  decode tick — `steps_per_tick` tokens x all slots — is ONE jitted
  `lax.scan` program: token writes are vectorized scatters into pages,
  reads are one page-gather per layer. No host bookkeeping inside the
  hot loop, and only one host<->device round trip per tick (a
  per-token fetch would put a host sync between every decode step).
- Which shape a pool has where: an engine whose decode attends through
  the Pallas kernel stores its plain (bf16 / f32) K and V pools as that
  kernel's rows (`kernels/paged_attention.py pool_rows_shape`: the same
  bytes as the shape above, which on the chip is another layout
  wherever head_dim < 128) and writes them through the Pallas call
  `paged_kv_write`, which aliases them; `_scatter_kv` chooses from the
  trace's `decode_kernel_scope` and the pool it is handed, so the write
  follows the attend and XLA never copies a pool into a layout of its
  own. A jnp engine, int8 pools with their scale planes, the index
  pool of a model with a key selection and direct callers of the op
  keep the shape above and XLA's scatter (int8: the write rescales
  whole pages; index: no Pallas call reads it). What gathers pages
  (prefill, verify: `_attend_pages`, `_attend_selected`) views the
  gathered window by heads and tokens, never the pool, and a page that
  leaves the engine (a host-tier entry, an exported bundle) is
  `(kv_heads, page_size, head_dim)` whatever its pool's shape.
- Scheduling (admission, page allocation, retirement) is host-side
  Python BETWEEN ticks, and since ISSUE 32 beside the tick that flies:
  the next tick is launched from the rows the last one left on the
  device before the host reads that one back (`PagedKVEngine` doc).
  A request can join at any tick boundary — i.e.
  mid-decode of every other request — which is the continuous-batching
  capability the reference's serving launcher provides; requests leave
  as soon as they hit eos or their token budget, freeing pages
  immediately.
- Admission is reservation-based: a request is admitted only when its
  worst-case page need `ceil((prompt + max_new) / page_size)` fits the
  unreserved pool, so decode can NEVER run out of pages mid-flight (the
  preemption/swapping machinery a lazy admission policy would need is
  deliberately out of scope). Pages are still *allocated* lazily, tick
  by tick, so short answers return unused reservations early.
- One compiled decode program per engine (static `(max_slots,
  steps_per_tick, max_pages_per_slot)` shapes, do_sample variants
  compiled separately); prefill programs are bucketed by padded prompt
  length, two widths a bucket (1 and `max_slots`): requests admitted
  together to one bucket prefill under one wait, through the padded
  program only where that costs less than a call a row at width 1
  (`prefill_width`). Per-request sampling params (temperature / top_k /
  top_p / eos) are TRACED per-slot vectors, so heterogeneous sampling
  configs share one compile.

Models opt in exactly like dense KV-cache decode (models/generation.py)
but receive a `PagedState` as `cache_index` and per-layer `(k_pool,
v_pool)` pairs as `caches`; their attention layer calls
`paged_attention_update` (LlamaAttention does — models/llama.py).

Decode hot path (ISSUE 6): the tick's attention-over-pages can ride
the Pallas paged-decode kernel (kernels/paged_attention.py — block
tables as scalar-prefetch indices, GQA head fold, online softmax;
`PagedKVEngine(kernel=...)`), and KV pools can be stored int8 with
per-page-per-head f32 scales quantized at scatter time and
dequantized inside the kernel's K-loop (`kv_dtype="int8"` — about
half the KV HBM per slot vs bf16). The jnp gather/softmax path
remains the fallback for prefill, speculative verify, and
kernel-incompatible geometries.

Prefix caching (ISSUE 11): full pages of prompt tokens are immutable
once written, so `prefix_cache_pages=N` turns repeated prefixes
(shared system prompts) into block-table rows instead of recomputed
prefill — pages are keyed by a rolling hash chain
(inference/prefix.py), REFCOUNTED across the slots that share them,
and a warm `submit()` prefills only the uncached tail (O(tail), not
O(prompt)). Cold entries evict LRU under the page budget, and the
admission headroom counts reclaimable cached pages, so the cache can
never starve decode allocation.

Tiered KV (ISSUE 18): `host_tier_bytes=N` adds a host-RAM tier below
the device prefix cache (inference/kvtier.py). Eviction SPILLS a
zero-ref cached page to host instead of destroying it (D2H snapshot
captured on the scheduler thread, materialized on the tier's worker);
admission extends a device-cache run with host-resident pages via one
batched H2D upload and then runs the same tail-only warm prefill — a
restored prefix is a warm hit with a copy in front. `submit(session=)`
plus `suspend_after_s` generalize this to live conversations: a
finished turn's full pages (prompt AND generated tokens) stay keyed in
the cache, a long-idle session's pages spill to host freeing their
HBM, and the next turn rebuilds its block table from restored pages.

Generation by diffusion over blocks (ISSUE 36): a model whose config has
`block_length` (models/block_diffusion_moe.py) has no one-token step. Its
tick settles `steps_per_tick / block_length` whole blocks a slot
(`_block_tick_fn`: denoising forwards of `block_length` query rows a slot,
whose K and V overwrite the step's before, the model's unmasking rule, and
one more forward that stores the settled block), so a tick still hands up
to `steps_per_tick` tokens to each live slot and everything around the
tick program (admission, the chain of ticks in flight, accept, streams)
is the one-token engine's. A prompt's whole blocks are prefilled under the
mask by blocks; its last tokens open the slot's first block (`_Slot.open`)
and a prefill yields no token. `paged_attention_update(block=)` is the
op's side of it.

Latent attention: a model whose config has `kv_lora_rank`
(models/latent_attn_moe.py) caches ONE row a token a layer, its normed
latent and its shared rotary key, in a pool of its own under the block
table, in place of K and V (`latent_attention_update`). A decode step
attends in the absorbed form, every head against the same rows, through
kernels/mla_decode.py; a prefill chunk in the expanded form, the rows
expanded into each head's keys and values a block at a time
(kernels/mla_prefill.py on the Pallas path, `_attend_latent_expanded`
else). What assumes K and V pages refuses such a
model by name (`_refuse_uncarried`), as it refuses an index pool, rings
and blocks.

Layers with a state a slot: a model whose `config.layer_types` names
"mamba" layers (models/ssm_hybrid.py) keeps each such layer's conv window
and SSM state in the engine's state cache (`_SlotState`), one row a slot
beside the page groups, which the programs take with the pools: a tick's
rows are the slots, a prefill's name theirs in `PagedState.slots`
(`slot_state_read` / `slot_state_write`; a prompt's first program reads
zeros, a padding row writes nothing). Layers that hold nothing of their
own (`_HOLD_NOTHING`: a cross layer, which reads another layer's pages,
and a gated memory unit) ride no table. A prefill's scan is the Pallas
`ssm_scan` on the Pallas path (`selective_scan`); what assumes that the
pages hold a slot's whole context refuses such a model by name.
"""
from __future__ import annotations

import collections
import contextlib
import math
import queue
import threading
import time
import weakref
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.core import compile_cache, jax_compat
from paddle_tpu.core.tensor import Tensor
from paddle_tpu import observability
from paddle_tpu.kernels import mla_decode as _mla
from paddle_tpu.kernels import mla_prefill as _mlp
from paddle_tpu.kernels import moe_grouped as _mg
from paddle_tpu.kernels import paged_attention as _pk
from paddle_tpu.kernels import ssm_scan as _ssm
from paddle_tpu.kernels.prefill_attention import (chunk_attention,
                                                  chunk_attention_problems)
from paddle_tpu.nn.functional.key_selection import index_scores, select_top
from paddle_tpu.observability import requests as obs_requests
from paddle_tpu.inference.overload import (DeadlineExceeded,
                                           EngineOverloaded,
                                           OverloadError,
                                           TenantQuotaExceeded)
from paddle_tpu.inference.disagg import DisaggStats, PageBundleEntry
from paddle_tpu.inference.kvtier import HostKVTier
from paddle_tpu.inference.prefix import PrefixCache, chain_keys
from paddle_tpu.inference.tenancy import WeightedFairScheduler

__all__ = ["PagedState", "paged_attention_update", "latent_attention_update",
           "paged_kv_store", "paged_attention_read", "slot_state_read",
           "slot_state_write", "selective_scan", "decode_kernel_scope",
           "PagedKVEngine"]


# the float32 scores that one prefill program's whole-window attend may
# hold (`PagedKVEngine._prefill_limit`): 16 rows x 32 heads x a 512
# bucket x a 768-token window is 0.75 GiB
_PREFILL_SCORE_BYTES = 2 ** 30

# the tokens of one prefill program of a model with latent attention: a
# call reads every weight once (6.3 GB at the serve cell's five layers,
# ~7.7 ms at the HBM's peak) and re-expands the slot's context (a prompt
# of n tokens expands about n^2 / (2 x this) latent rows a layer); at
# 2,048 a q block of the prefill kernel expands a key block for 1,024
# queries (2 x 512 x 128 MACs a key a head against 320 a (query, key)
# pair), and a prompt's last call pads half a program on average
_LATENT_PREFILL_TOKENS = 2048

# the tokens of one prefill program of a model with a state a slot (Mamba
# layers): the state rides from one program to the next, so the length is
# the work's. At 1,024 a program's matmuls (2 x 3.3 G weights x 1,024, 35 ms
# at the bf16 peak for the serve cell's 3.8 B model) already take 3.7 times
# the read of its weights (9.4 ms), so a longer one saves little, and the
# window layers' rings grow with it (`ring_pages_for`)
_STATE_PREFILL_TOKENS = 1024

# What a prefill program costs by its shape, in tokens (`prefill_width`),
# fitted once on a v5e over the dense serve cell's model (SmolLM2-1.7B in
# bf16, 3.4 GB of weights; 48 pages of 16 a slot) through the engine's own
# admission: `prefill_s` over 5 groups each, ms, of which 2.8 a group are
# its dispatch and wait (my chip runs, PR 34, calls 1 and 3).
# A call at width 1, by the slope from 8 to 16 calls under one wait, at
# buckets 8 .. 512: 5.00 4.97 5.12 5.59 6.64 9.36 16.37: one read of the
# weights (4.2 at the HBM's peak) up to ~128 tokens, then 0.032 a token.
# Two rows alone under one wait: 12.8 12.8 13.1 14.0 16.2 21.5 36.0.
# The program padded to 16 rows, two rows live or sixteen alike, at
# buckets 8 .. 128: 40.3 42.3 52.0 74.0 103.4 (172 / 325 at 256 / 512:
# PR 30); padded to 4 rows: 9.8 10.6 11.4 13.6 21.8. So the padded
# program is one read of the weights or its tokens, whichever is more,
# and beside that ~0.13 x rows x rows (33 at 16 rows, 2 at 4: every row,
# padding too, gathers its whole block-table window in float32 in every
# layer, and sixteen rows' windows no longer fit the fast memory).
_PREFILL_BALANCE_TOKENS = 128   # under these a call is one read of the weights
_PREFILL_ROWS_SQ_TOKENS = 3.3   # a padded program's rows x rows, each


def prefill_width(n, ppad, max_slots):
    """The width of the program that prefills `n` rows of one bucket of
    `ppad` tokens: `max_slots` (all rows in one call, the rest padding)
    or 1 (a call a row, back to back under one wait). In tokens, a call
    at width 1 costs max(B, ppad), B being the tokens under which it is
    one read of the weights, and the padded program max(B, max_slots x
    ppad) + Q x max_slots x max_slots, padding included (the constants
    above and their readings). A bucket's groups all take one width, the
    one that is cheaper for TWO rows, whatever their number: pairs are
    what an admission pass mostly finds, and a bucket that has prefilled
    one prompt alone and one pair has then compiled every program it
    will ask for (a storm never meets a first compile). So short rows on
    an engine of few slots ride the padded program (4 slots: up to 32
    tokens); many slots (from 7 on), or rows past B, where a row shares
    nothing with its neighbours and padding is pure cost, run at width
    1."""
    pair = 2 * max(_PREFILL_BALANCE_TOKENS, ppad)
    padded = (max(_PREFILL_BALANCE_TOKENS, max_slots * ppad)
              + _PREFILL_ROWS_SQ_TOKENS * max_slots * max_slots)
    return max_slots if n > 1 and pair > padded else 1


# the phases of a scheduler tick, in order: each is the span
# `engine.tick.<phase>` and one column of `PagedKVEngine.tick_log`
TICK_PHASES = ("retire", "admit", "alloc", "upload", "launch", "readback",
               "accept")


class PagedState(NamedTuple):
    """Per-call paged-cache coordinates, threaded through model forward
    as `cache_index` (a NamedTuple is a jax pytree, so it traces).

    block_tables: (b, max_pages) int32 — logical page j of slot i lives
        in physical page block_tables[i, j]; the engine keeps 0 as a
        never-allocated page so unallocated entries gather zeros
        (writes for invalid rows are DROPPED, never routed anywhere).
    lens: (b,) int32 — tokens already committed to the cache per slot.
    n_valid: (b,) int32 — how many of this call's `s` new tokens are
        real per slot (prefill: the unpadded prompt length; decode: 1
        for live slots, 0 for finished/empty ones — their writes are
        dropped).
    ring_tables: (b, ring_pages) int32, or None where no layer attends
        inside a window — the second page table, of the layers that do
        (`paged_attention_update(window=)`): position p of slot i lives
        in physical page ring_tables[i, (p // page_size) % ring_pages]
        of THOSE layers' pools, a ring that is never grown and whose
        pages are overwritten in place.
    slots: (b,) int32, or None where the rows are the engine's slots in
        order (a decode tick) — the slot whose fixed-size state (a Mamba
        layer's, `slot_state_read`) each row reads and writes; a row
        that is padding names one past the last slot, so its write drops.
    """
    block_tables: jnp.ndarray
    lens: jnp.ndarray
    n_valid: jnp.ndarray
    ring_tables: jnp.ndarray | None = None
    slots: jnp.ndarray | None = None


def ring_pages_for(window, tokens, page_size):
    """Pages a ring must hold so that a call of `tokens` new tokens a
    slot finds, after its write, every key of every one of them: the
    positions (t - window, t] of its first to its last token are window +
    tokens - 1 in a row, which from any offset in a page touch this many
    pages."""
    return (window + tokens + page_size - 3) // page_size + 1


def _val(x):
    return x._value if isinstance(x, Tensor) else jnp.asarray(x)


# -- decode-kernel selection (trace-time) -----------------------------------
# The engine's compiled programs pick the attend path at TRACE time via
# this thread-local scope: PagedKVEngine wraps its model calls in
# decode_kernel_scope(engine.decode_kernel, ...), and
# paged_attention_update reads the scope while tracing. Direct callers
# of the public op default to the jnp path (unchanged behavior).
_decode_cfg = threading.local()


@contextlib.contextmanager
def decode_kernel_scope(kind="jnp", interpret=False):
    """Select the decode attend path ("pallas" | "jnp") for
    paged_attention_update calls traced inside this scope. `interpret`
    runs the Pallas kernel in interpreter mode (CPU/tier-1)."""
    prev = getattr(_decode_cfg, "cfg", None)
    _decode_cfg.cfg = (kind, bool(interpret))
    try:
        yield
    finally:
        _decode_cfg.cfg = prev


def _decode_kernel_choice():
    """(kind, interpret) of the `decode_kernel_scope` this trace runs in,
    ("jnp", False) outside any: what `paged_attention_update` takes for a
    decode call."""
    return getattr(_decode_cfg, "cfg", None) or ("jnp", False)


def _token_coords(state: PagedState, s, page_size, num_pages, ring=False):
    """(physical page, offset in it) of each of this call's b * s tokens,
    flat; an invalid row's page points past the pool, so that a scatter
    with mode="drop" drops its write. `ring`: through the ring table,
    whose logical pages wrap."""
    bt, lens, n_valid = (_val(state.ring_tables if ring
                              else state.block_tables),
                         _val(state.lens), _val(state.n_valid))
    b = bt.shape[0]
    pos = lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # (b,s)
    valid = jnp.arange(s, dtype=jnp.int32)[None, :] < n_valid[:, None]
    logical = pos // page_size
    logical = (logical % bt.shape[1] if ring
               else jnp.clip(logical, 0, bt.shape[1] - 1))
    phys = jnp.take_along_axis(bt, logical, axis=1)          # (b, s)
    # invalid rows: point past the pool and DROP the write (r5 review:
    # routing them to page 0 corrupted callers whose block tables
    # legitimately allocate page 0 — the public op has no trash-page
    # reservation; the engine's page-0 convention is gather-only)
    phys = jnp.where(valid, phys, num_pages)
    off = pos % page_size
    return phys.reshape(b * s), off.reshape(b * s)


def _scatter_kv(kp, vp, k, v, state: PagedState, k_scale=None,
                v_scale=None, ring=False):
    """Scatter this call's (b, s, hk, d) k/v into their pages (`ring`:
    the pages of the ring table, `_token_coords`).

    Plain pools: inside `decode_kernel_scope("pallas")`, pools stored as
    the decode kernel's rows (`kernels/paged_attention.py
    pool_rows_shape`: what a Pallas engine's K and V pools are) take the
    Pallas write, which aliases them (`paged_kv_write`, interpreted where
    the scope says so): the write follows the attend, so that no XLA op
    touches such a pool and none relays it. Anything else is one
    vectorized XLA scatter per pool, over the pool seen as (num_pages, hk,
    page_size, d), the same values at the same places. int8 pools (k_scale/
    v_scale present, (num_pages, hk) f32): quantize AT SCATTER TIME —
    per-page-per-head symmetric scales grow monotonically (scatter-max
    of |token|/127 into the touched pages), previously written int8
    content of a touched page is RESCALED in one gather->round->scatter
    pass (old/new scale ratio), and the new tokens quantize with the
    final scale. The f32/bf16 pool never exists in HBM; only the
    touched pages (<= b*s of them) move.

    Returns (kp, vp, k_scale, v_scale) — scales None when unquantized.
    """
    b, s, hk, d = k.shape
    num_pages = kp.shape[0]
    page_size = _pk.page_size_of(kp, hk, d)
    phys_f, off_f = _token_coords(state, s, page_size, num_pages, ring)

    if k_scale is None:
        kind, interpret = _decode_kernel_choice()
        if kind == "pallas" and kp.shape == _pk.pool_rows_shape(
                num_pages, hk, d, page_size, kp.dtype):
            kp, vp = _pk.paged_kv_write(kp, vp, k, v, phys_f, off_f,
                                        interpret=interpret)
            return kp, vp, None, None

        def scatter(pool, toks):
            by_head = pool.reshape(num_pages, hk, page_size, d)
            return by_head.at[phys_f, :, off_f, :].set(
                toks.reshape(b * s, hk, d).astype(pool.dtype),
                mode="drop").reshape(pool.shape)

        return scatter(kp, k), scatter(vp, v), None, None

    def quant_scatter(pool, scale, toks):
        toks = toks.reshape(b * s, hk, d).astype(jnp.float32)
        cand = jnp.max(jnp.abs(toks), axis=-1) / 127.0       # (b*s, hk)
        new_scale = scale.at[phys_f].max(cand, mode="drop")
        idx = jnp.minimum(phys_f, num_pages - 1)  # clamp gathers only;
        #                          invalid rows' writes still DROP below
        old_g = scale[idx]                                   # (b*s, hk)
        new_g = new_scale[idx]
        ratio = jnp.where(new_g > 0,
                          old_g / jnp.maximum(new_g, 1e-30), 0.0)
        pages = pool[idx].astype(jnp.float32) \
            * ratio[:, :, None, None]                # (b*s, hk, ps, d)
        pages = jnp.clip(jnp.round(pages), -127, 127).astype(pool.dtype)
        pool = pool.at[phys_f].set(pages, mode="drop")
        qtok = jnp.clip(
            jnp.round(toks / jnp.maximum(new_g, 1e-30)[:, :, None]),
            -127, 127).astype(pool.dtype)
        pool = pool.at[phys_f, :, off_f, :].set(qtok, mode="drop")
        return pool, new_scale

    kp, k_scale = quant_scatter(kp, k_scale, k)
    vp, v_scale = quant_scatter(vp, v_scale, v)
    return kp, vp, k_scale, v_scale


def _attend_pages(q, kp, vp, state: PagedState, k_scale=None,
                  v_scale=None, hk=None, block=0, scale=None):
    """jnp fallback attend: gather each slot's page window and run a
    dense masked softmax in f32. GQA folds query heads into a head-
    group axis (reshape + einsum) instead of jnp.repeat-ing K/V —
    the gathered window is never materialized hq/hk times.

    q: (b, s, hq, d). `hk`: the pools' kv heads, where they are stored
    as rows and do not say (the gathered window is seen by heads and
    tokens, never the pool). `block`: causal by blocks of `block`
    positions, a query sees every key of its own block. `scale`: the
    scores' factor, 1 / sqrt(d) by default. Returns (b, s, hq*d) in
    q.dtype.
    """
    bt, lens = _val(state.block_tables), _val(state.lens)
    b, s, hq, d = q.shape
    hk = hk or kp.shape[1]
    pos = lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    if block:
        pos = (pos // block + 1) * block - 1    # its block's last position

    # window column c IS logical position c (page j holds positions
    # [j*page_size, (j+1)*page_size)), so the causal bound is c <= pos.
    ks, vs = _gathered(kp, bt, hk, d), _gathered(vp, bt, hk, d)
    L = ks.shape[2]
    ks = ks.astype(jnp.float32)
    vs = vs.astype(jnp.float32)
    if k_scale is not None:
        # dequantize the gathered window: per-page-per-head scales
        # broadcast over (page_size, d) — (b, mp, hk) -> (b, hk, L, 1)
        ps = L // bt.shape[1]
        ksg = jnp.repeat(jnp.swapaxes(k_scale[bt], 1, 2), ps,
                         axis=2)[..., None]
        vsg = jnp.repeat(jnp.swapaxes(v_scale[bt], 1, 2), ps,
                         axis=2)[..., None]
        ks = ks * ksg
        vs = vs * vsg
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)           # (b,hq,s,d)
    col = jnp.arange(L, dtype=jnp.int32)[None, None, None, :]
    mask = col <= pos[:, None, :, None]                      # (b,1,s,L)
    if hq != hk:
        g = hq // hk
        qg = qt.reshape(b, hk, g, s, d)
        scores = jnp.einsum("bhgsd,bhcd->bhgsc", qg, ks)
        scores = scores / math.sqrt(d) if scale is None else scores * scale
        scores = jnp.where(mask[:, :, None], scores, -1e9)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhgsc,bhcd->bhgsd", p, vs)
        out = out.reshape(b, hq, s, d)
    else:
        scores = jnp.einsum("bhsd,bhcd->bhsc", qt, ks)
        scores = scores / math.sqrt(d) if scale is None else scores * scale
        scores = jnp.where(mask, scores, -1e9)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhsc,bhcd->bhsd", p, vs)
    return jnp.swapaxes(out, 1, 2).reshape(b, s, hq * d).astype(q.dtype)


# what one block of scores of the selected-keys attend may take, in
# float32 bytes: its key blocks are sized from the shapes under it
_SELECT_SCORE_BYTES = 256 * 2 ** 20


def _index_scores(qi, w, ip, state: PagedState):
    """The learned index scores of this call's tokens against every key of
    their slot's page window -> (b, s, L) float32. qi (b, s, hi, di), w
    (b, s, hi), ip the index pool (num_pages, 1, page_size, di), one key a
    token."""
    bt = _val(state.block_tables)
    ks = ip[bt].reshape(bt.shape[0], -1, qi.shape[-1])       # (b, L, di)
    return index_scores(qi, ks, w, _SELECT_SCORE_BYTES)


def _select_keys(qi, w, ip, state: PagedState, topk):
    """(b, s, L) bool: the keys each of this call's tokens attends over
    (the reference's S_t), among the columns of its slot's page window."""
    lens = _val(state.lens)
    s = qi.shape[1]
    with jax.named_scope("indexer"):
        scores = _index_scores(qi, w, ip, state)
    with jax.named_scope("select"):
        pos = lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        col = jnp.arange(scores.shape[-1], dtype=jnp.int32)
        causal = col[None, None, :] <= pos[:, :, None]
        return select_top(scores, causal, topk)


def _attend_selected(q, kp, vp, state: PagedState, select, hk=None,
                     scale=None):
    """Attend over the selected keys only: `select` (b, s, L) bool over
    the columns of each slot's page window (causality is in it). The keys
    go by in blocks of whole pages under an online softmax, as many blocks
    as the longest row reaches, so a long window costs no more memory than
    one block's scores (`_SELECT_SCORE_BYTES`, from the shapes) and a
    prefill chunk does not pay for the window's empty end.

    q: (b, s, hq, d); `hk` and `scale` as in `_attend_pages`. Returns
    (b, s, hq*d) in q.dtype."""
    bt, lens, n_valid = (_val(state.block_tables), _val(state.lens),
                         _val(state.n_valid))
    b, s, hq, d = q.shape
    hk = hk or kp.shape[1]
    ps = _pk.page_size_of(kp, hk, d)
    g, mp = hq // hk, bt.shape[1]
    # pages a block: the largest power of two whose scores fit
    bp = 1
    while bp * 2 <= mp and b * hq * s * bp * 2 * ps * 4 \
            <= _SELECT_SCORE_BYTES:
        bp *= 2
    nblk = -(-mp // bp)
    cols = bp * ps
    btp = jnp.pad(bt, ((0, 0), (0, nblk * bp - mp)))
    sel = jnp.pad(select, ((0, 0), (0, 0), (0, nblk * cols - mp * ps)))
    qg = jnp.moveaxis(q.reshape(b, s, hk, g, d), 1, 3)     # (b,hk,g,s,d)
    scale = scale or 1.0 / math.sqrt(d)

    def block(i, carry):
        m, l, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(btp, i * bp, bp, 1)
        kb, vb = (jnp.moveaxis(_pk.pages_by_head(pool[pages], hk, d), 2,
                               1).reshape(b, hk, cols, d)
                  for pool in (kp, vp))
        seen = jax.lax.dynamic_slice_in_dim(sel, i * cols, cols, 2)
        seen = seen[:, None, None]                          # (b,1,1,s,c)
        sc = jnp.einsum("bhgsd,bhcd->bhgsc", qg, kb.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
        sc = jnp.where(seen, sc, -1e30)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhgsc,bhcd->bhgsd", p.astype(q.dtype), vb.astype(q.dtype),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    reach = jnp.max(lens + jnp.maximum(n_valid, 1))         # keys in use
    need = jnp.clip(-(-reach // cols), 1, nblk)
    shape = (b, hk, g, s, 1)
    _m, l, acc = jax.lax.fori_loop(
        0, need, block,
        (jnp.full(shape, -1e30, jnp.float32), jnp.zeros(shape, jnp.float32),
         jnp.zeros((b, hk, g, s, d), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)
    return jnp.moveaxis(out, 3, 1).reshape(b, s, hq * d).astype(q.dtype)


def _attend_indexed(q, k, v, cache, state: PagedState, index):
    """The three-pool form of `paged_attention_update`: K, V and one index
    key a token under the same block table; this call's index keys are
    written where its k and v are, the keys each token attends over are
    selected from the index scores (`index`: qi (b, s, hi, di), ki (b, s,
    di), w (b, s, hi), topk), and attention runs over those alone."""
    kp, vp, ip = (_val(c) for c in cache)
    qi, ki, w, topk = index
    qi, ki, w = _val(qi), _val(ki), _val(w)
    b, s, hq, d = q.shape
    hk = k.shape[2]
    with jax.named_scope("kv_write"):
        kp, vp, _ks, _vs = _scatter_kv(kp, vp, k, v, state)
        phys_f, off_f = _token_coords(state, s, ip.shape[2], ip.shape[0])
        ip = ip.at[phys_f, 0, off_f, :].set(
            ki.reshape(b * s, -1).astype(ip.dtype), mode="drop")
    select = _select_keys(qi, w, ip, state, topk)
    kind, interpret = _decode_kernel_choice()
    with jax.named_scope("paged_attn"):
        if kind == "pallas" and s == 1:
            out = _pk.paged_decode_attention(
                q[:, 0], kp, vp, _val(state.block_tables),
                _val(state.lens), interpret=interpret,
                select=select[:, 0], kv_heads=hk)
            out = out[:, None].reshape(b, s, hq * d).astype(q.dtype)
        else:
            out = _attend_selected(q, kp, vp, state, select, hk)
    return Tensor(out), (Tensor(kp), Tensor(vp), Tensor(ip))


def _gathered(pool, table, hk, d):
    """The pages `table` (b, P) names, in its order, as a row's keys in
    order: (b, hk, P * page_size, d)."""
    b = table.shape[0]
    return jnp.moveaxis(_pk.pages_by_head(pool[table], hk, d), 2,
                        1).reshape(b, hk, -1, d)


def _chunk_kernel_takes(q, hk):
    """Whether this call's many-token attend goes through the Pallas chunk
    kernel: inside `decode_kernel_scope("pallas")`, for shapes the kernel
    takes (`chunk_attention_problems`)."""
    kind, interpret = _decode_kernel_choice()
    _b, s, hq, d = q.shape
    return kind == "pallas" and s > 1 \
        and not chunk_attention_problems(s, hq, hk, d, interpret)


def _ring_view(state: PagedState, s, window, page_size):
    """What a layer that attends inside a window reads of its ring, after
    the call's write: the pages that hold the positions (t - window, t] of
    the call's first to its last token, in order, as a block table of
    their own -> (pages (b, P), the first token's column (b,), seen (b, s,
    P * page_size) bool). Column c of row i is position first_page_i *
    page_size + c, so columns are tokens in order and a ring that has
    wrapped reads like a table that has not; a page of the ring outside
    those P is not read."""
    rt, lens = _val(state.ring_tables), _val(state.lens)
    ring = rt.shape[1]
    # a ring shorter than that holds every context whole (the engine caps
    # it at a slot's longest): its columns past a context are never seen
    pages = min(ring_pages_for(window, s, page_size), ring)
    first = jnp.maximum(lens - window + 1, 0) // page_size          # (b,)
    logical = first[:, None] + jnp.arange(pages, dtype=jnp.int32)[None]
    table = jnp.take_along_axis(rt, logical % ring, axis=1)
    col = first[:, None] * page_size \
        + jnp.arange(pages * page_size, dtype=jnp.int32)[None]     # (b, C)
    t = lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None]        # (b, s)
    seen = (col[:, None, :] <= t[:, :, None]) \
        & (col[:, None, :] > t[:, :, None] - window)
    return table, lens - first * page_size, seen


def _attend_window(q, k, v, cache, state: PagedState, window, scale=None):
    """The ring form of `paged_attention_update`: this call's k and v go
    into the ring's pages, over what they held a ring's length ago, and
    each token attends over the `window` newest positions up to its own
    (itself included) and nothing else, through `_ring_view`: the decode
    kernel and the blocked attend take the view as they take a key
    selection."""
    if len(cache) != 2:
        raise ValueError("a window layer's cache is (k_pool, v_pool): "
                         "neither scale planes nor an index pool ride a "
                         "ring")
    if state.ring_tables is None:
        raise ValueError(
            "paged_attention_update(window=) reads PagedState.ring_tables: "
            "the page table of the layers that attend inside a window "
            "(PagedKVEngine builds it from the model's config)")
    kp, vp = _val(cache[0]), _val(cache[1])
    b, s, hq, d = q.shape
    hk = k.shape[2]
    with jax.named_scope("kv_write"):
        kp, vp, _ks, _vs = _scatter_kv(kp, vp, k, v, state, ring=True)
    table, at, seen = _ring_view(state, s, int(window),
                                 _pk.page_size_of(kp, hk, d))
    kind, interpret = _decode_kernel_choice()
    with jax.named_scope("paged_attn"):
        if kind == "pallas" and s == 1:
            out = _pk.paged_decode_attention(
                q[:, 0], kp, vp, table, at, interpret=interpret,
                select=seen[:, 0], kv_heads=hk, sm_scale=scale)
            out = out[:, None].reshape(b, s, hq * d).astype(q.dtype)
        elif _chunk_kernel_takes(q, hk):
            # the view's columns are positions in order from its first
            # page on: the kernel's mask is that rule, not `seen`
            lens = _val(state.lens)
            out = chunk_attention(
                q, _gathered(kp, table, hk, d), _gathered(vp, table, hk, d),
                lens, lens - at, window=window, sm_scale=scale,
                interpret=interpret)
        else:
            out = _attend_selected(
                q, kp, vp, PagedState(table, at, _val(state.n_valid)),
                seen, hk, scale)
    return Tensor(out), (Tensor(kp), Tensor(vp))


def _fold_rows(q, hk):
    """A block's s query rows a slot as ONE decode row of s times the
    heads: (b, s, hq, d) -> (b, hk * g * s, d), the s rows of a query head
    beside each other in the group of its kv head, which is where the
    decode kernel folds a group's heads (`_unfold_rows` is the way back)."""
    b, s, hq, d = q.shape
    return jnp.transpose(q.reshape(b, s, hk, hq // hk, d),
                         (0, 2, 3, 1, 4)).reshape(b, hq * s, d)


def _unfold_rows(out, s, hk):
    """(b, hk * g * s, d) of `_fold_rows` -> (b, s, hq * d)."""
    b, rows, d = out.shape
    return jnp.transpose(out.reshape(b, hk, rows // (hk * s), s, d),
                         (0, 3, 1, 2, 4)).reshape(b, s, rows // s * d)


def paged_attention_update(q, k, v, cache, state: PagedState, index=None,
                           window=None, block=None, scale=None):
    """Write this call's k/v into the slot's pages, then attend over the
    slot's whole paged window. One code path serves BOTH phases of the
    reference contract (block_multi_head_attention_kernel.cu's prefill
    and decode): prefill is s=prompt tokens at lens=0, decode is s=1.

    q: (b, s, hq, d), k/v: (b, s, hk, d) — already position-encoded.
    cache: (k_pool, v_pool), each (num_pages, hk, page_size, d), or
    stored as the decode kernel's rows (module doc: a Pallas engine's
    own pools; the geometry is read from k and the pool's size) — or,
    for int8 KV quantization, (k_pool, v_pool, k_scale, v_scale) with
    int8 pools and (num_pages, hk) f32 per-page-per-head scales — or,
    for a learned key selection, (k_pool, v_pool, index_pool) with one
    index key a token, (num_pages, 1, page_size, di), and `index` (this
    call's index queries, index keys, head weights and topk:
    `_attend_indexed`).
    `window`: this layer attends over the newest `window` positions alone
    and its pools ride `state.ring_tables`, not the block table
    (`_attend_window`); `scale` is then the scores' factor (1 / sqrt(d)
    by default).
    `block`: attention is causal by blocks of `block` positions (generation
    by diffusion over blocks): a query sees every key up to the last
    position of its own block, later rows of this call included. A call
    of s == block rows at a block's start is a denoising step: its K and
    V overwrite what an earlier step of the same block left at lens ..
    lens + block - 1, and every row attends over lens + block keys; inside
    `decode_kernel_scope("pallas")` it rides the decode kernel with the
    rows folded beside the heads of their kv head (`_fold_rows`: no mask
    inside the kernel but the length's). Plain two-pool caches only.
    Returns (out (b, s, hq*d), new cache of the SAME arity).

    Decode calls (s == 1) traced inside
    `decode_kernel_scope("pallas")` take the Pallas paged-decode
    kernel (kernels/paged_attention.py); everything else — prefill,
    speculative verify, direct callers — runs the jnp gather/softmax
    path. Inside that scope every call's write into plain pools
    stored as rows is the Pallas write (`_scatter_kv`), whatever s.
    All index math is traced (block tables / lens are device
    data), so this runs under jit — unlike the eager op's host-numpy
    bookkeeping.
    """
    q, k, v = _val(q), _val(k), _val(v)
    if block and (window or len(cache) != 2):
        raise ValueError("paged_attention_update(block=) takes a plain "
                         "(k_pool, v_pool) cache under one block table")
    if window:
        return _attend_window(q, k, v, cache, state, window, scale)
    if scale is not None:
        raise ValueError("paged_attention_update(scale=) is a window "
                         "layer's: other calls score at 1 / sqrt(d)")
    if len(cache) == 3:
        if index is None:
            raise ValueError(
                "a 3-tuple cache is (k_pool, v_pool, index_pool): pass "
                "index=(q_index, k_index, head_weights, topk)")
        return _attend_indexed(q, k, v, cache, state, index)
    quantized = len(cache) == 4
    kp, vp = _val(cache[0]), _val(cache[1])
    k_scale = _val(cache[2]) if quantized else None
    v_scale = _val(cache[3]) if quantized else None
    if kp.dtype == jnp.int8 and not quantized:
        raise ValueError(
            "int8 k/v pools need a 4-tuple cache (k_pool, v_pool, "
            "k_scale, v_scale); got a 2-tuple — pass the per-page "
            "scales (see PagedKVEngine(kv_dtype='int8'))")
    b, s, hq, d = q.shape
    hk = k.shape[2]

    # the scopes are metadata of the compiled ops: a device trace can
    # tell the pool writes (and the copies XLA makes for them) from the
    # attention proper
    with jax.named_scope("kv_write"):
        kp, vp, k_scale, v_scale = _scatter_kv(kp, vp, k, v, state,
                                               k_scale, v_scale)

    kind, interpret = _decode_kernel_choice()
    with jax.named_scope("paged_attn"):
        if kind == "pallas" and s == 1:
            # the query position is lens (this token's k/v just landed
            # there); the kernel masks cols <= lens and skips pages
            # past it
            out = _pk.paged_decode_attention(
                q[:, 0], kp, vp, _val(state.block_tables),
                _val(state.lens), k_scale=k_scale, v_scale=v_scale,
                interpret=interpret, kv_heads=hk)
            out = out[:, None].reshape(b, s, hq * d).astype(q.dtype)
        elif kind == "pallas" and block and s == block:
            # a denoising step: every row sees the keys up to the block's
            # end, lens + block - 1, which is the kernel's length mask
            out = _unfold_rows(_pk.paged_decode_attention(
                _fold_rows(q, hk), kp, vp, _val(state.block_tables),
                _val(state.lens) + (block - 1), interpret=interpret,
                kv_heads=hk), s, hk).astype(q.dtype)
        elif not quantized and _chunk_kernel_takes(q, hk):
            # a chunk's keys are the slot's pages from the first on, under
            # the kernel a window layer's chunk takes; the choice is the
            # call's own (the scope, the shapes), whatever the model's
            # other layers are
            bt = _val(state.block_tables)
            out = chunk_attention(
                q, _gathered(kp, bt, hk, d), _gathered(vp, bt, hk, d),
                _val(state.lens), jnp.zeros_like(_val(state.lens)),
                block=block or 0, interpret=interpret)
        else:
            # what the chunk kernel does not take: int8 pools, a chunk
            # that is no whole sublane tile, the jnp scope, and on the
            # chip a head width that is no multiple of 128 (the dense
            # cell's 64). This fork is kept only so that such a model's
            # lowered prefill stays byte-equal until ROADMAP M3's PR
            # measures it through the kernel and deletes `_attend_pages`
            # with the score budget (`PagedKVEngine._prefill_limit`)
            out = _attend_pages(q, kp, vp, state, k_scale, v_scale, hk,
                                block or 0)
    if quantized:
        return Tensor(out), (Tensor(kp), Tensor(vp),
                             Tensor(k_scale), Tensor(v_scale))
    return Tensor(out), (Tensor(kp), Tensor(vp))


def paged_kv_store(k, v, cache, state: PagedState):
    """Write this call's k/v (b, s, hk, d) into the slot's pages under the
    block table, and attend nothing: the write of a layer whose queries
    run at fewer positions than its keys (`paged_attention_read`).
    Returns the new (k_pool, v_pool)."""
    if len(cache) != 2:
        raise ValueError("paged_kv_store takes a plain (k_pool, v_pool) "
                         "cache")
    with jax.named_scope("kv_write"):
        kp, vp, _ks, _vs = _scatter_kv(_val(cache[0]), _val(cache[1]),
                                       _val(k), _val(v), state)
    return Tensor(kp), Tensor(vp)


def paged_attention_read(q, cache, state: PagedState, pos, hk,
                         scale=None):
    """One query a row over pages another call wrote, under the block
    table: q (b, hq, d) at position `pos` (b,) attends over the keys 0 ..
    pos of the pools' `hk` heads and writes nothing, through the decode
    kernel inside `decode_kernel_scope("pallas")` (its length is `pos`),
    the jnp attend else. `scale`: the scores' factor, 1 / sqrt(d) by
    default. Returns (b, hq * d) in q.dtype."""
    kp, vp = _val(cache[0]), _val(cache[1])
    q, pos = _val(q), _val(pos)
    b, hq, d = q.shape
    bt = _val(state.block_tables)
    kind, interpret = _decode_kernel_choice()
    with jax.named_scope("paged_attn"):
        if kind == "pallas":
            out = _pk.paged_decode_attention(
                q, kp, vp, bt, pos, interpret=interpret, kv_heads=hk,
                sm_scale=scale)
            return out.reshape(b, hq * d).astype(q.dtype)
        out = _attend_pages(q[:, None], kp, vp, PagedState(bt, pos, pos),
                            hk=hk, scale=scale)
    return out[:, 0]


def slot_state_read(cache, state: PagedState):
    """A layer's fixed-size state a slot (a Mamba layer's conv window and
    SSM state: `cache`, each (slots, ...)), as this call's rows: a tick's
    rows are the slots, a prefill's rows read `state.slots`, and a row
    that starts a prompt (lens 0) starts from zeros."""
    arrays = [_val(a) for a in cache]
    if state.slots is None:
        return arrays
    slots = _val(state.slots)
    fresh = _val(state.lens) == 0
    return [jnp.where(fresh.reshape((-1,) + (1,) * (a.ndim - 1)), 0,
                      a[jnp.clip(slots, 0, a.shape[0] - 1)])
            for a in arrays]


def slot_state_write(cache, rows, state: PagedState):
    """The inverse of `slot_state_read`: a tick's rows ARE the new state
    (a dead row's step leaves its own as it was: the model's), a
    prefill's rows go back to their slots, a padding row's write drops.
    Returns the new cache."""
    if state.slots is None:
        return tuple(Tensor(r.astype(_val(a).dtype))
                     for a, r in zip(cache, rows))
    slots = _val(state.slots)
    return tuple(Tensor(_val(a).at[slots].set(r.astype(_val(a).dtype),
                                              mode="drop"))
                 for a, r in zip(cache, rows))


def selective_scan(u, delta, bt, ct, at, d, state0):
    """A Mamba layer's selective scan over this call's tokens
    (kernels/ssm_scan.py has the shapes): the Pallas `ssm_scan` inside
    `decode_kernel_scope("pallas")` for a many-token call whose shapes it
    takes, the `lax.scan` else (a decode step's one token). -> (y (b, s,
    E) float32, the final state (b, N, E) float32)."""
    kind, interpret = _decode_kernel_choice()
    _b, s, e = u.shape
    if kind == "pallas" and s > 1 and not _ssm.ssm_scan_problems(
            s, e, at.shape[0], interpret):
        return _ssm.ssm_scan(u, delta, bt, ct, at, d, state0,
                             interpret=interpret)
    return _ssm.ssm_scan_reference(u, delta, bt, ct, at, d, state0)


def _latent_rows(c, k_pe, width):
    """A latent pool's rows of this call's tokens: [c | k_pe], zeros up to
    the pool's `width` (`kernels/mla_decode.py kernel_row_width`)."""
    row = jnp.concatenate([c, k_pe.astype(c.dtype)], axis=-1)
    return jnp.pad(row, ((0, 0), (0, 0), (0, width - row.shape[-1])))


def latent_block_pages(rows, heads, s, max_pages, page_size):
    """Pages of a slot one block of the expanded attend takes: the largest
    power of two whose float32 scores (rows x heads x s x keys) fit
    `_SELECT_SCORE_BYTES`. The engine counts a prefill's expanded rows by
    the same blocks (`mla_expand_rows`)."""
    bp = 1
    while bp * 2 <= max_pages and rows * heads * s * bp * 2 * page_size \
            * 4 <= _SELECT_SCORE_BYTES:
        bp *= 2
    return bp


def _attend_latent_expanded(q_nope, q_pe, pool, w_uk, w_uv, state, scale):
    """The expanded form over a latent pool: the keys go by in blocks of
    whole pages under an online softmax, as many blocks as the longest row
    reaches, and each block's latent rows are expanded through W_uk, W_uv
    inside the loop, so neither the scores of the whole context nor its
    expanded keys and values are ever held (a block's: `latent_block_pages`).
    q_nope (b, s, H, nope), q_pe (b, s, H, rope); -> (b, s, H * v)."""
    bt, lens, n_valid = (_val(state.block_tables), _val(state.lens),
                         _val(state.n_valid))
    b, s, h, _ = q_nope.shape
    rank, rope = w_uk.shape[0], q_pe.shape[-1]
    ps, mp = pool.shape[1], bt.shape[1]
    bp = latent_block_pages(b, h, s, mp, ps)
    nblk = -(-mp // bp)
    cols = bp * ps
    btp = jnp.pad(bt, ((0, 0), (0, nblk * bp - mp)))
    pos = lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    dt = q_nope.dtype
    w_uk, w_uv = w_uk.astype(dt), w_uv.astype(dt)

    def block(i, carry):
        m, l, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(btp, i * bp, bp, 1)
        rows = pool[pages].reshape(b, cols, -1).astype(dt)
        c, k_pe = rows[..., :rank], rows[..., rank:rank + rope]
        k_nope = jnp.einsum("bcr,rhn->bhcn", c, w_uk)
        v = jnp.einsum("bcr,rhv->bhcv", c, w_uv)
        sc = (jnp.einsum("bshn,bhcn->bhsc", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshr,bcr->bhsc", q_pe, k_pe,
                           preferred_element_type=jnp.float32)) * scale
        col = i * cols + jnp.arange(cols, dtype=jnp.int32)
        seen = (col[None, None, :] <= pos[:, :, None])[:, None]  # (b,1,s,c)
        sc = jnp.where(seen, sc, -1e30)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhsc,bhcv->bhsv", p.astype(dt), v,
                                       preferred_element_type=jnp.float32)
        return m_new, l, acc

    reach = jnp.max(lens + jnp.maximum(n_valid, 1))         # keys in use
    need = jnp.clip(-(-reach // cols), 1, nblk)
    shape = (b, h, s, 1)
    _m, l, acc = jax.lax.fori_loop(
        0, need, block,
        (jnp.full(shape, -1e30, jnp.float32), jnp.zeros(shape, jnp.float32),
         jnp.zeros((b, h, s, w_uv.shape[-1]), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)
    return jnp.moveaxis(out, 1, 2).reshape(b, s, -1).astype(dt)


def _latent_prefill_kernel(kind, interpret, s, nope, rope, dv, rank, pool):
    """Whether a prefill call of `s` tokens a row takes the prefill kernel
    (kernels/mla_prefill.py) under `decode_kernel_scope(kind, interpret)`,
    over `pool` (anything with the pool's shape and dtype); else it takes
    `_attend_latent_expanded`."""
    return kind == "pallas" and not _mlp.mla_prefill_problems(
        s, nope, rope, dv, rank, pool.shape[2], pool.shape[1], pool.dtype,
        interpret)


def latent_expanded_rows(kind, interpret, lens, n_valid, s, *, heads, nope,
                         rope, dv, rank, pool, max_pages):
    """Latent rows one layer of a prefill call expands through W_kvb, by
    the blocks of the path `latent_attention_update` takes under
    `decode_kernel_scope(kind, interpret)`: the kernel's (each q block's
    key blocks up to its last valid query) or `_attend_latent_expanded`'s
    (each row's blocks of pages up to the longest row's reach)."""
    ps = pool.shape[1]
    if _latent_prefill_kernel(kind, interpret, s, nope, rope, dv, rank,
                              pool):
        return _mlp.expanded_rows(lens, n_valid, s, max_pages, ps)
    rows = len(lens)
    bp = latent_block_pages(rows, heads, s, max_pages, ps)
    cols = bp * ps
    reach = int(np.max(np.asarray(lens) + np.maximum(n_valid, 1)))
    return rows * cols * min(max(-(-reach // cols), 1), -(-max_pages // bp))


def latent_attention_update(q_nope, q_pe, c, k_pe, w_uk, w_uv, cache,
                            state: PagedState, *, scale):
    """Write this call's latent rows into the slot's pages, then attend
    over the slot's whole paged window: the cache of a layer with latent
    attention (`models/latent_attn_moe.py`), ONE row [c | k_pe] a token,
    `cache` = (pool,) with pool (num_pages, page_size, width), width the
    row's values or, where the decode kernel reads it, whole lane tiles
    (`kernels/mla_decode.py kernel_row_width`, zeros past the row).

    q_nope (b, s, H, nope), q_pe (b, s, H, rope), c (b, s, rank), k_pe
    (b, s, rope), all position-encoded; w_uk (rank, H, nope), w_uv (rank,
    H, v): W_kvb by heads. A decode call (s == 1) takes the absorbed form:
    q~ = W_uk q_nope under the scope `mla_absorb`, the kernel (inside
    `decode_kernel_scope("pallas")`) or `mla_decode_reference` over every
    head's [q~ | q_pe] against the rows, o = õ W_uv under `mla_unabsorb`.
    Anything longer (a prefill chunk) takes the expanded form under the
    scope `mla_prefill`: inside `decode_kernel_scope("pallas")` the kernel
    of `kernels/mla_prefill.py`, which copies the slot's pages a key block
    at a time and expands each block's keys and values in VMEM, else
    `_attend_latent_expanded` (`_latent_prefill_kernel` chooses). Returns
    (out (b, s, H * v), the new cache)."""
    q_nope, q_pe, c, k_pe = (_val(a) for a in (q_nope, q_pe, c, k_pe))
    (pool,) = (_val(a) for a in cache)
    b, s, h, _ = q_nope.shape
    rank = c.shape[-1]
    ps, width = pool.shape[1], pool.shape[2]
    with jax.named_scope("kv_write"):
        phys_f, off_f = _token_coords(state, s, ps, pool.shape[0])
        pool = pool.at[phys_f, off_f, :].set(
            _latent_rows(c, k_pe, width).reshape(b * s, width).astype(
                pool.dtype), mode="drop")
    kind, interpret = _decode_kernel_choice()
    if s > 1:
        with jax.named_scope("mla_prefill"):
            if _latent_prefill_kernel(kind, interpret, s, q_nope.shape[-1],
                                      q_pe.shape[-1], w_uv.shape[-1], rank,
                                      pool):
                out = _mlp.mla_prefill_attention(
                    q_nope, q_pe, pool, _val(state.block_tables), w_uk,
                    w_uv, _val(state.lens), _val(state.n_valid),
                    sm_scale=scale, interpret=interpret)
            else:
                out = _attend_latent_expanded(q_nope, q_pe, pool, w_uk,
                                              w_uv, state, scale)
        return Tensor(out), (Tensor(pool),)
    with jax.named_scope("mla_absorb"):
        q_abs = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk.astype(
            q_nope.dtype), preferred_element_type=jnp.float32)
        q_cat = _latent_rows(q_abs.astype(pool.dtype), q_pe[:, 0], width)
    # a slot with no new token reads nothing (the kernel's negative length)
    lens = jnp.where(_val(state.n_valid) > 0, _val(state.lens), -1)
    with jax.named_scope("paged_attn"):
        if kind == "pallas":
            o_abs = _mla.mla_decode_attention(
                q_cat, pool, _val(state.block_tables), lens, rank=rank,
                sm_scale=scale, interpret=interpret)
        else:
            o_abs = _mla.mla_decode_reference(
                q_cat, pool, _val(state.block_tables), lens, rank=rank,
                sm_scale=scale)
    with jax.named_scope("mla_unabsorb"):
        out = jnp.einsum("bhr,rhv->bhv", o_abs.astype(q_nope.dtype),
                         w_uv.astype(q_nope.dtype),
                         preferred_element_type=jnp.float32)
    return Tensor(out.reshape(b, 1, -1).astype(q_nope.dtype)), (Tensor(pool),)


def _last_valid_logits(lv, n_valid):
    """(bw, v): each prefill row's logits at its last valid token, from
    the model's (bw, s, v) — or from (bw, 1, v) where the model projected
    that token alone (a prefill needs no other; at a large vocabulary the
    other rows' logits are most of a chunk's work)."""
    if lv.shape[1] == 1:
        return lv[:, 0]
    idxs = jnp.clip(n_valid - 1, 0, lv.shape[1] - 1)
    return jnp.take_along_axis(lv, idxs[:, None, None], axis=1)[:, 0]


def _process_logits_rowwise(x, temp, topk, topp):
    """Row-vectorized twin of generation._process_logits_traced:
    temperature/top_k/top_p are PER-SLOT traced vectors (b,), so one
    compiled tick serves a batch of heterogeneous sampling configs.
    Filters disable themselves per row (top_k<=0 or >=v, top_p>=1)."""
    x = x.astype(jnp.float32) / temp[:, None]
    v = x.shape[-1]
    sd = jnp.sort(x, axis=-1)[:, ::-1]
    kk = jnp.clip(topk.astype(jnp.int32), 1, v)
    kth = jnp.take_along_axis(sd, (kk - 1)[:, None], axis=1)   # (b, 1)
    use_k = (topk > 0) & (topk < v)
    kth = jnp.where(use_k[:, None], kth, -jnp.inf)
    x = jnp.where(x < kth, -1e9, x)
    # ONE sort serves both filters: top-k masking thresholds on VALUE,
    # so it commutes with sorting — sort(mask(x)) == mask(sort(x)) —
    # and the top-p pass reuses `sd` with the same threshold instead of
    # re-sorting the masked logits (was two full vocab sorts per tick)
    sp = jnp.where(sd < kth, -1e9, sd)
    probs = jax.nn.softmax(sp, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = cum - probs < topp[:, None]
    thresh = jnp.min(jnp.where(keep, sp, jnp.inf), axis=-1,
                     keepdims=True)
    thresh = jnp.where((topp < 1.0)[:, None], thresh, -jnp.inf)
    return jnp.where(x < thresh, -1e9, x)


class _Request:
    """One in-flight generation request (engine-internal + the handle
    returned to callers; thread-safe token streaming via a queue)."""
    _next_id = 0
    _id_lock = threading.Lock()

    def __init__(self, ids, max_new_tokens, eos_token_id, do_sample,
                 temperature, top_k, top_p, pages_needed,
                 deadline=None, engine=None):
        with _Request._id_lock:
            self.rid = _Request._next_id
            _Request._next_id += 1
        self.prompt = np.asarray(ids, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = -1 if eos_token_id is None else int(eos_token_id)
        self.do_sample = bool(do_sample)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.pages_needed = pages_needed
        self.deadline = deadline    # expire-in-queue (overload.Deadline)
        # weakly back-reference the engine so result() can detect a
        # scheduler that nobody is driving (stall guard) without keeping
        # the engine alive through abandoned request handles
        self._engine = weakref.ref(engine) if engine is not None else None
        self.sample_index = 0       # engine-local; set by submit()
        self.prefix_keys = []       # full-page hash chain; set by submit()
        self.obs = None             # request-tracing context (or None)
        self.tenant = None          # tenant id (tenancy; set by submit)
        self.session = None         # conversation id (tiered KV; set
        #                             by submit — keys suspend/resume)
        self.queued_at = time.monotonic()   # per-tenant queue-wait clock
        self.admitted_at = None     # same clock, when it took a slot
        self.tokens: list[int] = []          # accepted generated tokens
        self.queue: queue.Queue = queue.Queue()
        self.done = threading.Event()
        self.cancelled = threading.Event()
        self.error = None

    def cancel(self):
        """Abandon the request: the engine retires its slot (freeing
        pages) at the next tick boundary instead of decoding the rest
        of the budget for nobody (client-disconnect path)."""
        self.cancelled.set()

    # -- caller-facing --------------------------------------------------
    def stream_tokens(self):
        """Yield accepted token ids one at a time as they are produced."""
        while True:
            item = self.queue.get()
            if item is None:
                if self.error is not None:
                    raise self.error
                return
            yield from item

    def result(self, stall_timeout=60.0):
        """Block until finished; return the generated token list.

        Stall guard: submit() does NOT auto-start the background ticker
        (only stream() does), so a bare submit()+result() would
        otherwise block forever. If the request is unfinished and
        nothing is driving the scheduler — no live ticker thread, no
        tick running or left in flight, no new step() call — for
        `stall_timeout`
        seconds, raise with the fix named instead of hanging. The
        default is deliberately generous: an external driver doing slow
        host work BETWEEN step() calls must not trip it (the guard
        exists to turn an infinite hang into an explained error, not to
        detect stalls fast)."""
        eng_ref = self._engine
        last_seq = None
        last_t = time.monotonic()
        while not self.done.wait(0.5):
            if eng_ref is None:
                continue          # engine unknown (legacy): plain wait
            eng = eng_ref()
            if eng is None:
                if self.done.is_set():
                    break     # finished during the wait (TOCTOU)
                # the engine was garbage-collected with this request
                # unfinished: NOTHING can ever finish it — raise now
                raise RuntimeError(
                    "result(): the engine owning this request was "
                    "garbage-collected before the request finished — "
                    "keep the PagedKVEngine alive and drive it "
                    "(start() or run_until_idle()) until result() "
                    "returns")
            ticker = eng._ticker
            seq = eng._step_seq
            # a live ticker, a tick in flight (first-call XLA compiles
            # run well past any timeout) or a new step() call all count
            # as someone driving the scheduler
            progressing = ((ticker is not None and ticker.is_alive())
                           or eng._in_step or eng._flying is not None
                           or seq != last_seq)
            del eng, ticker   # don't pin the engine (and its KV pools)
            #                   across the wait — the collected-engine
            #                   branch above must stay reachable
            if progressing:
                last_seq = seq
                last_t = time.monotonic()
                continue
            if time.monotonic() - last_t > stall_timeout:
                if self.done.is_set():
                    break     # finished during the wait (TOCTOU)
                raise RuntimeError(
                    "result(): request unfinished and no scheduler is "
                    "driving the engine (no ticker thread, no step() "
                    f"progress for {stall_timeout:.1f}s) — call "
                    "engine.start() for background serving or "
                    "engine.run_until_idle() after submit(); submit() "
                    "does not auto-start the ticker (stream() does)")
        if self.error is not None:
            raise self.error
        return list(self.tokens)


class _Slot:
    __slots__ = ("req", "lens", "tok", "pages", "emitted", "shared",
                 "open")

    def __init__(self, req, lens, tok):
        self.req = req
        self.lens = int(lens)       # tokens committed to the paged cache
        self.tok = int(tok)         # next decode input (last emitted)
        self.pages: list[int] = []  # physical pages in block-table order
        self.emitted = 0            # generated tokens accepted so far
        self.shared = 0             # leading prefix-cache pages (not
        #                             drawn from the free list here)
        self.open = ()              # a block engine: the prompt's tokens
        #                             past its last whole block, known
        #                             from the start in the first block
        #                             it generates and never emitted


class _PageGroup:
    """The pages of the model's layers of one kind, and what hands them
    out: which layers ride this table, their pools (one tuple a layer,
    each pool `num_pages` long, page 0 the trash page), the block table
    (slots x pages a slot) and the free list. An engine holds one for the
    layers that keep every token of a slot, whose rows grow with the
    context, and, for a model with layers that attend inside a window, a
    second (`window` > 0) whose rows are rings: `pages_per_slot` pages a
    slot, taken as the context first reaches them, then overwritten in
    place and never more (`PagedState.ring_tables`)."""
    __slots__ = ("layers", "num_pages", "pages_per_slot", "window", "bt",
                 "free", "pools")

    def __init__(self, layers, num_pages, pages_per_slot, slots, window=0):
        self.layers = list(layers)
        self.num_pages = int(num_pages)
        self.pages_per_slot = int(pages_per_slot)
        self.window = int(window)
        self.bt = np.zeros((slots, self.pages_per_slot), np.int32)
        self.free = list(range(self.num_pages - 1, 0, -1))   # 0 = trash
        self.pools = []

    def held(self, slot_idx):
        """Pages the slot's row names (a page is never 0)."""
        return int(np.count_nonzero(self.bt[slot_idx]))

    def grow(self, slot_idx, need_total):
        """A ring's row up to `need_total` pages, at most the ring: the
        pool holds a ring a slot, so the free list cannot run dry."""
        for j in range(self.held(slot_idx),
                       min(need_total, self.pages_per_slot)):
            self.bt[slot_idx, j] = self.free.pop()

    def release(self, slot_idx):
        """A ring's pages back to the free list."""
        row = self.bt[slot_idx]
        self.free.extend(int(p) for p in row[row > 0][::-1])
        row[:] = 0

    def bytes_per_slot(self):
        """HBM bytes a slot's full row pins in these layers' pools, from
        the real buffers."""
        return self.pages_per_slot * sum(
            a.size * a.dtype.itemsize // self.num_pages
            for grp in self.pools for a in grp)


class _SlotState:
    """The fixed-size state a slot keeps in the model's layers that carry
    one (a Mamba layer's conv window and SSM state), beside the page
    groups: which layers hold it and their arrays, one tuple a layer, each
    array (slots, ...). Nothing is handed out: a slot's row is its own
    from admission to retirement, and a prefill that starts a prompt reads
    it as zeros (`slot_state_read`), so a retired slot's state needs no
    clearing."""
    __slots__ = ("layers", "slots", "pools")

    def __init__(self, layers, slots, shapes):
        self.layers = list(layers)
        self.slots = int(slots)
        self.pools = [tuple(jnp.zeros((self.slots,) + tuple(shape), dtype)
                            for shape, dtype in shapes)
                      for _ in self.layers]

    def bytes_per_slot(self):
        """HBM bytes a slot's state pins in these layers, from the real
        buffers."""
        return sum(a.size * a.dtype.itemsize // self.slots
                   for grp in self.pools for a in grp)


# the kinds of layer (`config.layer_types`) that hold nothing of their own:
# a cross layer reads another layer's pages, a gated memory unit the
# memory of its own step
_HOLD_NOTHING = ("cross_attention", "gmu")


def _refuse_uncarried(what, uncarried, whys, extra=()):
    """The engine's one refusal of the features that assume a cache of K
    and V pages, for a model whose cache is something else (`what` says
    what it keeps): `uncarried` is each such feature's (name, switched
    on), `whys` why this cache does not carry it, in the same order, and
    `extra` (on, reason) pairs of the model's own, named first. Raises
    one ValueError naming every one that is on."""
    refused = [why for on, why in extra if on] + [
        f"{name} ({why})" for (name, on), why in zip(uncarried, whys) if on]
    if refused:
        raise ValueError(f"{what}; not carried by: " + "; ".join(refused))


def _state_of(tables, lens, n_valid):
    """`PagedState` from what a program was handed as its table(s): the
    block table, or a tuple (it, the ring table[, the rows' slots])."""
    if isinstance(tables, tuple):
        return PagedState(tables[0], lens, n_valid, *tables[1:])
    return PagedState(tables, lens, n_valid)


class _TickArgs(NamedTuple):
    """What a plain tick takes beside its slots' rows and the pools, as
    device arrays: a chained tick takes its predecessor's again."""
    fn: object          # the tick program (_tick_fn)
    bt: object          # the block table
    bt_sent: np.ndarray     # ... as the host held it at the upload
    eos: object
    sample: tuple       # temp, topk, topp, wants; () in a greedy tick


class _Flight(NamedTuple):
    """A decode tick the device has been handed and the host has not
    read back."""
    toks: object        # (slots, steps)
    carry: tuple        # the next tick's rows: tok (a block engine: the
    #                     open block's tokens and which are known), lens,
    #                     active, limit
    counted: dict       # the model's counters, summed over the tick
    live: list          # (slot index, request) of every live slot
    args: _TickArgs


class PagedKVEngine:
    """Continuous-batching scheduler over paged KV pools (module doc).

    model: a CausalLM whose attention supports `PagedState` cache
        coordinates (models/llama.py LlamaAttention).
    max_slots: decode batch width (static shape of the tick program).
    page_size / num_pages: pool geometry; page 0 is reserved as the
        trash page, so `num_pages - 1` pages are allocatable.
    max_pages_per_slot: block-table width; bounds prompt+generation
        length per request at `max_pages_per_slot * page_size`.
    steps_per_tick: decode steps fused into one device program call
        (admission granularity AND host round-trip amortization).
    max_pending: bound on the not-yet-admitted queue. None (default)
        queues unboundedly (batch/offline use); serving deployments set
        it so `submit` sheds with EngineOverloaded — a typed, retryable
        rejection — instead of letting queue depth (and every queued
        request's latency) grow without limit.
    kernel: decode attend path. "pallas" forces the Pallas paged-decode
        kernel (kernels/paged_attention.py; interpreter mode off-TPU) —
        raises a descriptive ValueError naming misaligned dims when the
        geometry can't take it (the ring_attention_local(use_flash=True)
        contract). "jnp" forces the gather/softmax fallback. None
        (default) auto-selects: the kernel on TPU when shapes allow,
        the jnp path otherwise (interpret mode is for parity testing,
        not speed, so auto never picks it on CPU).
    kv_dtype: KV pool storage. None keeps today's behavior (`dtype`, by
        default the model parameter dtype); "bf16" forces bf16 pools;
        "int8" stores pools as int8 with per-page-per-head f32 scales,
        quantized at scatter time and dequantized inside the attend —
        about half the KV HBM per slot vs bf16 (kv_bytes_per_slot()
        reports the exact figure from the real buffer dtypes).
    prefix_cache_pages: page budget for the prompt prefix cache
        (module doc; 0 = disabled, the default). Full prompt pages are
        keyed by the inference/prefix.py hash chain and shared across
        slots by refcount: a warm submit points its leading
        block-table entries at the cached pages and prefills only the
        uncached tail. Cold entries evict LRU at the budget, and
        on-demand when decode allocation needs the page back — a page
        is recycled (int8 scale rows zeroed) only when its refcount
        hits zero.
    tenancy: optional tenancy.TenantTable (None = disabled, the
        default, with admission order and shed behavior byte-identical
        to the pre-tenancy engine). When set, pending admission
        replaces FIFO with a weighted-fair pick across per-tenant
        queues (strict priority classes above the fair tiers), so
        decode slots divide by policy weight under saturation; a
        tenant past its own `max_queued` sheds with a typed 429
        (TenantQuotaExceeded); and under global `max_pending`
        pressure the engine evicts the newest queued request of the
        tenant most over its weighted fair share instead of shedding
        a well-behaved newcomer. Per-tenant shares surface in
        `tenant_snapshot()` and the tenant.* instruments.

    One decode tick in flight. The loops that own the scheduler
    (`_ticker_loop`, `run_until_idle`) launch tick N+1 from tick N's
    rows on the device before they read N back, whenever nothing can
    change which request sits in which slot before N+1 (`_may_chain`:
    no request pending or staged, no live slot cancelled, no draft
    model, a slot that outlives N by its budget); otherwise they land
    the tick in flight first and run retire -> admit -> launch as
    `step()` always does. `step()` itself returns with nothing in
    flight. While N+1 flies:
    - a slot that ended inside N (eos or budget) is dead in N+1 on the
      device (the rows carry `active` and `limit`): it writes no K/V
      there and emits nothing;
    - the accept of N+1 goes by request, not by slot index: rows of a
      request retired meanwhile (ended in N, cancelled) are dropped;
    - pages that a retirement frees are handed only to programs
      dispatched after N+1 (a prefill, `_recycle_pages`' zeroing, a
      tier upload, the next tick: one device stream, in order);
    - an error of N+1 surfaces at its read back and fails every waiter
      through `_ticker_loop`'s handler; `stop()` lands it before the
      ticker ends; `has_work()` and `result()`'s stall guard count it
      as work and as progress.
    `stats["ticks_chained"]` beside `ticks` counts the ticks launched
    that way; a tick's index in the step keys goes to the ticks that
    decoded something, so sampled tokens do not depend on the depth.
    """

    def __init__(self, model, *, max_slots=4, page_size=16, num_pages=64,
                 max_pages_per_slot=None, steps_per_tick=4, seed=0,
                 prefill_chunk=None, draft_model=None, spec_tokens=4,
                 dtype=None, max_pending=None, kernel=None,
                 kv_dtype=None, prefix_cache_pages=0, tenancy=None,
                 host_tier_bytes=0, suspend_after_s=None, role="both"):
        compile_cache.ensure()
        cfg = model.config
        self.model = model
        self.max_slots = int(max_slots)
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.max_pages_per_slot = int(
            max_pages_per_slot
            or min(num_pages - 1, max(1, (num_pages - 1) // max_slots)))
        self.steps_per_tick = int(steps_per_tick)
        self.max_pending = (None if max_pending is None
                            else int(max_pending))
        # prompts longer than this prefill in fixed-size chunks through
        # ONE reused program (chunked prefill — the paged core appends
        # at lens>0) instead of compiling a program per padded length.
        # None = the bucketed whole-prompt path for every prompt whose
        # scores fit one program (`_prefill_limit`, from the shapes),
        # chunks of that limit for a longer one.
        self.prefill_chunk = (int(prefill_chunk) if prefill_chunk
                              else None)
        # what a cache holds a token: the model's kv heads, or where it
        # stores them otherwise (paired heads of differential attention as
        # one of twice the width) what its config says it stores
        n_kv = getattr(cfg, "cache_kv_heads", None) \
            or getattr(cfg, "num_key_value_heads", None) \
            or cfg.num_attention_heads
        hd = getattr(cfg, "cache_head_dim", None) \
            or getattr(cfg, "head_dim", None) \
            or cfg.hidden_size // cfg.num_attention_heads
        if dtype is None:
            p = next(iter(model.parameters()))
            dtype = str(p.dtype)
        if kv_dtype not in (None, "bf16", "int8"):
            raise ValueError(f"kv_dtype must be None, 'bf16' or 'int8' "
                             f"(got {kv_dtype!r})")
        self.kv_dtype = kv_dtype
        pool_dtype = {"bf16": "bfloat16", "int8": "int8",
                      None: dtype}[kv_dtype]
        # a model whose attention selects its keys (a learned indexer:
        # the config says how wide an index key is and how many keys a
        # token keeps) carries a THIRD pool a layer under the same block
        # table, one index key a token. What assumes two pools refuses
        # here, by name, so that none can drop the third in silence.
        self.index_dim = int(getattr(cfg, "index_head_dim", 0) or 0)
        self.index_topk = int(getattr(cfg, "index_topk", 0) or 0)
        # what assumes a cache of K and V pages, each under the name it is
        # switched on by: a model whose cache is something else refuses
        # every one that is on, with its own reasons (`_refuse_uncarried`)
        uncarried = (("kv_dtype='int8'", kv_dtype == "int8"),
                     ("prefix_cache_pages", bool(int(prefix_cache_pages))),
                     ("host_tier_bytes", bool(int(host_tier_bytes))),
                     (f"role={role!r}", role != "both"),
                     ("draft_model", draft_model is not None))
        if self.index_dim:
            _refuse_uncarried(
                "this model keeps an index pool beside K and V "
                f"(index_head_dim={self.index_dim})", uncarried, (
                    "the scale planes take the cache's third and fourth "
                    "place",
                    "a warm tail would select over index keys no test "
                    "holds to the reference yet",
                    "a spilled page is its K and V alone",
                    "an exported page is its K and V alone",
                    "the verify pass attends over every key"))
        # a model with latent attention (the config says how wide the
        # latent is) keeps ONE pool a layer under the block table, a row
        # [c | k_pe] a token, in place of K and V
        # (`latent_attention_update`)
        self.latent_rank = int(getattr(cfg, "kv_lora_rank", 0) or 0)
        self.latent_width = (self.latent_rank + int(cfg.qk_rope_head_dim)
                             if self.latent_rank else 0)
        if self.latent_rank:
            _refuse_uncarried(
                "this model keeps one latent row a token a layer in place "
                f"of K and V (kv_lora_rank={self.latent_rank}: "
                f"{self.latent_width} values)", uncarried, (
                    "no int8 latent row is built or held to the "
                    "reference",
                    "no test holds a warm tail over latent pages to the "
                    "reference yet",
                    "a spilled page is a page of K and V",
                    "an exported page is a page of K and V",
                    "the verify pass attends over K and V"))
        elif not self.index_dim and draft_model is not None and (
                getattr(draft_model.config, "index_head_dim", 0)
                or getattr(draft_model.config, "kv_lora_rank", 0)):
            raise ValueError("a draft model with an index pool or a latent "
                             "pool is not carried (its pools are K and V "
                             "alone)")
        # a model with layers that carry a fixed-size state a slot (the
        # config's `layer_types` names them "mamba": a conv window and an
        # SSM state) keeps it in the engine's state cache (`_SlotState`),
        # beside the pages, and layers that hold nothing of their own
        # (`_HOLD_NOTHING`) ride no table. What assumes that a page holds
        # all a slot's context refuses here, by name.
        kinds = list(getattr(cfg, "layer_types", None) or [])
        state_layers = [i for i, kind in enumerate(kinds) if kind == "mamba"]
        # the state cache: a slot's last K - 1 pre-conv rows in the pools'
        # dtype and its SSM state (N, E) in float32, a Mamba layer
        self._state = None
        if state_layers:
            _refuse_uncarried(
                f"this model keeps a fixed-size state a slot in layers "
                f"{state_layers} (a Mamba layer's conv window and SSM "
                "state)", uncarried, (
                    "no int8 K or V beside a state is held to the "
                    "reference",
                    "a warm tail would start from a state no page holds (a "
                    "page's end keeps no snapshot of it)",
                    "a spilled page carries no state",
                    "an exported page carries no state",
                    "a rejected draft token has already moved the state"),
                extra=((self.index_dim, "an index pool (the key selection "
                        "reads every key of a slot)"),
                       (self.latent_rank, "a latent pool"),
                       (int(getattr(cfg, "block_length", 0) or 0),
                        "a block length (a denoising step rewrites its "
                        "block, and a state cannot be rewound)")))
            e, n = int(cfg.mamba_d_inner), int(cfg.mamba_d_state)
            self._state = _SlotState(
                state_layers, self.max_slots,
                (((int(cfg.mamba_d_conv) - 1, e), pool_dtype),
                 ((n, e), jnp.float32)))
        # a model with layers that attend inside a window (the config's
        # `layer_types` names them, `sliding_window` is the window) keeps
        # those layers' K and V in rings under a SECOND page table
        # (`_PageGroup`); num_pages and max_pages_per_slot describe the
        # layers that keep every token. What assumes one table refuses
        # here, by name.
        window = int(getattr(cfg, "sliding_window", 0) or 0)
        window_layers = [i for i, kind in enumerate(kinds)
                         if kind == "sliding_attention"] if window else []
        # the window of the layers in rings; 0: no layer is
        self.window = window if window_layers else 0
        if window_layers:
            if len(kinds) != cfg.num_hidden_layers:
                raise ValueError(
                    f"layer_types names {len(kinds)} layers, the model "
                    f"has {cfg.num_hidden_layers}")
            _refuse_uncarried(
                f"this model keeps layers {window_layers} in rings of a "
                f"window of {self.window} under a second page table",
                uncarried, (
                    "a ring's pages are overwritten in place; their scales "
                    "only grow",
                    "a window layer's page of a prefix is overwritten once "
                    "the context has gone round the ring",
                    "a spilled page is a page of every layer under one "
                    "table",
                    "an exported page is a page of every layer under one "
                    "table",
                    "a rejected draft token has already overwritten the "
                    "ring's oldest"),
                extra=((self.index_dim, "an index pool (the key selection "
                        "reads every key of a slot)"),
                       (self.latent_rank, "a latent pool (rings hold K and "
                        "V)")))
        # a model that generates by diffusion over blocks (the config
        # says how long a block is) has no one-token step: a tick settles
        # steps_per_tick / block_length whole blocks a slot, each by the
        # config's denoising steps of block_length query rows a slot and
        # one more forward that stores the block's K and V
        # (`_block_tick_fn`). What assumes a token a step, or pages that
        # are final once written, refuses here, by name.
        self.block_length = int(getattr(cfg, "block_length", 0) or 0)
        if self.block_length:
            if self.steps_per_tick % self.block_length \
                    or self.page_size % self.block_length:
                raise ValueError(
                    f"this model generates by blocks of "
                    f"{self.block_length}: steps_per_tick "
                    f"({self.steps_per_tick}) and page_size "
                    f"({self.page_size}) must be multiples of it (a tick "
                    "settles whole blocks, a block lies in one page)")
            if (self.prefill_chunk or 0) % self.block_length:
                raise ValueError(
                    f"prefill_chunk ({self.prefill_chunk}) must be a "
                    f"multiple of block_length ({self.block_length}): a "
                    "chunk ends where a block ends")
            _refuse_uncarried(
                f"this model generates by blocks of {self.block_length} "
                "(block_length)", uncarried, (
                    "a block's K and V are rewritten every denoising step; "
                    "a page's scales only grow",
                    "a prompt's last partial block is not final, and no "
                    "test holds a warm tail under the mask by blocks to the "
                    "reference",
                    "it spills prefix-cache pages",
                    "an exported page is a prefix-cache page",
                    "a block is denoised, not verified"),
                extra=((self.index_dim, "an index pool (the key selection "
                        "is causal by position)"),
                       (window_layers, "layers in rings (a window inside a "
                        "block is not defined)"),
                       (self.latent_rank, "a latent pool (the block's rows "
                        "ride the decode kernel of K and V)")))
        self._cache_arity = (4 if kv_dtype == "int8"
                             else 3 if self.index_dim
                             else 1 if self.latent_rank else 2)

        def make_pools(n_heads, head_dim, n_layers, num_pages=None):
            """One layer's pools (`num_pages` long: the engine's, or a
            group's of its own), `n_layers` times. Plain K and V pools
            of an engine whose decode attends through the Pallas kernel
            are stored as that kernel's rows (`pool_rows_shape`): the
            kernel and the Pallas write are then the only ops that
            touch them and XLA has no second layout to copy them into.
            int8 pools with their scale planes and the index pool keep
            (num_pages, heads, page_size, width) and XLA's scatter: the
            int8 write rescales whole pages (no cell runs it, and a
            second quantising kernel is not worth growing for it), and
            no Pallas call reads an index pool. A latent pool is (num_pages,
            page_size, the row's values), at whole lane tiles where the
            decode kernel reads it (`kernel_row_width`), under XLA's
            scatter."""
            num_pages = num_pages or self.num_pages
            if self.latent_rank:    # never a draft's: refused above
                width = (_mla.kernel_row_width(self.latent_width)
                         if self.decode_kernel == "pallas"
                         else self.latent_width)
                return [(jnp.zeros((num_pages, self.page_size, width),
                                   pool_dtype),) for _ in range(n_layers)]
            shape = (num_pages, n_heads, self.page_size, head_dim)
            sshape = (num_pages, n_heads)
            if self.kv_write == "pallas":
                shape = _pk.pool_rows_shape(num_pages, n_heads,
                                            head_dim, self.page_size,
                                            pool_dtype)
            if self.index_dim:      # never a draft's: refused above
                ishape = (num_pages, 1, self.page_size,
                          self.index_dim)
                return [(jnp.zeros(shape, pool_dtype),
                         jnp.zeros(shape, pool_dtype),
                         jnp.zeros(ishape, pool_dtype))
                        for _ in range(n_layers)]
            if kv_dtype == "int8":
                return [(jnp.zeros(shape, "int8"),
                         jnp.zeros(shape, "int8"),
                         jnp.zeros(sshape, jnp.float32),
                         jnp.zeros(sshape, jnp.float32))
                        for _ in range(n_layers)]
            return [(jnp.zeros(shape, pool_dtype),
                     jnp.zeros(shape, pool_dtype))
                    for _ in range(n_layers)]

        # decode attend path (class doc): resolve once, fail fast on a
        # forced-but-impossible geometry with the misaligned dims named
        on_tpu = self._on_tpu = jax_compat.on_tpu()
        # query rows the decode kernel sees a kv head's group as: a
        # block's rows ride beside the heads (`_fold_rows`)
        q_heads = cfg.num_attention_heads * max(1, self.block_length)
        if kernel not in (None, "pallas", "jnp"):
            raise ValueError(f"kernel must be None, 'pallas' or 'jnp' "
                             f"(got {kernel!r})")
        self._kernel_interpret = not on_tpu
        # a key selection rides the kernel only where its score columns
        # are tokens in order
        # (a window layer's view of its ring rides it as one does)
        select_problems = (_pk.select_shape_problems(
            n_kv, hd, self.page_size, pool_dtype)
            if self.index_dim or window_layers else [])
        if self.latent_rank:
            # the latent decode kernel (kernels/mla_decode.py) reads rows
            # of whole lane tiles
            latent_problems = _mla.mla_decode_problems(
                cfg.num_attention_heads,
                _mla.kernel_row_width(self.latent_width), self.latent_rank,
                self.page_size, pool_dtype)
            if kernel == "pallas" and latent_problems \
                    and not self._kernel_interpret:
                raise ValueError("mla_decode_attention: "
                                 + "; ".join(latent_problems))
            self.decode_kernel = ("pallas" if kernel == "pallas" or (
                kernel is None and on_tpu and not latent_problems)
                else "jnp")
        elif kernel == "pallas":
            _pk.check_decode_shapes(q_heads, n_kv, hd, self.page_size,
                                    interpret=self._kernel_interpret,
                                    kv_dtype=pool_dtype)
            if select_problems:
                raise ValueError("; ".join(select_problems))
            self.decode_kernel = "pallas"
        elif kernel is None and on_tpu and not select_problems and \
                not _pk.decode_shape_problems(q_heads, n_kv, hd,
                                              self.page_size,
                                              kv_dtype=pool_dtype):
            self.decode_kernel = "pallas"
        else:
            self.decode_kernel = "jnp"
        # speculative decoding (greedy-lossless): a draft model rides
        # its OWN page pools over the SAME block tables — paged caches
        # make rejection rollback free (lens simply doesn't advance;
        # stale positions are masked and overwritten)
        if draft_model is not None and prefill_chunk:
            raise NotImplementedError(
                "speculative decoding + chunked prefill: the draft "
                "prefill mirrors the bucketed path only (compose later)")
        self.draft_model = draft_model
        self.spec_tokens = int(spec_tokens)
        self.draft_pools = None
        if draft_model is not None:
            dcfg = draft_model.config
            dn_kv = getattr(dcfg, "num_key_value_heads", None) \
                or dcfg.num_attention_heads
            dhd = getattr(dcfg, "head_dim", None) \
                or dcfg.hidden_size // dcfg.num_attention_heads
            if self.decode_kernel == "pallas":
                if kernel == "pallas":      # forced: fail fast, named
                    _pk.check_decode_shapes(
                        dcfg.num_attention_heads, dn_kv, dhd,
                        self.page_size,
                        interpret=self._kernel_interpret,
                        kv_dtype=pool_dtype)
                elif _pk.decode_shape_problems(
                        dcfg.num_attention_heads, dn_kv, dhd,
                        self.page_size,
                        kv_dtype=pool_dtype):  # auto: draft can't ride
                    self.decode_kernel = "jnp"
        # which op writes a step's K and V: the Pallas write wherever
        # the pools are stored as rows (`_scatter_kv` chooses the same
        # way, from the scope and the pool)
        self.kv_write = ("pallas" if self.decode_kernel == "pallas"
                         and kv_dtype != "int8" and not self.latent_rank
                         else "xla")
        # what a K or V page is outside the engine (a host-tier entry,
        # an exported bundle), whatever shape the pools store it in
        self._page_shape = (n_kv, self.page_size, hd)
        # the page tables (`_PageGroup`): `_full` is every layer's but
        # for a model with window layers, whose rings are `_ring`'s, one a
        # slot, each long enough for the longest write of one call
        self._full = _PageGroup(
            [i for i in range(cfg.num_hidden_layers)
             if i not in window_layers and i not in state_layers
             and (not kinds or kinds[i] not in _HOLD_NOTHING)],
            self.num_pages, self.max_pages_per_slot, self.max_slots)
        self._ring = None
        # whether a prefill's attention goes through the Pallas chunk
        # kernel: `paged_attention_update` chooses by the scope and the
        # call's shapes, and this is the same rule (plain pools, a chunk
        # of whole sublane tiles). Then no score is ever written to
        # memory (`_prefill_limit`)
        self._chunk_kernel = bool(
            self.decode_kernel == "pallas" and self._cache_arity == 2
            and not chunk_attention_problems(
                8, cfg.num_attention_heads, n_kv, hd,
                self._kernel_interpret))
        if window_layers:
            longest = self._bucket(self.prefill_chunk
                                   or self._prefill_limit(1))
            ring = min(ring_pages_for(self.window, longest, self.page_size),
                       self.max_pages_per_slot)
            self._ring = _PageGroup(window_layers,
                                    self.max_slots * ring + 1, ring,
                                    self.max_slots, window=self.window)
        for grp in self._groups:
            grp.pools = make_pools(n_kv, hd, len(grp.layers), grp.num_pages)
        if draft_model is not None:
            self._draft_page_shape = (dn_kv, self.page_size, dhd)
            self.draft_pools = make_pools(dn_kv, dhd,
                                          dcfg.num_hidden_layers)
        # the kernel's own account of how it engages at this geometry
        # (heads and pages a grid step, grid, VMEM bytes): a count from
        # shapes, so it reads the same with and without a chip
        self.decode_plan = None
        if self.decode_kernel == "pallas" and not self.latent_rank:
            self.decode_plan = _pk.decode_plan(
                q_heads, n_kv, hd, self.page_size,
                self.max_pages_per_slot, pool_dtype, slots=self.max_slots)
        # pages promised to admitted slots but not yet popped from the
        # free list; admission headroom = len(_free) - _reserved_unalloc
        self._reserved_unalloc = 0
        # prompt prefix cache (class doc): key -> page map plus the
        # refcount ledger for EVERY allocated page (cache disabled =
        # every page has exactly one ref, its slot)
        if int(prefix_cache_pages) < 0:
            raise ValueError(f"prefix_cache_pages must be >= 0, got "
                             f"{prefix_cache_pages}")
        self.prefix_cache = (PrefixCache(prefix_cache_pages)
                             if int(prefix_cache_pages) else None)
        # host-RAM KV tier (module doc): spill/restore below the device
        # cache, plus session suspend/resume riding the same machinery
        if int(host_tier_bytes) < 0:
            raise ValueError(f"host_tier_bytes must be >= 0, got "
                             f"{host_tier_bytes}")
        if int(host_tier_bytes) and self.prefix_cache is None:
            raise ValueError(
                "host_tier_bytes requires prefix_cache_pages > 0: the "
                "tier spills and restores PREFIX-CACHE pages (chain "
                "keys are the page identity)")
        self.host_tier = (HostKVTier(int(host_tier_bytes))
                          if int(host_tier_bytes) else None)
        if suspend_after_s is not None and self.host_tier is None:
            raise ValueError(
                "suspend_after_s requires host_tier_bytes > 0: a "
                "suspended session's pages live in the host tier")
        self.suspend_after_s = (None if suspend_after_s is None
                                else float(suspend_after_s))
        # disaggregated prefill/decode (inference/disagg.py): a
        # prefill-pool engine eagerly captures committed prefix pages
        # to its host tier so /kv/pull can export them; a decode-pool
        # engine imports peer pages through the _tier_restore-shaped
        # ledger. "both" (the default) is the monolithic engine —
        # every disagg path is dormant.
        if role not in ("prefill", "decode", "both"):
            raise ValueError(f"role must be 'prefill', 'decode' or "
                             f"'both' (got {role!r})")
        if role == "prefill" and self.host_tier is None:
            raise ValueError(
                "role='prefill' requires host_tier_bytes > 0: committed "
                "pages export through the host-snapshot path")
        if role == "decode" and self.prefix_cache is None:
            raise ValueError(
                "role='decode' requires prefix_cache_pages > 0: "
                "imported pages land in the prefix cache")
        self.role = role
        self.disagg = DisaggStats(role)
        # bundles staged by the serving thread (stage_import), drained
        # into the pools by the scheduler at the top of _admit; guarded
        # by self._lock like _pending
        self._import_staged: list = []
        # session id -> {keys, last, suspended}; scheduler-thread-only
        # (retire inserts, admit touches, the suspend sweep spills)
        self._sessions: collections.OrderedDict[str, dict] = \
            collections.OrderedDict()
        self._page_refs: dict[int, int] = {}
        # incremental twin of "cached pages only the cache still
        # holds": _ref_page/_unref_page/_prefix_insert/_evict keep it
        # current at the ref transitions, so admission headroom is
        # O(1) instead of O(cache) per pending request
        self._cached_pages: set[int] = set()
        self._reclaimable = 0
        self._slots: list[_Slot | None] = [None] * self.max_slots
        self._pending: list[_Request] = []
        self._inflight = 0      # submitted, not yet retired/dropped
        self._lock = threading.Lock()
        self._programs = {}
        # per-model {name: array} weight dicts every program takes as
        # its first argument; captured when the first program is built
        self._weights = None
        self._tick_count = 0
        self._step_seq = 0      # step() calls ever made (result() stall
        self._in_step = False   # guard watches both for driver progress)
        self._seed = int(seed)
        self._submitted = 0
        self._key = jax.random.key(seed)
        # on the device once: the tick program folds the tick's index in
        self._key_data = jax.random.key_data(self._key)
        # the decode tick launched ahead and not read back yet, between
        # two iterations of a loop that owns the scheduler (_step_tick)
        self._flying: _Flight | None = None
        self._ticker = None
        # multi-tenant QoS (class doc): the WFQ pick + per-tenant
        # shares; None keeps every scheduling path byte-identical
        self.tenancy = tenancy
        self._wfq = (WeightedFairScheduler(tenancy)
                     if tenancy is not None else None)
        self._tenant_lock = threading.Lock()
        self._tenant_stats: dict[str, dict] = {}
        # incremental per-tenant queued counts (guarded by self._lock):
        # submit increments, admit/cancel/expire/shed/crash decrement.
        # The QUOTA check reads this, not len-of-_pending scans — _admit
        # swaps self._pending out while it prefills (seconds on a first
        # compile), and a storm submitting into that window must still
        # count against its bulkhead
        self._queued_by_tenant: dict[str, int] = {}
        # telemetry for tests / the serving bench
        self.stats = {"ticks": 0, "ticks_chained": 0,
                      "kv_write_kernel_ticks": 0,
                      "prefills": 0, "tokens_out": 0,
                      # rows every prefill call computed, the rows of
                      # padding among them, and the rows of groups of
                      # two or more that ran at width 1 (_prefill_width)
                      "prefill_rows_run": 0, "prefill_rows_padded": 0,
                      "prefill_rows_split": 0,
                      "admitted": 0, "finished": 0, "cancelled": 0,
                      "expired": 0, "overloaded": 0,
                      # the prefills' wall, and the ticker's CPU time
                      # over the same intervals (time.thread_time)
                      "prefill_s": 0.0, "prefill_cpu_s": 0.0,
                      # always on, by the tick's own clock (_note_tick):
                      # tick_wall_s = tick_host_s + readback_s + the
                      # prefill_s spent inside ticks; of tick_host_s,
                      # tick_host_wait_s is what the ticker spent off a
                      # CPU (the interpreter lock, the engine lock, the OS)
                      "tick_wall_s": 0.0, "tick_host_s": 0.0,
                      "tick_host_wait_s": 0.0,
                      "readback_s": 0.0, "tick_max_s": 0.0,
                      # of requests retired from a slot: the time from
                      # submit to the slot, and from submit to the end
                      "queue_wait_s": 0.0, "request_s": 0.0,
                      "prefix_hits": 0, "prefix_misses": 0,
                      "prefix_hit_tokens": 0, "prefix_pages_shared": 0,
                      "prefix_evictions": 0,
                      # prefill programs' calls of the grouped expert
                      # kernel, their rows, and the bounds on their
                      # (m-tile, expert) visits (_note_grouped)
                      "moe_grouped_calls": 0, "moe_grouped_rows": 0,
                      "moe_grouped_visits_bound": 0}
        if self.index_dim:
            # decode steps of live slots, and those whose context had
            # outgrown topk, so that the selection chose among its keys
            self.stats.update(decode_slot_steps=0, select_engaged_steps=0)
        if self._ring is not None:
            # decode steps of live slots, those whose context had outgrown
            # the window, and per such step the tokens the two tables hold
            # for the slot, summed over the layers, beside what one table
            # for every layer would hold
            self.stats.update(decode_slot_steps=0, window_engaged_steps=0,
                              kv_tokens_held=0, kv_tokens_flat=0)
        if self.latent_rank:
            # latent rows the decode attention read (each live slot's
            # keys, every layer, a step: the model's counter), rows a
            # prefill expanded through W_kvb (every block of pages its
            # calls looped over, padding included) and the prompt tokens
            # of those calls (`_note_expanded`)
            self.stats.update(mla_decode_rows=0, mla_expand_rows=0,
                              mla_prefill_tokens=0)
        if self._state is not None:
            # valid prompt tokens a prefill call ran through the selective
            # scan (in each Mamba layer), the calls, and the prompt
            # positions at which the cross-decoder did not run (a call runs
            # it at each row's last token alone): `_note_state`
            self.stats.update(ssm_scan_tokens=0, ssm_scan_calls=0)
            if any(kind == "cross_attention" for kind in kinds):
                self.stats.update(cross_skipped_tokens=0)
        if self.block_length:
            # slot-forwards of the denoising steps and of the forwards that
            # store a settled block, positions those steps unmasked, and
            # blocks settled, each a live slot's
            self.stats.update({"block_forwards_denoise": 0,
                               "block_forwards_store": 0,
                               "block_positions_unmasked": 0,
                               "blocks_done": 0})
        # what the model counts itself a decode step (its
        # `decode_counter_keys`, e.g. the distinct experts its rows hit):
        # the tick program asks the forward for them (`with_counters`),
        # sums them and returns them with its tokens
        self._model_counts = bool(getattr(model, "decode_counter_keys", ()))
        # one row a tick, the newest 256: (seq, start by perf_counter,
        # the TICK_PHASES' seconds in order, live slots, prefills
        # admitted, the ticker's CPU seconds over the host phases) —
        # what says which phase a slow tick spent it in, and whether the
        # ticker was working, when no profiler was running
        self.tick_log: collections.deque = collections.deque(maxlen=256)
        # serving integration: PredictorServer must not serialize
        # concurrent streams through its executable lock — the engine's
        # ticker thread is the only chip user
        self.concurrent_safe = True

    def kv_bytes_per_slot(self):
        """HBM bytes one fully-grown slot pins across every layer's KV
        pools (int8 scale planes and draft-model pools included) and its
        state cache, computed from the REAL buffer dtypes — so `kv_dtype`
        is honored end-to-end instead of assuming f32/bf16 element
        sizes."""
        per_page = 0
        for grp in self.draft_pools or []:
            for arr in grp:
                per_page += (arr.size * arr.dtype.itemsize
                             // self.num_pages)
        return per_page * self.max_pages_per_slot \
            + sum(grp.bytes_per_slot() for grp in self._holders)

    # the table of the layers that keep every token, under the names the
    # scheduler has always used for it
    @property
    def _bt(self):
        return self._full.bt

    @property
    def _free(self):
        return self._full.free

    @property
    def _groups(self):
        return [self._full] if self._ring is None \
            else [self._full, self._ring]

    @property
    def _holders(self):
        """The page groups and the state cache: what holds a layer's
        cache."""
        return self._groups + ([self._state] if self._state else [])

    @property
    def pools(self):
        """Every layer's cache that holds one, in the model's order, as
        the programs take them; each lives in its group or the state
        cache."""
        holders = self._holders
        if len(holders) == 1:
            return self._full.pools
        held = sorted(((i, pool) for h in holders
                       for i, pool in zip(h.layers, h.pools)),
                      key=lambda pair: pair[0])
        return [pool for _i, pool in held]

    @pools.setter
    def pools(self, pools):
        holders = self._holders
        if len(holders) == 1 or pools is None:
            for h in holders:
                h.pools = pools
            return
        order = sorted(i for h in holders for i in h.layers)
        at = dict(zip(order, pools))
        for h in holders:
            h.pools = [at[i] for i in h.layers]

    def page_groups(self):
        """What each page table holds, as the engine built it: the layers
        that ride it, their window (0: every token is kept), the pages of
        one layer's pool and of a slot's row, and the bytes of its pools
        from the real buffers."""
        return [{"layers": list(g.layers), "window": g.window,
                 "pool_pages": g.num_pages,
                 "pages_per_slot": g.pages_per_slot,
                 "pool_bytes": sum(a.size * a.dtype.itemsize
                                   for kv in g.pools or [] for a in kv)}
                for g in self._groups]

    def _tables(self, rows=None):
        """The block table as the host holds it now, a copy: one array,
        or with window layers (the table, the ring table). `rows`: of
        these slots only, one row each, None for a row left empty; with a
        state cache then (the table, the ring table or None, each row's
        slot, one past the last for an empty row)."""
        def cut(bt):
            if rows is None:
                return bt.copy()
            out = np.zeros((len(rows), bt.shape[1]), np.int32)
            for r, idx in enumerate(rows):
                if idx is not None:
                    out[r] = bt[idx]
            return out
        if rows is not None and self._state is not None:
            return (cut(self._full.bt),
                    None if self._ring is None else cut(self._ring.bt),
                    np.asarray([self.max_slots if idx is None else idx
                                for idx in rows], np.int32))
        if self._ring is None:
            return cut(self._full.bt)
        return cut(self._full.bt), cut(self._ring.bt)

    def _tables_changed(self, sent):
        """Whether the host's table(s) differ from the copy `sent`."""
        sent = sent if isinstance(sent, tuple) else (sent,)
        return not all(np.array_equal(grp.bt, was)
                       for grp, was in zip(self._groups, sent))

    def export_metrics(self, registry):
        """Publish the engine's telemetry counters into a metrics
        registry as scrape-time gauges (PredictorServer's GET /metrics
        calls this on its generator). Monotonic stats stay gauges
        because they are absolute values sampled at scrape time, not
        increments."""
        s = self.stats
        registry.set_gauge("inference.kv.bytes_per_slot",
                           self.kv_bytes_per_slot())
        if self.host_tier is not None:
            registry.set_gauge("inference.kvtier.host_pages",
                               len(self.host_tier))
        registry.set_gauge("engine.ticks", s["ticks"])
        registry.set_gauge("engine.prefills", s["prefills"])
        registry.set_gauge("engine.tokens_out", s["tokens_out"])
        registry.set_gauge("engine.admitted", s["admitted"])
        registry.set_gauge("engine.finished", s["finished"])
        registry.set_gauge("engine.cancelled", s["cancelled"])
        registry.set_gauge("engine.expired", s["expired"])
        registry.set_gauge("engine.overloaded", s["overloaded"])
        registry.set_gauge("engine.tick_max_seconds", s["tick_max_s"])
        registry.set_gauge("engine.tick_host_seconds", s["tick_host_s"])
        registry.set_gauge("engine.tick_host_wait_seconds",
                           s["tick_host_wait_s"])
        registry.set_gauge("engine.decode_grid_steps",
                           self.decode_plan.grid_steps
                           if self.decode_plan else 0)
        for plan in {p for fn in self._programs.values()
                     for p in getattr(fn, "grouped_plans", ())}:
            registry.set_gauge("moe.grouped_plan", plan.visits,
                               m=plan.m, e=plan.e, tm=plan.tm, tk=plan.tk,
                               tn=plan.tn)
        # _pending is swapped by the ticker under _lock; an unguarded
        # len() here races the swap (found by the guarded-field
        # analyzer pass — the same shape as the PR 12 quota bypass)
        with self._lock:
            pending = len(self._pending)
        registry.set_gauge("engine.pending", pending)

    def prefix_stats(self):
        """The prefix-cache /stats block (PredictorServer embeds it so
        the router can probe per-replica KV locality); None when the
        cache is disabled."""
        if self.prefix_cache is None:
            return None
        s = self.stats
        h, m = s["prefix_hits"], s["prefix_misses"]
        return {"enabled": True,
                "hits": h, "misses": m,
                "hit_rate": round(h / (h + m), 4) if (h + m) else 0.0,
                "hit_tokens": s["prefix_hit_tokens"],
                "pages_shared": s["prefix_pages_shared"],
                "evictions": s["prefix_evictions"],
                "cached_pages": len(self.prefix_cache),
                "page_budget": self.prefix_cache.page_budget}

    def kvtier_stats(self):
        """The host-tier /stats block (PredictorServer embeds it beside
        the prefix block; the router reads hits/lookups for its
        tier-hit-rate column); None when the tier is disabled."""
        return (None if self.host_tier is None
                else self.host_tier.snapshot())

    def disagg_stats(self):
        """The /stats `disagg` block. Always present for engine-backed
        servers: the router's prober reads `role` from it to learn
        pool membership without any fleet configuration."""
        return self.disagg.snapshot()

    # -- disagg handoff (inference/disagg.py module doc) -----------------
    def export_pages(self, keys):
        """Prefill-side export: PageBundleEntry objects for the longest
        leading run of `keys` resident in the host tier (serving packs
        them for /kv/pull). Runs on an HTTP thread — the host tier is
        the thread-safe boundary; device pools are never touched.
        Flushes pending captures first so pages committed by a prefill
        that JUST finished are visible."""
        if self.host_tier is None:
            return []
        self.host_tier.flush(timeout=10.0)
        return [PageBundleEntry(k, e.layers, e.draft)
                for k, e in self.host_tier.peek_run(keys)]

    def disagg_missing(self, keys):
        """Decode-side dedup planner: the suffix of `keys` NOT already
        resident in this engine's prefix cache or host tier — i.e. the
        pages a handoff must actually move. Advisory (HTTP thread; the
        scheduler mutates both tiers concurrently): a stale answer
        costs a redundant transfer or a truncated run, never
        correctness."""
        if self.prefix_cache is None:
            return list(keys)
        have = self.prefix_cache.leading_run(keys)
        if self.host_tier is not None:
            for k in keys[have:]:
                if not self.host_tier.has(k):
                    break
                have += 1
        return list(keys[have:])

    def stage_import(self, entries):
        """Queue peer page bundles for insertion (serving thread). The
        scheduler drains them at the top of its next _admit, BEFORE the
        prefix lookup of the request they arrived ahead of (the
        router-forwarded chain keys make this a prefetch, not a
        race)."""
        if not entries:
            return
        if self.prefix_cache is None:
            raise RuntimeError("disagg import requires a prefix cache")
        with self._lock:
            self._import_staged.extend(entries)

    def _disagg_import(self, entries):
        """Scheduler thread: insert staged peer pages through the SAME
        ledger dance as _tier_restore — pop a free page (evicting
        cold cache entries on demand), ref it for the cache, insert,
        batched H2D scatter. Headroom-neutral: every page consumed is
        a cache-owned reclaimable page, so admission math is untouched.
        Keys already resident (the peer raced us) are dedup-skipped."""
        cache = self.prefix_cache
        ents, pages = [], []
        skipped = 0
        for ent in entries:
            if ent.key in cache:
                skipped += 1
                continue
            if not self._tier_entry_compatible(ent):
                continue
            if not self._free and \
                    not self._evict_prefix_entries(budget_only=False):
                break               # device cache full of in-use pages
            page = self._free.pop()
            # ledger mirror of _tier_restore: cache ref only (ref 1),
            # cached, reclaimable — importing leaves admission
            # headroom exactly where it was
            self._ref_page(page)
            cache.insert(ent.key, page)
            self._cached_pages.add(page)
            self._reclaimable += 1
            ents.append(ent)
            pages.append(page)
        if ents:
            self._tier_upload(ents, pages)
            self._evict_prefix_entries(budget_only=True)
            self.disagg.note_imported(
                len(ents), sum(e.nbytes for e in ents))
        if skipped:
            self.disagg.note_dedup(skipped)

    # -- submission ------------------------------------------------------
    def _reclaimable_pages(self):
        """Cached pages only the cache still holds — evictable on
        demand, so they count as admission headroom. An incrementally
        maintained counter (constructor note): exact on the scheduler
        thread (the only mutator), advisory from submit() callers."""
        return self._reclaimable

    def admission_headroom(self):
        """Pages not promised to any admitted slot (free plus
        reclaimable cached pages, minus outstanding reservations) —
        the budget new admissions draw from. Advisory (the ticker
        mutates concurrently)."""
        return (len(self._free) + self._reclaimable_pages()
                - self._reserved_unalloc)

    def submit(self, ids, max_new_tokens=32, *, eos_token_id=None,
               do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
               deadline=None, tenant=None, session=None,
               **_ignored) -> _Request:
        if deadline is not None and deadline.expired():
            raise DeadlineExceeded(
                "deadline exceeded before engine admission")
        ids = np.asarray(ids, np.int32).reshape(-1)
        if self.block_length and do_sample:
            raise ValueError(
                "this model generates by blocks (block_length="
                f"{self.block_length}): do_sample is not carried (a "
                "denoising step takes each position's best token)")
        total = ids.size + int(max_new_tokens)
        pages = -(-total // self.page_size)
        if pages > self.max_pages_per_slot:
            raise ValueError(
                f"request needs {pages} pages (prompt {ids.size} + "
                f"max_new {max_new_tokens}) > max_pages_per_slot "
                f"{self.max_pages_per_slot}")
        if pages > self.num_pages - 1:
            raise ValueError(f"request needs {pages} pages > pool size "
                             f"{self.num_pages - 1}")
        req = _Request(ids, max_new_tokens, eos_token_id, do_sample,
                       temperature, top_k, top_p, pages,
                       deadline=deadline, engine=self)
        req.tenant = tenant
        # session identity opts a conversation into turn retention and
        # suspend/resume (tiered KV); only meaningful with a prefix
        # cache — without one there is nothing to key pages by
        req.session = (str(session)
                       if session is not None
                       and self.prefix_cache is not None else None)
        # hash the prompt's full pages NOW (caller thread, cheap); the
        # cache LOOKUP happens at admission on the scheduler thread.
        # The last full page is keyed too (it is immutable — decode
        # writes land in the next page); sharing depth is capped at
        # match time so a fully-cached prompt still prefills its last
        # page (the first generated token needs those logits).
        req.prefix_keys = (chain_keys(ids, self.page_size)
                           if self.prefix_cache is not None else [])
        if observability.ENABLED:
            # adopt the serving layer's request context (propagated by
            # contextvar into the stream-producer thread) or start a
            # fresh one for direct submit() callers; claiming token
            # accounting keeps the HTTP consumer from double-recording
            # the emissions this engine records itself
            ctx = obs_requests.current()
            if ctx is None:
                ctx = obs_requests.register(
                    obs_requests.RequestContext.new())
            if tenant is not None and ctx.tenant is None:
                # direct submit() callers attribute here; the serving
                # layer already stamped HTTP-originated contexts
                ctx.tenant = tenant
            if self.tenancy is not None and tenant is not None \
                    and ctx.tenant_key is None:
                ctx.tenant_key = self.tenancy.key(tenant)
            ctx.claim_tokens()
            req.obs = ctx
            ctx.record("queued", rid=req.rid)
            # ref BEFORE the request becomes visible to the ticker: a
            # running ticker may expire/cancel the row the instant it
            # lands in _pending, and that release must not underflow
            # the count (a multi-row stream() shares one serving
            # context across rows; the context must outlive them all)
            ctx.adopt_engine()
        try:
            self._submit_locked(req, pages)
        except OverloadError as e:
            if req.obs is not None:
                # the shed row never entered _pending, so nothing else
                # will release its ref; for an engine-created or
                # single-row context this finishes it with the shed's
                # own counter ("shed_engine" / "shed_tenant"), so the
                # HTTP layer's later finish is an idempotent no-op
                req.obs.engine_finish(e.counter)
            raise
        return req

    def _submit_locked(self, req, pages):
        with self._lock:
            if self.tenancy is not None:
                # the tenant's OWN pending quota sheds first (typed
                # 429, bulkhead): its storm must not reach the global
                # bound other tenants share
                pol = self.tenancy.policy(req.tenant)
                tkey = self.tenancy.key(req.tenant)
                # quota reads the INCREMENTAL counter, not _pending:
                # _admit swaps _pending out while it prefills, and a
                # storm submitting into that window must still count
                if pol.max_queued is not None \
                        and self._queued_by_tenant.get(tkey, 0) \
                        >= pol.max_queued:
                    self.stats["overloaded"] += 1
                    self._note_tenant_shed(tkey, "queue")
                    raise TenantQuotaExceeded(
                        f"tenant {tkey!r} over engine queue quota "
                        f"({pol.max_queued} pending)", retry_after=0.1)
            if self.max_pending is not None:
                # shed when the request can neither start NOW (free
                # slot + page headroom, nothing queued ahead of it)
                # nor wait within the pending bound — the serving tier
                # turns this into a retryable 503, instead of this
                # request waiting unboundedly
                queued = len(self._pending)
                admissible_now = (
                    queued == 0
                    and any(s is None for s in self._slots)
                    and pages <= self.admission_headroom())
                if not admissible_now and queued >= self.max_pending:
                    victim = (self._pressure_victim_locked(req)
                              if self.tenancy is not None else None)
                    if victim is None:
                        self.stats["overloaded"] += 1
                        raise EngineOverloaded(
                            f"engine overloaded: {queued} pending >= "
                            f"max_pending {self.max_pending} and no "
                            "admission headroom", retry_after=0.1)
                    # pressure eviction prefers the over-share tenant:
                    # its newest queued request yields the global slot
                    # to the well-behaved newcomer
                    self._shed_pending_locked(victim)
            # engine-local index: prefill sampling derives from
            # (engine seed, this index), so two engines with the same
            # seed replay identically regardless of process history
            req.sample_index = self._submitted
            self._submitted += 1
            self._inflight += 1
            self._pending.append(req)
            if self.tenancy is not None:
                k = self.tenancy.key(req.tenant)
                self._queued_by_tenant[k] = \
                    self._queued_by_tenant.get(k, 0) + 1
        return req

    def _queued_dec_locked(self, req):
        """A request left queued-land (admitted / cancelled / expired
        / shed / crash-doomed). Caller holds self._lock."""
        if self.tenancy is None:
            return
        k = self.tenancy.key(req.tenant)
        n = self._queued_by_tenant.get(k, 0) - 1
        if n > 0:
            self._queued_by_tenant[k] = n
        else:
            self._queued_by_tenant.pop(k, None)

    def _pressure_victim_locked(self, req):
        """Under global max_pending pressure, the queued request to
        evict in the newcomer's favor: the NEWEST pending request of
        the tenant most over its weighted fair share of the queue —
        and only when that tenant's weighted backlog strictly exceeds
        the newcomer's own (so a storm never evicts itself a slot, and
        equal-share tenants shed the newcomer as before). None when no
        such tenant exists. Shares read the incremental queued
        counter (it also covers requests an in-flight _admit pass is
        holding), but the victim itself must be CURRENTLY in
        self._pending — if the over-share tenant's backlog is all
        mid-admission, there is nothing evictable and the newcomer
        sheds the classic way."""
        counts = dict(self._queued_by_tenant)
        nkey = self.tenancy.key(req.tenant)
        # weighted backlog the newcomer WOULD have, including itself
        nshare = (counts.get(nkey, 0) + 1) \
            / self.tenancy.policy(req.tenant).weight
        worst = None
        for k, n in counts.items():
            if k == nkey:
                continue
            share = n / self.tenancy.policy(k).weight
            if share > nshare and (worst is None or share > worst[1]):
                worst = (k, share)
        if worst is None:
            return None
        for r in reversed(self._pending):
            if self.tenancy.key(r.tenant) == worst[0]:
                return r
        return None

    def _shed_pending_locked(self, victim):
        """Evict one queued request under pressure (caller holds the
        lock): typed retryable error, waiter woken, tracing ref
        released — exactly the submit-shed contract, applied to a
        request that was already queued."""
        self._pending.remove(victim)
        self._queued_dec_locked(victim)
        self._inflight -= 1
        self.stats["overloaded"] += 1
        self._note_tenant_shed(self.tenancy.key(victim.tenant),
                               "engine")
        victim.error = EngineOverloaded(
            "engine overloaded: evicted from the pending queue under "
            "pressure (tenant over its weighted fair share)",
            retry_after=0.1)
        if victim.obs is not None:
            victim.obs.engine_finish("shed_engine")
        victim.queue.put(None)
        victim.done.set()

    def _note_tenant_shed(self, tkey, reason):
        with self._tenant_lock:
            ts = self._tenant_stats.setdefault(
                tkey, {"admitted": 0, "slot_ticks": 0, "shed": 0})
            ts["shed"] += 1
        if observability.ENABLED:
            observability.inc("tenant.shed", tenant=tkey, reason=reason)

    def cancel_all(self):
        """Cancel every request the engine holds, queued or in a slot,
        and return how many. The scheduler retires each at its next
        tick, so every stream ends in order (its queue gets the closing
        None) and no socket is reset. Safe from any thread. A request
        the scheduler is admitting at this very moment is in neither
        place: a caller that must end them all calls again until
        `has_work()` is false."""
        with self._lock:
            held = list(self._pending)
        held += [s.req for s in list(self._slots) if s is not None]
        for req in held:
            req.cancel()
        return len(held)

    def has_work(self):
        # _inflight counts submit -> retire/drop, so the transient
        # window where _admit has popped self._pending but not yet
        # assigned slots cannot read as idle
        with self._lock:
            return self._inflight > 0 or self._flying is not None

    # -- scheduling core -------------------------------------------------
    def _bucket(self, n):
        return max(8, 1 << (n - 1).bit_length())

    def _ref_page(self, page):
        n = self._page_refs.get(page, 0)
        self._page_refs[page] = n + 1
        if n == 1 and page in self._cached_pages:
            # a cache-only page just got a slot ref: not evictable-to-
            # free anymore
            self._reclaimable -= 1

    def _unref_page(self, page):
        """Drop one reference; True when the page just became free
        (the caller recycles it). A page is NEVER freed while a live
        slot or the cache still references it."""
        n = self._page_refs.get(page, 1) - 1
        if n <= 0:
            self._page_refs.pop(page, None)
            return True
        self._page_refs[page] = n
        if n == 1 and page in self._cached_pages:
            # back to cache-only: evicting it would free a page
            self._reclaimable += 1
        return False

    def _recycle_pages(self, pages):
        """Return zero-ref pages to the free list. int8 KV: reset the
        freed pages' quant scales first — scales only ever GROW at
        scatter time (scatter-max), so without this a recycled page
        would quantize its next tenant's k/v with the largest
        magnitude any previous tenant ever wrote. Shared prefix pages
        reach here only when the LAST referent (slot or cache) lets
        go, which is what keeps their scales frozen while shared."""
        if not pages:
            return
        if self._cache_arity == 4:
            idx = jnp.asarray(pages, jnp.int32)
            self.pools = [(kp, vp, ks.at[idx].set(0.0),
                           vs.at[idx].set(0.0))
                          for kp, vp, ks, vs in self.pools]
            if self.draft_pools is not None:
                self.draft_pools = [(kp, vp, ks.at[idx].set(0.0),
                                     vs.at[idx].set(0.0))
                                    for kp, vp, ks, vs in
                                    self.draft_pools]
        self._free.extend(reversed(pages))

    def _evict_prefix_entries(self, budget_only=True):
        """Shrink the prefix cache: to its page budget
        (`budget_only=True`, LRU regardless of sharing — a still-
        referenced page just leaves the key space and is freed later
        by its slots' refcounts), or by ONE reclaimable entry
        (`budget_only=False`, the on-demand lever when the free list
        runs dry — only an entry whose page actually becomes free
        helps there). Returns pages freed."""
        cache = self.prefix_cache
        freed = []
        if cache is None:
            return freed
        if budget_only:
            while cache.over_budget():
                key_page = cache.pop_lru()
                if key_page is None:
                    break
                self._note_evicted(key_page[1], freed, key=key_page[0])
        else:
            key_page = cache.pop_lru_where(
                lambda p: self._page_refs.get(p, 0) == 1)
            if key_page is not None:
                self._note_evicted(key_page[1], freed, key=key_page[0])
        self._recycle_pages(freed)
        return freed

    def _note_evicted(self, page, freed, key=None):
        """Shared eviction epilogue: SPILL the page to the host tier
        when one is configured (never destroy a reusable page while
        host RAM has budget — the capture must precede the ledger exit
        and recycle so the snapshot sees the page's content), then
        leave the cached-page ledger, drop the cache's ref, collect
        the page if that freed it."""
        if key is not None and self.host_tier is not None \
                and not self.host_tier.has(key):
            # a key already host-resident never re-captures: the chain
            # key commits to the full token prefix, and KV content is
            # a pure function of it
            from paddle_tpu.distributed import chaos
            if chaos.ENABLED and chaos.should_fire("kvtier.spill.fail"):
                # degraded mode: plain (destructive) eviction — the
                # page is gone from every tier, the next hit is cold
                self.host_tier.note_spill_skipped()
            else:
                self._tier_capture(key, page)
        self._cached_pages.discard(page)
        if self._page_refs.get(page, 0) == 1:
            self._reclaimable -= 1      # was cache-only: leaving the
            #                             cache ends its reclaimability
        self.stats["prefix_evictions"] += 1
        if observability.ENABLED:
            observability.inc("inference.prefix.evictions")
        if self._unref_page(page):
            freed.append(page)

    def _alloc_pages(self, slot_idx, need_total):
        """Grow slot's allocation to `need_total` pages (lazy; the
        reservation made at admission guarantees the free list — plus
        reclaimable prefix-cache pages, evicted here on demand —
        covers it)."""
        slot = self._slots[slot_idx]
        while len(slot.pages) < need_total:
            if not self._free:
                # admission reserved against free + reclaimable, so a
                # dry free list means a cold cache entry owes us a page
                if not self._evict_prefix_entries(budget_only=False):
                    raise RuntimeError(
                        "page pool exhausted despite reservation: "
                        f"free=0 reserved={self._reserved_unalloc} "
                        f"cached={0 if self.prefix_cache is None else len(self.prefix_cache)}")
            page = self._free.pop()
            self._ref_page(page)
            self._reserved_unalloc -= 1
            self._bt[slot_idx, len(slot.pages)] = page
            slot.pages.append(page)
        if self._ring is not None:
            self._ring.grow(slot_idx, need_total)

    def _prefix_lookup(self, req):
        """Longest cached run of the prompt's full pages, capped at
        `(prompt - 1) // page_size` so at least the prompt's final
        token is always prefilled (its logits pick the first generated
        token) — the last partial page is never shared by
        construction (chain_keys only keys full pages). The chaos site
        `prefix.cache.bypass` turns a hit into a miss — the hit-rate
        lever for deterministic tests."""
        cache = self.prefix_cache
        if cache is None or not req.prefix_keys:
            return []
        shareable = (int(req.prompt.size) - 1) // self.page_size
        if shareable <= 0:
            return []
        from paddle_tpu.distributed import chaos
        if chaos.ENABLED and chaos.should_fire("prefix.cache.bypass"):
            return []
        return cache.match(req.prefix_keys[:shareable])

    def _note_prefix_outcome(self, req, h):
        """Hit/miss accounting at the admission decision (requeued
        requests retry their lookup next pass and must not double-
        count). Prompts too short to ever share (< page_size + 1
        tokens) count neither way."""
        if self.prefix_cache is None:
            return
        ps = self.page_size
        if h > 0:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += h * ps
            self.stats["prefix_pages_shared"] += h
            if observability.ENABLED:
                observability.inc("inference.prefix.hits")
                observability.inc("inference.prefix.hit_tokens", h * ps)
                observability.inc("inference.prefix.pages_shared", h)
        elif (int(req.prompt.size) - 1) // ps > 0:
            self.stats["prefix_misses"] += 1
            if observability.ENABLED:
                observability.inc("inference.prefix.misses")

    def _prefix_insert(self, slot_idx, req):
        """Register a freshly prefilled slot's full prompt pages in the
        prefix cache (scheduler thread, right after the prefill that
        wrote them). Existing keys win — a duplicate prompt admitted
        in the same storm keeps the canonical copy; its own pages
        retire with its slot. Then enforce the LRU page budget."""
        cache = self.prefix_cache
        if cache is None:
            return
        slot = self._slots[slot_idx]
        if slot is None:        # retired within its own prefill tick
            return
        n_full = min(len(req.prefix_keys),
                     int(req.prompt.size) // self.page_size,
                     len(slot.pages))
        for j in range(n_full):
            if cache.insert(req.prefix_keys[j], slot.pages[j]):
                # ref BEFORE joining the cached-page ledger: the slot
                # still holds the page (ref >= 2), so it enters the
                # cache non-reclaimable and flips when the slot retires
                self._ref_page(slot.pages[j])
                self._cached_pages.add(slot.pages[j])
        self._evict_prefix_entries(budget_only=True)

    def _disagg_capture(self, req):
        """Prefill-pool engines eagerly snapshot a request's committed
        full prompt pages into the host tier right after the prefill
        that wrote them (scheduler thread): that host copy is what
        /kv/pull exports, so the handoff never touches device pools
        from an HTTP thread. Chain keys are content identity — a key
        already host-resident never re-captures."""
        if self.role != "prefill":
            return
        cache = self.prefix_cache
        n_full = min(len(req.prefix_keys),
                     int(req.prompt.size) // self.page_size)
        for j in range(n_full):
            key = req.prefix_keys[j]
            if self.host_tier.has(key):
                continue
            page = cache.get(key)
            if page is not None:
                self._tier_capture(key, page)

    # -- host tier (tiered KV, module doc) -------------------------------
    def _tier_capture(self, key, page):
        """Snapshot one page's pool buffers as device slices and hand
        them to the tier's worker. jax arrays are immutable, so the
        slices pin the page's CURRENT content no matter what the pool
        buffers do next (recycle scale-zeroing, donation); the
        blocking D2H (np.asarray) happens on the WORKER thread, so a
        spill never stalls a tick. `copy_to_host_async` starts the
        transfer early where the backend supports it. A K or V page
        leaves as (kv_heads, page_size, head_dim) whatever shape its
        pool stores it in (the same bytes), so that engines with
        different kernels exchange pages."""

        def slices(pools, page_shape):
            out = []
            for grp in pools:
                cut = tuple(a[page].reshape(page_shape) if i < 2
                            else a[page] for i, a in enumerate(grp))
                for a in cut:
                    f = getattr(a, "copy_to_host_async", None)
                    if f is not None:
                        try:
                            f()
                        except Exception:  # lint: disable=silent-swallow -- the async D2H is a hint; the worker's np.asarray does the real transfer either way
                            pass
                out.append(cut)
            return out

        draft = (slices(self.draft_pools, self._draft_page_shape)
                 if self.draft_pools is not None else None)
        self.host_tier.spill(key, slices(self.pools, self._page_shape),
                             draft)

    def _tier_entry_compatible(self, entry):
        """A host entry must match this engine's pool geometry exactly
        (defensive: entries are engine-born, but a stale entry after a
        reconfig must drop, not corrupt pages)."""
        if len(entry.layers) != len(self.pools):
            return False
        grp = entry.layers[0]
        ref = self.pools[0]
        if len(grp) != len(ref):
            return False
        if tuple(grp[0].shape) != self._page_shape or \
                str(grp[0].dtype) != str(ref[0].dtype):
            return False
        # entry.draft may be None even when this engine runs a draft
        # model: the host tier sheds draft mirrors first under budget
        # pressure, and a disagg peer may not run a draft at all.
        # _tier_upload zero-fills the draft pages; speculation just
        # proposes badly against them (the target model verifies every
        # proposal, so outputs stay exact — only acceptance drops).
        return True

    def _tier_upload(self, ents, pages):
        """One batched H2D `.at[idx].set` per pool buffer (the
        DevicePrefetcher lesson: stack on host, place once — not one
        tiny transfer per page per layer). The pages land in the shape
        their pool stores them in (`_tier_capture`); the whole-page
        scatter is XLA's, in a program of its own."""
        idx = jnp.asarray(pages, jnp.int32)

        def put(pools, per_entry):
            out = []
            for li, grp in enumerate(pools):
                out.append(tuple(
                    grp[ai].at[idx].set(jnp.asarray(
                        np.stack([pe[li][ai] for pe in per_entry]).reshape(
                            len(per_entry), *grp[ai].shape[1:])))
                    for ai in range(len(grp))))
            return out

        self.pools = put(self.pools, [e.layers for e in ents])
        if self.draft_pools is not None:
            blank = None
            drafts = []
            for e in ents:
                if e.draft is not None:
                    drafts.append(e.draft)
                    continue
                if blank is None:   # draft mirror was shed (or the
                    #                 peer runs no draft): zero pages
                    blank = [tuple(np.zeros(a.shape[1:], a.dtype)
                                   for a in grp)    # any shape of a page
                             for grp in self.draft_pools]
                drafts.append(blank)
            self.draft_pools = put(self.draft_pools, drafts)

    def _tier_restore(self, req, shared_pages):
        """Host-tier consult on a device-cache miss or partial hit:
        extend the leading shared run with pages restored from host
        RAM. Each restored page is drawn from the free list and enters
        the ledger exactly like a freshly inserted prefix page (cache
        ref only, reclaimable), so `admission_headroom()` stays
        truthful; _admit then refs the whole run for the slot like any
        warm hit, and the tail-only prefill downstream is unchanged —
        a restored prefix is a warm hit with a copy in front."""
        tier = self.host_tier
        cache = self.prefix_cache
        if tier is None or cache is None or not req.prefix_keys:
            return shared_pages
        have = len(shared_pages)
        shareable = (int(req.prompt.size) - 1) // self.page_size
        keys = req.prefix_keys[have:shareable]
        if not keys:
            return shared_pages
        # restored pages come off the free list NOW instead of off the
        # reservation later — the same total draw as admitting this
        # request with its current hits — so only consult the tier
        # when the request would fit anyway (a restore must never push
        # a request past the headroom check _admit runs next)
        if req.pages_needed - have > self.admission_headroom():
            return shared_pages
        run = tier.match_run(keys)
        if not run:
            return shared_pages
        from paddle_tpu.distributed import chaos
        if chaos.ENABLED:
            # a slow H2D restore (PCIe congestion, huge pages): the
            # warm-TTFT lever for tiered-KV latency tests
            chaos.maybe_delay("kvtier.restore.delay")
        ents, pages = [], []
        for key, entry in run:
            if not self._tier_entry_compatible(entry):
                tier.discard(key)
                break
            if not self._free and \
                    not self._evict_prefix_entries(budget_only=False):
                break
            page = self._free.pop()
            # ledger mirror of _prefix_insert-then-retire settling:
            # cache ref only (ref 1), cached, reclaimable — restoring
            # leaves admission headroom exactly where it was
            self._ref_page(page)
            cache.insert(key, page)
            self._cached_pages.add(page)
            self._reclaimable += 1
            ents.append(entry)
            pages.append(page)
        if not ents:
            return shared_pages
        self._tier_upload(ents, pages)
        tier.note_restored(len(pages), sum(e.nbytes for e in ents))
        # restored entries joined the device cache hot; enforce its
        # page budget against the coldest entries (which spill in turn)
        self._evict_prefix_entries(budget_only=True)
        return shared_pages + pages

    # -- sessions (suspend/resume, module doc) ---------------------------
    def _session_retain(self, slot):
        """A finished turn with a session id keeps its KV: register
        the slot's FULL committed pages — prompt AND generated tokens
        — in the prefix cache under the chain over the committed token
        stream, and stamp the session's activity clock. The next
        turn's prompt replays those tokens verbatim, so its chain keys
        match and prefill runs only the new text; the suspend sweep
        spills the same keys to host RAM if the session idles."""
        req = slot.req
        cache = self.prefix_cache
        if cache is None or req.session is None:
            return
        # slot.lens counts tokens whose KV the engine committed (the
        # final emitted token's KV was never fed back)
        committed = (list(map(int, req.prompt))
                     + req.tokens)[:slot.lens]
        keys = chain_keys(committed, self.page_size)
        n = min(len(keys), len(slot.pages))
        for j in range(n):
            if cache.insert(keys[j], slot.pages[j]):
                self._ref_page(slot.pages[j])
                self._cached_pages.add(slot.pages[j])
        rec = self._sessions.pop(req.session, None) \
            or {"keys": [], "last": 0.0, "suspended": False}
        rec["keys"] = keys[:n]
        rec["last"] = time.monotonic()
        rec["suspended"] = False
        self._sessions[req.session] = rec
        while len(self._sessions) > 4096:   # bound the registry: the
            self._sessions.popitem(last=False)  # LRU session just
        #                                         loses retention
        self._evict_prefix_entries(budget_only=True)

    def _session_touch(self, sid):
        """Admission saw this session again: reset its idle clock and
        count the resume if it was suspended (its pages just came back
        through _tier_restore / the warm path)."""
        rec = self._sessions.get(sid)
        if rec is None:
            return
        rec["last"] = time.monotonic()
        self._sessions.move_to_end(sid)
        if rec["suspended"]:
            rec["suspended"] = False
            if self.host_tier is not None:
                self.host_tier.note_resume()

    def _suspend_sweep(self):
        """Engine-driven on tick: spill a long-idle session's cached
        pages to the host tier and free their HBM. Targeted eviction
        (PrefixCache.pop) — the session's OWN keys name exactly the
        pages it pins, LRU order is irrelevant. Sessions with a queued
        next turn are skipped (the admission about to run would
        restore them right back)."""
        if not self._sessions:
            return
        now = time.monotonic()
        with self._lock:
            queued = {r.session for r in self._pending
                      if r.session is not None}
        freed = []
        for sid, rec in self._sessions.items():
            if rec["suspended"] or sid in queued \
                    or now - rec["last"] < self.suspend_after_s:
                continue
            for k in rec["keys"]:
                page = self.prefix_cache.pop(k)
                if page is not None:
                    self._note_evicted(page, freed, key=k)
            rec["suspended"] = True
            self.host_tier.note_suspend()
        self._recycle_pages(freed)

    def _admission_order(self, pending):
        """The order pending requests are considered for admission:
        arrival (FIFO, byte-identical to the pre-tenancy engine)
        without a TenantTable; with one, an ITERATIVE weighted-fair
        pick across per-tenant FIFOs — each pick observes the charges
        of the admissions made earlier in the same pass, so decode
        slots divide by policy weight under saturation, with strict
        priority classes served above the fair tiers."""
        if self._wfq is None or len(pending) <= 1:
            return pending
        queues: dict[str, collections.deque] = {}
        for r in pending:
            queues.setdefault(self.tenancy.key(r.tenant),
                              collections.deque()).append(r)

        def order():
            while queues:
                t = self._wfq.pick(queues)
                q = queues[t]
                r = q.popleft()
                if not q:
                    del queues[t]
                yield r
        return order()

    def _note_tenant_admitted(self, req):
        """Per-tenant accounting + the WFQ stride charge at the moment
        a request takes a slot (scheduler thread)."""
        tkey = self.tenancy.key(req.tenant)
        self._wfq.charge(tkey)
        with self._tenant_lock:
            ts = self._tenant_stats.setdefault(
                tkey, {"admitted": 0, "slot_ticks": 0, "shed": 0})
            ts["admitted"] += 1
        if observability.ENABLED:
            observability.inc("tenant.admitted", tenant=tkey)
            observability.observe("tenant.queue_wait.seconds",
                                  time.monotonic() - req.queued_at,
                                  tenant=tkey)

    def _note_slot_ticks(self, live):
        """One decode slot-tick per live slot per scheduler tick — the
        weighted-fair share evidence (`tenant.decode.slots`). Counts
        aggregate per DISTINCT tenant first so the hot tick path pays
        one lock pass and one counter inc per tenant, not per slot."""
        counts: dict[str, int] = {}
        for i in live:
            k = self.tenancy.key(self._slots[i].req.tenant)
            counts[k] = counts.get(k, 0) + 1
        with self._tenant_lock:
            for k, n in counts.items():
                ts = self._tenant_stats.setdefault(
                    k, {"admitted": 0, "slot_ticks": 0, "shed": 0})
                ts["slot_ticks"] += n
        if observability.ENABLED:
            for k, n in counts.items():
                observability.inc("tenant.decode.slots", n, tenant=k)

    def tenant_snapshot(self):
        """Per-tenant engine shares for the serving /stats rows:
        admissions, decode slot-ticks, sheds, and live pending counts.
        {} without tenancy."""
        if self.tenancy is None:
            return {}
        with self._lock:
            # the incremental counter also covers requests an
            # in-flight _admit pass is holding (self._pending alone
            # under-reports during a prefill window)
            pend = dict(self._queued_by_tenant)
        with self._tenant_lock:
            out = {k: dict(v) for k, v in self._tenant_stats.items()}
        for k, n in pend.items():
            out.setdefault(k, {"admitted": 0, "slot_ticks": 0,
                               "shed": 0})
        for k in out:
            out[k]["pending"] = pend.get(k, 0)
        return out

    def _admit(self):
        with self._lock:
            pending, self._pending = self._pending, []
            staged, self._import_staged = self._import_staged, []
        if staged:
            # peer pages pulled ahead of a routed request (disagg
            # prefetch): land them before this pass's prefix lookups
            # so the request they precede admits warm
            self._disagg_import(staged)
        requeue = []
        admitted = []
        for req in self._admission_order(pending):
            if req.cancelled.is_set():
                self.stats["cancelled"] += 1
                with self._lock:
                    self._inflight -= 1
                    self._queued_dec_locked(req)
                if req.obs is not None:
                    req.obs.engine_finish("cancelled")
                req.queue.put(None)
                req.done.set()
                continue
            if req.deadline is not None and req.deadline.expired():
                # expired while queued: fail it WITHOUT spending a
                # slot, pages, or a prefill on work nobody waits for
                self.stats["expired"] += 1
                with self._lock:
                    self._inflight -= 1
                    self._queued_dec_locked(req)
                req.error = DeadlineExceeded(
                    "deadline exceeded while queued for engine "
                    "admission")
                if req.obs is not None:
                    req.obs.engine_finish("expired")
                req.queue.put(None)
                req.done.set()
                continue
            idx = next((i for i, s in enumerate(self._slots)
                        if s is None), None)
            shared_pages = (self._prefix_lookup(req)
                            if idx is not None else [])
            if idx is not None and self.host_tier is not None:
                # device miss / partial hit: extend the run from the
                # host tier (H2D upload; headroom-neutral)
                shared_pages = self._tier_restore(req, shared_pages)
            # refs BEFORE the headroom check: matched pages stop being
            # reclaimable, so the check below sees the post-hit budget
            for p in shared_pages:
                self._ref_page(p)
            h = len(shared_pages)
            if idx is None or \
                    req.pages_needed - h > self.admission_headroom():
                for p in shared_pages:
                    self._unref_page(p)     # cache ref remains; never frees
                requeue.append(req)
                continue
            self._note_prefix_outcome(req, h)
            if req.session is not None:
                self._session_touch(req.session)
            # only the uncached tail draws fresh pages from the pool
            self._reserved_unalloc += req.pages_needed - h
            admitted.append((idx, req))
            # reserve the slot immediately so the next pending request
            # can't claim it while we batch this tick's prefills
            slot = _Slot(req, lens=0, tok=0)
            slot.shared = h
            self._slots[idx] = slot
            for j, p in enumerate(shared_pages):
                self._bt[idx, j] = p
                slot.pages.append(p)
            self._alloc_pages(idx, -(-req.prompt.size // self.page_size))
            self.stats["admitted"] += 1
            req.admitted_at = time.monotonic()
            if self.tenancy is not None:
                with self._lock:
                    self._queued_dec_locked(req)
                self._note_tenant_admitted(req)
            if req.obs is not None:
                # rid pairs this row's scheduled with ITS queued event
                # (per-row queue_wait clock in a shared context)
                req.obs.record("scheduled", rid=req.rid, slot=idx)
        # same-TAIL-bucket prefills run as ONE group under one wait: one
        # padded program call or a call a row, whichever costs less
        # (`_prefill_width`); warm requests bucket by their UNCACHED
        # tail — that is the whole prefill they run
        groups = {}
        long_grp = []
        alone = self._prefill_limit(1)
        for idx, req in admitted:
            tail = req.prompt.size \
                - self._slots[idx].shared * self.page_size
            if tail > (self.prefill_chunk or alone):
                long_grp.append((idx, req))
                continue
            groups.setdefault(self._bucket(tail), []).append((idx, req))
        if long_grp and self.prefill_chunk:
            self._prefill_chunked_group(long_grp)
        else:
            # too long for one program by the engine's own reckoning:
            # each alone, so that none is padded to the group's width
            for pair in long_grp:
                self._prefill_chunked_group([pair], chunk=alone)
        for ppad, grp in groups.items():
            self._prefill_group(ppad, grp)
        if requeue:
            with self._lock:
                self._pending = requeue + self._pending

    def _prefill_limit(self, rows):
        """The longest padded prompt that `rows` rows prefill in ONE
        program, from the shapes: the float32 scores of a whole-window
        attend (rows x heads x tokens x the block table's window, what
        `_attend_pages` holds at once) stay under
        `_PREFILL_SCORE_BYTES`. A power of two; a longer prompt goes
        through the chunk program in pieces of this length."""
        if self.draft_model is not None:
            return 1 << 30      # the chunk program has no draft mirror
        if self._state is not None:
            # a state is carried from one program to the next whatever
            # their length, so a program is sized by its work: at
            # `_STATE_PREFILL_TOKENS` tokens, all rows together
            limit = max(8, _STATE_PREFILL_TOKENS // rows)
            return 1 << (limit.bit_length() - 1)
        if self.latent_rank:
            # the expanded attend holds one block of scores whatever the
            # call's length (the kernel's in VMEM, the jnp path's by
            # `latent_block_pages`): a program of `_LATENT_PREFILL_TOKENS`
            # tokens, all rows together
            limit = max(8, _LATENT_PREFILL_TOKENS // rows)
            return 1 << (limit.bit_length() - 1)
        if self._chunk_kernel and self.window:
            # through the chunk kernel a call holds no scores; what a
            # longer call costs is a longer ring (`ring_pages_for`). A
            # call of as many tokens as the window, all rows together,
            # keeps a ring under two windows and reads each weight once
            # a window of tokens. (A model without rings whose shapes the
            # kernel takes holds no scores either and keeps the budget
            # below all the same: the rule goes, with `_attend_pages`,
            # in the PR that measures the one-table cells' prefill
            # through the kernel, ROADMAP M3.)
            limit = max(8, self.window // rows)
            return 1 << (limit.bit_length() - 1)
        cfg = self.model.config
        per_token = (rows * cfg.num_attention_heads * 4
                     * self.max_pages_per_slot * self.page_size)
        limit = max(8, _PREFILL_SCORE_BYTES // per_token)
        return 1 << (limit.bit_length() - 1)

    def _prefill(self, slot_idx, req):
        """Single-request prefill (kept for direct callers/tests):
        delegates to the group path."""
        self._slots[slot_idx] = _Slot(req, lens=0, tok=0)
        self._alloc_pages(slot_idx,
                          -(-int(req.prompt.size) // self.page_size))
        chunk = self.prefill_chunk or self._prefill_limit(1)
        if req.prompt.size > chunk:
            self._prefill_chunked_group([(slot_idx, req)], chunk=chunk)
        else:
            self._prefill_group(self._bucket(int(req.prompt.size)),
                                [(slot_idx, req)])

    def _prefill_len(self, req):
        """The tokens of the prompt a prefill stores: all of it, or for a
        model that generates by blocks its whole blocks (the rest opens
        the first block it generates: `_Slot.open`). The program is the
        one of the whole prompt's bucket either way."""
        n = int(req.prompt.size)
        return n - n % self.block_length if self.block_length else n

    def _prefilled(self, slot_idx, req, logits):
        """A prompt's prefill has been read back: the slot's length, and
        its first token from the last position's `logits`, accepted at
        once. A model that generates by blocks yields no token here."""
        slot = self._slots[slot_idx]
        slot.lens = self._prefill_len(req)
        if self.block_length:
            slot.open = req.prompt[slot.lens:]
            return
        slot.tok = self._first_token(logits, req)
        # register the prompt's full pages BEFORE accept (a
        # max_new_tokens=1 request retires inside _accept, freeing
        # its pages — too late to share them)
        self._prefix_insert(slot_idx, req)
        self._disagg_capture(req)
        self._accept(slot_idx, [slot.tok])

    def _first_token(self, logits, req):
        """Select a request's first token from its prefill logits —
        host-side, seeded from (engine seed, submission index) so
        same-seed engines replay identically."""
        if req.do_sample:
            from paddle_tpu.models.generation import _np_process_logits
            rng = np.random.default_rng(
                np.random.SeedSequence([self._seed, req.sample_index]))
            x = _np_process_logits(logits[None, :], req.temperature,
                                   req.top_k, req.top_p)[0]
            u = rng.uniform(1e-9, 1.0, size=x.shape).astype(np.float32)
            return int(np.argmax(x - np.log(-np.log(u))))
        return int(np.argmax(logits))

    def _prefill_width(self, n, ppad):
        """`prefill_width` of `n` rows of `ppad` tokens, and 1 where the
        scores of `max_slots` rows would not fit one program."""
        if ppad > self._prefill_limit(self.max_slots):
            return 1
        return prefill_width(n, ppad, self.max_slots)

    def _note_prefills(self, rows, bw, calls, padded, t0):
        """Count a group of `rows` prompts that went through `calls`
        calls of width `bw`, `padded` of their rows padding, since
        `t0`: the (perf_counter, thread_time) readings at its start."""
        s = self.stats
        s["prefills"] += rows
        s["prefill_rows_run"] += bw * calls
        s["prefill_rows_padded"] += padded
        if rows > 1 and bw == 1:
            s["prefill_rows_split"] += rows
        s["prefill_s"] += time.perf_counter() - t0[0]
        s["prefill_cpu_s"] += time.thread_time() - t0[1]

    def _note_expanded(self, lens, n_valid, s):
        """Count the latent rows one prefill call of `s` tokens a row
        expands through W_kvb in every layer, by the blocks of the path
        its programs take (`latent_expanded_rows`), and the call's prompt
        tokens."""
        if not self.latent_rank:
            return
        cfg = self.model.config
        expanded = latent_expanded_rows(
            self.decode_kernel, self._kernel_interpret, lens, n_valid, s,
            heads=cfg.num_attention_heads, nope=cfg.qk_nope_head_dim,
            rope=cfg.qk_rope_head_dim, dv=cfg.v_head_dim,
            rank=self.latent_rank, pool=self.pools[0][0],
            max_pages=self.max_pages_per_slot)
        self.stats["mla_expand_rows"] += expanded * len(self._full.layers)
        self.stats["mla_prefill_tokens"] += int(np.sum(n_valid))

    def _note_state(self, n_valid):
        """Count one prefill call of a model with a state a slot: its
        valid prompt tokens, all of which went through the scan, and the
        positions of them at which the cross-decoder did not run (it runs
        at each row's last)."""
        if self._state is None:
            return
        s = self.stats
        s["ssm_scan_tokens"] += int(np.sum(n_valid))
        s["ssm_scan_calls"] += 1
        if "cross_skipped_tokens" in s:
            s["cross_skipped_tokens"] += int(np.sum(np.maximum(n_valid - 1,
                                                               0)))

    def _note_grouped(self, fn, calls):
        """Count `calls` calls of the program `fn` into the grouped
        expert kernel's counters: each call runs every kernel call its
        trace recorded."""
        s = self.stats
        for p in fn.grouped_plans:
            s["moe_grouped_calls"] += calls
            s["moe_grouped_rows"] += calls * p.m
            s["moe_grouped_visits_bound"] += calls * p.visits

    def _prefill_chunked_group(self, grp, chunk=None):
        """Feed long prompts through the fixed-size chunk program in
        LOCKSTEP rounds — the paged core appends at lens>0 (the
        reference's chunked-prefill contract, seq_lens_decoder > 0),
        and a storm of long prompts pays ceil(max_len/chunk) program
        calls total instead of one full chunk loop per request.
        Exhausted rows ride later rounds with n_valid=0 (writes drop).
        Where the padded rounds would cost more than the rows alone
        (`_prefill_width`), each row runs its own rounds at width 1,
        one row's after another's, all under one wait."""
        chunk = chunk or self.prefill_chunk
        bw = self._prefill_width(len(grp), chunk)
        parts = ([range(len(grp))] if bw > 1
                 else [[r] for r in range(len(grp))])
        # consumed per row: warm rows (prefix-cache hit) start past the
        # shared pages
        done = [self._slots[idx].shared * self.page_size for idx, _ in grp]
        plens = [self._prefill_len(req) for _, req in grp]
        rounds = [-(-(n - d) // chunk) for n, d in zip(plens, done)]
        calls = sum(max(rounds[r] for r in part) for part in parts)
        with observability.span("engine.prefill", bucket=chunk,
                                rows=len(grp), group=bw,
                                chunks=max(rounds), calls=calls):
            t0 = time.perf_counter(), time.thread_time()
            for _idx, req in grp:
                if req.obs is not None:
                    req.obs.record("prefill_start", rid=req.rid)
            fn = self._prefill_chunk_fn(chunk, bw)
            final_logits = [None] * len(grp)
            padded = 0
            for part in parts:
                while any(done[r] < plens[r] for r in part):
                    ids = np.zeros((bw, chunk), np.int32)
                    lens = np.zeros(bw, np.int32)
                    nv = np.zeros(bw, np.int32)
                    rows = [None] * bw
                    for j, r in enumerate(part):
                        idx, req = grp[r]
                        take = min(chunk, plens[r] - done[r])
                        if take <= 0:
                            continue
                        ids[j, :take] = req.prompt[done[r]:done[r] + take]
                        lens[j] = done[r]
                        nv[j] = take
                        rows[j] = idx
                    last, flat = fn(
                        jnp.asarray(ids), jnp.asarray(lens),
                        jnp.asarray(nv),
                        jax.tree.map(jnp.asarray, self._tables(rows)),
                        [a for kv in self.pools for a in kv])
                    self.pools = self._unflat_pools(flat)
                    self._note_expanded(lens, nv, chunk)
                    self._note_state(nv)
                    padded += bw - int((nv > 0).sum())
                    for j, r in enumerate(part):
                        done[r] += int(nv[j])
                        if nv[j] > 0 and done[r] >= plens[r]:
                            final_logits[r] = (last, j)  # read below
            # one wait for the whole group: the calls queue on the device
            # back to back, none waits for the host to read the one before
            final_logits = [np.asarray(last)[j] for last, j in final_logits]
            self._note_prefills(len(grp), bw, calls, padded, t0)
            self._note_grouped(fn, calls)
            for _idx, req in grp:
                if req.obs is not None:
                    req.obs.record("prefill_end", rid=req.rid)
        for r, (idx, req) in enumerate(grp):
            self._prefilled(idx, req, final_logits[r])

    def _prefill_chunk_fn(self, chunk, bw=1):
        key = ("prefill_chunk", chunk, bw)
        if key in self._programs:
            return self._programs[key]
        model = self.model

        def run(ids, lens, n_valid, bt_rows, pool_flat):
            state = _state_of(bt_rows, lens, n_valid)
            pos = lens[:, None] + jnp.arange(chunk,
                                             dtype=jnp.int32)[None, :]
            logits, new_caches = model(
                Tensor(ids), caches=self._layer_caches(pool_flat),
                position_ids=Tensor(pos), cache_index=state)
            return (_last_valid_logits(_val(logits), n_valid),
                    [_val(a) for kv in new_caches for a in kv])

        fn = self._jit(run, donate=(4,))
        self._programs[key] = fn
        return fn

    def _prefill_group(self, ppad, grp):
        """Prefill all (slot, request) pairs of one padded-length bucket
        under ONE wait. Two static batch widths per bucket, chosen by
        `_prefill_width`: max_slots (one call, padded with n_valid=0
        rows whose writes drop) where short rows on few slots cost less
        together, else 1, a call a row dispatched back to back, each
        call's donated pools feeding the next — the compile count stays
        at two per bucket, and the groups of a bucket use one of them."""
        bw = self._prefill_width(len(grp), ppad)
        calls = [grp] if bw > 1 else [[pair] for pair in grp]
        with observability.span("engine.prefill", bucket=ppad,
                                rows=len(grp), group=bw, chunks=1,
                                calls=len(calls)):
            t0 = time.perf_counter(), time.thread_time()
            for _idx, req in grp:
                if req.obs is not None:
                    req.obs.record("prefill_start", rid=req.rid)
            fn = self._prefill_fn(ppad, bw)
            outs = []
            for part in calls:
                ids = np.zeros((bw, ppad), np.int32)
                lens = np.zeros(bw, np.int32)
                nv = np.zeros(bw, np.int32)
                for row, (idx, req) in enumerate(part):
                    # warm slots (prefix-cache hit) prefill ONLY the
                    # uncached tail: lens starts past the shared pages,
                    # and the tail attends over their KV through the
                    # block table
                    off = self._slots[idx].shared * self.page_size
                    tail = req.prompt[off:self._prefill_len(req)]
                    ids[row, :tail.size] = tail
                    lens[row] = off
                    nv[row] = tail.size
                bt = jax.tree.map(jnp.asarray, self._tables(
                    [idx for idx, _req in part]
                    + [None] * (bw - len(part))))
                last_logits, flat = fn(
                    jnp.asarray(ids), jnp.asarray(lens), jnp.asarray(nv),
                    bt, [a for kv in self.pools for a in kv])
                self.pools = self._unflat_pools(flat)
                self._note_expanded(lens, nv, ppad)
                self._note_state(nv)
                if self.draft_model is not None:
                    # the draft's pools share the same block tables, so
                    # shared pages already hold the PREFIX's draft KV too
                    # (same tokens, written when the entry was cached) —
                    # the draft prefill also runs only the tail
                    dfn = self._draft_prefill_fn(ppad, bw)
                    dflat = dfn(jnp.asarray(ids), jnp.asarray(lens),
                                jnp.asarray(nv), bt,
                                [a for kv in self.draft_pools for a in kv])
                    self.draft_pools = self._unflat_pools(dflat)
                outs.append(last_logits)
            # one wait for the whole group, after the last dispatch
            logits_np = [row for out, part in zip(outs, calls)
                         for row in np.asarray(out)[:len(part)]]
            self._note_prefills(len(grp), bw, len(calls),
                                bw * len(calls) - len(grp), t0)
            self._note_grouped(fn, len(calls))
            for _idx, req in grp:
                if req.obs is not None:
                    req.obs.record("prefill_end", rid=req.rid)
        for row, (idx, req) in enumerate(grp):
            self._prefilled(idx, req, logits_np[row])

    def _accept(self, slot_idx, toks):
        """Feed accepted tokens to the request; retire the slot when the
        request is finished. Returns True if the slot stays live."""
        slot = self._slots[slot_idx]
        req = slot.req
        out = []
        finished = False
        for t in toks:
            out.append(int(t))
            slot.emitted += 1
            if (req.eos_token_id >= 0 and int(t) == req.eos_token_id) \
                    or slot.emitted >= req.max_new_tokens:
                finished = True
                break
        req.tokens.extend(out)
        self.stats["tokens_out"] += len(out)
        if out:
            req.queue.put(out)
            if req.obs is not None:
                # first call records first_token (-> TTFT); later
                # calls record the tick's emission (-> ITL). The row id
                # keys the gap clock so sibling rows of one multi-row
                # request don't read each other's emission times
                req.obs.record_tokens(len(out), stream=req.rid)
        if finished:
            self._retire(slot_idx)
        return not finished

    def _retire(self, slot_idx, reason=None):
        slot = self._slots[slot_idx]
        cancelled = slot.req.cancelled.is_set()
        if reason is None and not cancelled \
                and slot.req.session is not None:
            # session retention BEFORE the refcounted release below:
            # the cache refs it adds are what keep the conversation's
            # pages alive through the slot's unref
            self._session_retain(slot)
        # refcounted release: a page returns to the free list (and, for
        # int8 KV, has its quant scale rows zeroed — _recycle_pages)
        # only when its LAST referent lets go. Shared prefix pages stay
        # allocated, scales frozen, while other slots or the prefix
        # cache still hold them.
        freeable = [p for p in slot.pages if self._unref_page(p)]
        self._recycle_pages(freeable)
        # release the unallocated remainder of this slot's reservation
        # (shared pages were never reserved NOR allocated from the free
        # list, so pages_needed - len(pages) is the remainder either way)
        self._reserved_unalloc -= slot.req.pages_needed - len(slot.pages)
        self._bt[slot_idx, :] = 0
        if self._ring is not None:
            self._ring.release(slot_idx)
        self._slots[slot_idx] = None
        with self._lock:
            self._inflight -= 1
        if not cancelled:
            self.stats["finished"] += 1      # cancelled counts separately
        req = slot.req
        if req.admitted_at is not None:
            # in one update, so that a copy of the stats taken on another
            # thread never holds the one without the other
            s = self.stats
            s.update(queue_wait_s=s["queue_wait_s"]
                     + req.admitted_at - req.queued_at,
                     request_s=s["request_s"]
                     + time.monotonic() - req.queued_at)
        if slot.req.obs is not None:
            slot.req.obs.engine_finish(
                reason or ("cancelled" if cancelled else "finished"))
        slot.req.queue.put(None)
        slot.req.done.set()

    def _slot_arrays(self, live):
        """Host-side per-slot marshaling shared by the normal and
        speculative ticks."""
        b = self.max_slots
        arrs = dict(tok=np.zeros(b, np.int32),
                    lens=np.zeros(b, np.int32),
                    active=np.zeros(b, bool),
                    limit=np.zeros(b, np.int32),
                    eos=np.full(b, -1, np.int32),
                    temp=np.ones(b, np.float32),
                    topk=np.zeros(b, np.int32),
                    topp=np.ones(b, np.float32),
                    wants=np.zeros(b, bool))
        if self.block_length:
            # the first block a slot generates: the prompt's tokens past
            # its last whole block, known from the start
            arrs["open_tok"] = np.zeros((b, self.block_length), np.int32)
            arrs["open_known"] = np.zeros((b, self.block_length), bool)
        for i in live:
            slot = self._slots[i]
            if len(slot.open):
                arrs["open_tok"][i, :len(slot.open)] = slot.open
                arrs["open_known"][i, :len(slot.open)] = True
            arrs["tok"][i] = slot.tok
            arrs["lens"][i] = slot.lens
            arrs["active"][i] = True
            arrs["limit"][i] = slot.req.max_new_tokens - slot.emitted
            arrs["eos"][i] = slot.req.eos_token_id
            arrs["temp"][i] = slot.req.temperature
            arrs["topk"][i] = slot.req.top_k
            arrs["topp"][i] = slot.req.top_p
            arrs["wants"][i] = slot.req.do_sample
        return arrs

    def _accept_tick(self, live, out_np, counts, eos, lens_np):
        """Shared accept epilogue: truncate by budget then eos, feed the
        request, advance slot state for survivors. `out_np` (slots,
        steps a tick) holds each slot's new tokens first and `counts` how
        many of them are new: a tick's steps, less in a slot's last tick
        by its budget and, where a tick settles blocks, in its first by
        the prompt's tokens that opened the block."""
        for i in live:
            slot = self._slots[i]
            emitted = list(out_np[i, :int(counts[i])])
            if eos[i] >= 0 and eos[i] in emitted:
                emitted = emitted[:emitted.index(eos[i]) + 1]
            if self._accept(i, emitted):
                slot.lens = int(lens_np[i])
                slot.tok = int(emitted[-1])
                slot.open = ()

    def step(self):
        """One scheduler tick: admit pending requests (prefill), then
        one fused multi-step decode over every live slot. Returns True
        if any work was done. When it returns no tick is in flight:
        only the loops that own the scheduler (`_ticker_loop`,
        `run_until_idle`) leave one on the device between two calls."""
        return self._step(ahead=False)

    def _step(self, ahead):
        self._step_seq += 1
        self._in_step = True   # an iteration under way (incl. a long
        try:                   # first-call compile) is driver progress
            return self._step_tick(ahead)
        finally:
            self._in_step = False

    def _may_chain(self, live):
        """Whether the tick after the newest one may be launched from
        its carry before the host has read it: only while nothing can
        change which request sits in which slot before that tick, and
        some slot outlives the newest tick by its budget. The caller
        has seen to the cancelled slots; a draft model never comes here
        (`_step_spec`: what a slot emits depends on the data)."""
        n = self.steps_per_tick
        if not any(self._slots[i].req.max_new_tokens
                   - self._slots[i].emitted > n for i in live):
            return False
        with self._lock:
            return not self._pending and not self._import_staged

    def _step_tick(self, ahead):
        """One tick under its spans (observability/trace.py SPANS) and
        its own clock: `engine.tick` holds the TICK_PHASES in order,
        `marks` the perf_counter and the ticker's thread_time readings
        at each boundary. The counters and `tick_log` are always on
        (`_note_tick`); the spans show in any profiler capture.

        An iteration lands exactly one decode tick (read back, accept):
        the one in flight, or with none in flight the one it launches
        from the host's rows after `retire -> admit`. Before it lands
        that tick it launches the next from the tick's carry on the
        device, if `ahead` and `_may_chain`, and leaves it in flight.
        With a tick in flight that must not be chained (a request came
        in, a slot was cancelled) the iteration lands it and does
        nothing else: the next one admits. What holds while a tick is
        in flight is in the class doc."""
        from paddle_tpu.distributed import chaos
        if chaos.ENABLED:
            # a slow scheduler tick (congested chip, straggler host):
            # stretches TTFT and ITL — the request-tracing tests' lever
            chaos.maybe_delay("engine.tick.delay")
        # taken off the engine while this iteration owns it: an error
        # drops it, and the ticker's handler fails its requests
        flight, self._flying = self._flying, None
        if flight is None and not any(self._slots):
            with self._lock:
                idle = not self._pending and not self._import_staged
            if idle:
                # an idle poll is no tick: no span, no tick_log row
                if self.suspend_after_s is not None:
                    self._suspend_sweep()
                return False
        clock, cpu = time.perf_counter, time.thread_time
        pre = {k: self.stats[k] for k in ("prefill_s", "prefill_cpu_s",
                                          "prefills")}
        n = self.steps_per_tick
        with observability.span(
                "engine.tick", seq=self._step_seq,
                **({"blocks": n // self.block_length}
                   if self.block_length else {})):
            marks = [(clock(), cpu())]
            with observability.span("engine.tick.retire"):
                retired = 0
                for i, slot in enumerate(self._slots):
                    if slot is not None and slot.req.cancelled.is_set():
                        self.stats["cancelled"] += 1
                        self._retire(i)
                        retired += 1
                if self.suspend_after_s is not None:
                    self._suspend_sweep()
            marks.append((clock(), cpu()))
            with observability.span("engine.tick.admit"):
                if flight is None:
                    self._admit()
            marks.append((clock(), cpu()))
            if flight is None:
                live = [i for i, s in enumerate(self._slots)
                        if s is not None]
            else:
                live = [i for i, req in flight.live
                        if self._slots[i] is not None
                        and self._slots[i].req is req]
            if live and self.draft_model is not None:
                if self.tenancy is not None:
                    self._note_slot_ticks(live)
                self._step_spec(live, marks)
            elif live or flight is not None:
                # the decode tick, phase by phase. The program is called
                # from this frame, as it always was: with a helper method
                # and a closure between here and the jitted call the
                # program's first call (trace and lowering) took 13 s
                # against 6 s on the v5e host, a quarter more set-up for
                # a server (measured, cause not found: PERF.md, PR 25)
                chain = bool(ahead and live
                             and not (retired and flight is not None)
                             and self._may_chain(live))
                args = None if flight is None else flight.args
                with observability.span("engine.tick.alloc"):
                    if flight is None or chain:
                        # pages for the tokens of the tick to land and,
                        # ahead, of the one chained after it: the host
                        # knows `lens` as of the former's launch
                        for i in live:
                            slot = self._slots[i]
                            budget_tokens = (slot.req.prompt.size
                                             + slot.req.max_new_tokens)
                            need = min(slot.lens + n * (1 + chain),
                                       budget_tokens)
                            self._alloc_pages(i, -(-need // self.page_size))
                    if flight is None:
                        a = self._slot_arrays(live)
                marks.append((clock(), cpu()))
                with observability.span("engine.tick.upload"):
                    if flight is None:
                        any_sample = bool(a["wants"].any())
                        sent = self._tables()   # never written again
                        rows = tuple(jnp.asarray(a[k]) for k in (
                            ("open_tok", "open_known") if self.block_length
                            else ("tok",)) + ("lens", "active", "limit"))
                        args = _TickArgs(
                            self._tick_fn(any_sample),
                            jax.tree.map(jnp.asarray, sent), sent,
                            jnp.asarray(a["eos"]),
                            tuple(jnp.asarray(a[k]) for k in
                                  ("temp", "topk", "topp", "wants"))
                            if any_sample else ())
                    elif chain and self._tables_changed(args.bt_sent):
                        # a chained tick uploads the block table if a
                        # page was added or a row retired, nothing else
                        sent = self._tables()
                        args = args._replace(bt=jax.tree.map(jnp.asarray, sent),
                                             bt_sent=sent)
                marks.append((clock(), cpu()))
                with observability.span("engine.tick.launch"):
                    pairs = [(i, self._slots[i].req) for i in live]
                    newest = flight
                    for _ in range((flight is None) + chain):
                        toks_out, carry_f, flat, *counted = args.fn(
                            rows if newest is None else newest.carry,
                            args.bt, args.eos, self._key_data,
                            np.int32(self._tick_count), *args.sample,
                            [x for kv in self.pools for x in kv])
                        self.pools = self._unflat_pools(flat)
                        self._tick_count += 1
                        if self.tenancy is not None:
                            self._note_slot_ticks(live)
                        newest = _Flight(toks_out, carry_f,
                                         counted[0] if counted else {},
                                         pairs, args)
                        if flight is None:
                            flight = newest
                    self.stats["ticks_chained"] += chain
                marks.append((clock(), cpu()))
                with observability.span("engine.tick.readback"):
                    toks_np = np.asarray(flight.toks)       # (b, n)
                    lens_np = np.asarray(flight.carry[-3])
                    counted = {name: int(v)
                               for name, v in flight.counted.items()}
                marks.append((clock(), cpu()))
                self._ticked(counted)
                # what each slot took, from the host's rows as the tick
                # before left them: the carry the device started from
                counts = np.zeros(self.max_slots, np.int32)
                eos = np.full(self.max_slots, -1, np.int32)
                for i in live:
                    slot = self._slots[i]
                    counts[i] = min(slot.req.max_new_tokens - slot.emitted,
                                    n - len(slot.open))
                    eos[i] = slot.req.eos_token_id
                if self.index_dim or self._ring is not None:
                    took = counts[live]
                    lens0 = np.asarray([self._slots[i].lens for i in live],
                                       np.int64)
                    self.stats["decode_slot_steps"] += int(took.sum())
                    # step j of a slot attends over lens + j + 1 keys
                    edge = self.index_topk or self.window
                    self.stats["select_engaged_steps" if self.index_dim
                               else "window_engaged_steps"] += int(np.clip(
                                   lens0 + took - np.maximum(lens0, edge),
                                   0, took).sum())
                if self._ring is not None:
                    # the keys of those steps: every one in the layers
                    # that keep every token, the window's in the rings
                    keys = lens0[:, None] + 1 + np.arange(n)[None]
                    keys = np.where(np.arange(n)[None] < took[:, None],
                                    keys, 0)
                    whole = int(keys.sum())
                    ringed = int(np.minimum(keys, self.window).sum())
                    rings = len(self._ring.layers)
                    self.stats["kv_tokens_flat"] += whole * (
                        rings + len(self._full.layers))
                    self.stats["kv_tokens_held"] += (
                        whole * len(self._full.layers) + ringed * rings)
                with observability.span("engine.tick.accept"):
                    # rows of a request retired since the launch (ended
                    # in the tick before, cancelled) are not in `live`
                    self._accept_tick(live, toks_np, counts, eos, lens_np)
                marks.append((clock(), cpu()))
                if not live:
                    # every slot was dead in this tick (or cancelled
                    # under it): the index it took goes to the next, as
                    # it would have with nothing launched ahead
                    self._tick_count -= 1
                if newest is not flight:
                    self._flying = newest
        self._note_tick(marks, len(live), pre)
        return bool(live) or flight is not None

    def _ticked(self, counted=None):
        """Count one decode tick whose read back has just ended, with
        the counts its program returned (`counted`, read back already):
        in one update, so that a copy of the stats taken on another
        thread (the benchmark's window) never holds a tick's counts
        without the tick."""
        s = self.stats
        s.update({k: s.get(k, 0) + v for k, v in (counted or {}).items()},
                 ticks=s["ticks"] + 1)
        self.stats["kv_write_kernel_ticks"] += self.kv_write == "pallas"
        if observability.ENABLED:
            observability.inc("inference.decode.kernel",
                              path=self.decode_kernel)
            observability.inc("inference.kv_write.kernel",
                              path=self.kv_write)

    def _step_spec(self, live, marks):
        """Speculative tick: greedy AND sampled slots ride it together
        (per-slot regimes in-graph; _spec_tick_fn doc). The same phases
        as the plain tick's, their clock marks appended to `marks`."""
        clock, cpu = time.perf_counter, time.thread_time
        g = self.spec_tokens
        with observability.span("engine.tick.alloc"):
            for i in live:
                slot = self._slots[i]
                budget = slot.req.prompt.size + slot.req.max_new_tokens
                need = min(slot.lens + g + 1, budget)
                self._alloc_pages(i, -(-need // self.page_size))
            a = self._slot_arrays(live)
        marks.append((clock(), cpu()))
        with observability.span("engine.tick.upload"):
            fn = self._spec_tick_fn(bool(a["wants"].any()))
            key = jax.random.fold_in(self._key, self._tick_count)
            args = [jnp.asarray(a["tok"]), jnp.asarray(a["lens"]),
                    jnp.asarray(a["active"]), jnp.asarray(self._bt),
                    jax.random.key_data(key), jnp.asarray(a["temp"]),
                    jnp.asarray(a["topk"]), jnp.asarray(a["topp"]),
                    jnp.asarray(a["wants"])]
        marks.append((clock(), cpu()))
        with observability.span("engine.tick.launch"):
            out, n_emit, lens_f, tflat, dflat = fn(
                *args, [x for kv in self.pools for x in kv],
                [x for kv in self.draft_pools for x in kv])
            self.pools = self._unflat_pools(tflat)
            self.draft_pools = self._unflat_pools(dflat)
            self._tick_count += 1
        marks.append((clock(), cpu()))
        with observability.span("engine.tick.readback"):
            out_np = np.asarray(out)
            emit_np = np.asarray(n_emit)
            lens_np = np.asarray(lens_f)
        marks.append((clock(), cpu()))
        self._ticked()
        self.stats["spec_ticks"] = self.stats.get("spec_ticks", 0) + 1
        self.stats["spec_proposed"] = (self.stats.get("spec_proposed", 0)
                                       + g * len(live))
        self.stats["spec_accepted"] = (
            self.stats.get("spec_accepted", 0)
            + int(sum(emit_np[i] - 1 for i in live)))
        counts = np.minimum(emit_np, a["limit"])
        with observability.span("engine.tick.accept"):
            self._accept_tick(live, out_np, counts, a["eos"], lens_np)
        marks.append((clock(), cpu()))

    def _note_tick(self, marks, live, pre):
        """The always-on record of one tick from its clock marks: the
        counters and one `tick_log` row. `pre` holds the prefill
        counters as the tick began. A tick that found no live slot after
        admission has the first two phases only; the rest read 0."""
        phases, cpu_phases = ([b - a for a, b in zip(x, x[1:])]
                              + [0.0] * (len(TICK_PHASES) + 1 - len(x))
                              for x in zip(*marks))
        s = self.stats
        wall, readback = sum(phases), phases[5]
        # what the host spent while the device could not be working
        # for this engine: the wall less the waits on the device; and
        # the ticker's CPU time over the same phases
        host = wall - readback - (s["prefill_s"] - pre["prefill_s"])
        host_cpu = (sum(cpu_phases) - cpu_phases[5]
                    - (s["prefill_cpu_s"] - pre["prefill_cpu_s"]))
        # in one update, so that a copy of the stats taken on another
        # thread never holds a tick's wait without its host time. The
        # wait is not clamped at 0 a tick: where the thread's CPU clock
        # advances in scheduler ticks (10 ms on the v5e host) a tick's
        # CPU reads 0 or 10 ms, so only a sum over many ticks is sound,
        # and a clamp would count every tick read as 0 as all wait
        s.update(tick_wall_s=s["tick_wall_s"] + wall,
                 readback_s=s["readback_s"] + readback,
                 tick_host_s=s["tick_host_s"] + host,
                 tick_host_wait_s=s["tick_host_wait_s"] + host - host_cpu,
                 tick_max_s=max(s["tick_max_s"], wall))
        self.tick_log.append((self._step_seq, marks[0][0], *phases, live,
                              s["prefills"] - pre["prefills"], host_cpu))

    def run_until_idle(self):
        """Synchronously drain all pending + active requests (tests,
        batch generation). When the background ticker is running it OWNS
        the scheduler — stepping here too would race on pages/pools — so
        this just waits for it to drain the work."""
        t = self._ticker
        if t is not None and t.is_alive():
            import time
            while self.has_work():
                time.sleep(0.005)
            return
        while self.has_work():
            if not self._step(ahead=True):
                # nothing live but pending couldn't admit: impossible by
                # construction unless slots freed next step; guard
                # against a spin if the pool is wedged.  _pending is
                # read under _lock: scrape threads may be swapping it
                # (found by the guarded-field analyzer pass)
                with self._lock:
                    wedged = not any(self._slots) and bool(self._pending)
                    detail = (f"free={len(self._free)} "
                              f"reserved={self._reserved_unalloc}")
                if wedged:
                    raise RuntimeError(
                        f"pending requests cannot be admitted: {detail}")

    def generate(self, prompts, max_new_tokens=32, **kw):
        """Batch convenience: submit all, drain, return token lists."""
        reqs = [self.submit(p, max_new_tokens, **kw) for p in prompts]
        self.run_until_idle()
        return [r.result() for r in reqs]

    # -- background ticker (HTTP serving) --------------------------------
    def start(self):
        """Run the scheduler in a daemon thread until stop(). stream()
        auto-starts it when serving; submit() does NOT — pair submit()
        with start() or run_until_idle() (a bare submit()+result()
        raises after result()'s stall guard instead of blocking
        forever)."""
        with self._lock:
            if self._ticker is None or not self._ticker.is_alive():
                self._stop_flag = False
                self._ticker = threading.Thread(
                    target=self._ticker_loop, daemon=True)
                self._ticker.start()
        return self

    def stop(self):
        self._stop_flag = True
        t = self._ticker
        if t is not None:
            t.join(timeout=30)
        if self.host_tier is not None:
            # drain + join the spill worker (a later spill restarts it,
            # so stop()/start() cycles keep working)
            self.host_tier.stop()

    def _ticker_loop(self):
        import time
        idle = 0.0
        try:
            while not getattr(self, "_stop_flag", False):
                if self._step(ahead=True):
                    idle = 0.0
                else:
                    idle = min(0.05, idle + 0.005)
                    with observability.span("engine.idle"):
                        time.sleep(idle)
            if self._flying is not None:
                self.step()     # stop(): land the tick in flight
        except Exception as e:      # noqa: BLE001 — fail all waiters
            with self._lock:
                doomed = self._pending
                self._pending = []
                self._inflight -= len(doomed)   # dropped, not retired
                for req in doomed:
                    self._queued_dec_locked(req)
            for req in doomed:                  # never got a slot
                req.error = e
                if req.obs is not None:
                    req.obs.engine_finish("error")
                req.queue.put(None)
                req.done.set()
            for i, s in enumerate(self._slots):
                if s is not None:
                    s.req.error = e
                    # _retire returns the slot's pages + reservation
                    # to the pool (a restarted ticker isn't
                    # permanently short on capacity), releases the
                    # row's tracing ref with the real outcome, and
                    # wakes the waiter
                    self._retire(i, reason="error")
            raise

    def stream(self, input_ids, max_new_tokens=32, *, eos_token_id=None,
               pad_token_id=0, do_sample=False, temperature=1.0,
               top_k=0, top_p=1.0, attention_mask=None, seed=None,
               deadline=None, tenant=None, session=None, **_ignored):
        """generate_stream-compatible surface for PredictorServer: each
        ROW of input_ids becomes an independent engine request (they
        join the continuous batch individually), and the yielded step
        arrays are re-aligned across rows, padding finished rows — so
        the HTTP contract matches models/generation.generate_stream.
        Closing the iterator early (client disconnect) CANCELS the
        underlying requests so the engine stops decoding for nobody."""
        if seed is not None and do_sample:
            import warnings
            warnings.warn(
                "PagedKVEngine ignores per-request seed: sampling noise "
                "in a continuous batch derives from the ENGINE seed and "
                "batch composition; construct the engine with seed= for "
                "reproducible replay", stacklevel=2)
        ids = np.asarray(input_ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None, :]
        if attention_mask is not None:
            m = np.asarray(attention_mask).astype(bool)
            rows = [ids[i][m[i]] for i in range(ids.shape[0])]
        else:
            rows = list(ids)
        self.start()
        # guard ref across the submission window: the ticker is already
        # running, so a fast first row can retire — dropping the shared
        # context's last engine ref — before the next row submits,
        # finishing the whole request early. engine_finish("finished")
        # never beats an abnormal row reason, so releasing the guard in
        # any order is safe.
        guard_ctx = obs_requests.current() if observability.ENABLED \
            else None
        if guard_ctx is not None:
            guard_ctx.adopt_engine()
        reqs = []
        try:
            try:
                for r in rows:
                    reqs.append(self.submit(
                        r, max_new_tokens, eos_token_id=eos_token_id,
                        do_sample=do_sample, temperature=temperature,
                        top_k=top_k, top_p=top_p, deadline=deadline,
                        tenant=tenant, session=session))
            except BaseException:
                # partial multi-row admission must not leak: whatever a
                # later row raised (shed, per-row validation), cancel
                # the rows already submitted before re-raising — they
                # would otherwise decode to max_new_tokens for a caller
                # that already got an exception
                for r in reqs:
                    r.cancel()
                raise
        finally:
            if guard_ctx is not None:
                guard_ctx.engine_finish("finished")
        streams = [r.stream_tokens() for r in reqs]
        try:
            for step in range(int(max_new_tokens)):
                row = np.full(len(reqs), pad_token_id, np.int32)
                alive = False
                for j, it in enumerate(streams):
                    if it is None:
                        continue
                    try:
                        row[j] = next(it)
                        alive = True
                    except StopIteration:
                        streams[j] = None
                if not alive:
                    return
                yield row
        finally:
            for r in reqs:
                r.cancel()          # no-op if already finished

    # -- compiled programs ----------------------------------------------
    def _layer_caches(self, flat):
        """Flat buffer list -> per-layer cache tuples ((k, v) pools, or
        (k, v, k_scale, v_scale) for int8 KV)."""
        n = self._cache_arity
        return [tuple(Tensor(flat[n * i + j]) for j in range(n))
                for i in range(len(flat) // n)]

    def _unflat_pools(self, flat):
        """Inverse of `[a for grp in pools for a in grp]`."""
        n = self._cache_arity
        return [tuple(flat[n * i + j] for j in range(n))
                for i in range(len(flat) // n)]

    def _jit(self, run, donate=()):
        """jit one engine program. Two things every program needs:

        - the models' weights enter as the program's FIRST ARGUMENT,
          swapped into the Layer tree for the duration of the trace. A
          Layer called under jit otherwise closes over its parameter
          arrays, and jax embeds closed-over arrays in the HLO as
          constants — a 1.1B-parameter model is then 2.2 GB of literals
          in EVERY program (tick, each prefill bucket): compiled,
          cached on disk and held in HBM once per program on top of
          the weights themselves;
        - the trace runs under this engine's decode_kernel_scope so
          every paged_attention_update it reaches (including inside
          scan bodies) picks the configured attend path.

        `donate` indexes `run`'s own arguments (the pool buffers, on
        TPU only: the CPU backend cannot reuse donated buffers)."""
        import functools

        from paddle_tpu.jit.functional import _swapped, state_arrays
        models = [m for m in (self.model, self.draft_model)
                  if m is not None]
        if self._weights is None:
            self._weights = [state_arrays(m) for m in models]

        plans = []

        def traced(weights, *args):
            with contextlib.ExitStack() as stack:
                stack.enter_context(decode_kernel_scope(
                    self.decode_kernel, self._kernel_interpret))
                stack.enter_context(_mg.recording(plans))
                for m, w in zip(models, weights):
                    stack.enter_context(_swapped(m, w))
                return run(*args)

        fn = jax.jit(traced, donate_argnums=tuple(
            i + 1 for i in donate) if self._on_tpu else ())
        call = functools.partial(fn, self._weights)
        # the grouped expert kernel's plan of each call the trace holds
        # (kernels/moe_grouped.py `grouped_plan`), filled when it traces
        call.grouped_plans = plans
        return call

    def _prefill_fn(self, ppad, bw=1):
        """Bucketed prefill program. `lens` is the per-row start
        position — 0 for cold prompts, `shared * page_size` for warm
        ones (prefix-cache hit: only the tail rides `ids`, attending
        over the shared pages through the block table) — traced data,
        so cold and warm share one compile per (ppad, bw)."""
        key = ("prefill", ppad, bw)
        if key in self._programs:
            return self._programs[key]
        model = self.model

        def run(ids, lens, n_valid, bt_rows, pool_flat):
            state = _state_of(bt_rows, lens, n_valid)
            pos = lens[:, None] + jnp.arange(ppad,
                                             dtype=jnp.int32)[None, :]
            logits, new_caches = model(
                Tensor(ids), caches=self._layer_caches(pool_flat),
                position_ids=Tensor(pos), cache_index=state)
            return (_last_valid_logits(_val(logits), n_valid),
                    [_val(a) for kv in new_caches for a in kv])

        fn = self._jit(run, donate=(4,))
        self._programs[key] = fn
        return fn

    def _draft_prefill_fn(self, ppad, bw):
        key = ("draft_prefill", ppad, bw)
        if key in self._programs:
            return self._programs[key]
        model = self.draft_model

        def run(ids, lens, n_valid, bt_rows, pool_flat):
            state = PagedState(bt_rows, lens, n_valid)
            pos = lens[:, None] + jnp.arange(ppad,
                                             dtype=jnp.int32)[None, :]
            _, new_caches = model(
                Tensor(ids), caches=self._layer_caches(pool_flat),
                position_ids=Tensor(pos), cache_index=state)
            return [_val(a) for kv in new_caches for a in kv]

        fn = self._jit(run, donate=(4,))
        self._programs[key] = fn
        return fn

    def _spec_tick_fn(self, any_sample=True):
        """Unified speculative tick: g draft steps on the draft pools,
        ONE target verify over the g+1 candidate positions, per-slot
        acceptance in-graph. Greedy slots accept by token equality
        (lossless vs solo greedy); sampled slots run Leviathan
        rejection sampling — accept d_i with prob p_i(d_i)/q_i(d_i),
        correct from the residual max(p-q, 0), bonus row q=0 — so the
        emitted distribution IS the target's processed softmax
        (models/generation.py generate_speculative contract, composed
        with paged caches: rejection rollback is free)."""
        key = ("spec_tick", any_sample)
        if key in self._programs:
            return self._programs[key]
        target, draft = self.model, self.draft_model
        g = self.spec_tokens

        def run(tok, lens, active, bt, key_data, temp, topk, topp,
                wants, target_flat, draft_flat):
            live32 = active.astype(jnp.int32)
            base = jax.random.wrap_key_data(key_data)

            def dstep(carry, j):
                cur, dflat = carry
                state = PagedState(bt, lens + j, live32)
                logits, dcaches = draft(
                    Tensor(cur[:, None]),
                    caches=self._layer_caches(list(dflat)),
                    position_ids=Tensor((lens + j)[:, None]),
                    cache_index=state)
                last = _val(logits)[:, -1]
                greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
                if not any_sample:   # greedy-only program: no sorts,
                    #                  no q_rows materialization
                    return (greedy, tuple(_val(a) for kv in dcaches
                                          for a in kv)), \
                        (greedy, jnp.zeros((last.shape[0], 1),
                                           jnp.float32))
                x = _process_logits_rowwise(last, temp, topk, topp)
                qprob = jax.nn.softmax(x, axis=-1)
                gkey = jax.random.fold_in(base, j)
                noise = jax.random.gumbel(gkey, x.shape, jnp.float32)
                sampled = jnp.argmax(x + noise, axis=-1).astype(jnp.int32)
                nxt = jnp.where(wants, sampled, greedy)
                onehot = jax.nn.one_hot(nxt, last.shape[-1],
                                        dtype=jnp.float32)
                qrow = jnp.where(wants[:, None], qprob, onehot)
                return (nxt, tuple(_val(a) for kv in dcaches
                                   for a in kv)), (nxt, qrow)

            (_, dflat_f), (d_toks, q_rows) = jax.lax.scan(
                dstep, (tok, tuple(draft_flat)),
                jnp.arange(g, dtype=jnp.int32))
            d_toks = jnp.swapaxes(d_toks, 0, 1)          # (B, g)
            q_rows = jnp.swapaxes(q_rows, 0, 1)          # (B, g, v)

            ids = jnp.concatenate([tok[:, None], d_toks], axis=1)
            state = PagedState(bt, lens, live32 * (g + 1))
            pos = lens[:, None] + jnp.arange(g + 1,
                                             dtype=jnp.int32)[None, :]
            logits, tcaches = target(
                Tensor(ids), caches=self._layer_caches(target_flat),
                position_ids=Tensor(pos), cache_index=state)
            lv = _val(logits)                            # (B, g+1, v)
            v = lv.shape[-1]
            picks = jnp.argmax(lv, axis=-1).astype(jnp.int32)

            def write_bonus_draft_kv(n_acc, dflat):
                """Full acceptance advances lens by g+1, committing
                position lens+g (token d_{g-1}) — the one position the
                g draft steps never wrote (they covered lens..lens+g-1).
                Without this write, later draft steps attend over
                zeros/stale KV there (output stays correct — target
                verify — but acceptance silently degrades over long
                generations). One extra draft step writes it; rows
                without full acceptance drop the write via n_valid=0
                (their stale tail is overwritten by the next tick's
                draft scan anyway)."""
                bonus = (active & (n_acc == g)).astype(jnp.int32)
                bstate = PagedState(bt, lens + g, bonus)
                _, dcaches = draft(
                    Tensor(d_toks[:, g - 1:g]),
                    caches=self._layer_caches(list(dflat)),
                    position_ids=Tensor((lens + g)[:, None]),
                    cache_index=bstate)
                return [_val(a) for kv in dcaches for a in kv]

            if not any_sample:
                match = (picks[:, :g] == d_toks).astype(jnp.int32)
                n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                corr = jnp.take_along_axis(
                    picks, n_acc[:, None], axis=1)[:, 0]
                col = jnp.arange(g + 1, dtype=jnp.int32)[None, :]
                padded = jnp.concatenate(
                    [d_toks, jnp.zeros((d_toks.shape[0], 1),
                                       jnp.int32)], 1)
                out = jnp.where(col < n_acc[:, None], padded,
                                jnp.where(col == n_acc[:, None],
                                          corr[:, None], 0))
                out = jnp.where(active[:, None], out, 0)
                n_emit = jnp.where(active, n_acc + 1, 0)
                lens_f = lens + live32 * (1 + n_acc)
                return (out, n_emit, lens_f,
                        [_val(a) for kv in tcaches for a in kv],
                        write_bonus_draft_kv(n_acc, dflat_f))
            xt = _process_logits_rowwise(
                lv.reshape(-1, v),
                jnp.repeat(temp, g + 1), jnp.repeat(topk, g + 1),
                jnp.repeat(topp, g + 1)).reshape(lv.shape)
            p_rows = jax.nn.softmax(xt, axis=-1)         # (B, g+1, v)

            # per-position acceptance
            p_at_d = jnp.take_along_axis(
                p_rows[:, :g], d_toks[..., None], axis=-1)[..., 0]
            q_at_d = jnp.take_along_axis(
                q_rows, d_toks[..., None], axis=-1)[..., 0]
            ukey = jax.random.fold_in(base, g + 1)
            u = jax.random.uniform(ukey, d_toks.shape, jnp.float32)
            acc_sampled = u * jnp.maximum(q_at_d, 1e-30) < p_at_d
            acc_greedy = picks[:, :g] == d_toks
            match = jnp.where(wants[:, None], acc_sampled,
                              acc_greedy).astype(jnp.int32)
            n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # (B,)

            # correction token at row n_acc: greedy -> target argmax;
            # sampled -> residual max(p - q, 0) (bonus row: q = 0)
            q_pad = jnp.concatenate(
                [q_rows, jnp.zeros((q_rows.shape[0], 1, v),
                                   jnp.float32)], axis=1)
            p_corr = jnp.take_along_axis(
                p_rows, n_acc[:, None, None], axis=1)[:, 0]  # (B, v)
            q_corr = jnp.take_along_axis(
                q_pad, n_acc[:, None, None], axis=1)[:, 0]
            res = jnp.maximum(p_corr - q_corr, 0.0)
            has_res = jnp.sum(res, axis=-1, keepdims=True) > 1e-30
            res_dist = jnp.where(has_res, res, p_corr)
            ckey = jax.random.fold_in(base, g + 2)
            cnoise = jax.random.gumbel(ckey, res_dist.shape, jnp.float32)
            corr_sampled = jnp.argmax(
                jnp.log(jnp.maximum(res_dist, 1e-30)) + cnoise,
                axis=-1).astype(jnp.int32)
            corr_greedy = jnp.take_along_axis(
                picks, n_acc[:, None], axis=1)[:, 0]
            corr = jnp.where(wants, corr_sampled, corr_greedy)

            col = jnp.arange(g + 1, dtype=jnp.int32)[None, :]
            padded = jnp.concatenate(
                [d_toks, jnp.zeros((d_toks.shape[0], 1), jnp.int32)], 1)
            out = jnp.where(col < n_acc[:, None], padded,
                            jnp.where(col == n_acc[:, None],
                                      corr[:, None], 0))
            out = jnp.where(active[:, None], out, 0)
            n_emit = jnp.where(active, n_acc + 1, 0)
            lens_f = lens + live32 * (1 + n_acc)
            return (out, n_emit, lens_f,
                    [_val(a) for kv in tcaches for a in kv],
                    write_bonus_draft_kv(n_acc, dflat_f))

        fn = self._jit(run, donate=(9, 10))
        self._programs[key] = fn
        return fn

    def _tick_fn(self, any_sample):
        """The decode tick: `steps_per_tick` steps over every slot, one
        program for every tick of the engine (two with the sampling
        variant). `rows` is the slots' (tok, lens, active, limit): the
        host's, where it launches a tick with nothing in flight and so
        knows every slot, or the previous tick's second output handed
        on as it is, where a tick is chained before the host has read
        the previous one. (No mask mixes the two: every launch is all
        of one or all of the other, and a select at the program's head
        made XLA move a pool between memories every step.) The step
        keys are fold_in(fold_in(engine key, tick_i), step), folded
        inside the program so that a chained tick uploads no key.
        Returns the tokens (slots, steps), the next tick's rows, whose
        second element is the slots' final lens, the pools and, from a
        model that counts, the sums of its counters."""
        if self.block_length:
            return self._block_tick_fn()
        key = ("tick", any_sample)
        if key in self._programs:
            return self._programs[key]
        model = self.model
        n = self.steps_per_tick

        def run(rows, bt, eos, key_data, tick_i, *rest):
            if any_sample:
                temp, topk, topp, wants = rest[:4]
                pool_flat = rest[4]
                tick_key = jax.random.fold_in(
                    jax.random.wrap_key_data(key_data), tick_i)
            else:
                pool_flat = rest[0]
            tok, lens, active, limit = rows

            def body(carry, step_i):
                tok, lens, fin, cnt, flat = carry
                live = jnp.logical_and(active, jnp.logical_not(fin))
                state = _state_of(bt, lens, live.astype(jnp.int32))
                logits, new_caches, *counts = model(
                    Tensor(tok[:, None]),
                    caches=self._layer_caches(list(flat)),
                    position_ids=Tensor(lens[:, None]),
                    cache_index=state,
                    **({"with_counters": True} if self._model_counts
                       else {}))
                counts = ({k: jnp.asarray(_val(v), jnp.int32)
                           for k, v in counts[0].items()}
                          if self._model_counts else None)
                last = _val(logits)[:, -1]
                with jax.named_scope("sample"):
                    greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
                    if any_sample:
                        sk = jax.random.fold_in(tick_key, step_i)
                        noise = jax.random.gumbel(sk, last.shape,
                                                  jnp.float32)
                        proc = _process_logits_rowwise(last, temp, topk,
                                                       topp)
                        sampled = jnp.argmax(proc + noise,
                                             axis=-1).astype(jnp.int32)
                        nxt = jnp.where(wants, sampled, greedy)
                    else:
                        nxt = greedy
                    nxt = jnp.where(live, nxt, 0)
                new_lens = lens + live.astype(jnp.int32)
                new_cnt = cnt + live.astype(jnp.int32)
                hit_eos = live & (eos >= 0) & (nxt == eos)
                new_fin = fin | hit_eos | (new_cnt >= limit)
                new_flat = tuple(_val(a) for kv in new_caches for a in kv)
                carry = (nxt, new_lens, new_fin, new_cnt, new_flat)
                return carry, (nxt if counts is None else (nxt, counts))

            fin0 = jnp.logical_not(active)
            cnt0 = jnp.zeros_like(lens)
            (tok_f, lens_f, fin_f, cnt_f, flat_f), toks = jax.lax.scan(
                body, (tok, lens, fin0, cnt0, tuple(pool_flat)),
                jnp.arange(n, dtype=jnp.int32))
            # a slot that ended inside this tick (eos or budget) is dead
            # in the next one: it writes no K/V there and emits nothing
            nxt = (tok_f, lens_f, active & ~fin_f, limit - cnt_f)
            if self._model_counts:
                toks, counts = toks
                return (jnp.swapaxes(toks, 0, 1), nxt, list(flat_f),
                        {k: jnp.sum(v) for k, v in counts.items()})
            return jnp.swapaxes(toks, 0, 1), nxt, list(flat_f)

        # donate the pool buffers (the last positional arg; its index
        # depends on the 4 sampling vectors), like _prefill_fn and
        # _spec_tick_fn do — without it steady-state decode holds ~2x
        # KV-pool memory. The rows are not donated: the host reads a
        # tick's back after the next tick has taken them
        fn = self._jit(run, donate=(9 if any_sample else 5,))
        self._programs[key] = fn
        return fn

    def _block_forward(self, ids, lens, rows_live, bt, flat):
        """One forward of a block's rows through the model against the
        pages: ids (slots, B) at positions lens .. lens + B - 1; the slots
        of `rows_live` write their rows' K and V there (over what an
        earlier forward of the block left) and every row attends over the
        lens + B keys of its slot. -> (the logits of every row, the pools,
        the model's counters of this forward)."""
        blen = self.block_length
        state = _state_of(bt, lens, rows_live.astype(jnp.int32) * blen)
        place = jnp.arange(blen, dtype=jnp.int32)
        logits, new_caches, *counts = self.model(
            Tensor(ids), caches=self._layer_caches(list(flat)),
            position_ids=Tensor(lens[:, None] + place[None]),
            cache_index=state,
            **({"with_counters": True} if self._model_counts else {}))
        counts = ({k: jnp.asarray(_val(v), jnp.int32)
                   for k, v in counts[0].items()}
                  if self._model_counts else {})
        return (_val(logits),
                tuple(_val(a) for kv in new_caches for a in kv), counts)

    def _block_tick_fn(self):
        """The tick of a model that generates by diffusion over blocks
        (`models/block_diffusion_moe.py`): steps_per_tick / block_length
        blocks a slot, one after another, so that a tick delivers up to
        steps_per_tick tokens a slot as a one-token tick does. A block,
        B = block_length rows a slot at positions lens .. lens + B - 1
        (`_block_forward`):

        - it starts as the mask id wherever a position is not known (all
          of it, but for the first block a slot generates, which the
          prompt's tokens past its last whole block open: `rows`);
        - `denoising_steps` forwards (scope `denoise`), each against the
          pages of the earlier blocks and the block's own current rows,
          whose K and V the forward writes over the step's before. After
          each (scope `unmask`), a masked position's token is the best of
          ITS logits and its confidence that token's probability, and
          the model's rule says which positions the step unmasks
          (`block_diffusion_moe.confidence`, `.unmask`). Which positions
          are masked is carried as booleans, never read off the ids: a
          prompt may hold the mask id. A slot with no position masked
          sits the remaining steps out (it writes nothing);
        - one more forward over the final tokens (scope `store`), whose K
          and V are the block's cache; its logits are not computed.

        Returns what `_tick_fn`'s program does: the tokens (slots, steps),
        each slot's NEW ones first (a block's positions not known at its
        start, in order; the host knows how many: `_accept_tick`), the
        next tick's rows, the pools, and the counters: the model's own a
        forward, the slot-forwards of either kind, the positions unmasked
        and the blocks settled. Greedy only (`submit` refuses sampling),
        so `key_data` and `tick_i` are taken and not used."""
        from paddle_tpu.models import block_diffusion_moe as rule
        key = ("block_tick",)
        if key in self._programs:
            return self._programs[key]
        cfg = self.model.config
        n, blen = self.steps_per_tick, self.block_length
        steps = cfg.denoising_steps
        how = (blen // steps, cfg.remasking, float(cfg.confidence_threshold))
        mask_id = int(cfg.mask_token_id)

        def run(rows, bt, eos, key_data, tick_i, pool_flat):
            tok, known, lens, active, limit = rows

            def block(carry, _):
                tok, known, lens, active, limit, flat = carry
                known0 = known
                ids = jnp.where(known, tok, mask_id)

                def denoise(c, _):
                    ids, known, flat = c
                    work = active & ~jnp.all(known, -1)
                    with jax.named_scope("denoise"):
                        logits, flat, counts = self._block_forward(
                            ids, lens, work, bt, flat)
                    with jax.named_scope("unmask"):
                        best, conf = rule.confidence(logits)
                        pick = rule.unmask(conf, ~known & work[:, None],
                                           *how)
                        ids = jnp.where(pick, best, ids)
                    return (ids, known | pick, flat), dict(
                        counts, block_forwards_denoise=jnp.sum(work),
                        block_positions_unmasked=jnp.sum(pick))

                (ids, known, flat), counted = jax.lax.scan(
                    denoise, (ids, known, flat), None, length=steps)
                with jax.named_scope("store"):
                    _lg, flat, counts = self._block_forward(
                        ids, lens, active, bt, flat)
                counted = {k: jnp.sum(v) for k, v in counted.items()}
                for k, v in counts.items():
                    counted[k] = counted[k] + v
                counted["block_forwards_store"] = jnp.sum(active)
                counted["blocks_done"] = jnp.sum(active)
                # the block's new tokens: those not known at its start, up
                # to the slot's budget, in order; an eos among them ends it
                new = ~known0 & active[:, None]
                order = jnp.cumsum(new, -1) - 1
                out = new & (order < limit[:, None])
                hit_eos = jnp.any(out & (eos[:, None] >= 0)
                                  & (ids == eos[:, None]), -1)
                limit = limit - jnp.sum(out, -1).astype(limit.dtype)
                lens = lens + active.astype(lens.dtype) * blen
                active = active & ~hit_eos & (limit > 0)
                carry = (jnp.zeros_like(tok), jnp.zeros_like(known), lens,
                         active, limit, flat)
                return carry, (ids, counted)

            opened = jnp.sum(known, -1)     # of a slot's first block
            (tok_f, known_f, lens_f, active_f, limit_f, flat_f), (
                ids, counted) = jax.lax.scan(
                    block, (tok, known, lens, active, limit,
                            tuple(pool_flat)), None, length=n // blen)
            # (blocks, slots, B) -> (slots, steps), the new tokens first
            ids = jnp.swapaxes(ids, 0, 1).reshape(-1, n)
            at = jnp.minimum(jnp.arange(n)[None] + opened[:, None], n - 1)
            toks = jnp.take_along_axis(ids, at, axis=1)
            return (toks, (tok_f, known_f, lens_f, active_f, limit_f),
                    list(flat_f),
                    {k: jnp.sum(v) for k, v in counted.items()})

        fn = self._jit(run, donate=(5,))
        self._programs[key] = fn
        return fn
