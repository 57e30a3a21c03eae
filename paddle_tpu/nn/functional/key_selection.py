"""The learned key selection of a sparse-attention layer ("lightning
indexer"): a few narrow index heads score every key for every token, and
the `k` highest-scoring causal keys are the only ones the token's heads
attend over. Plain `jax.numpy` on arrays; the model's cache-less forward
(`models/sparse_attn_moe.py`) and the paged engine
(`inference/paged.py`) both select through these two functions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["index_scores", "select_top"]

# what the scores of all index heads at once may take, in float32 bytes;
# above it one head's scores are formed at a time
SCORE_BYTES = 256 * 2 ** 20


def index_scores(qi, ks, w, score_bytes=SCORE_BYTES):
    """I[t, c] = sum_j w[t, j] relu(qi[t, j] . ks[c]) -> (b, s, L) float32.
    qi (b, s, hi, di) the tokens' index queries, ks (b, L, di) one index
    key a candidate, w (b, s, hi) the heads' weights."""
    b, s, hi, _di = qi.shape
    w = w.astype(jnp.float32)
    # + 0.0: a sum of -0.0 terms orders under +0.0 in `select_top`
    if b * s * hi * ks.shape[1] * 4 <= score_bytes:
        sc = jnp.einsum("bshd,bld->bshl", qi, ks,
                        preferred_element_type=jnp.float32)
        return jnp.sum(w[..., None] * jax.nn.relu(sc), axis=2) + 0.0

    def add_head(j, acc):
        # a prefill chunk: one index head's (b, s, L) scores at a time
        sc = jnp.einsum("bsd,bld->bsl", qi[:, :, j], ks,
                        preferred_element_type=jnp.float32)
        return acc + w[:, :, j, None] * jax.nn.relu(sc)
    return jax.lax.fori_loop(
        0, hi, add_head, jnp.zeros((b, s, ks.shape[1]), jnp.float32)) + 0.0


def select_top(scores, causal, k):
    """(b, s, L) bool: the k keys of highest score among each row's causal
    ones, ties to the lower index; every causal key where they are at most
    k. No sort: the k-th largest score is found bit by bit over an order-
    keeping integer image of the floats (32 compare-and-count passes), the
    ties at it are dealt by position."""
    if scores.shape[-1] <= k:
        return causal
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    # order-keeping: flip the magnitude bits of negatives, then the sign
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    key = jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)
    key = jnp.where(causal, key, jnp.uint32(0))

    def bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, prefix)
    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = key > kth[..., None]
    at = key == kth[..., None]
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (at & (jnp.cumsum(at, axis=-1) <= room))) & causal
