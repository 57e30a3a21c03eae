"""Decoder that generates by diffusion over blocks, with routed experts in
every layer — forward only, for serving.

The shape of SDAR-30B-A3B-Chat as its config gives it (`model_type`
`sdar_moe`): per layer

- grouped-query attention at its own head width with an RMSNorm over each
  q and k head before RoPE (`sparse_attn_moe.NormedGQA`: the same
  projections; no indexer), under a mask that is **causal by blocks**: with
  block length B a query at position p sees every key at a position below
  (p // B + 1) * B: all earlier blocks and the whole of its own, later
  positions of its block included;
- the routed-expert layer of `nn/layer/moe.py` (`MoEMLP`, dropless:
  softmax over all experts, top k, gates renormalised; no shared expert).

Generation is the block-diffusion loop, and it is the engine's
(`PagedKVEngine`, which learns from `config.block_length` that a tick
settles whole blocks): a block to generate starts as `mask_token_id` at
every position not yet known; a denoising step runs the block's B rows
against the cache and the block's own current rows, reads each masked
position's best token and its probability from THAT position's logits, and
unmasks the most confident; when none is masked the block runs once more
with its final tokens, and that run's K and V are the block's cache. How
many steps a block takes, by which rule positions are unmasked and over
which confidence (`denoising_steps`, `remasking`, `confidence_threshold`)
are a deployment's choice and ride the config only so that the engine
finds them beside the model.

Served through `PagedKVEngine`; the cache-less forward takes the mask (or
builds the block-causal one), so that a test can hold the layers to the
reference without an engine. No training path: the config gives no noise
schedule.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.sparse_attn_moe import (
    NormedGQA, SparseAttnMoeDecoderLayer, SparseAttnMoeForCausalLM,
    SparseAttnMoeModel, _val)

__all__ = ["BlockDiffusionMoeConfig", "tiny_block_diffusion_moe_config",
           "REMASKING", "block_causal_mask", "confidence", "unmask",
           "BlockAttention",
           "BlockDiffusionMoeDecoderLayer", "BlockDiffusionMoeModel",
           "BlockDiffusionMoeForCausalLM"]

# how a denoising step chooses the positions it unmasks, n = block_length /
# denoising_steps of them a step: the n most confident; every position over
# `confidence_threshold` where those are at least n, else the n most
# confident; the n leftmost
REMASKING = ("low_confidence_static", "low_confidence_dynamic", "sequential")


@dataclass
class BlockDiffusionMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    initializer_range: float = 0.02
    # the experts
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    # generation by blocks: the block's length and the id a position not
    # yet known carries
    block_length: int = 4
    mask_token_id: int = 151669
    # the deployment's choice (module doc)
    denoising_steps: int = 4
    remasking: str = "low_confidence_static"
    confidence_threshold: float = 0.9
    # sequence length used by helpers that need one
    seq_length: int = 4096

    def __post_init__(self):
        if self.remasking not in REMASKING:
            raise ValueError(f"remasking must be one of {REMASKING} (got "
                             f"{self.remasking!r})")
        if self.denoising_steps < 1 \
                or self.block_length % self.denoising_steps:
            raise ValueError(
                f"denoising_steps must divide block_length: a step unmasks "
                f"block_length / denoising_steps positions (got "
                f"{self.denoising_steps} for {self.block_length})")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} is no id "
                             f"of a vocabulary of {self.vocab_size}")


def tiny_block_diffusion_moe_config(**overrides) -> BlockDiffusionMoeConfig:
    """2-layer toy config for tests / CPU dryruns."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                max_position_embeddings=512, rope_theta=10000.0,
                num_experts=8, num_experts_per_tok=2,
                moe_intermediate_size=32, block_length=4, mask_token_id=255,
                seq_length=32)
    base.update(overrides)
    return BlockDiffusionMoeConfig(**base)


def block_causal_mask(positions, block):
    """(b, s) positions -> (b, s, s) bool: row q sees column k where k's
    position is below the end of q's block."""
    positions = jnp.asarray(positions)
    ends = (positions // block + 1) * block
    return positions[:, None, :] < ends[:, :, None]


def confidence(logits):
    """(..., B, vocab) logits of a block's rows -> (each position's best
    token, int32, and that token's softmax probability in float32): a
    position is read from ITS row, with no shift by one."""
    lg = logits.astype(jnp.float32)
    peak = jnp.max(lg, -1, keepdims=True)
    return (jnp.argmax(lg, -1).astype(jnp.int32),
            1.0 / jnp.sum(jnp.exp(lg - peak), -1))


def unmask(conf, masked, per_step, strategy, threshold):
    """conf, masked (..., B) -> (..., B) bool: the masked positions a
    denoising step unmasks by `strategy` (REMASKING), `per_step` of them
    (all that are masked, where fewer are); ties to the lower position."""
    place = jnp.arange(conf.shape[-1], dtype=jnp.int32)
    if strategy == "sequential":
        conf = jnp.broadcast_to(-place.astype(jnp.float32), conf.shape)
    conf = jnp.where(masked, conf, -jnp.inf)
    # a position's rank among the masked: those more confident, and of
    # the equally confident those before it
    mine, other = conf[..., :, None], conf[..., None, :]
    ahead = (other > mine) | ((other == mine)
                              & (place[None, :] < place[:, None]))
    first = masked & (jnp.sum(ahead & masked[..., None, :], -1) < per_step)
    if strategy != "low_confidence_dynamic":
        return first
    over = masked & (conf > threshold)
    return jnp.where(jnp.sum(over, -1, keepdims=True) >= per_step, over,
                     first)


class BlockAttention(NormedGQA):
    """`NormedGQA` under the mask that is causal by blocks; against the
    pages it passes its block length to `paged_attention_update`."""

    def forward(self, x, position_ids=None, cache=None, cache_index=None,
                attn_mask=None):
        q, k, v = self.project(x, position_ids)
        with jax.named_scope("core"):
            if cache is None:
                out, new_cache = self.attend_kept(q, k, v, attn_mask), None
            else:
                from paddle_tpu.inference.paged import (
                    PagedState, paged_attention_update)
                if not isinstance(cache_index, PagedState):
                    raise NotImplementedError(
                        "generation by blocks is cached in pages only: "
                        "serve through PagedKVEngine (a block's K and V "
                        "are rewritten until it is final)")
                out, new_cache = paged_attention_update(
                    q, k, v, cache, cache_index,
                    block=self.config.block_length)
        with jax.named_scope("out_proj"):
            out = self.o_proj(out)
        return out if cache is None else (out, new_cache)


class BlockDiffusionMoeDecoderLayer(SparseAttnMoeDecoderLayer):
    attention_class = BlockAttention


class BlockDiffusionMoeModel(SparseAttnMoeModel):
    layer_class = BlockDiffusionMoeDecoderLayer


class BlockDiffusionMoeForCausalLM(SparseAttnMoeForCausalLM):
    model_class = BlockDiffusionMoeModel

    @property
    def rows_a_step(self):
        # a denoising step carries a block's rows and needs every row's
        # logits; a longer cached call is a prefill
        return self.config.block_length

    def forward(self, input_ids, labels=None, position_ids=None,
                attn_mask=None, caches=None, cache_index=None,
                with_counters=False):
        """-> logits. Without `caches` over `attn_mask` (b, s, s) bool, by
        default the block-causal mask of `position_ids` (by default 0 ..
        s - 1); with `caches` as the parent's: (logits, new caches[,
        counters]), the logits of every row of a denoising step and of a
        prefill's last valid token alone."""
        if labels is not None:
            raise NotImplementedError(
                "forward only: the config gives no noise schedule to train "
                "a block-diffusion model by")
        if caches is not None:
            if attn_mask is not None:
                raise NotImplementedError(
                    "against the pages the mask is the block's rule "
                    "(paged_attention_update(block=))")
            return self.cached(input_ids, position_ids, caches, cache_index,
                               with_counters)
        ids = _val(input_ids)
        if position_ids is None:
            position_ids = Tensor(jnp.broadcast_to(
                jnp.arange(ids.shape[1], dtype=jnp.int32), ids.shape))
        if attn_mask is None:
            attn_mask = block_causal_mask(_val(position_ids),
                                          self.config.block_length)
        return self._logits(self.model(
            input_ids, position_ids=position_ids,
            attn_mask=jnp.broadcast_to(
                _val(attn_mask), (ids.shape[0], ids.shape[1], ids.shape[1]))))
