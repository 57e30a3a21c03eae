"""Decoder with a learned key selection in every attention layer and
routed experts in every block — forward only, for serving.

The shape of the language model of Keye-VL-2.0-30B-A3B (and of the
"lightning indexer" sparse attention its config names): per layer

- grouped-query attention whose heads are wider than hidden / heads
  (`head_dim` is a config key, q projects to heads x head_dim), with an
  RMSNorm over each q and k head before RoPE;
- an indexer beside q/k/v: a few narrow index heads qI, ONE index key kI a
  token (LayerNorm'd, RoPE'd, cached like k and v) and a weight a head, all
  from the block's input. I[t, s] = sum_j w_j relu(qI_j . kI_s) scores key
  s for token t; the `index_topk` highest-scoring keys s <= t are the only
  ones token t's heads attend over (all of them while t < index_topk);
- the block's second half is the routed-expert layer of `nn/layer/moe.py`
  (`MoEMLP`, the one `Qwen2MoeSparseBlock` uses), dropless: softmax over
  all experts, top k, gates renormalised; no shared expert, no dense layer.

Served through `PagedKVEngine`, which learns from `config.index_head_dim` /
`index_topk` that a layer carries a third pool (one index key a token) and
selects inside `paged_attention_update`. Asked to (`with_counters`), the
cached forward also returns the distinct experts its rows hit, which the
engine's tick program sums over its steps. There is no
training path: top-k passes no gradient, and how an indexer is trained is
not part of a model's config.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.nn.functional import fused_rotary_position_embedding
from paddle_tpu.nn.functional.key_selection import index_scores, select_top
from paddle_tpu.nn.layer.moe import MoEMLP
from paddle_tpu.nn.layer.norm import LayerNorm, RMSNorm

__all__ = ["SparseAttnMoeConfig", "tiny_sparse_attn_moe_config",
           "NormedGQA", "IndexedAttention", "SparseAttnMoeDecoderLayer",
           "SparseAttnMoeModel", "SparseAttnMoeForCausalLM"]


@dataclass
class SparseAttnMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    initializer_range: float = 0.02
    # the indexer: heads x width of the index queries, one index key a
    # token of the same width, and how many keys a token attends over
    index_num_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    # the experts
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    # sequence length used by helpers that need one
    seq_length: int = 4096


def tiny_sparse_attn_moe_config(**overrides) -> SparseAttnMoeConfig:
    """2-layer toy config for tests / CPU dryruns; `index_topk` small
    enough that short prompts cross it."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                max_position_embeddings=512, rope_theta=10000.0,
                index_num_heads=2, index_head_dim=16, index_topk=8,
                num_experts=8, num_experts_per_tok=2,
                moe_intermediate_size=32, seq_length=32)
    base.update(overrides)
    return SparseAttnMoeConfig(**base)


def _val(x):
    return x._value if isinstance(x, Tensor) else jnp.asarray(x)


def _linear(d_in, d_out, config):
    init = nn.initializer.Normal(0.0, config.initializer_range)
    return nn.Linear(d_in, d_out, bias_attr=False,
                     weight_attr=paddle_tpu.nn.ParamAttr(initializer=init))


class Indexer(nn.Layer):
    """Index queries, the token's index key and the heads' weights."""

    def __init__(self, config: SparseAttnMoeConfig):
        super().__init__()
        d = config.hidden_size
        self.wq = _linear(d, config.index_num_heads * config.index_head_dim,
                          config)
        self.wk = _linear(d, config.index_head_dim, config)
        self.k_norm = LayerNorm(config.index_head_dim,
                                epsilon=config.rms_norm_eps)
        self.weights_proj = _linear(d, config.index_num_heads, config)


class NormedGQA(nn.Layer):
    """GQA at its own head width with an RMSNorm over each q and k head
    before RoPE: the projections, and the dense attend of a whole
    sequence over the keys a boolean mask keeps. What chooses the keys
    (a learned selection, a mask by blocks) is the subclass's."""

    def __init__(self, config):
        super().__init__()
        self.config = config
        d, hd = config.hidden_size, config.head_dim
        self.q_proj = _linear(d, config.num_attention_heads * hd, config)
        self.k_proj = _linear(d, config.num_key_value_heads * hd, config)
        self.v_proj = _linear(d, config.num_key_value_heads * hd, config)
        self.o_proj = _linear(config.num_attention_heads * hd, d, config)
        self.q_norm = RMSNorm(hd, epsilon=config.rms_norm_eps)
        self.k_norm = RMSNorm(hd, epsilon=config.rms_norm_eps)

    def project(self, x, position_ids):
        """-> q (b, s, h, hd), k, v (b, s, hk, hd), normed and rotated."""
        cfg = self.config
        b, s = x.shape[0], x.shape[1]
        h, hk, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        with jax.named_scope("qkv"):
            q = self.q_norm(self.q_proj(x).reshape([b, s, h, hd]))
            k = self.k_norm(self.k_proj(x).reshape([b, s, hk, hd]))
            v = self.v_proj(x).reshape([b, s, hk, hd])
        with jax.named_scope("rope"):
            q, k, _ = fused_rotary_position_embedding(
                q, k, None, position_ids=position_ids,
                rotary_emb_base=cfg.rope_theta)
        return q, k, v

    @staticmethod
    def attend_kept(q, k, v, keep):
        """A whole sequence without a cache: softmax over the keys `keep`
        (b, s, s) bool marks, densely, as the paged paths compute it."""
        q, k, v = _val(q), _val(k), _val(v)
        b, s, h, hd = q.shape
        hk = k.shape[2]
        qg = q.reshape(b, s, hk, h // hk, hd)
        att = jnp.einsum("bshgd,blhd->bhgsl", qg, k,
                         preferred_element_type=jnp.float32) / math.sqrt(hd)
        att = jnp.where(keep[:, None, None], att, -1e30)
        p = jax.nn.softmax(att, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhgsl,blhd->bshgd", p, v)
        return Tensor(out.reshape(b, s, h * hd).astype(q.dtype))


class IndexedAttention(NormedGQA):
    """`NormedGQA` over the keys the indexer selects."""

    def __init__(self, config: SparseAttnMoeConfig):
        super().__init__(config)
        self.indexer = Indexer(config)

    def forward(self, x, position_ids=None, cache=None, cache_index=None):
        cfg = self.config
        b, s = x.shape[0], x.shape[1]
        theta = cfg.rope_theta
        q, k, v = self.project(x, position_ids)
        with jax.named_scope("indexer"):
            ix = self.indexer
            qi = ix.wq(x).reshape([b, s, cfg.index_num_heads,
                                   cfg.index_head_dim])
            ki = ix.k_norm(ix.wk(x)).reshape([b, s, 1, cfg.index_head_dim])
            qi, ki, _ = fused_rotary_position_embedding(
                qi, ki, None, position_ids=position_ids,
                rotary_emb_base=theta)
            w = ix.weights_proj(x)                           # (b, s, hi)
        index = (qi, ki.reshape([b, s, cfg.index_head_dim]), w,
                 cfg.index_topk)
        with jax.named_scope("core"):
            if cache is None:
                out, new_cache = self._attend_whole(q, k, v, index), None
            else:
                from paddle_tpu.inference.paged import (
                    PagedState, paged_attention_update)
                if not isinstance(cache_index, PagedState):
                    raise NotImplementedError(
                        "the key selection is cached in pages only: serve "
                        "through PagedKVEngine (a dense KV buffer holds no "
                        "index keys)")
                out, new_cache = paged_attention_update(
                    q, k, v, cache, cache_index, index=index)
        with jax.named_scope("out_proj"):
            out = self.o_proj(out)
        return out if cache is None else (out, new_cache)

    def _attend_whole(self, q, k, v, index):
        """A whole sequence without a cache: the same selection and the
        same softmax as the paged path, densely."""
        qi, ki, w, topk = index
        b, s = q.shape[0], q.shape[1]
        with jax.named_scope("indexer"):
            scores = index_scores(_val(qi), _val(ki), _val(w))
        with jax.named_scope("select"):
            causal = jnp.tril(jnp.ones((s, s), bool))[None]
            keep = select_top(scores, jnp.broadcast_to(causal, (b, s, s)),
                              topk)
        return self.attend_kept(q, k, v, keep)


class SparseAttnMoeDecoderLayer(nn.Layer):
    attention_class = IndexedAttention

    def __init__(self, config):
        super().__init__()
        self.self_attn = self.attention_class(config)
        # the repo's expert layer, dropless: a served token is never
        # dropped (Qwen2MoeSparseBlock wraps the same MoEMLP)
        self.mlp = MoEMLP(config.hidden_size, config.moe_intermediate_size,
                          config.num_experts,
                          top_k=config.num_experts_per_tok,
                          initializer_range=config.initializer_range,
                          dropless=True)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)

    def forward(self, h, position_ids=None, cache=None, cache_index=None,
                **attn_kw):
        """-> h, or with a cache (h, the new cache, the distinct experts
        the rows hit). `attn_kw` goes to the attention as it is."""
        res = h
        with jax.named_scope("norm"):
            h = self.input_layernorm(h)
        new_cache = None
        with jax.named_scope("attn"):
            if cache is not None:
                h, new_cache = self.self_attn(
                    h, position_ids=position_ids, cache=cache,
                    cache_index=cache_index, **attn_kw)
            else:
                h = self.self_attn(h, position_ids=position_ids, **attn_kw)
        with jax.named_scope("norm"):
            h = res + h
            res = h
            h2 = self.post_attention_layernorm(h)
        with jax.named_scope("moe"):
            if cache is None:
                return res + self.mlp(h2)
            h2, hit = self.mlp(h2, with_hit=True)
        return res + h2, new_cache, hit


class SparseAttnMoeModel(nn.Layer):
    layer_class = SparseAttnMoeDecoderLayer

    def __init__(self, config):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=paddle_tpu.nn.ParamAttr(initializer=init))
        self.layers = nn.LayerList(
            [self.layer_class(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_index=None, **attn_kw):
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
        new_caches, hits = [], []
        for layer, cache in zip(self.layers,
                                caches or [None] * len(self.layers)):
            h = layer(h, position_ids=position_ids, cache=cache,
                      cache_index=cache_index, **attn_kw)
            if cache is not None:
                h, c, hit = h
                new_caches.append(c)
                hits.append(hit)
        return h if caches is None else (h, new_caches, hits)


class SparseAttnMoeForCausalLM(nn.Layer):
    model_class = SparseAttnMoeModel
    # query rows a slot a decode call carries: a cached call of more rows
    # is a prefill, which needs its last valid token's logits alone
    rows_a_step = 1

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = self.model_class(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_size, config)

    def _logits(self, h):
        with jax.named_scope("norm"):
            h = self.model.norm(h)
        with jax.named_scope("lm_head_loss"):
            return self.lm_head(h)

    # what the cached forward counts when asked to (`with_counters`); an
    # engine that finds this sums them over a tick's decode steps
    decode_counter_keys = ("moe_experts_hit", "moe_layer_steps")

    def forward(self, input_ids, labels=None, position_ids=None,
                attn_mask=None, caches=None, cache_index=None,
                with_counters=False):
        """-> logits; with `caches` (logits, new caches), and with
        `with_counters` as a third what this call counted: the distinct
        experts its rows hit, summed over the layers, and the layers it is
        summed over."""
        if labels is not None or attn_mask is not None:
            raise NotImplementedError(
                "forward only: no loss (top-k passes no gradient and an "
                "indexer's training is not part of a config) and no "
                "padding mask (the engine's rows carry their lengths)")
        if caches is None:
            return self._logits(self.model(input_ids,
                                           position_ids=position_ids))
        return self.cached(input_ids, position_ids, caches, cache_index,
                           with_counters)

    def cached(self, input_ids, position_ids, caches, cache_index,
               with_counters):
        h, caches, hits = self.model(input_ids, position_ids=position_ids,
                                     caches=caches, cache_index=cache_index)
        if h.shape[1] > self.rows_a_step:
            # a prefill needs each row's LAST valid token's logits alone:
            # at a large vocabulary the rest would be most of its work
            hv = _val(h)
            last = jnp.clip(_val(cache_index.n_valid) - 1, 0, hv.shape[1] - 1)
            h = Tensor(jnp.take_along_axis(hv, last[:, None, None], axis=1))
        if not with_counters:
            return self._logits(h), caches
        return self._logits(h), caches, {
            "moe_experts_hit": sum(_val(n) for n in hits),
            "moe_layer_steps": len(hits)}
