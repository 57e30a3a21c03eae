"""Decoder with latent attention (MLA) and routed experts beside shared
ones — forward only, for serving.

The shape of Kanana-2-30B-A3B (`model_type` deepseek_v3, no q-LoRA). `h`
is the residual stream, `t` a token's position, H heads:

- attention half: a = RMSNorm(h); q = W_q a, H x (nope + rope) values
  split as [q_nope | q_pe]; [c | k_pe] = W_kva a, `kv_lora_rank` + rope
  values, then c = RMSNorm(c) (`kv_a_layernorm`); k_pe is ONE rotary key
  that every head shares. RoPE (`rope_theta`, no scaling; with
  `rope_interleave` adjacent pairs of dims rotate together) on q_pe and
  k_pe. [k_nope_h | v_h] = c W_kvb,h (nope + v values a head);
  s_h(t, j) = (q_nope_h(t) . k_nope_h(j) + q_pe_h(t) . k_pe(j)) /
  sqrt(nope + rope), causal softmax, o_h = sum_j p v_h(j), y = [o_1 ..
  o_H] W_o; h = h + y;
- second half: m = RMSNorm(h). The first `first_k_dense_replace` layers
  are a dense SwiGLU at `intermediate_size`; the rest route (`MoEMLP`,
  dropless): scores s = sigmoid(W_r m) over `n_routed_experts`, the
  `num_experts_per_tok` of highest s + b chosen (b the
  `e_score_correction_bias`, which chooses and never weighs; `n_group`
  1, so no group limit), gates s / sum of the chosen s times
  `routed_scaling_factor`; y = shared(m) + sum of the gated chosen
  experts, `shared` one SwiGLU of `n_shared_experts` x
  `moe_intermediate_size`, ungated. h = h + y;
- final RMSNorm, untied head.

A cache holds ONE row a token a layer, [c | k_pe] (`kv_lora_rank` +
`qk_rope_head_dim` values), in pages under the engine's block table:
`PagedKVEngine` learns it from `kv_lora_rank` and the layer calls
`paged.latent_attention_update`, which writes the row and attends: a
decode step in the absorbed form (W_uk,h into the query, W_uv,h onto the
output, the kernel of `kernels/mla_decode.py` between), a prefill chunk in
the expanded form over blocks of the slot's pages. Asked to
(`with_counters`), the cached forward also returns what its expert layers
counted and the latent rows its decode attention read. No training path,
no q-LoRA, no YaRN: each refused by name.
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
from paddle_tpu.models.llama import LlamaConfig, LlamaMLP
from paddle_tpu.models.qwen2_moe import SharedExpertMoE
from paddle_tpu.nn.layer.moe import MoEMLP
from paddle_tpu.nn.layer.norm import RMSNorm

__all__ = ["LatentAttnMoeConfig", "tiny_latent_attn_moe_config",
           "LatentAttention", "LatentAttnMoeDecoderLayer",
           "LatentAttnMoeModel", "LatentAttnMoeForCausalLM"]


@dataclass
class LatentAttnMoeConfig:
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144           # the leading dense layers
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    # carried as the config gives them; the cache is one latent row a
    # token, so no K or V head is stored
    num_key_value_heads: int = 32
    head_dim: int = 64
    q_lora_rank: int | None = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e6
    rope_interleave: bool = True
    rope_scaling: dict | None = None
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    moe_intermediate_size: int = 768
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    # sequence length used by helpers that need one
    seq_length: int = 4096

    def __post_init__(self):
        refused = [why for on, why in (
            (self.q_lora_rank is not None, f"q_lora_rank="
             f"{self.q_lora_rank} (a low-rank query: q = W_qb "
             "RMSNorm(W_qa a) is not built)"),
            (self.rope_scaling is not None, f"rope_scaling="
             f"{self.rope_scaling!r} (YaRN's frequencies and mscale are "
             "not built)"),
            (self.n_group != 1 or self.topk_group != 1,
             f"n_group={self.n_group}, topk_group={self.topk_group} (the "
             "group limit of the routing is not built)"),
            (self.scoring_func != "sigmoid" or self.topk_method
             != "noaux_tc", f"scoring_func={self.scoring_func!r}, "
             f"topk_method={self.topk_method!r} (sigmoid scores chosen with "
             "the correction bias are built)")) if on]
        if refused:
            raise NotImplementedError(
                "latent attention with experts is built for the config of "
                "Kanana-2-30B-A3B; not carried: " + "; ".join(refused))

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def tiny_latent_attn_moe_config(**overrides) -> LatentAttnMoeConfig:
    """Toy config for tests / CPU dryruns: a dense layer, then two expert
    layers, every width cut and every kind of part kept."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=3, first_k_dense_replace=1,
                num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, max_position_embeddings=512,
                n_routed_experts=8, num_experts_per_tok=2,
                n_shared_experts=2, moe_intermediate_size=16,
                seq_length=32)
    base.update(overrides)
    return LatentAttnMoeConfig(**base)


def _val(x):
    return x._value if isinstance(x, Tensor) else jnp.asarray(x)


def _linear(d_in, d_out, config):
    init = nn.initializer.Normal(0.0, config.initializer_range)
    return nn.Linear(d_in, d_out, bias_attr=False,
                     weight_attr=paddle_tpu.nn.ParamAttr(initializer=init))


class LatentAttention(nn.Layer):
    """Multi-head latent attention without q-LoRA (module doc)."""

    def __init__(self, config: LatentAttnMoeConfig):
        super().__init__()
        self.config = config
        d, h = config.hidden_size, config.num_attention_heads
        self.q_proj = _linear(d, h * config.qk_head_dim, config)
        self.kv_a_proj_with_mqa = _linear(
            d, config.kv_lora_rank + config.qk_rope_head_dim, config)
        self.kv_a_layernorm = RMSNorm(config.kv_lora_rank,
                                      epsilon=config.rms_norm_eps)
        self.kv_b_proj = _linear(
            config.kv_lora_rank,
            h * (config.qk_nope_head_dim + config.v_head_dim), config)
        self.o_proj = _linear(h * config.v_head_dim, d, config)

    def kv_b(self):
        """W_kvb as (W_uk, W_uv): (rank, H, nope) and (rank, H, v)."""
        cfg = self.config
        w = _val(self.kv_b_proj.weight).reshape(
            cfg.kv_lora_rank, cfg.num_attention_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)
        return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]

    def forward(self, x, position_ids=None, cache=None, cache_index=None):
        cfg = self.config
        b, s = x.shape[0], x.shape[1]
        h, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim)
        with jax.named_scope("qkv"):
            q = self.q_proj(x).reshape([b, s, h, nope + rope])
            ckv = _val(self.kv_a_proj_with_mqa(x))
            c = self.kv_a_layernorm(Tensor(ckv[..., :cfg.kv_lora_rank]))
            k_pe = Tensor(ckv[..., None, cfg.kv_lora_rank:])   # (b,s,1,r)
        q_nope, q_pe = Tensor(_val(q)[..., :nope]), Tensor(_val(q)[..., nope:])
        with jax.named_scope("rope"):
            q_pe, k_pe, _ = fused_rotary_position_embedding(
                q_pe, k_pe, None, position_ids=position_ids,
                use_neox_rotary_style=not cfg.rope_interleave,
                rotary_emb_base=cfg.rope_theta)
        w_uk, w_uv = self.kv_b()
        scale = 1.0 / math.sqrt(nope + rope)
        with jax.named_scope("core"):
            if cache is None:
                out = self._attend_whole(q_nope, q_pe, c, k_pe, w_uk, w_uv,
                                         scale)
                new_cache = None
            else:
                from paddle_tpu.inference.paged import (
                    PagedState, latent_attention_update)
                if not isinstance(cache_index, PagedState):
                    raise NotImplementedError(
                        "latent attention is cached in pages only, one "
                        "latent row a token: serve through PagedKVEngine")
                out, new_cache = latent_attention_update(
                    q_nope, q_pe, c, k_pe[:, :, 0], w_uk, w_uv, cache,
                    cache_index, scale=scale)
        with jax.named_scope("out_proj"):
            out = self.o_proj(out)
        return out if cache is None else (out, new_cache)

    @staticmethod
    def _attend_whole(q_nope, q_pe, c, k_pe, w_uk, w_uv, scale):
        """A whole sequence without a cache, in the expanded form."""
        q_nope, q_pe, c, k_pe = (_val(a) for a in (q_nope, q_pe, c, k_pe))
        b, s, h, _ = q_nope.shape
        t = jnp.arange(s)
        k_nope = jnp.einsum("bsr,rhn->bshn", c, w_uk.astype(c.dtype))
        v = jnp.einsum("bsr,rhv->bshv", c, w_uv.astype(c.dtype))
        att = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope,
                          preferred_element_type=jnp.float32)
               + jnp.einsum("bthr,bsr->bhts", q_pe, k_pe[:, :, 0],
                            preferred_element_type=jnp.float32)) * scale
        att = jnp.where((t[None, :] <= t[:, None])[None, None], att, -1e30)
        p = jax.nn.softmax(att, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhts,bshv->bthv", p, v)
        return Tensor(out.reshape(b, s, -1).astype(q_nope.dtype))


class LatentAttnMoeDecoderLayer(nn.Layer):
    """Pre-norm block: latent attention, then a dense SwiGLU in the
    leading layers and routed experts beside shared ones after."""

    def __init__(self, config: LatentAttnMoeConfig, index):
        super().__init__()
        d = config.hidden_size
        self.self_attn = LatentAttention(config)
        self.dense = index < config.first_k_dense_replace
        if self.dense:
            self.mlp = LlamaMLP(LlamaConfig(
                hidden_size=d, intermediate_size=config.intermediate_size,
                initializer_range=config.initializer_range))
        else:
            self.mlp = SharedExpertMoE(
                MoEMLP(d, config.moe_intermediate_size,
                       config.n_routed_experts,
                       top_k=config.num_experts_per_tok,
                       initializer_range=config.initializer_range,
                       dropless=True, score_func=config.scoring_func,
                       route_norm=config.norm_topk_prob,
                       route_scale=config.routed_scaling_factor,
                       expert_bias=True),
                d, config.n_shared_experts * config.moe_intermediate_size,
                initializer_range=config.initializer_range,
                shared_gate=False)
        self.input_layernorm = RMSNorm(d, epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(d,
                                                epsilon=config.rms_norm_eps)

    def forward(self, h, position_ids=None, cache=None, cache_index=None):
        """-> h, or with a cache (h, the new cache, what the expert layer
        counted: `MoEMLP.forward(with_hit=True)`'s, None from a dense
        layer)."""
        with jax.named_scope("norm"):
            a = self.input_layernorm(h)
        new_cache = None
        with jax.named_scope("attn"):
            if cache is not None:
                a, new_cache = self.self_attn(
                    a, position_ids=position_ids, cache=cache,
                    cache_index=cache_index)
            else:
                a = self.self_attn(a, position_ids=position_ids)
        with jax.named_scope("norm"):
            h = h + a
            m = self.post_attention_layernorm(h)
        hit = None
        if self.dense:
            with jax.named_scope("mlp"):
                y = self.mlp(m)
        else:
            with jax.named_scope("moe"):
                if cache is None:
                    y = self.mlp(m)
                else:
                    y, hit = self.mlp(m, with_hit=True)
        h = h + y
        return h if cache is None else (h, new_cache, hit)


class LatentAttnMoeModel(nn.Layer):
    def __init__(self, config: LatentAttnMoeConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=paddle_tpu.nn.ParamAttr(initializer=init))
        self.layers = nn.LayerList(
            [LatentAttnMoeDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_index=None):
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
        if position_ids is None:
            position_ids = Tensor(jnp.broadcast_to(
                jnp.arange(h.shape[1], dtype=jnp.int32), h.shape[:2]))
        new_caches, hits = [], []
        for layer, cache in zip(self.layers,
                                caches or [None] * len(self.layers)):
            h = layer(h, position_ids=position_ids, cache=cache,
                      cache_index=cache_index)
            if cache is not None:
                h, c, hit = h
                new_caches.append(c)
                if hit is not None:
                    hits.append(hit)
        return h if caches is None else (h, new_caches, hits)


class LatentAttnMoeForCausalLM(nn.Layer):
    def __init__(self, config: LatentAttnMoeConfig):
        super().__init__()
        self.config = config
        self.model = LatentAttnMoeModel(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_size, config)

    def _logits(self, h):
        with jax.named_scope("norm"):
            h = self.model.norm(h)
        with jax.named_scope("lm_head_loss"):
            return self.lm_head(h)

    # what the cached forward counts when asked to (`with_counters`); an
    # engine that finds this sums them over a tick's decode steps
    decode_counter_keys = ("moe_experts_hit", "moe_layer_steps",
                           "mla_decode_rows")

    def forward(self, input_ids, labels=None, position_ids=None,
                attn_mask=None, caches=None, cache_index=None,
                with_counters=False):
        """-> logits; with `caches` (logits, new caches), and with
        `with_counters` as a third what this call counted, summed over
        its layers: the distinct experts its expert layers hit, those
        layers, and the latent rows its attention read (each valid row's
        keys, lens + its new tokens, in every layer)."""
        if labels is not None or attn_mask is not None:
            raise NotImplementedError(
                "forward only: no loss (the correction bias is set by a "
                "rule that is no part of a config) and no padding mask "
                "(the engine's rows carry their lengths)")
        if caches is None:
            return self._logits(self.model(input_ids,
                                           position_ids=position_ids))
        h, caches, hits = self.model(input_ids, position_ids=position_ids,
                                     caches=caches, cache_index=cache_index)
        if h.shape[1] > 1:
            # a prefill needs each row's LAST valid token's logits alone
            hv = _val(h)
            last = jnp.clip(_val(cache_index.n_valid) - 1, 0, hv.shape[1] - 1)
            h = Tensor(jnp.take_along_axis(hv, last[:, None, None], axis=1))
        if not with_counters:
            return self._logits(h), caches
        n_valid = _val(cache_index.n_valid)
        keys = jnp.where(n_valid > 0, _val(cache_index.lens) + n_valid, 0)
        return self._logits(h), caches, {
            "moe_experts_hit": sum(_val(n) for n in hits),
            "moe_layer_steps": len(hits),
            "mla_decode_rows": jnp.sum(keys) * len(self.model.layers)}
