"""Qwen2-MoE-class model family (BASELINE.json config #5:
"Qwen2-MoE / DeepSeekMoE with fleet expert-parallel").

The reference trains this through PaddleNLP with
incubate.distributed.models.moe.MoELayer + fleet's expert-parallel groups;
here the decoder reuses the Llama attention stack with the expert-parallel
MoEMLP (paddle_tpu.nn.layer.moe), plus the Qwen2-MoE shared expert with a
sigmoid gate. Expert weights shard over the mesh's 'ep' axis via
paddle_tpu.parallel.plan.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import nn
from paddle_tpu import tensor as T
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.layer.norm import RMSNorm
from paddle_tpu.nn.layer.moe import MoEMLP
from paddle_tpu.models.llama import (LlamaAttention, LlamaMLP, LlamaConfig)


@dataclass
class Qwen2MoeConfig(LlamaConfig):
    num_experts: int = 60
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 1408
    shared_expert_intermediate_size: int = 5632
    capacity_factor: float = 1.25
    router_aux_loss_coef: float = 0.001
    # dropless dMoE (ragged grouped matmul) instead of GShard capacity
    # dispatch — zero dropped tokens (nn/layer/moe.py _moe_mlp_dropless)
    moe_dropless: bool = False


def tiny_qwen2_moe_config(**overrides) -> Qwen2MoeConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=256,
                rope_theta=10000.0, seq_length=32, num_experts=4,
                num_experts_per_tok=2, moe_intermediate_size=32,
                shared_expert_intermediate_size=64)
    base.update(overrides)
    return Qwen2MoeConfig(**base)


class SharedExpertMoE(nn.Layer):
    """Routed experts (`moe`, a `MoEMLP` the caller built with its
    router's options) plus one always-on SwiGLU shared expert that every
    token passes through. `shared_gate`: the shared expert's output is
    weighed by a sigmoid gate from the token (Qwen2-MoE); without it the
    shared expert is added whole (the layer of models/window_attn_moe.py).
    Where `moe` holds a share of its experts the shared expert is still
    whole here: every device of the group computes it alike for its own
    tokens."""

    def __init__(self, moe, hidden_size, shared_intermediate_size,
                 initializer_range=0.02, shared_gate=True):
        super().__init__()
        self.moe = moe
        self.shared_expert = LlamaMLP(LlamaConfig(
            hidden_size=hidden_size,
            intermediate_size=shared_intermediate_size,
            initializer_range=initializer_range))
        self.shared_expert_gate = None
        if shared_gate:
            init = nn.initializer.Normal(0.0, initializer_range)
            self.shared_expert_gate = nn.Linear(
                hidden_size, 1,
                weight_attr=paddle_tpu.nn.ParamAttr(initializer=init),
                bias_attr=False)

    def forward(self, x, with_hit=False):
        """-> the block's output; `with_hit` as `MoEMLP.forward`'s."""
        with jax.named_scope("shared_expert"):
            shared = self.shared_expert(x)
            if self.shared_expert_gate is not None:
                shared = F.sigmoid(self.shared_expert_gate(x)) * shared
        if not with_hit:
            return self.moe(x) + shared
        moe_out, hit = self.moe(x, with_hit=True)
        return moe_out + shared, hit

    @property
    def aux_loss(self):
        return self.moe.aux_loss


class Qwen2MoeSparseBlock(SharedExpertMoE):
    """MoE experts + always-on shared expert with sigmoid gate
    (Qwen2-MoE architecture)."""

    def __init__(self, config: Qwen2MoeConfig):
        super().__init__(
            MoEMLP(config.hidden_size, config.moe_intermediate_size,
                   config.num_experts, top_k=config.num_experts_per_tok,
                   capacity_factor=config.capacity_factor,
                   initializer_range=config.initializer_range,
                   dropless=config.moe_dropless),
            config.hidden_size, config.shared_expert_intermediate_size,
            initializer_range=config.initializer_range)


class Qwen2MoeDecoderLayer(nn.Layer):
    def __init__(self, config: Qwen2MoeConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = Qwen2MoeSparseBlock(config)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)

    def forward(self, h, position_ids=None, attn_mask=None, cache=None,
                cache_index=None):
        res = h
        h = self.input_layernorm(h)
        new_cache = None
        if cache is not None:
            h, new_cache = self.self_attn(
                h, position_ids=position_ids, attn_mask=attn_mask,
                cache=cache, cache_index=cache_index)
        else:
            h = self.self_attn(h, position_ids=position_ids,
                               attn_mask=attn_mask)
        h = res + h
        res = h
        h2 = self.post_attention_layernorm(h)
        h2 = self.mlp(h2)
        out = res + h2
        return out if cache is None else (out, new_cache)


class Qwen2MoeModel(nn.Layer):
    def __init__(self, config: Qwen2MoeConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=paddle_tpu.nn.ParamAttr(initializer=init))
        self.layers = nn.LayerList(
            [Qwen2MoeDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, position_ids=None, attn_mask=None,
                caches=None, cache_index=None):
        from paddle_tpu.distributed.recompute import recompute
        h = self.embed_tokens(input_ids)
        if caches is not None:
            new_caches = []
            for layer, cache in zip(self.layers, caches):
                h, c = layer(h, position_ids=position_ids,
                             attn_mask=attn_mask, cache=cache,
                             cache_index=cache_index)
                new_caches.append(c)
            return self.norm(h), new_caches
        for layer in self.layers:
            if self.config.recompute and self.training:
                h = recompute(layer, h, position_ids=position_ids,
                              attn_mask=attn_mask)
            else:
                h = layer(h, position_ids=position_ids,
                          attn_mask=attn_mask)
        return self.norm(h)

    def aux_losses(self):
        return [l.mlp.aux_loss for l in self.layers
                if l.mlp.aux_loss is not None]


class Qwen2MoeForCausalLM(nn.Layer):
    def __init__(self, config: Qwen2MoeConfig):
        super().__init__()
        self.config = config
        self.model = Qwen2MoeModel(config)
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.lm_head = nn.Linear(
            config.hidden_size, config.vocab_size,
            weight_attr=paddle_tpu.nn.ParamAttr(initializer=init),
            bias_attr=False)

    def forward(self, input_ids, labels=None, position_ids=None,
                attn_mask=None, caches=None, cache_index=None):
        if caches is not None:
            if labels is not None:
                raise ValueError("KV-cache decode is inference-only; "
                                 "drop labels or caches")
            h, caches = self.model(input_ids, position_ids=position_ids,
                                   attn_mask=attn_mask, caches=caches,
                                   cache_index=cache_index)
            return self.lm_head(h), caches
        h = self.model(input_ids, position_ids=position_ids,
                       attn_mask=attn_mask)
        logits = self.lm_head(h)
        if labels is None:
            return logits
        from paddle_tpu.models.llama import next_token_loss
        loss = next_token_loss(logits, labels, self.config.vocab_size)
        auxes = self.model.aux_losses()
        if auxes:
            total_aux = auxes[0]
            for a in auxes[1:]:
                total_aux = total_aux + a
            loss = loss + self.config.router_aux_loss_coef * total_aux
        return loss, logits

    def generate(self, input_ids, max_new_tokens=32, **kwargs):
        """KV-cache autoregressive generation (models/generation.py)."""
        from paddle_tpu.models.generation import generate
        return generate(self, input_ids, max_new_tokens, **kwargs)
