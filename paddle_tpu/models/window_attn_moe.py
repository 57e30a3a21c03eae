"""Decoder that mixes layers attending inside a window with layers attending
over everything, gates its attention's output, and routes its tokens by
sigmoid scores over experts of which this device may hold a share — forward
only, for serving.

The shape of Trinity-Large-Preview (`model_type` afmoe). `h` is the
residual stream, `t` a token's position:

- the embedding is scaled by sqrt(hidden) (`mup_enabled`);
- attention half: a = RMSNorm(h); q = RMSNorm_head(W_q a), k =
  RMSNorm_head(W_k a), v = W_v a, g = W_gate a, with heads wider than
  hidden / heads (`head_dim` is a config key). A WINDOW layer
  (`layer_types[i] == "sliding_attention"`) rotates q and k (rotate-half
  RoPE over the whole head width) and attends over the keys s with t -
  `sliding_window` < s <= t; a FULL layer has no position encoding at all
  and attends over every s <= t. o = softmax(q k / sqrt(head_dim)) v,
  then o * sigmoid(g) (the output gate, before W_o); h = h + RMSNorm(W_o
  o): the norm is on the branch's output, before the residual is added;
- second half: m = RMSNorm(h). The first `num_dense_layers` layers are a
  dense SwiGLU at `intermediate_size`; the rest an expert layer
  (`SharedExpertMoE`): scores s = sigmoid(W_r m) over all `num_experts`,
  the `num_experts_per_tok` experts of highest s + b chosen (b a bias a
  layer that no gradient trains; it chooses and never weighs), gates s /
  sum of the chosen s, times `route_scale`; y = shared(m) + sum of the
  gated chosen experts THAT ARE HELD HERE (`held_experts` = (first,
  count): one device's share of an expert-parallel group, run without
  the exchange; what the others would add is left out). h = h +
  RMSNorm(y);
- final RMSNorm, untied head.

Served through `PagedKVEngine`, which learns from `config.layer_types` /
`sliding_window` that the window layers' K and V live in rings under a
second page table (`PagedState.ring_tables`,
`paged_attention_update(window=)`). Asked to (`with_counters`), the cached
forward also returns what its expert layers counted. There is no training
path: the routing bias is set by a balancing rule that is no part of a
config.
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

__all__ = ["WindowAttnMoeConfig", "tiny_window_attn_moe_config",
           "GatedWindowAttention", "WindowAttnMoeDecoderLayer",
           "WindowAttnMoeModel", "WindowAttnMoeForCausalLM"]

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclass
class WindowAttnMoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288          # the leading dense layers
    num_hidden_layers: int = 60
    num_dense_layers: int = 6
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    # one of WINDOW / FULL a layer; None: three window layers, one full
    layer_types: list | None = None
    sliding_window: int = 4096
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    mup_enabled: bool = True
    # the experts: the router scores `num_experts`; `held_experts` =
    # (first, count) says which of them this device holds (None: all)
    num_experts: int = 256
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    moe_intermediate_size: int = 3072
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.448
    held_experts: tuple | None = None
    # sequence length used by helpers that need one
    seq_length: int = 4096

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = [FULL if i % 4 == 3 else WINDOW
                                for i in range(self.num_hidden_layers)]
        bad = [t for t in self.layer_types if t not in (WINDOW, FULL)]
        if bad or len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {WINDOW!r} or {FULL!r} (got {self.layer_types})")
        if self.num_shared_experts != 1:
            raise NotImplementedError("one shared expert a layer")


def tiny_window_attn_moe_config(**overrides) -> WindowAttnMoeConfig:
    """Toy config for tests / CPU dryruns: a dense window layer, two expert
    window layers and an expert full layer, with a window that short
    prompts cross."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=4, num_dense_layers=1,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                layer_types=[WINDOW, WINDOW, WINDOW, FULL],
                sliding_window=8, max_position_embeddings=512,
                num_experts=8, num_experts_per_tok=2,
                moe_intermediate_size=32, seq_length=32)
    base.update(overrides)
    return WindowAttnMoeConfig(**base)


def _val(x):
    return x._value if isinstance(x, Tensor) else jnp.asarray(x)


def _linear(d_in, d_out, config):
    init = nn.initializer.Normal(0.0, config.initializer_range)
    return nn.Linear(d_in, d_out, bias_attr=False,
                     weight_attr=paddle_tpu.nn.ParamAttr(initializer=init))


class GatedWindowAttention(nn.Layer):
    """GQA at its own head width with per-head q/k RMSNorm and an output
    gate; `window` > 0 makes it a window layer (RoPE, keys inside the
    window), 0 a full layer (no position encoding, every key)."""

    def __init__(self, config: WindowAttnMoeConfig, window):
        super().__init__()
        self.config = config
        self.window = int(window)
        self.rope = bool(window)        # a full layer encodes no position
        d, hd = config.hidden_size, config.head_dim
        self.q_proj = _linear(d, config.num_attention_heads * hd, config)
        self.k_proj = _linear(d, config.num_key_value_heads * hd, config)
        self.v_proj = _linear(d, config.num_key_value_heads * hd, config)
        self.gate_proj = _linear(d, config.num_attention_heads * hd, config)
        self.o_proj = _linear(config.num_attention_heads * hd, d, config)
        self.q_norm = RMSNorm(hd, epsilon=config.rms_norm_eps)
        self.k_norm = RMSNorm(hd, epsilon=config.rms_norm_eps)

    def forward(self, x, position_ids=None, cache=None, cache_index=None):
        cfg = self.config
        b, s = x.shape[0], x.shape[1]
        h, hk, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        with jax.named_scope("qkv"):
            q = self.q_norm(self.q_proj(x).reshape([b, s, h, hd]))
            k = self.k_norm(self.k_proj(x).reshape([b, s, hk, hd]))
            v = self.v_proj(x).reshape([b, s, hk, hd])
            g = self.gate_proj(x)
        if self.rope:
            with jax.named_scope("rope"):
                q, k, _ = fused_rotary_position_embedding(
                    q, k, None, position_ids=position_ids,
                    rotary_emb_base=cfg.rope_theta)
        with jax.named_scope("core"):
            if cache is None:
                out, new_cache = self._attend_whole(q, k, v), None
            else:
                from paddle_tpu.inference.paged import (
                    PagedState, paged_attention_update)
                if not isinstance(cache_index, PagedState):
                    raise NotImplementedError(
                        "window and full layers are cached in pages under "
                        "two tables: serve through PagedKVEngine (a dense "
                        "KV buffer has no ring)")
                if self.window:
                    with jax.named_scope("window"):
                        out, new_cache = paged_attention_update(
                            q, k, v, cache, cache_index, window=self.window)
                else:
                    out, new_cache = paged_attention_update(
                        q, k, v, cache, cache_index)
        with jax.named_scope("gate"):
            out = self._gate(out, g)
        with jax.named_scope("out_proj"):
            out = self.o_proj(out)
        return out if cache is None else (out, new_cache)

    @staticmethod
    def _gate(out, g):
        """The output gate: the heads' outputs times sigmoid(W_gate a)."""
        return out * nn.functional.sigmoid(g)

    def _attend_whole(self, q, k, v):
        """A whole sequence without a cache: the same mask and the same
        softmax as the paged paths, densely."""
        q, k, v = _val(q), _val(k), _val(v)
        b, s, h, hd = q.shape
        hk = k.shape[2]
        t = jnp.arange(s)
        seen = t[None, :] <= t[:, None]
        if self.window:
            seen = seen & (t[None, :] > t[:, None] - self.window)
        qg = q.reshape(b, s, hk, h // hk, hd)
        with jax.named_scope("window" if self.window else "full"):
            att = jnp.einsum("bshgd,blhd->bhgsl", qg, k,
                             preferred_element_type=jnp.float32) \
                / math.sqrt(hd)
            att = jnp.where(seen[None, None, None], att, -1e30)
            p = jax.nn.softmax(att, axis=-1).astype(v.dtype)
            out = jnp.einsum("bhgsl,blhd->bshgd", p, v)
        return Tensor(out.reshape(b, s, h * hd).astype(q.dtype))


class WindowAttnMoeDecoderLayer(nn.Layer):
    """One block: four norms (before and after each half); the second
    half dense in the leading layers, experts after."""

    def __init__(self, config: WindowAttnMoeConfig, index):
        super().__init__()
        window = config.sliding_window \
            if config.layer_types[index] == WINDOW else 0
        self.self_attn = GatedWindowAttention(config, window)
        self.dense = index < config.num_dense_layers
        d = config.hidden_size
        if self.dense:
            self.mlp = LlamaMLP(LlamaConfig(
                hidden_size=d, intermediate_size=config.intermediate_size,
                initializer_range=config.initializer_range))
        else:
            self.mlp = SharedExpertMoE(
                MoEMLP(d, config.moe_intermediate_size, config.num_experts,
                       top_k=config.num_experts_per_tok,
                       initializer_range=config.initializer_range,
                       dropless=True, score_func=config.score_func,
                       route_norm=config.route_norm,
                       route_scale=config.route_scale, expert_bias=True,
                       held=config.held_experts),
                d, config.moe_intermediate_size,
                initializer_range=config.initializer_range,
                shared_gate=False)
        for name in ("input_layernorm", "post_attention_layernorm",
                     "pre_mlp_layernorm", "post_mlp_layernorm"):
            setattr(self, name, RMSNorm(d, epsilon=config.rms_norm_eps))

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
            h = h + self.post_attention_layernorm(a)
            m = self.pre_mlp_layernorm(h)
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
        with jax.named_scope("norm"):
            h = h + self.post_mlp_layernorm(y)
        return h if cache is None else (h, new_cache, hit)


class WindowAttnMoeModel(nn.Layer):
    def __init__(self, config: WindowAttnMoeConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=paddle_tpu.nn.ParamAttr(initializer=init))
        self.layers = nn.LayerList(
            [WindowAttnMoeDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_index=None):
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
            if self.config.mup_enabled:
                h = h * math.sqrt(self.config.hidden_size)
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


class WindowAttnMoeForCausalLM(nn.Layer):
    def __init__(self, config: WindowAttnMoeConfig):
        super().__init__()
        self.config = config
        self.model = WindowAttnMoeModel(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_size, config)

    def _logits(self, h):
        with jax.named_scope("norm"):
            h = self.model.norm(h)
        with jax.named_scope("lm_head_loss"):
            return self.lm_head(h)

    # what the cached forward counts when asked to (`with_counters`); an
    # engine that finds this sums them over a tick's decode steps
    decode_counter_keys = ("moe_experts_hit", "moe_layer_steps",
                           "moe_pairs_held", "moe_pairs_routed")

    def forward(self, input_ids, labels=None, position_ids=None,
                attn_mask=None, caches=None, cache_index=None,
                with_counters=False):
        """-> logits; with `caches` (logits, new caches), and with
        `with_counters` as a third what this call's expert layers counted,
        summed over them: the distinct experts hit among those held, the
        layers, the (row, expert) pairs that fell on held experts and all
        the pairs routed."""
        if labels is not None or attn_mask is not None:
            raise NotImplementedError(
                "forward only: no loss (the routing bias is set by a rule "
                "that is no part of a config) and no padding mask (the "
                "engine's rows carry their lengths)")
        if caches is None:
            return self._logits(self.model(input_ids,
                                           position_ids=position_ids))
        h, caches, hits = self.model(input_ids, position_ids=position_ids,
                                     caches=caches, cache_index=cache_index)
        rows = h.shape[0] * h.shape[1]
        if h.shape[1] > 1:
            # a prefill needs each row's LAST valid token's logits alone
            hv = _val(h)
            last = jnp.clip(_val(cache_index.n_valid) - 1, 0, hv.shape[1] - 1)
            h = Tensor(jnp.take_along_axis(hv, last[:, None, None], axis=1))
        if not with_counters:
            return self._logits(h), caches
        # a layer that holds every expert counts a scalar, a share a pair
        counted = [jnp.atleast_1d(_val(n)) for n in hits]
        routed = rows * self.config.num_experts_per_tok
        return self._logits(h), caches, {
            "moe_experts_hit": sum(n[0] for n in counted),
            "moe_layer_steps": len(counted),
            "moe_pairs_held": sum(n[-1] if n.shape[0] > 1 else routed
                                  for n in counted),
            "moe_pairs_routed": routed * len(counted)}
