"""Llama-3 model family, TPU-native.

The reference trains Llama via PaddleNLP's llm/ recipes on top of
paddle.nn + incubate fused ops (fused_rms_norm, fused_rotary_position_
embedding, swiglu, fused attention) and fleet hybrid parallel; this module
is the in-tree equivalent the BASELINE.json north-star config
("Llama-3-8B pretrain, DP+TP, >=40% MFU on v5p") trains.

Design notes (TPU-first):
- All matmuls are (B*S, D) x (D, F) shaped — large, static, bf16-friendly —
  so XLA tiles them onto the MXU.
- Attention goes through nn.functional.scaled_dot_product_attention, which
  routes to the Pallas flash kernel for long sequences.
- The decoder stack iterates Python-side (unrolled under jit). The parallel
  trainer (paddle_tpu.parallel) optionally rewrites the stack into a
  lax.scan over stacked layer params for fast compiles + pipeline parallel.
- Sharding is NOT baked into the model: paddle_tpu.parallel.plan attaches a
  GSPMD sharding plan (param-name -> PartitionSpec) for dp/fsdp/mp/sp axes,
  replacing the reference's ColumnParallelLinear/RowParallelLinear split
  classes (fleet/layers/mpu/mp_layers.py:335,542) with plain Linears +
  shardings.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import nn
from paddle_tpu import tensor as T
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.layer.norm import RMSNorm
from paddle_tpu.incubate.nn.functional import (
    fused_rotary_position_embedding, swiglu,
)


@dataclass
class LlamaConfig:
    """Mirror of PaddleNLP's LlamaConfig fields that matter for pretrain."""
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 8192
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    use_flash_attention: bool = False
    # single (d, d + 2*kv) qkv matmul / single (d, 2*f) gate-up matmul
    # (PaddleNLP LlamaConfig.fuse_attention_qkv / fuse_attention_ffn):
    # fewer, larger MXU matmuls and one fused dW in the backward.
    # CAVEAT under tensor parallel: the fused output dim is sharded
    # contiguously over 'mp', so the q/k/v (or gate/up) split boundaries
    # cut mid-shard and GSPMD inserts a reshard per layer — prefer the
    # unfused projections on mp>1 meshes until a per-rank-interleaved
    # fused layout exists (PaddleNLP interleaves the fused weight).
    fuse_attention_qkv: bool = False
    fuse_attention_ffn: bool = False
    # rerun each decoder layer's forward during backward instead of saving
    # activations (fleet.utils.recompute equivalent -> jax.checkpoint)
    recompute: bool = False
    # sequence length used by helpers that need one (bench, example inputs)
    seq_length: int = 4096
    # -- fused train-path kernels (ISSUE 14; kernels/blockwise_ce.py +
    # kernels/fused_norm.py) ------------------------------------------
    # loss_chunk > 0: next_token_loss streams the hidden->vocab
    # projection + softmax-CE in `loss_chunk`-row blocks so the
    # [B*S, vocab] logits tensor NEVER materializes (fwd or bwd) — at
    # Llama-3 vocab that tensor dwarfs every activation and caps batch
    # size. 0 = the old dense path (logits returned as before; the
    # blockwise path returns (loss, None)).
    loss_chunk: int = 0
    # optional vocab streaming inside each row block (0 = whole vocab
    # per chunk): peak logits-shaped intermediate is
    # (loss_chunk, loss_vocab_block or vocab)
    loss_vocab_block: int = 0
    # route the decoder's RMSNorms through the fused norm(+residual)
    # custom_vjp op (one read of x, residual written in the same pass,
    # closed-form backward); numerics identical to rms_norm_ref
    fused_norm: bool = False
    # route RoPE through the fused apply (mul/lane-roll/mul/add, no
    # slice/concat transpose chain; inverse-rotation backward)
    fused_rope: bool = False
    # -- decomposed FSDP collectives (ISSUE 19; parallel/overlap.py) --
    # overlap_fsdp: route the FSDP-critical projections (q/k/v/o,
    # gate/up/down and their fused variants) through the chunked
    # ppermute rings so the weight all-gather streams under the matmul
    # instead of ahead of it. overlap_chunks: sub-chunks per resident
    # shard (finer pipelining); 0 disables the rewrite even when
    # overlap_fsdp is set — both knobs off = byte-identical jaxpr to
    # the propagated path. The trainer's overlap_fsdp_guard activates
    # the same rewrite without touching the model config.
    overlap_fsdp: bool = False
    overlap_chunks: int = 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def next_token_loss(logits, labels, vocab_size):
    """Shifted next-token cross entropy: position t scores labels[t+1].
    Shifts the LABELS (tiny) and marks the final position ignore_index
    instead of slicing logits[:, :-1] — at (B*S, vocab) that slice is a
    multi-hundred-MB copy XLA materializes before the loss.
    cross_entropy's mean already excludes ignored positions (and any
    user-supplied -100 padding)."""
    b = labels.shape[0]
    shifted = T.concat(
        [labels[:, 1:], T.full([b, 1], -100, labels.dtype)], axis=1)
    return F.cross_entropy(
        T.reshape(logits, [-1, vocab_size]),
        T.reshape(shifted, [-1]),
        ignore_index=-100, reduction="mean")


def next_token_loss_blockwise(hidden, weight, labels, config,
                              transpose_w=False):
    """Shifted next-token CE straight from the FINAL HIDDEN states —
    the lm_head projection is fused into the blockwise loss
    (kernels/blockwise_ce.py), so the [B*S, vocab] tensor never
    exists. `weight` is the lm_head weight (D, V); pass
    transpose_w=True for the tied-embedding (V, D) layout — the CALLER
    states the layout explicitly (shape-sniffing it would silently
    skip the transpose when vocab == hidden). Same label shift +
    ignore_index semantics as `next_token_loss`."""
    b = labels.shape[0]
    d = hidden.shape[-1]
    shifted = T.concat(
        [labels[:, 1:], T.full([b, 1], -100, labels.dtype)], axis=1)
    return F.blockwise_cross_entropy(
        T.reshape(hidden, [-1, d]), weight, T.reshape(shifted, [-1]),
        chunk=config.loss_chunk, vocab_block=config.loss_vocab_block,
        ignore_index=-100, transpose_w=transpose_w)


def llama3_8b_config(**overrides) -> LlamaConfig:
    return LlamaConfig(**overrides)


def tiny_llama_config(**overrides) -> LlamaConfig:
    """4-layer toy config for tests / CPU dryruns."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=4, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=256,
                rope_theta=10000.0, seq_length=32)
    base.update(overrides)
    return LlamaConfig(**base)


def _maybe_overlap_linear(layer, x, name, cfg):
    """Route one FSDP-critical projection through the decomposed
    ppermute ring (parallel/overlap.py) when the model config or the
    trainer's overlap_fsdp_guard asks for it. Every other case (guard
    off + knobs off, chunks < 1, no mesh, mesh without the axis, plan
    leaves the param off 'fsdp') falls back to the plain Linear call —
    the disabled path traces a byte-identical jaxpr."""
    from paddle_tpu.parallel import overlap as _ov
    ov = _ov.current_overlap()
    if ov is None and not (cfg.overlap_fsdp and cfg.overlap_chunks > 0):
        return layer(x)
    axis = ov["axis"] if ov else "fsdp"
    chunks = ov["chunks"] if ov else cfg.overlap_chunks
    if chunks < 1:
        return layer(x)
    mesh = _ov.resolve_overlap_mesh(ov["mesh"] if ov else None)
    if mesh is None or axis not in mesh.axis_names:
        return layer(x)
    from paddle_tpu.parallel.plan import fsdp_partition, llama_sharding_plan
    sd = fsdp_partition(llama_sharding_plan(mesh.axis_names),
                        name + ".weight", axis)
    if sd is None:
        return layer(x)
    return _ov.overlap_linear(x, layer.weight, axis=axis, chunks=chunks,
                              shard_dim=sd)


class LlamaAttention(nn.Layer):
    """GQA attention with RoPE (PaddleNLP LlamaAttention equivalent;
    reference fused path: incubate fused_rope + flash_attention kernels
    phi/kernels/gpu/flash_attn_kernel.cu)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        d, hd = config.hidden_size, config.head_dim
        kv_out = config.num_key_value_heads * hd
        init = nn.initializer.Normal(0.0, config.initializer_range)
        attr = paddle_tpu.nn.ParamAttr(initializer=init)
        if config.fuse_attention_qkv:
            self.qkv_proj = nn.Linear(d, d + 2 * kv_out, weight_attr=attr,
                                      bias_attr=False)
        else:
            self.q_proj = nn.Linear(d, d, weight_attr=attr, bias_attr=False)
            self.k_proj = nn.Linear(d, kv_out, weight_attr=attr,
                                    bias_attr=False)
            self.v_proj = nn.Linear(d, kv_out, weight_attr=attr,
                                    bias_attr=False)
        self.o_proj = nn.Linear(d, d, weight_attr=attr, bias_attr=False)

    def forward(self, hidden_states, position_ids=None, attn_mask=None,
                cache=None, cache_index=None):
        # named scopes (qkv, rope, core, out_proj under the layer's
        # attn) are metadata of the compiled ops: a device trace can
        # then say which block an op belongs to
        cfg = self.config
        b, s = hidden_states.shape[0], hidden_states.shape[1]
        with jax.named_scope("qkv"):
            if cfg.fuse_attention_qkv:
                kv_out = cfg.num_key_value_heads * cfg.head_dim
                qkv = _maybe_overlap_linear(self.qkv_proj, hidden_states,
                                            "qkv_proj", cfg)
                q, k, v = T.split(qkv, [cfg.hidden_size, kv_out, kv_out],
                                  axis=-1)
            else:
                q = _maybe_overlap_linear(self.q_proj, hidden_states,
                                          "q_proj", cfg)
                k = _maybe_overlap_linear(self.k_proj, hidden_states,
                                          "k_proj", cfg)
                v = _maybe_overlap_linear(self.v_proj, hidden_states,
                                          "v_proj", cfg)
            q = T.reshape(q, [b, s, cfg.num_attention_heads, cfg.head_dim])
            k = T.reshape(k, [b, s, cfg.num_key_value_heads, cfg.head_dim])
            v = T.reshape(v, [b, s, cfg.num_key_value_heads, cfg.head_dim])
        with jax.named_scope("rope"):
            if cfg.fused_rope:
                # fused train-path apply (kernels/fused_norm.py):
                # identical rotation, one pass, inverse-rotation backward
                from paddle_tpu.incubate.nn.functional import \
                    fused_rope_apply
                q, k = fused_rope_apply(q, k, position_ids=position_ids,
                                        rotary_emb_base=cfg.rope_theta)
            else:
                q, k, _ = fused_rotary_position_embedding(
                    q, k, None, position_ids=position_ids,
                    rotary_emb_base=cfg.rope_theta)
        with jax.named_scope("core"):
            out, new_cache = self._core(q, k, v, attn_mask, cache,
                                        cache_index)
        with jax.named_scope("out_proj"):
            if cache is not None:
                return self.o_proj(out), new_cache
            return _maybe_overlap_linear(self.o_proj, out, "o_proj", cfg)

    def _core(self, q, k, v, attn_mask, cache, cache_index):
        """Attention proper over position-encoded q, k, v: the paged or
        the incremental cache path, else flash / SDPA. Returns (out
        (b, s, hidden), new cache or None)."""
        cfg = self.config
        b, s = q.shape[0], q.shape[1]
        if cache is not None:
            from paddle_tpu.inference.paged import (PagedState,
                                                    paged_attention_update)
            if isinstance(cache_index, PagedState):
                # paged (block) KV serving: cache is a (k_pool, v_pool)
                # page-pool pair, cache_index carries the block tables +
                # per-slot lengths (inference/paged.py; reference serving
                # path: block_multi_head_attention_kernel.cu)
                return paged_attention_update(q, k, v, cache, cache_index)
            # incremental decode (models/generation.py): write this
            # step's k/v into the fixed-size buffer at cache_index,
            # then attend over the whole buffer under a position mask
            # (key j visible to query i iff j <= cache_index + i)
            from paddle_tpu.models.generation import kv_cache_update
            k_buf = kv_cache_update(cache[0], k, cache_index)
            v_buf = kv_cache_update(cache[1], v, cache_index)
            kl = k_buf.shape[1]
            k_pos = T.arange(0, kl, dtype="int32")
            q_pos = T.reshape(
                cache_index + T.arange(0, s, dtype="int32"), [s, 1])
            mask = T.unsqueeze(
                T.unsqueeze(T.unsqueeze(k_pos, 0) <= q_pos, 0), 0)
            if attn_mask is not None:
                # combine a user padding mask (bool keep-mask or
                # additive float, broadcastable over (b, h, s, kl))
                # with the position mask instead of dropping it
                if "bool" in str(attn_mask.dtype):
                    mask = T.logical_and(mask, attn_mask)
                else:
                    # -inf (not a large-negative) so SDPA's
                    # fully-masked-row guard (isneginf in _sdpa_ref)
                    # fires for rows a float mask hides entirely;
                    # no +inf exists here, so the sum never NaNs
                    fmask = T.cast(mask, "float32")
                    mask = T.where(
                        mask, T.zeros_like(fmask),
                        T.full_like(fmask, float("-inf"))) + attn_mask
            out = F.scaled_dot_product_attention(
                q, k_buf, v_buf, attn_mask=mask)
            return T.reshape(out, [b, s, cfg.hidden_size]), (k_buf, v_buf)
        if cfg.use_flash_attention and attn_mask is None:
            out, _ = F.flash_attention(q, k, v, causal=True)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None)
        return T.reshape(out, [b, s, cfg.hidden_size]), None


class LlamaMLP(nn.Layer):
    """SwiGLU MLP (PaddleNLP LlamaMLP; fused path incubate swiglu)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        d, f = config.hidden_size, config.intermediate_size
        init = nn.initializer.Normal(0.0, config.initializer_range)
        attr = paddle_tpu.nn.ParamAttr(initializer=init)
        self.fuse_ffn = config.fuse_attention_ffn
        if self.fuse_ffn:
            self.gate_up_fused_proj = nn.Linear(d, 2 * f, weight_attr=attr,
                                                bias_attr=False)
        else:
            self.gate_proj = nn.Linear(d, f, weight_attr=attr,
                                       bias_attr=False)
            self.up_proj = nn.Linear(d, f, weight_attr=attr,
                                     bias_attr=False)
        self.down_proj = nn.Linear(f, d, weight_attr=attr, bias_attr=False)

    def forward(self, x):
        cfg = self.config
        if self.fuse_ffn:
            # swiglu(x) splits the fused gate-up output in half (phi
            # SwiGLU kernel semantics)
            h = swiglu(_maybe_overlap_linear(
                self.gate_up_fused_proj, x, "gate_up_fused_proj", cfg))
        else:
            h = swiglu(
                _maybe_overlap_linear(self.gate_proj, x, "gate_proj", cfg),
                _maybe_overlap_linear(self.up_proj, x, "up_proj", cfg))
        return _maybe_overlap_linear(self.down_proj, h, "down_proj", cfg)


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)

    def forward(self, hidden_states, position_ids=None, attn_mask=None,
                cache=None, cache_index=None):
        fused = self.config.fused_norm
        eps = self.config.rms_norm_eps
        residual = hidden_states
        with jax.named_scope("norm"):
            if fused:
                # fused train-path norms (kernels/fused_norm.py): norm1
                # as one custom_vjp op; norm2 fuses the attention
                # residual add into the same pass (one read of
                # attn_out, h written once)
                h, _ = F.rms_norm_fused(hidden_states,
                                        self.input_layernorm.weight, eps)
            else:
                h = self.input_layernorm(hidden_states)
        new_cache = None
        with jax.named_scope("attn"):
            if cache is not None:
                h, new_cache = self.self_attn(
                    h, position_ids=position_ids, attn_mask=attn_mask,
                    cache=cache, cache_index=cache_index)
            else:
                h = self.self_attn(h, position_ids=position_ids,
                                   attn_mask=attn_mask)
        with jax.named_scope("norm"):
            if fused:
                h2, residual = F.rms_norm_fused(
                    h, self.post_attention_layernorm.weight, eps,
                    residual=residual)
            else:
                h = residual + h
                residual = h
                h2 = self.post_attention_layernorm(h)
        with jax.named_scope("mlp"):
            h2 = self.mlp(h2)
        out = residual + h2
        return out if cache is None else (out, new_cache)


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=paddle_tpu.nn.ParamAttr(initializer=init))
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def _final_norm(self, h):
        with jax.named_scope("norm"):
            if self.config.fused_norm:
                out, _ = F.rms_norm_fused(h, self.norm.weight,
                                          self.config.rms_norm_eps)
                return out
            return self.norm(h)

    def forward(self, input_ids, position_ids=None, attn_mask=None,
                caches=None, cache_index=None):
        from paddle_tpu.distributed.recompute import recompute
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
        if caches is not None:
            new_caches = []
            for layer, cache in zip(self.layers, caches):
                h, c = layer(h, position_ids=position_ids,
                             attn_mask=attn_mask, cache=cache,
                             cache_index=cache_index)
                new_caches.append(c)
            return self._final_norm(h), new_caches
        for layer in self.layers:
            if self.config.recompute and self.training:
                h = recompute(layer, h, position_ids=position_ids,
                              attn_mask=attn_mask)
            else:
                h = layer(h, position_ids=position_ids, attn_mask=attn_mask)
        return self._final_norm(h)


class LlamaForCausalLM(nn.Layer):
    """Causal LM head + shifted cross-entropy loss (PaddleNLP
    LlamaForCausalLM + LlamaPretrainingCriterion equivalent)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            init = nn.initializer.Normal(0.0, config.initializer_range)
            self.lm_head = nn.Linear(
                config.hidden_size, config.vocab_size,
                weight_attr=paddle_tpu.nn.ParamAttr(initializer=init),
                bias_attr=False)

    def logits(self, hidden):
        with jax.named_scope("lm_head_loss"):
            if self.lm_head is None:
                w = self.model.embed_tokens.weight
                return T.matmul(hidden, T.transpose(w, [1, 0]))
            return self.lm_head(hidden)

    def forward(self, input_ids, labels=None, position_ids=None,
                attn_mask=None, caches=None, cache_index=None):
        if caches is not None:
            if labels is not None:
                raise ValueError("KV-cache decode is inference-only; "
                                 "drop labels or caches")
            h, caches = self.model(input_ids, position_ids=position_ids,
                                   attn_mask=attn_mask, caches=caches,
                                   cache_index=cache_index)
            return self.logits(h), caches
        h = self.model(input_ids, position_ids=position_ids,
                       attn_mask=attn_mask)
        if labels is not None and self.config.loss_chunk:
            # blockwise fused loss: the lm_head matmul streams inside
            # the CE (kernels/blockwise_ce.py) — no [B*S, vocab] logits
            # exists to return, hence (loss, None)
            w = (self.model.embed_tokens.weight if self.lm_head is None
                 else self.lm_head.weight)
            with jax.named_scope("lm_head_loss"):
                loss = next_token_loss_blockwise(
                    h, w, labels, self.config,
                    transpose_w=self.lm_head is None)
            return loss, None
        logits = self.logits(h)
        if labels is None:
            return logits
        with jax.named_scope("lm_head_loss"):
            loss = next_token_loss(logits, labels, self.config.vocab_size)
        return loss, logits

    def generate(self, input_ids, max_new_tokens=32, **kwargs):
        """KV-cache autoregressive generation (PaddleNLP
        GenerationMixin.generate equivalent; see models/generation.py)."""
        from paddle_tpu.models.generation import generate
        return generate(self, input_ids, max_new_tokens, **kwargs)


def param_count(config: LlamaConfig) -> int:
    """Analytic parameter count."""
    d, f, v = config.hidden_size, config.intermediate_size, config.vocab_size
    hd = config.head_dim
    per_layer = (d * d + 2 * d * config.num_key_value_heads * hd + d * d
                 + 3 * d * f + 2 * d)
    head = 0 if config.tie_word_embeddings else d * v
    return v * d + config.num_hidden_layers * per_layer + d + head
