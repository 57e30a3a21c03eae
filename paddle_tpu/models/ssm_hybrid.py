"""Decoder-hybrid-decoder with Mamba layers, differential attention inside a
window and over everything, and a cross-decoder that reads one layer's
K and V through gated memory units — forward only, for serving.

The shape of Phi-4-mini-flash-reasoning (`model_type` phi4flash, the
SambaY architecture of arXiv:2507.06607). No layer encodes a position.
`h` is the residual stream; every layer is pre-norm with a residual,
h = h + Mixer(LN1(h)), then h = h + W_2(SiLU(x W_g) * x W_u) with x =
LN2(h) (LayerNorm with weight and bias); a final LN and the head tied to
the embedding close the model. With L layers and `mb_per_layer` m, layer i
is (`layer_types`):

- i < L/2: a Mamba layer where i % m == 0, else a window layer; i = L/2: a
  Mamba layer whose mixer output before W_out is also kept as the MEMORY;
  i = L/2 + 1: the one full-attention layer, whose K and V are the model's
  only cache over the whole context; after that a gated memory unit where
  i % m == 0, else a cross layer;
- Mamba-1 (x the normed input): [u | z] = x W_in (E = `mamba_expand` d
  channels each); u = SiLU(causal depthwise conv of width K over u, + b);
  [delta | B | C] = u W_x (R + N + N); Delta = softplus(delta W_dt + b_dt);
  A = -exp(A_log) (E x N); s_t = exp(Delta_t A) s_{t-1} + (Delta_t u_t)
  (x) B_t, y_t = s_t . C_t + D u_t; m_t = y_t * SiLU(z_t); out = m W_out;
- differential attention (Ye et al. 2024) in window, full and cross layers:
  q = x W_q (H heads of hd), k, v = x W_k, x W_v (Hk heads) in window and
  full layers; adjacent heads pair: query pair p is (q^1, q^2), kv pair g
  is (k^1, k^2) and its value is the two heads side by side (2 hd wide);
  pair p reads kv pair floor(p / (H / Hk)); a^j = softmax(q^j . k^j /
  sqrt(hd)) v over the keys the layer sees; lambda = exp(l_q1 . l_k1) -
  exp(l_q2 . l_k2) + lambda_init, lambda_init = 0.8 - 0.6 exp(-0.3 i); o_p
  = (1 - lambda_init) RMSNorm_{2hd}(a^1 - lambda a^2); out = [o_p] W_o. A
  window layer sees the keys t - `sliding_window` < s <= t, the full layer
  every s <= t; a cross layer has W_q and W_o alone and reads the full
  layer's K and V;
- a gated memory unit: out = (m * SiLU(x W_1)) W_2, m the memory at the
  same position.

Served through `PagedKVEngine`, which learns the kinds from `layer_types`:
the window layers' K and V live in rings under a second page table, the
full layer's under the block table, the Mamba layers' conv window and SSM
state per slot in the engine's state cache (`paged.slot_state_read`), and
cross layers and memory units hold nothing. A pair of kv heads is stored
as one head of 2 hd (`cache_kv_heads`, `cache_head_dim`): its bytes are
the pair's, and the query rows [q^1 | 0] and [0 | q^2] score it as q^1 .
k^1 and q^2 . k^2, so the paged kernels attend it unchanged. Nothing after
the full layer feeds a cache, so a prefill runs the cross-decoder at each
row's last prompt token alone (YOCO): exact, and the cross-decoder's
share of a prompt is skipped. No training path (the scan has no
backward).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.llama import LlamaConfig, LlamaMLP
from paddle_tpu.nn.layer.norm import LayerNorm, RMSNorm

__all__ = ["SsmHybridConfig", "tiny_ssm_hybrid_config", "MambaMixer",
           "DiffAttention", "GatedMemoryUnit", "SsmHybridDecoderLayer",
           "SsmHybridModel", "SsmHybridForCausalLM", "MAMBA", "WINDOW",
           "FULL", "CROSS", "GMU"]

MAMBA, WINDOW, FULL = "mamba", "sliding_attention", "full_attention"
CROSS, GMU = "cross_attention", "gmu"


@dataclass
class SsmHybridConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    mb_per_layer: int = 2
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    # the config gives no mamba_* keys: the family's defaults (module doc)
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int | None = None        # ceil(hidden / 16)
    initializer_range: float = 0.02
    # sequence length used by helpers that need one
    seq_length: int = 4096

    def __post_init__(self):
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = -(-self.hidden_size // 16)
        L, m = self.num_hidden_layers, self.mb_per_layer
        refused = [why for on, why in (
            (self.hidden_act != "silu", f"hidden_act={self.hidden_act!r} "
             "(a SwiGLU MLP is built)"),
            (self.mlp_bias or self.lm_head_bias, "mlp_bias / lm_head_bias "
             "(no bias is built)"),
            (not self.tie_word_embeddings, "tie_word_embeddings=False (the "
             "head is the embedding)"),
            (m < 2 or L % 2 or (L // 2) % m or (L // 2 + 1) % m == 0,
             f"mb_per_layer={m} with num_hidden_layers={L} (built: L even, "
             "layer L/2 a Mamba layer and L/2 + 1 an attention layer)"),
            (self.num_attention_heads % 2 or self.num_key_value_heads % 2
             or self.num_attention_heads % self.num_key_value_heads,
             f"{self.num_attention_heads} query and "
             f"{self.num_key_value_heads} kv heads (differential attention "
             "pairs adjacent heads)")) if on]
        if refused:
            raise NotImplementedError(
                "the decoder-hybrid-decoder is built for the config of "
                "Phi-4-mini-flash-reasoning; not carried: "
                + "; ".join(refused))

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self):
        return self.mamba_expand * self.hidden_size

    @property
    def layer_types(self):
        """Each layer's kind, from `mb_per_layer` and the depth (module
        doc)."""
        half, m = self.num_hidden_layers // 2, self.mb_per_layer
        out = []
        for i in range(self.num_hidden_layers):
            if i < half:
                out.append(MAMBA if i % m == 0 else WINDOW)
            elif i == half:
                out.append(MAMBA)
            elif i == half + 1:
                out.append(FULL)
            else:
                out.append(GMU if i % m == 0 else CROSS)
        return out

    @property
    def memory_layer(self):
        return self.num_hidden_layers // 2

    # what a cache holds a token: a pair of kv heads as one head of twice
    # the width (module doc), read by PagedKVEngine
    @property
    def cache_kv_heads(self):
        return self.num_key_value_heads // 2

    @property
    def cache_head_dim(self):
        return 2 * self.head_dim


def tiny_ssm_hybrid_config(**overrides) -> SsmHybridConfig:
    """Toy config for tests / CPU dryruns: eight layers, two Mamba and two
    window layers before the memory's Mamba layer, the full layer, a
    memory unit and a cross layer, with a window that short prompts
    cross."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=8, num_attention_heads=4,
                num_key_value_heads=2, sliding_window=8,
                max_position_embeddings=512, mamba_d_state=8,
                seq_length=32)
    base.update(overrides)
    return SsmHybridConfig(**base)


def _val(x):
    return x._value if isinstance(x, Tensor) else jnp.asarray(x)


def _linear(d_in, d_out, config, bias=False):
    init = nn.initializer.Normal(0.0, config.initializer_range)
    return nn.Linear(d_in, d_out, bias_attr=bias,
                     weight_attr=paddle_tpu.nn.ParamAttr(initializer=init))


class _MambaALog(nn.initializer.Initializer):
    """Mamba's A_log: A = -exp(A_log) = -(1 .. N) for every channel."""

    def __call__(self, shape, dtype):
        return jnp.log(jnp.broadcast_to(
            jnp.arange(1, shape[-1] + 1, dtype=jnp.float32), shape)).astype(
                dtype)


class _MambaDtBias(nn.initializer.Initializer):
    """Mamba's draw of b_dt: softplus(b_dt) = Delta, log-uniform in [1e-3,
    1e-1], so that a step keeps exp(-Delta k) of the state, k = 1 .. N."""

    def __call__(self, shape, dtype):
        dt = jnp.exp(nn.initializer.Uniform(math.log(1e-3), math.log(1e-1))(
            shape, jnp.float32))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _at(x, last):
    """Row i's position last[i]: (b, s, ...) -> (b, 1, ...)."""
    idx = last.reshape((-1, 1) + (1,) * (x.ndim - 2))
    return jnp.take_along_axis(x, idx, axis=1)


class MambaMixer(nn.Layer):
    """A Mamba-1 mixer (module doc). Cached, its conv window (the last K -
    1 rows of u before the conv) and its state s (N x E, float32, stored
    with the channels last) are the slot's, read and written through
    `paged.slot_state_read` / `slot_state_write`."""

    def __init__(self, config: SsmHybridConfig):
        super().__init__()
        self.config = config
        d, e = config.hidden_size, config.mamba_d_inner
        n, r, k = (config.mamba_d_state, config.mamba_dt_rank,
                   config.mamba_d_conv)
        self.in_proj = _linear(d, 2 * e, config)
        # a depthwise conv's own draw, uniform within 1 / sqrt(K)
        conv = paddle_tpu.nn.ParamAttr(initializer=nn.initializer.Uniform(
            -k ** -0.5, k ** -0.5))
        self.conv1d_weight = self.create_parameter(shape=[e, k], attr=conv)
        self.conv1d_bias = self.create_parameter(shape=[e], attr=conv)
        self.x_proj = _linear(e, r + 2 * n, config)
        self.dt_proj = _linear(r, e, config, bias=paddle_tpu.nn.ParamAttr(
            initializer=_MambaDtBias()))
        # Mamba's own draw: A = -(1 .. N) for every channel, D = 1
        self.A_log = self.create_parameter(
            shape=[e, n], default_initializer=_MambaALog())
        self.D = self.create_parameter(
            shape=[e], default_initializer=nn.initializer.Constant(1.0))
        self.out_proj = _linear(e, d, config)

    def forward(self, x, cache=None, cache_index=None):
        """-> (out, memory m (b, s, E)), with a cache also the new one."""
        cfg = self.config
        e, n, r = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
        k = cfg.mamba_d_conv
        with jax.named_scope("in_proj"):
            xz = _val(self.in_proj(x))
        u_raw, z = xz[..., :e], xz[..., e:]
        b, s = u_raw.shape[:2]
        from paddle_tpu.inference import paged
        with jax.named_scope("ssm"):
            if cache is None:
                conv0 = jnp.zeros((b, k - 1, e), u_raw.dtype)
                state0 = jnp.zeros((b, n, e), jnp.float32)
                n_valid = jnp.full((b,), s, jnp.int32)
            else:
                conv0, state0 = paged.slot_state_read(cache, cache_index)
                n_valid = _val(cache_index.n_valid)
            full = jnp.concatenate([conv0.astype(u_raw.dtype), u_raw], 1)
            w = _val(self.conv1d_weight).astype(jnp.float32)
            conv = sum(full[:, j:j + s].astype(jnp.float32) * w[:, j]
                       for j in range(k))
            u = jax.nn.silu(conv + _val(self.conv1d_bias).astype(
                jnp.float32))
            dbc = jnp.dot(u.astype(xz.dtype), _val(self.x_proj.weight),
                          preferred_element_type=jnp.float32)
            dt = jnp.dot(dbc[..., :r].astype(xz.dtype),
                         _val(self.dt_proj.weight),
                         preferred_element_type=jnp.float32)
            dt = jax.nn.softplus(dt + _val(self.dt_proj.bias).astype(
                jnp.float32))
            # a token past the row's valid ones leaves the state as it was
            valid = jnp.arange(s, dtype=jnp.int32)[None] < n_valid[:, None]
            dt = jnp.where(valid[..., None], dt, 0.0)
            bt = jnp.swapaxes(dbc[..., r:r + n], 1, 2)        # (b, N, s)
            ct = jnp.swapaxes(dbc[..., r + n:], 1, 2)
            at = -jnp.exp(_val(self.A_log).astype(jnp.float32)).T  # (N, E)
            y, state = paged.selective_scan(
                u, dt, bt, ct, at, _val(self.D), state0)
            m = (y * jax.nn.silu(z.astype(jnp.float32))).astype(xz.dtype)
            if cache is not None:
                # the K - 1 pre-conv rows that end at the last valid token
                rows = n_valid[:, None] + jnp.arange(k - 1)[None]
                conv1 = jnp.take_along_axis(full, rows[..., None], axis=1)
                new_cache = paged.slot_state_write(
                    cache, (conv1, state), cache_index)
        with jax.named_scope("out_proj"):
            out = self.out_proj(Tensor(m))
        return (out, m) if cache is None else (out, m, new_cache)


class DiffAttention(nn.Layer):
    """Differential attention (module doc) of a window (`window` > 0), full
    or cross layer."""

    def __init__(self, config: SsmHybridConfig, index, kind):
        super().__init__()
        self.config = config
        self.kind = kind
        self.window = config.sliding_window if kind == WINDOW else 0
        self.lambda_init = 0.8 - 0.6 * math.exp(-0.3 * index)
        d, hd = config.hidden_size, config.head_dim
        self.q_proj = _linear(d, config.num_attention_heads * hd, config)
        if kind != CROSS:
            self.k_proj = _linear(d, config.num_key_value_heads * hd, config)
            self.v_proj = _linear(d, config.num_key_value_heads * hd, config)
        self.o_proj = _linear(config.num_attention_heads * hd, d, config)
        init = nn.initializer.Normal(0.0, 0.1)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, self.create_parameter(
                shape=[hd], attr=paddle_tpu.nn.ParamAttr(initializer=init)))
        self.subln = RMSNorm(2 * hd, epsilon=config.layer_norm_eps)

    def _rows(self, x):
        """The query as rows of the paired cache's heads: (b, s, H, 2 hd),
        row 2p = [q^1_p | 0], row 2p + 1 = [0 | q^2_p]."""
        q = _val(self.q_proj(x))
        b, s = q.shape[:2]
        hd = self.config.head_dim
        q = q.reshape(b, s, -1, 2, 1, hd)
        zero = jnp.zeros_like(q)
        return jnp.concatenate(
            [jnp.concatenate([q[:, :, :, :1], zero[:, :, :, :1]], 4),
             jnp.concatenate([zero[:, :, :, 1:], q[:, :, :, 1:]], 4)],
            3).reshape(b, s, -1, 2 * hd)

    def kv(self, x):
        """A kv pair as one head of 2 hd: (b, s, Hk / 2, 2 hd) each."""
        k, v = _val(self.k_proj(x)), _val(self.v_proj(x))
        b, s = k.shape[:2]
        return (k.reshape(b, s, -1, 2 * self.config.head_dim),
                v.reshape(b, s, -1, 2 * self.config.head_dim))

    def _combine(self, a):
        """(b, s, H * 2 hd) from the rows -> the layer's output."""
        b, s = a.shape[:2]
        hd = self.config.head_dim
        a = a.reshape(b, s, -1, 2, 2 * hd).astype(jnp.float32)
        lam = (jnp.exp(jnp.dot(_val(self.lambda_q1).astype(jnp.float32),
                               _val(self.lambda_k1).astype(jnp.float32)))
               - jnp.exp(jnp.dot(_val(self.lambda_q2).astype(jnp.float32),
                                 _val(self.lambda_k2).astype(jnp.float32)))
               + self.lambda_init)
        o = self.subln(Tensor(a[:, :, :, 0] - lam * a[:, :, :, 1]))
        o = (1.0 - self.lambda_init) * _val(o)
        dtype = _val(self.o_proj.weight).dtype
        with jax.named_scope("out_proj"):
            return self.o_proj(Tensor(o.reshape(b, s, -1).astype(dtype)))

    @property
    def scale(self):
        return 1.0 / math.sqrt(self.config.head_dim)

    def whole(self, x, kv=None):
        """A whole sequence without a cache -> (out, (k, v)); a cross
        layer attends over `kv`, the full layer's."""
        q = self._rows(x)
        k, v = self.kv(x) if kv is None else kv
        b, s, hq, w = q.shape
        hk = k.shape[2]
        t = jnp.arange(s)
        seen = t[None, :] <= t[:, None]
        if self.window:
            seen = seen & (t[None, :] > t[:, None] - self.window)
        qg = q.reshape(b, s, hk, hq // hk, w)
        att = jnp.einsum("bshgd,blhd->bhgsl", qg, k,
                         preferred_element_type=jnp.float32) * self.scale
        att = jnp.where(seen[None, None, None], att, -1e30)
        p = jax.nn.softmax(att, axis=-1).astype(v.dtype)
        a = jnp.einsum("bhgsl,blhd->bshgd", p, v)
        return self._combine(a.reshape(b, s, hq * w)), (k, v)

    def cached(self, x, cache, cache_index, last=None):
        """Through the engine's pages -> (out, the new cache or None).

        A window layer: this call's K and V into its ring, each token over
        the window (`paged_attention_update(window=)`). The full layer: K
        and V of every token written, the query of each row's position
        `last` alone attends (nothing after it needs another). A cross
        layer: its query at `last` over the full layer's pages, `cache`,
        which it does not write."""
        from paddle_tpu.inference import paged
        if self.kind == WINDOW:
            k, v = self.kv(x)
            with jax.named_scope("window"):
                a, new = paged.paged_attention_update(
                    self._rows(x), k, v, cache, cache_index,
                    window=self.window, scale=self.scale)
            return self._combine(_val(a)), new
        new = None
        if self.kind == FULL:
            k, v = self.kv(x)
            new = paged.paged_kv_store(k, v, cache, cache_index)
            cache = new
            x = Tensor(_at(_val(x), last))
        pos = _val(cache_index.lens) + last
        with jax.named_scope("cross_attn" if self.kind == CROSS
                             else "full"):
            a = paged.paged_attention_read(
                self._rows(x)[:, 0], cache, cache_index, pos,
                self.config.cache_kv_heads, scale=self.scale)
        return self._combine(a[:, None]), new


class GatedMemoryUnit(nn.Layer):
    """out = (m * SiLU(x W_1)) W_2, m the memory at the same position."""

    def __init__(self, config: SsmHybridConfig):
        super().__init__()
        d, e = config.hidden_size, config.mamba_d_inner
        self.in_proj = _linear(d, e, config)
        self.out_proj = _linear(e, d, config)

    def forward(self, x, memory):
        with jax.named_scope("gmu"):
            g = jax.nn.silu(_val(self.in_proj(x)).astype(jnp.float32))
            gated = (_val(memory).astype(jnp.float32) * g).astype(
                _val(self.out_proj.weight).dtype)
            return self.out_proj(Tensor(gated))


class SsmHybridDecoderLayer(nn.Layer):
    """Pre-norm block: the layer's mixer (its kind), then a SwiGLU MLP."""

    def __init__(self, config: SsmHybridConfig, index):
        super().__init__()
        self.kind = config.layer_types[index]
        d = config.hidden_size
        if self.kind == MAMBA:
            self.mixer = MambaMixer(config)
        elif self.kind == GMU:
            self.mixer = GatedMemoryUnit(config)
        else:
            self.mixer = DiffAttention(config, index, self.kind)
        self.input_layernorm = LayerNorm(d, epsilon=config.layer_norm_eps)
        self.post_attention_layernorm = LayerNorm(
            d, epsilon=config.layer_norm_eps)
        self.mlp = LlamaMLP(LlamaConfig(
            hidden_size=d, intermediate_size=config.intermediate_size,
            initializer_range=config.initializer_range))

    def finish(self, h, out):
        """h + the mixer's out, then the MLP half."""
        with jax.named_scope("norm"):
            h = h + out
            x = self.post_attention_layernorm(h)
        with jax.named_scope("mlp"):
            return h + self.mlp(x)


class SsmHybridModel(nn.Layer):
    def __init__(self, config: SsmHybridConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=paddle_tpu.nn.ParamAttr(initializer=init))
        self.layers = nn.LayerList(
            [SsmHybridDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.final_layernorm = LayerNorm(config.hidden_size,
                                         epsilon=config.layer_norm_eps)

    def forward(self, input_ids):
        """A whole sequence without a cache: every layer at every
        position."""
        cfg = self.config
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
        memory = kv = None
        for i, layer in enumerate(self.layers):
            with jax.named_scope("norm"):
                x = layer.input_layernorm(h)
            if layer.kind == MAMBA:
                out, m = layer.mixer(x)
                if i == cfg.memory_layer:
                    memory = m
            elif layer.kind == GMU:
                out = layer.mixer(x, memory)
            else:
                with jax.named_scope("attn"):
                    out, kv_i = layer.mixer.whole(
                        x, kv if layer.kind == CROSS else None)
                if layer.kind == FULL:
                    kv = kv_i
            h = layer.finish(h, out)
        return h

    def cached(self, input_ids, caches, cache_index):
        """Through the engine's caches (one a layer that owns one, in the
        layers' order: the Mamba layers' states, the window layers' rings,
        the full layer's pages) -> (h at each row's last valid token (b, 1,
        d), the new caches). The layers up to the full one run at every
        token; the full layer writes every token's K and V and attends at
        the last valid one, and the cross-decoder runs there alone."""
        cfg = self.config
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
        s = h.shape[1]
        last = jnp.clip(_val(cache_index.n_valid) - 1, 0, s - 1)
        caches = list(caches)
        new_caches = []
        memory = pool = None
        for i, layer in enumerate(self.layers):
            with jax.named_scope("norm"):
                x = layer.input_layernorm(h)
            if layer.kind == MAMBA:
                out, m, c = layer.mixer(x, caches.pop(0), cache_index)
                new_caches.append(c)
                if i == cfg.memory_layer:
                    memory = Tensor(_at(m, last))
            elif layer.kind == GMU:
                out = layer.mixer(x, memory)
            elif layer.kind == CROSS:
                with jax.named_scope("attn"):
                    out, _ = layer.mixer.cached(x, pool, cache_index, last)
            else:
                with jax.named_scope("attn"):
                    out, pool = layer.mixer.cached(
                        x, caches.pop(0), cache_index, last)
                new_caches.append(pool)
                if layer.kind == FULL:
                    # from here on every layer runs at the last token alone
                    h = Tensor(_at(_val(h), last))
            h = layer.finish(h, out)
        return h, new_caches


class SsmHybridForCausalLM(nn.Layer):
    def __init__(self, config: SsmHybridConfig):
        super().__init__()
        self.config = config
        self.model = SsmHybridModel(config)

    def _logits(self, h):
        with jax.named_scope("norm"):
            h = self.model.final_layernorm(h)
        with jax.named_scope("lm_head_loss"):
            w = _val(self.model.embed_tokens.weight)
            return Tensor(jnp.einsum("bsd,vd->bsv", _val(h), w))

    def forward(self, input_ids, labels=None, position_ids=None,
                attn_mask=None, caches=None, cache_index=None):
        """-> logits (b, s, V); with `caches` (the logits at each row's last
        valid token (b, 1, V), the new caches). No position encoding:
        `position_ids` is taken and not read."""
        if labels is not None or attn_mask is not None:
            raise NotImplementedError(
                "forward only: no loss (the selective scan has no backward "
                "here) and no padding mask (the engine's rows carry their "
                "lengths)")
        if caches is None:
            return self._logits(self.model(input_ids))
        from paddle_tpu.inference.paged import PagedState
        if not isinstance(cache_index, PagedState):
            raise NotImplementedError(
                "the Mamba layers' state and the rings are the engine's: "
                "serve through PagedKVEngine")
        h, caches = self.model.cached(input_ids, caches, cache_index)
        return self._logits(h), caches
