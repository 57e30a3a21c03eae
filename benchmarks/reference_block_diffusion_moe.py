"""The plain reference of a decoder that generates by diffusion over blocks,
with routed experts in every layer (SDAR-30B-A3B-Chat, `model_type`
`sdar_moe`, as its `config.json` shapes it; what the config leaves open is
listed under `assumed` in the configuration file).

Per layer, x the residual stream (rows are positions), eps `rms_norm_eps`:

- a = RMSNorm(x); q = RMSNorm_head(a W_q) in H x hd, k = RMSNorm_head(a W_k)
  in Hkv x hd, v = a W_v in Hkv x hd; rotate-half RoPE (theta `rope_theta`,
  the whole head width) on q and k at the row's absolute position; head h
  attends over the keys the mask gives its row: softmax(q_h . k_g(h) /
  sqrt(hd)) v_g(h); then W_o; residual;
- m = RMSNorm(x); p = softmax(m W_r) over E; the k largest (ties to the
  lower index), gates renormalised to sum 1; y = sum_e g_e W_d,e (silu(m
  W_g,e) * m W_u,e); residual;
- final RMSNorm, untied head.

THE MASK is an argument (`forward`). In the model it is causal by blocks:
with block length B a row at position p sees every key at a position below
(p // B + 1) * B (`block_causal`).

GENERATION (`generate`, greedy): the sequence is cut into blocks of B at
absolute positions. A block to generate holds `mask_token_id` at every
position not yet known (the prompt's last P mod B tokens are known from the
start). A step runs the whole sequence so far under the mask above, takes
at each masked position the best token of THAT position's logits and its
softmax probability as confidence, and unmasks n = B / T positions:
`low_confidence_static` the n most confident (ties to the lower position),
`low_confidence_dynamic` every position over `confidence_threshold` where
those are at least n, else the n most confident, `sequential` the n
leftmost. After T steps the block is final. Every forward here is over the
whole sequence with its final tokens so far, so nothing stands for a
program's "store forward": what a cache must hold of a settled block is
what this recomputes.

`logits(params, cfg, ids)` is what `benchmarks/serve.py` reads: row
P - 1 + i as the logits that chose the server's i-th token, `ids` being the
prompt and the server's tokens but the last. Here a token is chosen by ITS
position's logits in a state of its block that no clean forward over `ids`
reproduces, so this is a TEACHER-FORCED REPLAY (`replay`): one more position
(a mask id) is appended, and for step t = 1..T one forward runs over [ids ;
the blocks' rows in their state at step t], positions repeated, the clean
rows causal by blocks among themselves and a state row seeing the clean rows
of earlier blocks and the state rows of its own. The replay unmasks by its
own float32 confidences, keeps the row of each position at the step that
unmasks it, and reveals the SERVER's token there; that row is returned at
index position - 1. What it cannot know: the order in which the server
unmasked where bf16 orders two confidences of a step the other way than
float32 (the replay then reads a token's row in another state of the block
than the server chose it in; reading the order off the server's tokens
instead, smallest gap first, gave the same readings on the chip and is not
done; how often two confidences lie within 1 % and what that costs is in
the configuration file's `draw.why` and the traffic file's `check.why`);
the last token, which `ids` lacks (the replay reveals its own best there,
so the rows of that block's later steps may see another token than the
server's did); and where the prompt ends: every block is replayed from the
all-masked state unless `prompt_tokens` says which positions were known
(the check's prompt is a whole number of blocks). Rows of positions inside
the prompt mean nothing and are not read.

There is no `next_token_losses`: it is the train kind's, and a model served
by diffusion has no next-token loss.

Straightforward `jax.numpy` in float32 with `highest` matmul precision: no
kernel, no cache, no batching, no code of the program. Weights are read by
the program's parameter names, (in, out) for projections, and widened to
float32 block by block (a kv head's group of query heads, a block of
experts, a slice of the vocabulary), so that a 1,280-token check at the
published widths fits beside the served model. `store` (the identity) is
what every value a program would keep goes through;
`tools/prove_serve_check.py` passes a rounding to compute the reference in
the precision under the configuration's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 16       # experts widened to float32 at a time
VOCAB_BLOCKS = 8        # slices of the output projection
STRATEGIES = ("low_confidence_static", "low_confidence_dynamic",
              "sequential")


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x: (S, H, hd) at `positions` (S,). Rotate-half: the two halves of a
    head are the pairs."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _keep(x):
    return x


class _Params:
    """params[name] widened to float32 on call; `.raw` as stored."""

    def __init__(self, params):
        self.params = params

    def __call__(self, name):
        return self.params[name].astype(jnp.float32)

    def raw(self, name):
        return self.params[name]


def experts(y, p, f32, cfg, store=_keep):
    """y (S, d) -> sum over each row's top-k experts, gates renormalised;
    every expert is computed for every row, a block of experts at a time,
    and the gate of an expert a row did not choose is 0."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(y @ f32(p + "mlp.router_weight"), -1)
    top, idx = jax.lax.top_k(probs, k)
    if cfg.get("norm_topk_prob", True):
        top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(y.shape[0])[:, None], idx].set(top)          # (S, E)
    wg, wu, wd = (p + "mlp.experts_gate_weight", p + "mlp.experts_up_weight",
                  p + "mlp.experts_down_weight")
    nb = min(EXPERT_BLOCK, e)
    assert e % nb == 0

    def block(out, i):
        sl = lambda n: jax.lax.dynamic_slice_in_dim(          # noqa: E731
            f32.raw(n), i * nb, nb, 0).astype(jnp.float32)
        g = store(jnp.einsum("sd,edf->esf", y, sl(wg)))
        u = store(jnp.einsum("sd,edf->esf", y, sl(wu)))
        o = store(jnp.einsum("esf,efd->esd", store(jax.nn.silu(g) * u),
                             sl(wd)))
        gate = jax.lax.dynamic_slice_in_dim(gates, i * nb, nb, 1)   # (S, nb)
        return out + jnp.einsum("esd,se->sd", o, gate), None
    out, _ = jax.lax.scan(block, jnp.zeros_like(y), jnp.arange(e // nb))
    return out


def hidden(params, cfg, ids, positions, mask, store=_keep):
    """ids, positions (S,) of ONE sequence's rows, mask (S, S) bool (row q
    sees column k) -> the final hidden (S, d) before the last norm."""
    f32 = _Params(params)
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s, g = ids.shape[0], h // hkv
    x = params["model.embed_tokens.weight"][ids].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        y = store(_rms_norm(x, f32(p + "input_layernorm.weight"), eps))
        q = store(y @ f32(a + "q_proj.weight")).reshape(s, h, hd)
        k = store(y @ f32(a + "k_proj.weight")).reshape(s, hkv, hd)
        v = store(y @ f32(a + "v_proj.weight")).reshape(s, hkv, hd)
        q = store(_rope(_rms_norm(q, f32(a + "q_norm.weight"), eps),
                        positions, theta))
        k = store(_rope(_rms_norm(k, f32(a + "k_norm.weight"), eps),
                        positions, theta))

        def group(qkv):
            qg, kg, vg = qkv              # (g, S, hd), (S, hd), (S, hd)
            sc = jnp.einsum("gqd,kd->gqk", qg, kg) / jnp.sqrt(float(hd))
            sc = jnp.where(mask[None], sc, -jnp.inf)
            return store(jnp.einsum("gqk,kd->gqd",
                                    store(jax.nn.softmax(sc, -1)), vg))
        qg = jnp.swapaxes(q, 0, 1).reshape(hkv, g, s, hd)
        att = jax.lax.map(group, (qg, jnp.swapaxes(k, 0, 1),
                                  jnp.swapaxes(v, 0, 1)))   # (hkv, g, S, hd)
        att = jnp.moveaxis(att.reshape(h, s, hd), 0, 1).reshape(s, h * hd)
        x = store(x + store(att @ f32(a + "o_proj.weight")))
        y = store(_rms_norm(x, f32(p + "post_attention_layernorm.weight"),
                            eps))
        x = store(x + store(experts(y, p, f32, cfg, store)))
    return x


def head(params, cfg, x, store=_keep):
    """The final norm and the untied output projection: hidden rows (R, d)
    -> (R, vocab) float32 logits, a slice of the vocabulary at a time."""
    x = store(_rms_norm(x, params["model.norm.weight"].astype(jnp.float32),
                        cfg["rms_norm_eps"]))
    w = params["lm_head.weight"]                                 # (d, V)
    v = w.shape[1]
    nb = VOCAB_BLOCKS if v % VOCAB_BLOCKS == 0 else 1
    blocks = jnp.moveaxis(w.reshape(w.shape[0], nb, v // nb), 1, 0)
    out = jax.lax.map(lambda wb: store(x @ wb.astype(jnp.float32)), blocks)
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v)


def forward(params, cfg, ids, positions, mask, store=_keep, rows=None):
    """-> (S, vocab) float32 logits of ONE sequence's rows under `mask`, or
    of the rows `rows` indexes alone (the head is most of a long
    sequence's memory)."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, cfg, jnp.asarray(ids), jnp.asarray(positions),
                   jnp.asarray(mask), store)
        return head(params, cfg, x if rows is None else x[rows], store)


def block_causal(positions, block):
    """(S, S) bool: row q sees column k where k's position is below the
    end of q's block."""
    positions = jnp.asarray(positions)
    return positions[None, :] < ((positions // block + 1) * block)[:, None]


def generation(cfg):
    """(B, mask id, T, strategy, threshold) of a configuration: the block
    and the mask id are the model's (`assumed`), the rest the deployment's,
    which the builder notes under `generation` (defaults: a position a
    step, the most confident first)."""
    gen = cfg.get("generation") or {}
    b = int(cfg["block_length"])
    t = int(gen.get("denoising_steps", b))
    strategy = gen.get("remasking", STRATEGIES[0])
    assert b % t == 0 and strategy in STRATEGIES, (b, t, strategy)
    return (b, int(cfg["mask_token_id"]), t, strategy,
            float(gen.get("confidence_threshold", 0.9)))


def unmasked(conf, masked, n, strategy, threshold):
    """conf, masked (..., B) -> (..., B) bool: the masked positions a step
    unmasks, n of them (all that are masked, where fewer are)."""
    b = conf.shape[-1]
    if strategy == "sequential":
        conf = jnp.broadcast_to(-jnp.arange(b, dtype=jnp.float32),
                                conf.shape)
    conf = jnp.where(masked, conf, -jnp.inf)
    _top, idx = jax.lax.top_k(conf, n)          # ties to the lower position
    first = jnp.any(idx[..., None] == jnp.arange(b), -2) & masked
    if strategy != "low_confidence_dynamic":
        return first
    over = masked & (conf > threshold)
    return jnp.where(jnp.sum(over, -1, keepdims=True) >= n, over, first)


def _confidence(lg):
    """(best token, its softmax probability) of each row of logits."""
    return (jnp.argmax(lg, -1).astype(jnp.int32),
            jnp.max(jax.nn.softmax(lg, -1), -1))


def generate(params, cfg, prompt, new_tokens, settings=None, store=_keep,
             rows=None):
    """The published loop, free-running and greedy: `prompt` (P,) ids ->
    `new_tokens` ids. Every forward is over the whole sequence so far
    (here: over the final length, the rows not yet reached seeing only
    themselves and seen by none, so that one program serves every step).
    `rows`: a dict that is given, by position, the logits that chose each
    generated token."""
    cfg = dict(cfg, generation=settings) if settings is not None else cfg
    blen, mask_id, steps, strategy, threshold = generation(cfg)
    prompt = np.asarray(prompt, np.int64)
    total = -(-(len(prompt) + new_tokens) // blen) * blen
    ids = np.full(total, mask_id, np.int64)
    ids[:len(prompt)] = prompt
    known = np.arange(total) < len(prompt)
    positions = jnp.arange(total, dtype=jnp.int32)
    by_block = block_causal(positions, blen)

    @jax.jit
    def step(ids, reached, at):
        mask = (by_block & (positions[None] < reached)) \
            | jnp.eye(total, dtype=bool)
        lg = forward(params, cfg, ids, positions, mask, store,
                     rows=at + jnp.arange(blen))
        return (*_confidence(lg), lg)

    for at in range(len(prompt) // blen * blen, total, blen):
        for _ in range(steps):
            best, conf, lg = step(jnp.asarray(ids, jnp.int32), at + blen, at)
            masked = jnp.asarray(~known[at:at + blen])
            pick = np.asarray(unmasked(conf, masked, blen // steps,
                                       strategy, threshold))
            if rows is not None:
                rows.update({at + j: lg[j] for j in np.flatnonzero(pick)})
            ids[at:at + blen] = np.where(pick, np.asarray(best),
                                         ids[at:at + blen])
            known[at:at + blen] |= pick
        assert known[at:at + blen].all()
    return ids[len(prompt):len(prompt) + new_tokens].tolist()


ROW_BLOCKS = 64         # blocks whose logits the replay holds at a time


def replay(params, cfg, ids, store=_keep, prompt_tokens=None):
    """The teacher-forced replay (module doc) -> (rows (S, vocab) float32,
    row position - 1 being the logits that chose the token at that
    position; per step the (confidences, still masked before the step),
    each (blocks, B), of every block). The logits of a step are made
    `ROW_BLOCKS` blocks at a time and kept only where the step unmasks:
    beside the served model there is room for one (S, vocab) array, not
    for one a step."""
    blen, mask_id, steps, strategy, threshold = generation(cfg)
    ids = jnp.asarray(ids, jnp.int32)
    s = ids.shape[0]
    total = -(-(s + 1) // blen) * blen
    clean = jnp.concatenate(
        [ids, jnp.full((total - s,), mask_id, jnp.int32)])
    given = jnp.arange(total) < s           # the server's token is known
    positions = jnp.arange(total, dtype=jnp.int32)
    block_of = positions // blen
    both = jnp.concatenate([positions, positions])
    # rows: the clean sequence, then every block's state rows
    sees_clean = jnp.concatenate(
        [block_of[None, :] <= block_of[:, None],
         block_of[None, :] < block_of[:, None]])
    sees_state = jnp.concatenate(
        [jnp.zeros((total, total), bool),
         block_of[None, :] == block_of[:, None]])
    mask = jnp.concatenate([sees_clean, sees_state], axis=1)
    nblocks = total // blen
    group = max(n for n in range(1, min(ROW_BLOCKS, nblocks) + 1)
                if nblocks % n == 0) * blen         # rows a time

    known = positions < (prompt_tokens or 0)
    state = jnp.where(known, clean, mask_id)
    # row p - 1 holds position p's row; position 0's is never asked for
    out = jnp.zeros((s, params["lm_head.weight"].shape[1]), jnp.float32)
    seen = []
    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            # this step's weights are this step's own values: the compiler
            # must not keep one step's widened or transposed copies of
            # them alive for the next
            weights, state = jax.lax.optimization_barrier((params, state))
            x = hidden(weights, cfg, jnp.concatenate([clean, state]), both,
                       mask, store)[total:]
            masked = ~known

            def rows_at(i, carry, x=x, masked=masked, weights=weights):
                out, best, conf, pick = carry
                at = i * group
                cut = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                    a, at, group)
                lg = head(weights, cfg, cut(x), store)
                b, c = _confidence(lg)
                p = unmasked(c.reshape(-1, blen),
                             cut(masked).reshape(-1, blen), blen // steps,
                             strategy, threshold).reshape(-1)
                # position at + j goes to row at + j - 1: shifted by one,
                # the first of all dropped, rows past the last dropped
                to = at - 1 + jnp.arange(group)
                out = out.at[jnp.where(p & (to >= 0), to, s)].set(
                    lg, mode="drop")
                put = lambda a, v: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731,E501
                    a, v, at, 0)
                return out, put(best, b), put(conf, c), put(pick, p)

            out, best, conf, pick = jax.lax.fori_loop(
                0, total // group, rows_at,
                (out, jnp.zeros(total, jnp.int32),
                 jnp.zeros(total, jnp.float32), jnp.zeros(total, bool)))
            seen.append((conf.reshape(-1, blen), masked.reshape(-1, blen)))
            # teacher forcing: the server's token where `ids` has it
            state = jnp.where(pick, jnp.where(given, clean, best), state)
            known = known | pick
    return out, seen


def logits(params, cfg, ids, store=_keep, prompt_tokens=None):
    """What `benchmarks/serve.py` reads: the replay's rows. ids (S,)
    int32, the prompt and the server's tokens but the last -> (S, vocab)
    float32. `prompt_tokens`: the positions below it were known from the
    start; by default none inside a replayed block is (right wherever the
    prompt is a whole number of blocks: the rows of a block inside the
    prompt are not read)."""
    return replay(params, cfg, ids, store, prompt_tokens)[0]
