"""Cross-lower the hot path for the TPU platform, on the CPU box.

`jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))` runs the
Pallas -> Mosaic lowering (block-shape rules, layouts the lowering
checks, "Mosaic kernels cannot be automatically partitioned") without a
chip, with the one platform probe (`core/jax_compat.on_tpu`) forced
true so the auto-dispatch takes the branches it takes on the chip. It
found in minutes, and without chip budget, that blockwise CE and the
fused RMSNorm backward did not lower at all and that the mesh-partitioned
train step refused every Pallas call.

Where libtpu can DESCRIBE a v5e without one being attached
(`jax.experimental.topologies`), the kernel rows go one step further and
AOT-compile for it — the real Mosaic compiler, which is what refused
blockwise CE's dx kernel over the 16 MiB scoped-VMEM default. Where it
cannot, those rows cross-lower only.

None of this can replace the chip run: nothing executes, so wrong
numbers, runtime faults and whatever the attached chip's own compiler
pass differs in stay invisible. `python chip_smoke.py` on the chip is
the proof; this is the cheap guard in front of it. Shapes are
chip_smoke.py's: TinyLlama widths, depth cut to one layer for the
whole-step rows.
"""
import ast
import dataclasses
import glob
import sys
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import chip_smoke  # noqa: E402

from paddle_tpu.core import jax_compat  # noqa: E402

FULL = chip_smoke.FULL


@pytest.fixture
def as_tpu(monkeypatch):
    monkeypatch.setattr(jax_compat, "on_tpu", lambda: True)


def _lower_tpu(jitted, *args):
    return jitted.trace(*args).lower(lowering_platforms=("tpu",))


def _calls(lowered):
    return chip_smoke._custom_calls(lowered.as_text())


def _kernel_names(lowered):
    """The `name=` of every Pallas call in the lowered text: what a
    device trace shows as the custom call's instruction name."""
    return set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))


class _ShapeOnlyRng:
    """Stands in for numpy's Generator: the kernel cases are lowered
    from shapes alone, so every draw is zeros of the asked shape."""

    def standard_normal(self, size, dtype=np.float32):
        return np.zeros(size, dtype)

    def integers(self, lo, hi=None, size=None):
        return np.full(size, lo, np.int64)

    def uniform(self, lo, hi, size):
        return np.full(size, lo, np.float64)

    def random(self, size):
        return np.ones(size, np.float64)


_CASES = chip_smoke.kernel_cases(FULL)


@pytest.fixture(scope="module")
def described_v5e():
    """Sharding on a v5e chip that libtpu describes but nobody attached,
    or None where libtpu will not (then the kernel rows only lower)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        # libtpu asks the environment what machine it is on
        mp.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
        mp.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:1x1",
                chips_per_host_bounds=(1, 1, 1))
        except Exception as e:      # noqa: BLE001 — no libtpu, no row
            print(f"libtpu cannot describe a v5e here ({e}); "
                  f"kernel rows cross-lower only")
            yield None
            return
        yield SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name,build", _CASES, ids=[n for n, _ in _CASES])
def test_kernel_lowers_and_compiles_for_tpu(as_tpu, described_v5e, name,
                                            build):
    fn, args, _ = build(FULL, jnp.dtype("bfloat16"), False,
                        _ShapeOnlyRng())
    shapes = [None if a is None else jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=described_v5e) for a in args]
    if described_v5e is None:
        assert _calls(_lower_tpu(jax.jit(fn), *shapes)) >= 1
        return
    lowered = jax.jit(fn).lower(*shapes)
    assert _calls(lowered) >= 1
    lowered.compile()       # Mosaic: VMEM limits, layouts


# the paged-decode kernel at the geometries it serves, beside chip_smoke's
# TinyLlama heads: the serve cell's (SmolLM2-1.7B: 16 slots, 32 / 32 heads
# of 64, 48 pages of 16, and the same heads on int8 pages of 32) and
# Mistral-7B's (8 kv heads of 128, 4 query heads each). Mosaic's VMEM
# and tiling limits are met here, before chip time is spent
_SERVE_CELL = dataclasses.replace(FULL, heads=32, kv_heads=32,
                                  decode_slots=16, decode_tokens=768)
_MISTRAL = dataclasses.replace(FULL, hidden=4096, heads=32, kv_heads=8,
                               decode_slots=16, decode_tokens=1024)
# 64 kv heads of 128: more than one step's VMEM budget, so a head-block axis
_WIDE = dataclasses.replace(FULL, hidden=8192, heads=64, kv_heads=64,
                            decode_slots=4, decode_tokens=256)
_PAGED_GEOMETRIES = [
    ("wide_bf16_head_blocks", _WIDE, False),
    ("serve_cell_bf16_page16", _SERVE_CELL, False),
    ("serve_cell_int8_page32", _SERVE_CELL, True),
    ("mistral_bf16_page16", _MISTRAL, False),
    ("mistral_int8_page32", _MISTRAL, True),
]


@pytest.mark.parametrize("name,size,int8", _PAGED_GEOMETRIES,
                         ids=[g[0] for g in _PAGED_GEOMETRIES])
def test_paged_decode_compiles_at_serving_geometries(as_tpu, described_v5e,
                                                     name, size, int8):
    from paddle_tpu.kernels.paged_attention import decode_plan
    fn, args, _ = chip_smoke._paged_case(size, jnp.dtype("bfloat16"), False,
                                         _ShapeOnlyRng(), int8=int8)
    shapes = [None if a is None else jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=described_v5e) for a in args]
    q, pool, bt = args[0], args[1], args[3]
    plan = decode_plan(q.shape[1], pool.shape[1], q.shape[2], pool.shape[2],
                       bt.shape[1], pool.dtype, slots=q.shape[0])
    if size is _SERVE_CELL and not int8:
        assert bt.shape == (16, 48) and plan.grid_steps <= 768
    if size is _WIDE:
        assert plan.grid[1] > 1
    if described_v5e is None:
        lowered = _lower_tpu(jax.jit(fn), *shapes)
    else:
        lowered = jax.jit(fn).lower(*shapes)
    assert _calls(lowered) == 1
    assert _kernel_names(lowered) == {"paged_attention_decode"}
    # what the benchmark's patterns find the tick by: the block table is
    # the custom call's first operand, two-dimensional
    call = re.search(r"tpu_custom_call.*", lowered.as_text()).group(0)
    assert re.search(rf"\(tensor<{bt.shape[0]}x{bt.shape[1]}xi32>", call), \
        call[:300]
    if described_v5e is not None:
        lowered.compile()       # Mosaic: VMEM limits, tiling, layouts


_NAMES = {
    "flash_fwd_bwd_whole_kv": {"flash_fwd", "flash_bwd_fused"},
    "flash_fwd_bwd_streamed_kv": {"flash_fwd", "flash_bwd_dq_stream",
                                  "flash_bwd_dkv"},
    "paged_decode_bf16_page16": {"paged_attention_decode"},
    "paged_decode_int8_page32": {"paged_attention_decode"},
    "paged_kv_write_one_token": {"paged_kv_write"},
    "paged_kv_write_prompt": {"paged_kv_write"},
    "blockwise_ce_whole_vocab": {"blockwise_ce_fwd", "blockwise_ce_dx",
                                 "blockwise_ce_dw"},
    "blockwise_ce_vocab_block": {"blockwise_ce_fwd", "blockwise_ce_dx",
                                 "blockwise_ce_dw"},
    "rms_norm_residual": {"fused_rmsnorm_fwd", "fused_rmsnorm_bwd"},
    "rms_norm_no_residual": {"fused_rmsnorm_fwd", "fused_rmsnorm_bwd"},
    "rope_apply": {"fused_rope"},
    "weight_only_int8_matmul": {"quant_matmul"},
}


@pytest.mark.parametrize("name,build", _CASES, ids=[n for n, _ in _CASES])
def test_kernel_carries_its_name_in_the_lowered_text(as_tpu, name, build):
    """A trace reader finds a kernel by the name its call site gives
    it, not by operand shapes the next configuration changes."""
    fn, args, _ = build(FULL, jnp.dtype("bfloat16"), False,
                        _ShapeOnlyRng())
    shapes = [None if a is None else jax.ShapeDtypeStruct(a.shape, a.dtype)
              for a in args]
    assert _kernel_names(_lower_tpu(jax.jit(fn), *shapes)) == _NAMES[name]


def test_every_pallas_call_site_passes_a_literal_name():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = []
    for path in sorted(glob.glob(os.path.join(
            root, "paddle_tpu", "kernels", "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "pallas_call":
                kw = {k.arg: k.value for k in node.keywords}
                assert isinstance(kw.get("name"), ast.Constant), \
                    f"{path}:{node.lineno}: pallas_call without a " \
                    f"literal name="
                names.append(kw["name"].value)
    assert len(names) == len(set(names)) == 20
    assert set().union(*_NAMES.values()) \
        | {"flash_bwd_dq", "moe_experts_decode",
           "paged_attention_prefill", "moe_grouped",
           "mla_decode", "mla_prefill", "ssm_scan"} == set(names)


def _trainer(mesh=None, **cfg_kw):
    import paddle_tpu
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.parallel import Trainer, TrainStepConfig
    from paddle_tpu.parallel.plan import llama_sharding_plan
    cfg = chip_smoke.llama_config(FULL, 1, use_flash_attention=True,
                                  **cfg_kw)
    paddle_tpu.seed(0)
    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())
    plan = None if mesh is None else llama_sharding_plan(mesh.dim_names)
    return Trainer(model, optimizer, mesh=mesh, plan=plan,
                   config=TrainStepConfig(compute_dtype="bfloat16"))


def _lower_step(trainer):
    ids = np.zeros((FULL.batch, FULL.context), np.int32)
    batch = {"input_ids": ids, "labels": ids}
    trainer._step_fn = trainer._build_step(None)
    args = (trainer.params, trainer.opt_state,
            jnp.asarray(1e-4, jnp.float32), batch)
    with trainer._mesh_ctx():
        return _lower_tpu(trainer._step_fn, *args)


@pytest.mark.parametrize("cfg_kw,calls", [
    (dict(loss_chunk=0), 2),                   # flash fwd + fused bwd
    (dict(loss_chunk=0, recompute=True), 3),   # + the remat forward
    (dict(loss_chunk=512), 5),                 # + CE fwd, dx, dW
], ids=["dense_loss", "recompute", "loss_chunk_512"])
def test_train_step_lowers_for_tpu(as_tpu, cfg_kw, calls):
    lowered = _lower_step(_trainer(**cfg_kw))
    assert _calls(lowered) == calls
    want = {"flash_fwd", "flash_bwd_fused"}
    if cfg_kw.get("loss_chunk"):
        want |= {"blockwise_ce_fwd", "blockwise_ce_dx", "blockwise_ce_dw"}
    assert _kernel_names(lowered) == want
    # the blocks' scopes ride the ops' locations, the backward's as
    # `transpose(jvp(attn))/...`
    text = lowered.as_text(debug_info=True)
    for scope in ("embed", "attn", "qkv", "rope", "core", "out_proj",
                  "mlp", "norm", "lm_head_loss", "optimizer"):
        assert re.search(rf'[/("]{scope}[/)"]', text), scope


def test_mesh_train_step_lowers_per_shard(as_tpu):
    """Under fsdp 2 x mp 2 every Pallas entry runs inside a shard_map
    (kernels/sharding.py): XLA refuses to partition a Mosaic call. The
    flash operand must be the per-shard folded q — half the batch, half
    the kv heads — not the global array."""
    from paddle_tpu.distributed.mesh import init_mesh
    text = _lower_step(_trainer(mesh=init_mesh({"fsdp": 2, "mp": 2}),
                                loss_chunk=512)).as_text()
    assert chip_smoke._custom_calls(text) == 5
    rep = FULL.heads // FULL.kv_heads
    assert (f"tensor<{FULL.batch // 2}x{FULL.kv_heads // 2}"
            f"x{rep * FULL.context}x{FULL.head_dim}xbf16>") in text
    # blockwise CE: the 8,192 rows split four ways
    assert f"tensor<{FULL.batch * FULL.context // 4}x{FULL.hidden}" \
        f"xbf16>" in text


@pytest.mark.parametrize("size,b,mp,kv_dtype,page", [
    (FULL, FULL.slots, 40, None, 16),
    (FULL, FULL.slots, 40, "int8", 32),
    (_SERVE_CELL, 16, 48, None, 16),    # the serve cell's engine
], ids=["smoke_bf16", "smoke_int8", "serve_cell_bf16"])
def test_engine_programs_lower_for_tpu(as_tpu, size, b, mp, kv_dtype, page):
    import paddle_tpu
    from paddle_tpu.inference import PagedKVEngine
    from paddle_tpu.models import LlamaForCausalLM
    layers = 2
    paddle_tpu.seed(0)
    model = LlamaForCausalLM(chip_smoke.llama_config(size, layers))
    model = paddle_tpu.amp.decorate(models=model, level="O2",
                                    dtype="bfloat16")
    eng = PagedKVEngine(model, max_slots=b, page_size=page,
                        num_pages=b * mp + 1, max_pages_per_slot=mp,
                        kernel=None, kv_dtype=kv_dtype)
    assert eng.decode_kernel == "pallas"
    assert eng.decode_plan.grid[0] == b
    if size is _SERVE_CELL:
        # 16 x 32 x 48 steps of one (16, 64) tile before this plan
        assert eng.decode_plan.grid_steps <= 768
    pools = [a for kv in eng.pools for a in kv]
    z = lambda *s, dt=np.int32: np.zeros(s, dt)         # noqa: E731
    key = np.asarray(jax.random.key_data(jax.random.key(0)))

    tick = eng._tick_fn(False)
    rows = (z(b), z(b), z(b, dt=bool), z(b))    # tok, lens, active, limit
    lowered = _lower_tpu(tick.func, *tick.args, rows, z(b, mp), z(b), key,
                         np.int32(0), pools)
    # one decode kernel a layer: the kernel is traced and lowered once,
    # into a function of its own that every layer calls (XLA inlines it,
    # so the compiled tick holds one custom call a layer); the same for
    # the write of a step's K and V, which plain pools take (stored as
    # the decode kernel's rows) and int8 pools leave to XLA's scatter
    plain = kv_dtype is None
    assert eng.kv_write == ("pallas" if plain else "xla")
    assert eng.pools[0][0].shape == (
        (b * mp + 1, size.kv_heads // 2, 16, 128) if plain
        else (b * mp + 1, size.kv_heads, page, size.head_dim))
    assert _calls(lowered) == 1 + plain
    assert len(re.findall(r"call @_decode\(", lowered.as_text())) == layers
    assert len(re.findall(r"call @paged_kv_write\(", lowered.as_text())) \
        == layers * plain
    assert _kernel_names(lowered) == {"paged_attention_decode"} | (
        {"paged_kv_write"} if plain else set())
    # the benchmark finds the tick by the decode kernel's first operand,
    # the two-dimensional block table: the write's scalars are flat
    firsts = re.findall(r"tpu_custom_call.*?\(tensor<([\dx]+)xi32>",
                        lowered.as_text())
    assert sorted(firsts) == sorted([f"{b}x{mp}"] + [f"{b}"] * plain)
    text = lowered.as_text(debug_info=True)
    for scope in ("kv_write", "paged_attn", "sample"):
        assert re.search(rf'[/("]{scope}[/)"]', text), scope
    # the weights are the program's argument, never HLO literals
    assert len(lowered.as_text()) < 5e6

    prefill = eng._prefill_fn(512, b)
    lowered = _lower_tpu(prefill.func, *prefill.args, z(b, 512), z(b),
                         z(b), z(b, mp), pools)
    # prefill attends through the jnp gather path: no flash kernel yet
    # (ROADMAP open item) — pin it so the day it changes is noticed; its
    # one call is the write of the prompt's K and V
    assert _calls(lowered) == plain
    assert _kernel_names(lowered) == ({"paged_kv_write"} if plain
                                      else set())
    assert f"tensor<{b}x{mp}xi32>" not in "".join(
        re.findall(r"tpu_custom_call.*", lowered.as_text()))


# -- the decoder with a key selection and routed experts, at the widths of
# -- its serve cell (8 slots, 32 / 4 heads of 128, 416 pages of 16; 128
# -- experts of 2048 x 768, 8 a row)

def _shapes(described, *specs):
    return [jax.ShapeDtypeStruct(shape, dt, sharding=described)
            for shape, dt in specs]


def _expert_grid_is_traced(lowered, listed):
    """The few-rows expert kernel's call takes a scalar before its list of
    `listed` experts: the grid's second extent, the number of experts hit,
    rides in as a traced value (a static extent adds no operand)."""
    call = re.search(r"tpu_custom_call.*", lowered.as_text()).group(0)
    return re.search(rf": \(tensor<i32>, tensor<{listed}xi32>, ", call) \
        is not None


def test_few_rows_expert_kernel_compiles_at_the_cells_widths(
        as_tpu, described_v5e):
    from paddle_tpu.kernels.moe_experts import (moe_decode_problems,
                                                moe_experts_decode)
    bf16, e, d, f = jnp.bfloat16, 128, 2048, 768
    assert not moe_decode_problems(8, d, f, bf16)
    assert moe_decode_problems(8, d, 100, bf16) \
        and moe_decode_problems(4096, d, f, bf16)
    shapes = _shapes(described_v5e, ((8, d), bf16), ((e, d, f), bf16),
                     ((e, d, f), bf16), ((e, f, d), bf16),
                     ((8, 8), jnp.int32), ((8, 8), jnp.float32))
    fn = jax.jit(moe_experts_decode)
    lowered = _lower_tpu(fn, *shapes) if described_v5e is None \
        else fn.lower(*shapes)
    assert _kernel_names(lowered) == {"moe_experts_decode"}
    # 8 rows x 8 choices: a list of 64 of the 128 experts, ~51 hit
    assert _expert_grid_is_traced(lowered, 64)
    if described_v5e is not None:
        lowered.compile()       # Mosaic: VMEM under the scoped default


def test_paged_decode_under_a_selection_compiles_at_the_cells_geometry(
        as_tpu, described_v5e):
    from paddle_tpu.kernels.paged_attention import (decode_plan,
                                                    paged_decode_attention)
    bf16, b, mp, page = jnp.bfloat16, 8, 416, 16
    plan = decode_plan(32, 4, 128, page, mp, bf16, slots=b)
    assert (plan.fold, plan.pack, plan.heads) == (1, 1, 4)
    pool = ((b * mp + 1, 4, page, 128), bf16)
    shapes = _shapes(described_v5e, ((b, 32, 128), bf16), pool, pool,
                     ((b, mp), jnp.int32), ((b,), jnp.int32),
                     ((b, mp * page), jnp.bool_))
    fn = jax.jit(lambda q, k, v, bt, lens, sel: paged_decode_attention(
        q, k, v, bt, lens, select=sel))
    lowered = _lower_tpu(fn, *shapes) if described_v5e is None \
        else fn.lower(*shapes)
    assert _kernel_names(lowered) == {"paged_attention_decode"}
    # the tick-finding patterns: the block table is the call's first operand
    call = re.search(r"tpu_custom_call.*", lowered.as_text()).group(0)
    assert re.search(rf"\(tensor<{b}x{mp}xi32>", call), call[:300]
    if described_v5e is not None:
        lowered.compile()


def test_indexed_moe_engine_programs_lower_for_tpu(as_tpu, monkeypatch):
    import paddle_tpu
    from paddle_tpu.inference import PagedKVEngine
    from paddle_tpu.kernels.moe_grouped import grouped_plan
    from paddle_tpu.models.sparse_attn_moe import (SparseAttnMoeConfig,
                                                   SparseAttnMoeForCausalLM)
    from paddle_tpu.nn.layer import moe as moe_layer
    monkeypatch.setattr(moe_layer, "on_tpu", lambda: True)
    layers, b, mp = 2, 8, 416
    paddle_tpu.seed(0)
    model = SparseAttnMoeForCausalLM(SparseAttnMoeConfig(
        vocab_size=512, num_hidden_layers=layers, num_experts=16,
        hidden_size=256, moe_intermediate_size=128))
    model = paddle_tpu.amp.decorate(models=model, level="O2",
                                    dtype="bfloat16")
    model.eval()
    eng = PagedKVEngine(model, max_slots=b, page_size=16, num_pages=65,
                        max_pages_per_slot=mp, kernel=None)
    assert eng.decode_kernel == "pallas" and eng.index_dim == 64
    assert eng.decode_plan.grid == (b, 1, 52)
    # 8 rows x 32 heads x a 6,656-token window: chunks of 1,024 alone
    assert eng._prefill_limit(1) == 1024
    pools = [a for kv in eng.pools for a in kv]
    z = lambda *s, dt=np.int32: np.zeros(s, dt)         # noqa: E731
    key = np.asarray(jax.random.key_data(jax.random.key(0)))
    tick = eng._tick_fn(False)
    rows = (z(b), z(b), z(b, dt=bool), z(b))    # tok, lens, active, limit
    lowered = _lower_tpu(tick.func, *tick.args, rows, z(b, mp), z(b), key,
                         np.int32(0), pools)
    # each kernel traced and lowered once, called once a layer
    assert _kernel_names(lowered) == {"paged_attention_decode",
                                      "paged_kv_write",
                                      "moe_experts_decode"}
    assert _calls(lowered) == 3
    # K and V as the kernel's rows (at heads of 128 the shape by heads),
    # the index pool by heads under XLA's scatter
    assert [a.shape for a in eng.pools[0]] == [
        (65, 4, 16, 128), (65, 4, 16, 128), (65, 1, 16, 64)]
    text = lowered.as_text(debug_info=True)
    for scope in ("kv_write", "paged_attn", "indexer", "select", "moe",
                  "router", "experts", "sample"):
        assert re.search(rf'[/("]{scope}[/)"]', text), scope
    chunk = eng._prefill_chunk_fn(1024, 1)
    lowered = _lower_tpu(chunk.func, *chunk.args, z(1, 1024), z(1), z(1),
                         z(1, mp), pools)
    # a prefill's rows take the grouped kernel, its attention the jnp one;
    # the other call writes the chunk's K and V
    assert _calls(lowered) == 2
    assert _kernel_names(lowered) == {"paged_kv_write", "moe_grouped"}
    # the plan of each layer's call, from the shapes: 8,192 pairs over 16
    # experts, 512 rows an expert
    assert chunk.grouped_plans == [
        grouped_plan(1024 * 8, 16, 256, 128, jnp.bfloat16)] * layers
    assert chunk.grouped_plans[0].tm == 512
    text = lowered.as_text(debug_info=True)
    for scope in ("dispatch", "experts", "combine", "select"):
        assert re.search(rf'[/("]{scope}[/)"]', text), scope
    assert "ragged_dot" not in lowered.as_text()


# -- the decoder of window and full layers with a share of its experts, at
# -- the widths of its serve cell (16 slots, 48 / 8 heads of 128, pages of
# -- 16: 1,152 a slot in the full layer, a 257-page view of a 273-page ring
# -- in a window layer; 32 held experts of 3072 x 3072)

@pytest.mark.parametrize("rows", [16, 256], ids=["decode", "prefill-chunk"])
def test_few_rows_expert_kernel_at_a_share_compiles_at_the_cells_widths(
        as_tpu, described_v5e, rows):
    from paddle_tpu.kernels.moe_experts import (_f_tile, moe_decode_problems,
                                                moe_experts_decode)
    bf16, e, d, f = jnp.bfloat16, 32, 3072, 3072
    assert not moe_decode_problems(rows, d, f, bf16)
    assert _f_tile(d, f, 2) == 256              # twelve tiles of f
    shapes = _shapes(described_v5e, ((rows, d), bf16), ((e, d, f), bf16),
                     ((e, d, f), bf16), ((e, f, d), bf16),
                     ((rows, 4), jnp.int32), ((rows, 4), jnp.float32))
    fn = jax.jit(lambda *a: moe_experts_decode(*a, share=True))
    lowered = _lower_tpu(fn, *shapes) if described_v5e is None \
        else fn.lower(*shapes)
    assert _kernel_names(lowered) == {"moe_experts_decode"}
    # a list of all 32 held experts, ~8 hit a decode step
    assert _expert_grid_is_traced(lowered, 32)
    if described_v5e is not None:
        lowered.compile()       # Mosaic: VMEM under the scoped default


def test_paged_decode_over_a_rings_view_compiles_at_the_cells_geometry(
        as_tpu, described_v5e):
    """A group of 6 query heads a kv head, over the 257 pages that hold a
    window of 4,096, the view's edges as the kernel's per-key mask."""
    from paddle_tpu.inference.paged import ring_pages_for
    from paddle_tpu.kernels.paged_attention import (decode_plan,
                                                    decode_shape_problems,
                                                    paged_decode_attention,
                                                    select_shape_problems)
    bf16, b, page = jnp.bfloat16, 16, 16
    view, ring = ring_pages_for(4096, 1, page), ring_pages_for(4096, 256, page)
    assert (view, ring) == (257, 273)
    assert not decode_shape_problems(48, 8, 128, page, kv_dtype=bf16)
    assert not select_shape_problems(8, 128, page, bf16)
    plan = decode_plan(48, 8, 128, page, view, bf16, slots=b)
    assert (plan.fold, plan.pack, plan.heads, plan.pages) == (1, 1, 8, 8)
    assert plan.grid == (b, 1, 33)
    assert decode_plan(48, 8, 128, page, 1152, bf16, slots=b).grid \
        == (b, 1, 144)
    pool = ((b * ring + 1, 8, page, 128), bf16)
    shapes = _shapes(described_v5e, ((b, 48, 128), bf16), pool, pool,
                     ((b, view), jnp.int32), ((b,), jnp.int32),
                     ((b, view * page), jnp.bool_))
    fn = jax.jit(lambda q, k, v, bt, lens, sel: paged_decode_attention(
        q, k, v, bt, lens, select=sel))
    lowered = _lower_tpu(fn, *shapes) if described_v5e is None \
        else fn.lower(*shapes)
    assert _kernel_names(lowered) == {"paged_attention_decode"}
    if described_v5e is not None:
        lowered.compile()


@pytest.mark.parametrize("tokens,keys,window", [
    (4096, 513 * 16, 4096), (4096, 1152 * 16, 0), (2048, 385 * 16, 4096)],
    ids=["ring-view", "full-table", "bucket-2048"])
def test_chunk_attention_compiles_at_the_cells_geometry(
        as_tpu, described_v5e, tokens, keys, window):
    """A prefill call's attention: 48 / 8 heads of 128, a chunk of as many
    tokens as the window over a ring's 513-page view, and over the full
    layer's whole table."""
    from paddle_tpu.kernels.prefill_attention import (
        chunk_attention, chunk_attention_problems)
    bf16 = jnp.bfloat16
    assert not chunk_attention_problems(tokens, 48, 8, 128)
    assert chunk_attention_problems(12, 48, 8, 128) \
        and chunk_attention_problems(16, 48, 8, 64)
    shapes = _shapes(described_v5e, ((1, tokens, 48, 128), bf16),
                     ((1, 8, keys, 128), bf16), ((1, 8, keys, 128), bf16),
                     ((1,), jnp.int32), ((1,), jnp.int32))
    fn = jax.jit(lambda q, k, v, qp, kp: chunk_attention(
        q, k, v, qp, kp, window=window))
    lowered = _lower_tpu(fn, *shapes) if described_v5e is None \
        else fn.lower(*shapes)
    assert _kernel_names(lowered) == {"paged_attention_prefill"}
    if described_v5e is not None:
        lowered.compile()


def test_window_moe_engine_programs_lower_for_tpu(as_tpu, monkeypatch):
    import paddle_tpu
    from paddle_tpu.inference import PagedKVEngine
    from paddle_tpu.models.window_attn_moe import (WindowAttnMoeConfig,
                                                   WindowAttnMoeForCausalLM)
    from paddle_tpu.nn.layer import moe as moe_layer
    monkeypatch.setattr(moe_layer, "on_tpu", lambda: True)
    b, mp = 16, 1152
    paddle_tpu.seed(0)
    model = WindowAttnMoeForCausalLM(WindowAttnMoeConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_hidden_layers=3, num_dense_layers=1, num_attention_heads=12,
        num_key_value_heads=2, head_dim=128,
        layer_types=["sliding_attention", "sliding_attention",
                     "full_attention"],
        sliding_window=4096, num_experts=16, held_experts=(4, 4),
        moe_intermediate_size=128))
    model = paddle_tpu.amp.decorate(models=model, level="O2",
                                    dtype="bfloat16")
    model.eval()
    eng = PagedKVEngine(model, max_slots=b, page_size=16, num_pages=65,
                        max_pages_per_slot=mp, kernel=None)
    assert eng.decode_kernel == "pallas" and eng.kv_write == "pallas"
    # a prefill attends through the chunk kernel, which holds no scores:
    # a call takes as many tokens as the window, so a ring is the 257
    # pages of a window and 256 more
    assert eng._chunk_kernel and eng._prefill_limit(1) == 4096
    assert eng._prefill_limit(b) == 256
    assert eng._ring.pages_per_slot == 513
    assert eng._ring.num_pages == b * 513 + 1 and eng._full.num_pages == 65
    assert [kv[0].shape[0] for kv in eng.pools] == [8209, 8209, 65]
    pools = [a for kv in eng.pools for a in kv]
    z = lambda *s, dt=np.int32: np.zeros(s, dt)         # noqa: E731
    key = np.asarray(jax.random.key_data(jax.random.key(0)))
    tick = eng._tick_fn(False)
    rows = (z(b), z(b), z(b, dt=bool), z(b))    # tok, lens, active, limit
    lowered = _lower_tpu(tick.func, *tick.args, rows, (z(b, mp), z(b, 513)),
                         z(b), key, np.int32(0), pools)
    assert _kernel_names(lowered) == {"paged_attention_decode",
                                      "paged_kv_write",
                                      "moe_experts_decode"}
    # each kernel lowered once a geometry: the decode kernel over the full
    # table and over a ring's view, the write into either pool, the experts
    assert _calls(lowered) == 5
    text = lowered.as_text()
    # the tick-finding patterns: the full layer's call has the [slots,
    # max_pages_per_slot] block table as its first operand, a window
    # layer's the 257 pages of its view
    firsts = set(re.findall(r"tpu_custom_call[^\n]*?\(tensor<(\d+x\d+)xi32>",
                            text))
    assert {f"{b}x{mp}", f"{b}x257"} <= firsts, firsts
    text = lowered.as_text(debug_info=True)
    for scope in ("kv_write", "paged_attn", "window", "gate", "mlp", "moe",
                  "router", "shared_expert", "experts", "sample"):
        assert re.search(rf'[/("]{scope}[/)"]', text), scope
    chunk = eng._prefill_chunk_fn(4096, 1)
    lowered = _lower_tpu(chunk.func, *chunk.args, z(1, 4096), z(1), z(1),
                         (z(1, mp), z(1, 513)), pools)
    # a 4,096-row chunk takes the grouped kernel at the share, its
    # attention the chunk kernel (over a ring's view, and over the full
    # table from its start); the other calls write the chunk's K and V
    assert _kernel_names(lowered) == {"paged_kv_write",
                                      "paged_attention_prefill",
                                      "moe_grouped"}
    text = lowered.as_text(debug_info=True)
    for scope in ("dispatch", "experts", "combine", "window",
                  "shared_expert"):
        assert re.search(rf'[/("]{scope}[/)"]', text), scope
    assert "ragged_dot" not in lowered.as_text()


# -- the decoder that generates by diffusion over blocks, at the widths of
# -- its serve cell (16 slots, 32 / 4 heads of 128, 192 pages of 16, blocks
# -- of 4: a forward carries 64 rows, 32 a kv head in the decode kernel)

def test_a_blocks_rows_compile_into_the_decode_kernel_at_the_cells_geometry(
        as_tpu, described_v5e):
    from paddle_tpu.inference.paged import _fold_rows
    from paddle_tpu.kernels.paged_attention import (decode_plan,
                                                    paged_decode_attention)
    bf16, b, mp, page, blk = jnp.bfloat16, 16, 192, 16, 4
    plan = decode_plan(32 * blk, 4, 128, page, mp, bf16, slots=b)
    assert (plan.fold, plan.pack, plan.heads, plan.grid) == (
        1, 1, 4, (b, 1, 24))
    pool = ((b * mp + 1, 4, page, 128), bf16)
    shapes = _shapes(described_v5e, ((b, blk, 32, 128), bf16), pool, pool,
                     ((b, mp), jnp.int32), ((b,), jnp.int32))
    fn = jax.jit(lambda q, k, v, bt, lens: paged_decode_attention(
        _fold_rows(q, 4), k, v, bt, lens + (blk - 1)))
    lowered = _lower_tpu(fn, *shapes) if described_v5e is None \
        else fn.lower(*shapes)
    assert _kernel_names(lowered) == {"paged_attention_decode"}
    # the tick-finding patterns: the block table is the call's first operand
    call = re.search(r"tpu_custom_call.*", lowered.as_text()).group(0)
    assert re.search(rf"\(tensor<{b}x{mp}xi32>", call), call[:300]
    if described_v5e is not None:
        lowered.compile()


@pytest.mark.parametrize("tokens", [256, 2048])
def test_chunk_attention_by_blocks_compiles_at_the_cells_geometry(
        as_tpu, described_v5e, tokens):
    from paddle_tpu.kernels.prefill_attention import chunk_attention
    bf16, keys = jnp.bfloat16, 192 * 16
    shapes = _shapes(described_v5e, ((1, tokens, 32, 128), bf16),
                     ((1, 4, keys, 128), bf16), ((1, 4, keys, 128), bf16),
                     ((1,), jnp.int32), ((1,), jnp.int32))
    fn = jax.jit(lambda q, k, v, qp, kp: chunk_attention(
        q, k, v, qp, kp, block=4))
    lowered = _lower_tpu(fn, *shapes) if described_v5e is None \
        else fn.lower(*shapes)
    assert _kernel_names(lowered) == {"paged_attention_prefill"}
    if described_v5e is not None:
        lowered.compile()


def test_few_rows_expert_kernel_compiles_at_a_blocks_rows(as_tpu,
                                                           described_v5e):
    """64 rows a forward (16 slots x 4) of 8 picks each: the list of
    experts hit is all 128."""
    from paddle_tpu.kernels.moe_experts import (moe_decode_problems,
                                                moe_experts_decode)
    bf16, e, d, f, rows = jnp.bfloat16, 128, 2048, 768, 64
    assert not moe_decode_problems(rows, d, f, bf16)
    shapes = _shapes(described_v5e, ((rows, d), bf16), ((e, d, f), bf16),
                     ((e, d, f), bf16), ((e, f, d), bf16),
                     ((rows, 8), jnp.int32), ((rows, 8), jnp.float32))
    fn = jax.jit(moe_experts_decode)
    lowered = _lower_tpu(fn, *shapes) if described_v5e is None \
        else fn.lower(*shapes)
    assert _kernel_names(lowered) == {"moe_experts_decode"}
    # a list of all 128, ~99 hit a forward
    assert _expert_grid_is_traced(lowered, 128)
    if described_v5e is not None:
        lowered.compile()


def test_block_diffusion_engine_programs_lower_for_tpu(as_tpu, monkeypatch):
    import paddle_tpu
    from paddle_tpu.inference import PagedKVEngine
    from paddle_tpu.models.block_diffusion_moe import (
        BlockDiffusionMoeConfig, BlockDiffusionMoeForCausalLM)
    from paddle_tpu.nn.layer import moe as moe_layer
    monkeypatch.setattr(moe_layer, "on_tpu", lambda: True)
    layers, b, mp, blk = 2, 16, 192, 4
    paddle_tpu.seed(0)
    model = BlockDiffusionMoeForCausalLM(BlockDiffusionMoeConfig(
        vocab_size=512, num_hidden_layers=layers, num_experts=16,
        hidden_size=256, moe_intermediate_size=128, mask_token_id=511,
        denoising_steps=2))
    model = paddle_tpu.amp.decorate(models=model, level="O2",
                                    dtype="bfloat16")
    model.eval()
    eng = PagedKVEngine(model, max_slots=b, page_size=16, num_pages=65,
                        max_pages_per_slot=mp, kernel=None)
    assert eng.decode_kernel == "pallas" and eng.kv_write == "pallas"
    assert eng.block_length == blk and eng._chunk_kernel
    assert eng.decode_plan.grid == (b, 1, 24)
    # 1 row x 32 heads x a 3,072-token table: a whole 2,048-token prompt
    assert eng._prefill_limit(1) == 2048
    pools = [a for kv in eng.pools for a in kv]
    z = lambda *s, dt=np.int32: np.zeros(s, dt)         # noqa: E731
    key = np.asarray(jax.random.key_data(jax.random.key(0)))
    tick = eng._tick_fn(False)
    # the open block's tokens and which are known, lens, active, limit
    rows = (z(b, blk), z(b, blk, dt=bool), z(b), z(b, dt=bool), z(b))
    lowered = _lower_tpu(tick.func, *tick.args, rows, z(b, mp), z(b), key,
                         np.int32(0), pools)
    # each kernel traced and lowered once, whatever the forwards of a tick
    assert _kernel_names(lowered) == {"paged_attention_decode",
                                      "paged_kv_write",
                                      "moe_experts_decode"}
    assert _calls(lowered) == 3
    text = lowered.as_text()
    firsts = set(re.findall(r"tpu_custom_call[^\n]*?\(tensor<(\d+x\d+)xi32>",
                            text))
    assert f"{b}x{mp}" in firsts, firsts
    text = lowered.as_text(debug_info=True)
    for scope in ("denoise", "unmask", "store", "kv_write", "paged_attn",
                  "moe", "router", "experts"):
        assert re.search(rf'[/("]{scope}[/)"]', text), scope
    prefill = eng._prefill_fn(2048, 1)
    lowered = _lower_tpu(prefill.func, *prefill.args, z(1, 2048), z(1), z(1),
                         z(1, mp), pools)
    # a 2,048-row prefill takes the grouped kernel, its attention the
    # chunk kernel under the mask by blocks; the other call writes its K
    # and V
    assert _kernel_names(lowered) == {"paged_kv_write",
                                      "paged_attention_prefill",
                                      "moe_grouped"}
    assert "ragged_dot" not in lowered.as_text()


# -- the decoder with latent attention, at the widths of its serve cell (32
# -- slots, 32 heads over one latent row of 512 + 64 values a token, stored
# -- at 640, 1,152 pages of 16)

def test_the_latent_decode_kernel_compiles_at_the_cells_geometry(
        as_tpu, described_v5e):
    from paddle_tpu.kernels.mla_decode import (block_pages, kernel_row_width,
                                               mla_decode_attention,
                                               mla_decode_problems)
    bf16, b, mp = jnp.bfloat16, 32, 1152
    width = kernel_row_width(576)
    assert width == 640 and block_pages(16, mp) == 64
    assert not mla_decode_problems(32, width, 512, 16, bf16)
    shapes = _shapes(described_v5e, ((b, 32, width), bf16),
                     ((36865, 16, width), bf16), ((b, mp), jnp.int32),
                     ((b,), jnp.int32))
    fn = jax.jit(lambda q, pool, bt, lens: mla_decode_attention(
        q, pool, bt, lens, rank=512, sm_scale=192 ** -0.5))
    lowered = _lower_tpu(fn, *shapes) if described_v5e is None \
        else fn.lower(*shapes)
    assert _kernel_names(lowered) == {"mla_decode"}
    # the block table is the call's first operand: what the benchmark's
    # patterns find a tick program by
    call = re.search(r"tpu_custom_call.*", lowered.as_text()).group(0)
    assert re.search(rf"\(tensor<{b}x{mp}xi32>", call), call[:300]
    if described_v5e is not None:
        lowered.compile()       # Mosaic: whole lane tiles, VMEM


def test_the_latent_prefill_kernel_compiles_at_the_cells_geometry(
        as_tpu, described_v5e):
    from paddle_tpu.kernels.mla_prefill import (mla_prefill_attention,
                                                mla_prefill_problems)
    bf16, s, mp = jnp.bfloat16, 2048, 1152
    assert not mla_prefill_problems(s, 128, 64, 128, 512, 640, 16, bf16)
    shapes = _shapes(described_v5e, ((1, s, 32, 128), bf16),
                     ((1, s, 32, 64), bf16), ((36865, 16, 640), bf16),
                     ((1, mp), jnp.int32),
                     ((512, 32, 128), bf16), ((512, 32, 128), bf16),
                     ((1,), jnp.int32), ((1,), jnp.int32))
    fn = jax.jit(lambda *a: mla_prefill_attention(*a, sm_scale=192 ** -0.5))
    lowered = _lower_tpu(fn, *shapes) if described_v5e is None \
        else fn.lower(*shapes)
    assert _kernel_names(lowered) == {"mla_prefill"}
    # the grid's key extent rides in first, a traced scalar: no prefill call
    # takes a block table first, which is what finds a tick program
    call = re.search(r"tpu_custom_call.*", lowered.as_text()).group(0)
    assert re.search(r": \(tensor<i32>, tensor<1xi32>, tensor<1xi32>, ",
                     call), call[:300]
    if described_v5e is not None:
        lowered.compile()       # Mosaic: VMEM of a 1,024-query block


def test_latent_engine_programs_lower_for_tpu(as_tpu, monkeypatch):
    import paddle_tpu
    from paddle_tpu.inference import PagedKVEngine
    from paddle_tpu.models.latent_attn_moe import (LatentAttnMoeConfig,
                                                   LatentAttnMoeForCausalLM)
    from paddle_tpu.nn.layer import moe as moe_layer
    monkeypatch.setattr(moe_layer, "on_tpu", lambda: True)
    b, mp = 32, 1152
    paddle_tpu.seed(0)
    model = LatentAttnMoeForCausalLM(LatentAttnMoeConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_hidden_layers=2, n_routed_experts=16, moe_intermediate_size=128))
    model = paddle_tpu.amp.decorate(models=model, level="O2",
                                    dtype="bfloat16")
    model.eval()
    eng = PagedKVEngine(model, max_slots=b, page_size=16, num_pages=65,
                        max_pages_per_slot=mp, kernel=None)
    assert eng.decode_kernel == "pallas" and eng.kv_write == "xla"
    assert eng._prefill_limit(1) == 2048
    pools = [a for kv in eng.pools for a in kv]
    assert [p.shape for p in pools] == [(65, 16, 640)] * 2
    z = lambda *s, dt=np.int32: np.zeros(s, dt)         # noqa: E731
    key = np.asarray(jax.random.key_data(jax.random.key(0)))
    tick = eng._tick_fn(False)
    rows = (z(b), z(b), z(b, dt=bool), z(b))    # tok, lens, active, limit
    lowered = _lower_tpu(tick.func, *tick.args, rows, z(b, mp), z(b), key,
                         np.int32(0), pools)
    assert _kernel_names(lowered) == {"mla_decode", "moe_experts_decode"}
    firsts = set(re.findall(r"tpu_custom_call[^\n]*?\(tensor<(\d+x\d+)xi32>",
                            lowered.as_text()))
    assert f"{b}x{mp}" in firsts, firsts
    text = lowered.as_text(debug_info=True)
    for scope in ("kv_write", "mla_absorb", "mla_unabsorb", "paged_attn",
                  "moe", "shared_expert", "mlp"):
        assert re.search(rf'[/("]{scope}[/)"]', text), scope
    chunk = eng._prefill_chunk_fn(2048, 1)
    lowered = _lower_tpu(chunk.func, *chunk.args, z(1, 2048), z(1), z(1),
                         z(1, mp), pools)
    # a 2,048-row chunk takes the grouped kernel, and its attention the
    # expanded form in the prefill kernel, under its own scope
    assert _kernel_names(lowered) == {"moe_grouped", "mla_prefill"}
    text = lowered.as_text(debug_info=True)
    assert re.search(r'[/("]mla_prefill[/)"]', text)
    assert "ragged_dot" not in lowered.as_text()


# -- the decoder-hybrid-decoder with Mamba layers, at the widths of its
# -- serve cell (32 slots, 40 query rows over 10 kv pairs cached as heads
# -- of 128, 768 pages of 16, 5,120 Mamba channels of 16 states)

def test_the_scan_kernel_compiles_at_the_cells_geometry(as_tpu,
                                                         described_v5e):
    from paddle_tpu.kernels.ssm_scan import ssm_scan, ssm_scan_problems
    f32, s, e, n = jnp.float32, 1024, 5120, 16
    assert not ssm_scan_problems(s, e, n)
    shapes = _shapes(described_v5e, ((1, s, e), f32), ((1, s, e), f32),
                     ((1, n, s), f32), ((1, n, s), f32), ((n, e), f32),
                     ((e,), f32), ((1, n, e), f32))
    fn = jax.jit(lambda *a: ssm_scan(*a))
    lowered = _lower_tpu(fn, *shapes) if described_v5e is None \
        else fn.lower(*shapes)
    assert _kernel_names(lowered) == {"ssm_scan"}
    if described_v5e is not None:
        lowered.compile()       # Mosaic: the token loop's registers, VMEM


def test_ssm_hybrid_engine_programs_lower_for_tpu(as_tpu):
    import paddle_tpu
    from paddle_tpu.inference import PagedKVEngine
    from paddle_tpu.models.ssm_hybrid import (SsmHybridForCausalLM,
                                              tiny_ssm_hybrid_config)
    b, mp = 32, 768
    paddle_tpu.seed(0)
    cfg = tiny_ssm_hybrid_config(vocab_size=512, hidden_size=256,
                                 intermediate_size=512, num_attention_heads=4,
                                 num_key_value_heads=2, sliding_window=512,
                                 mamba_d_state=16)
    model = paddle_tpu.amp.decorate(models=SsmHybridForCausalLM(cfg),
                                    level="O2", dtype="bfloat16")
    model.eval()
    eng = PagedKVEngine(model, max_slots=b, page_size=16, num_pages=65,
                        max_pages_per_slot=mp, kernel=None)
    assert eng.decode_kernel == "pallas" and eng.kv_write == "pallas"
    assert eng._chunk_kernel and eng._prefill_limit(1) == 1024
    # a ring: the window's 33 pages at any offset and a program's 64
    assert eng._ring.pages_per_slot == 97
    assert eng._state.layers == [0, 2, 4] and eng._full.layers == [5]
    pools = [a for kv in eng.pools for a in kv]
    z = lambda *s, dt=np.int32: np.zeros(s, dt)         # noqa: E731
    key = np.asarray(jax.random.key_data(jax.random.key(0)))
    tick = eng._tick_fn(False)
    rows = (z(b), z(b), z(b, dt=bool), z(b))    # tok, lens, active, limit
    lowered = _lower_tpu(tick.func, *tick.args, rows, (z(b, mp), z(b, 97)),
                         z(b), key, np.int32(0), pools)
    # decode's scan and conv stay in jax.numpy inside the tick
    assert _kernel_names(lowered) == {"paged_attention_decode",
                                      "paged_kv_write"}
    # the tick-finding patterns: the full layer's and the cross layer's
    # calls take the [slots, max_pages_per_slot] block table first, a
    # window layer's the 33 pages of its view
    firsts = set(re.findall(r"tpu_custom_call[^\n]*?\(tensor<(\d+x\d+)xi32>",
                            lowered.as_text()))
    assert {f"{b}x{mp}", f"{b}x33"} <= firsts, firsts
    text = lowered.as_text(debug_info=True)
    for scope in ("ssm", "gmu", "cross_attn", "window", "kv_write",
                  "paged_attn", "in_proj", "mlp", "sample"):
        assert re.search(rf'[/("]{scope}[/)"]', text), scope
    chunk = eng._prefill_chunk_fn(1024, 1)
    lowered = _lower_tpu(chunk.func, *chunk.args, z(1, 1024), z(1), z(1),
                         (z(1, mp), z(1, 97), z(1)), pools)
    # the chunk's scan is the Pallas kernel; its window layers attend
    # through the chunk kernel; the full layer and the cross layer at the
    # last token alone, through the decode kernel
    assert _kernel_names(lowered) == {"ssm_scan", "paged_attention_prefill",
                                      "paged_attention_decode",
                                      "paged_kv_write"}
    text = lowered.as_text(debug_info=True)
    assert re.search(r'[/("]ssm[/)"]', text)


# -- no pool is copied: the serve cells' programs compiled for the chip ----

def _pool_copies(compiled, pool):
    """XLA's copies of arrays with a K or V pool's element count in a
    compiled program's text, by op: `copy` changes a layout, `copy-start`
    / `copy-done` move an array between memories."""
    found = {}
    for dims, op in re.findall(
            r"= \w+\[([\d,]+)\]\S* (copy|copy-start|copy-done)\(",
            compiled.as_text()):
        if np.prod([int(n) for n in dims.split(",")]) == pool.size:
            found[op] = found.get(op, 0) + 1
    return found


def _compile_for(described, program, *args):
    return program.func.lower(*jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=described),
        (*program.args, *args))).compile()


@pytest.mark.parametrize("cell", ["dense", "doc_qa"])
def test_serve_cells_programs_copy_no_pool(as_tpu, described_v5e,
                                           monkeypatch, cell):
    """The K and V pools are stored as the decode kernel's rows and only
    Pallas calls touch them, so XLA has no second layout to copy them
    into: the tick of either serve cell, compiled for a v5e, holds no
    copy of a pool-sized array of any kind (4 a pool a tick before), and
    the prefill programs no layout copy (2 a pool; the compiler may still
    move a pool through its fast memory beside the write, where it finds
    room: `copy-start`, not counted). At the cells' slots, heads and
    pages; two layers, which is what a copy count turns on."""
    if described_v5e is None:
        pytest.skip("libtpu cannot describe a v5e here: nothing compiles")
    import paddle_tpu
    from paddle_tpu.inference import PagedKVEngine
    paddle_tpu.seed(0)
    if cell == "dense":
        from paddle_tpu.models import LlamaForCausalLM
        b, mp, bucket = 16, 48, 512
        model = LlamaForCausalLM(chip_smoke.llama_config(_SERVE_CELL, 2))
        rows = (b * mp + 1, 16, 16, 128)
    else:
        from paddle_tpu.models.sparse_attn_moe import (
            SparseAttnMoeConfig, SparseAttnMoeForCausalLM)
        from paddle_tpu.nn.layer import moe as moe_layer
        monkeypatch.setattr(moe_layer, "on_tpu", lambda: True)
        b, mp, bucket = 8, 416, 1024
        model = SparseAttnMoeForCausalLM(SparseAttnMoeConfig(
            vocab_size=512, num_hidden_layers=2, num_experts=16,
            hidden_size=256, moe_intermediate_size=128))
        rows = (b * mp + 1, 4, 16, 128)
    model = paddle_tpu.amp.decorate(models=model, level="O2",
                                    dtype="bfloat16")
    model.eval()
    eng = PagedKVEngine(model, max_slots=b, page_size=16,
                        num_pages=b * mp + 1, max_pages_per_slot=mp,
                        kernel=None)
    pools = [a for kv in eng.pools for a in kv]
    assert eng.kv_write == "pallas" and pools[0].shape == rows
    z = lambda *s, dt=np.int32: np.zeros(s, dt)         # noqa: E731
    key = np.asarray(jax.random.key_data(jax.random.key(0)))
    slot_rows = (z(b), z(b), z(b, dt=bool), z(b))
    tick = _compile_for(described_v5e, eng._tick_fn(False), slot_rows,
                        z(b, mp), z(b), key, np.int32(0), pools)
    assert _pool_copies(tick, pools[0]) == {}
    prefill = (eng._prefill_fn(bucket, 1) if cell == "dense"
               else eng._prefill_chunk_fn(bucket, 1))
    prefill = _compile_for(described_v5e, prefill, z(1, bucket), z(1),
                           z(1), z(1, mp), pools)
    assert "copy" not in _pool_copies(prefill, pools[0])


@pytest.mark.parametrize("entry", ["ce", "norm", "rope"])
def test_sharded_entries_match_unsharded(entry):
    """The per-shard wrappers (kernels/sharding.py) against the same
    entry off the mesh, Pallas in interpret mode on the 8-CPU-device
    mesh: values and gradients agree."""
    from jax.sharding import Mesh
    from paddle_tpu.kernels.blockwise_ce import blockwise_ce_loss
    from paddle_tpu.kernels.fused_norm import (rms_norm_residual,
                                               rope_apply)
    rng = np.random.default_rng(0)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "mp"))
    n, d, v = 64, 32, 96
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    if entry == "ce":
        w = jnp.asarray(rng.standard_normal((d, v)) * 0.1, jnp.float32)
        labels = rng.integers(0, v, n)
        labels[:5] = -100
        fn = lambda x_, w_: blockwise_ce_loss(          # noqa: E731
            x_, w_, jnp.asarray(labels), chunk=8, kernel="pallas")
        args = (x, w)
    elif entry == "norm":
        w = jnp.asarray(rng.standard_normal(d), jnp.float32)
        r = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        fn = lambda x_, w_, r_: sum(jnp.sum(o * o) for o in  # noqa: E731
                                    rms_norm_residual(x_, w_, r_,
                                                      kernel="pallas"))
        args = (x, w, r)
    else:
        x4 = x.reshape(4, 4, 4, 32)
        fn = lambda x_: jnp.sum(jnp.sin(rope_apply(     # noqa: E731
            x_, kernel="pallas")))
        args = (x4,)
    grad = jax.jit(jax.value_and_grad(fn, argnums=tuple(
        range(len(args)))))
    want = grad(*args)
    with mesh:
        lowered = grad.lower(*args)
        got = lowered.compile()(*args)
    assert "shard_map" in lowered.as_text() \
        or "sdy.manual_computation" in lowered.as_text()
    for g, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_),
                                   rtol=1e-5, atol=1e-5)
