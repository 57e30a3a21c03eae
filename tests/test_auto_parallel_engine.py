"""Auto-parallel static Engine (reference: auto_parallel/static/
engine.py Engine + completion.py Completer + tuner/cost: tests
test_engine_api.py): trial-free mesh planning via the cost model,
structural plan completion, and fit/evaluate/cost on the 8-device CPU
mesh."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed.auto_parallel import (Engine, Strategy,
                                                  plan_mesh, complete_plan)
from paddle_tpu.models import LlamaForCausalLM
from paddle_tpu.models.llama import tiny_llama_config


def test_plan_mesh_ranks_candidates():
    model = LlamaForCausalLM(tiny_llama_config())
    axes, ranked = plan_mesh(model, 8, {"global_batch_size": 8,
                                        "peak_flops": 197e12})
    assert int(np.prod(list(axes.values()))) == 8
    assert len(ranked) > 3
    # for a 200k-param toy model pure model-parallel over 8 must not win
    assert axes.get("mp", 1) < 8


def test_complete_plan_structural_rules():
    from jax.sharding import PartitionSpec as P
    model = LlamaForCausalLM(tiny_llama_config())
    plan = complete_plan(model, {"dp": 2, "fsdp": 2, "mp": 2})
    # embedding: vocab over mp
    assert plan.spec_for("model.embed_tokens.weight") == P("mp", "fsdp")
    # attention: q/k/v column-parallel, o row-parallel
    assert plan.spec_for(
        "model.layers.0.self_attn.q_proj.weight") == P("fsdp", "mp")
    assert plan.spec_for(
        "model.layers.0.self_attn.o_proj.weight") == P("mp", "fsdp")
    # MLP: gate/up col, down row
    assert plan.spec_for(
        "model.layers.0.mlp.up_proj.weight") == P("fsdp", "mp")
    assert plan.spec_for(
        "model.layers.0.mlp.down_proj.weight") == P("mp", "fsdp")
    # vocab head column-parallel, norms replicated
    assert plan.spec_for("lm_head.weight") == P("fsdp", "mp")
    assert plan.spec_for("model.norm.weight") == P()


def test_complete_plan_bert_structure():
    """The positional col/row heuristic must also cover a non-Llama
    stack (no reliance on paddle naming conventions)."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.models.bert import BertForMaskedLM, tiny_bert_config
    model = BertForMaskedLM(tiny_bert_config())
    plan = complete_plan(model, {"dp": 4, "mp": 2})
    ffn1 = plan.spec_for("bert.encoder.layers.0.linear1.weight")
    ffn2 = plan.spec_for("bert.encoder.layers.0.linear2.weight")
    assert ffn1 == P(None, "mp") and ffn2 == P("mp", None)
    assert plan.spec_for(
        "bert.embeddings.word_embeddings.weight") == P("mp", None)


def test_engine_full_auto_fit_and_cost():
    paddle.seed(0)
    cfg = tiny_llama_config(num_hidden_layers=2)
    model = LlamaForCausalLM(cfg)
    o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    eng = Engine(model=model, optimizer=o).prepare(
        tuner_cfg={"global_batch_size": 8, "pp_degree": [1],
                   "peak_flops": 197e12})
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (8, 32)).astype(np.int32)
    data = [{"input_ids": ids, "labels": ids}] * 6
    losses = eng.fit(data)
    assert len(losses) == 6 and losses[-1] < losses[0]
    ev = eng.evaluate(data, steps=1)
    assert np.isfinite(ev)
    c = eng.cost({"global_batch_size": 8, "peak_flops": 197e12})
    assert c["step_time_s"] > 0 and c["memory_bytes_per_chip"] > 0


def test_engine_semi_auto_pipeline():
    paddle.seed(1)
    cfg = tiny_llama_config(num_hidden_layers=4)
    model = LlamaForCausalLM(cfg)
    o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    eng = Engine(model=model, optimizer=o,
                 strategy=Strategy(auto_mode="semi", pp_degree=2,
                                   dp_degree=2, mp_degree=2,
                                   num_microbatches=2)).prepare()
    assert eng.mesh_axes == {"pp": 2, "dp": 2, "mp": 2}
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (4, 32)).astype(np.int32)
    losses = eng.fit([{"input_ids": ids, "labels": ids}] * 3)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


# -- round 5: the automated plan trains REAL model families -----------------

def test_complete_plan_trains_llama_to_hand_plan_parity():
    """Completer output (structure-derived, no name conventions) trains
    tiny-llama on dp x fsdp x mp to the same losses as the hand-written
    llama_sharding_plan (GSPMD semantics are sharding-invariant), and it
    actually shards the big weights."""
    import paddle_tpu
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import init_mesh
    from paddle_tpu.distributed.auto_parallel.engine import complete_plan
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import tiny_llama_config
    from paddle_tpu.parallel import Trainer, TrainStepConfig
    from paddle_tpu.parallel.plan import llama_sharding_plan

    mesh = init_mesh({"dp": 2, "fsdp": 2, "mp": 2})
    ids = np.random.RandomState(0).randint(0, 256, (8, 32)).astype("int32")
    batch = {"input_ids": ids, "labels": ids}
    losses = {}
    for name in ("hand", "auto"):
        paddle_tpu.seed(0)
        cfg = tiny_llama_config(num_hidden_layers=2)
        model = LlamaForCausalLM(cfg)
        plan = (llama_sharding_plan(mesh.jax_mesh.axis_names)
                if name == "hand" else complete_plan(
                    model, mesh.jax_mesh.axis_names))
        o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
        tr = Trainer(model, o, mesh=mesh, plan=plan,
                     config=TrainStepConfig(compute_dtype=None))
        losses[name] = [float(tr.step(batch)) for _ in range(3)]
        if name == "auto":
            # the attention projections really sharded over mp
            spec = tr.params[
                "model.layers.0.self_attn.q_proj.weight"].sharding.spec
            assert "mp" in str(spec), spec
    np.testing.assert_allclose(losses["auto"], losses["hand"], rtol=2e-5)


def test_complete_plan_shards_moe_experts_over_ep():
    """The r5 MoE completion rule: stacked (E, ...) expert weights get
    P('ep') without name conventions; Qwen2-MoE trains under the
    completed plan."""
    import paddle_tpu
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import init_mesh
    from paddle_tpu.distributed.auto_parallel.engine import complete_plan
    from paddle_tpu.models.qwen2_moe import (Qwen2MoeForCausalLM,
                                             tiny_qwen2_moe_config)
    from paddle_tpu.parallel import Trainer, TrainStepConfig

    paddle_tpu.seed(0)
    cfg = tiny_qwen2_moe_config()
    model = Qwen2MoeForCausalLM(cfg)
    mesh = init_mesh({"dp": 2, "ep": 2, "mp": 2})
    plan = complete_plan(model, mesh.jax_mesh.axis_names)
    name = next(n for n in plan.table if "experts_gate_weight" in n)
    assert "ep" in str(plan.table[name])
    o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    tr = Trainer(model, o, mesh=mesh, plan=plan,
                 config=TrainStepConfig(compute_dtype=None))
    ids = np.random.RandomState(1).randint(
        0, cfg.vocab_size, (4, 32)).astype(np.int32)
    l1 = float(tr.step({"input_ids": ids, "labels": ids}))
    l2 = float(tr.step({"input_ids": ids, "labels": ids}))
    assert np.isfinite(l1) and l2 < l1
    spec = tr.params[name].sharding.spec
    assert "ep" in str(spec), spec
