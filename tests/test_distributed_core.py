"""Distributed core tests on the virtual 8-device CPU mesh.

Replaces the reference's multi-process collective tests
(test/collective/collective_allreduce_api.py etc. under launch) with
single-process XLA device virtualization (SURVEY.md §4).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist


def test_mesh_and_placements():
    mesh = dist.ProcessMesh([[0, 1, 2, 3], [4, 5, 6, 7]],
                            dim_names=["dp", "mp"])
    assert mesh.shape == [2, 4]
    assert mesh.get_dim_size("mp") == 4
    spec = dist.placements_to_spec(
        [dist.Shard(0), dist.Shard(1)], mesh, ndim=2)
    assert tuple(spec) == ("dp", "mp")
    spec = dist.placements_to_spec(
        [dist.Replicate(), dist.Shard(0)], mesh, ndim=2)
    assert tuple(spec) == ("mp",)
    # round trip
    back = dist.spec_to_placements(spec, mesh.jax_mesh)
    assert back[0] == dist.Replicate() and back[1] == dist.Shard(0)


def test_shard_tensor_and_reshard():
    mesh = dist.ProcessMesh([[0, 1, 2, 3], [4, 5, 6, 7]],
                            dim_names=["dp", "mp"])
    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    t = dist.shard_tensor(x, mesh, [dist.Shard(0), dist.Replicate()])
    assert t.is_dist()
    pl = t.placements
    assert pl[0] == dist.Shard(0) and pl[1] == dist.Replicate()
    np.testing.assert_array_equal(t.numpy(), x)
    # s -> s' (all-to-all-ish), s -> r (all-gather)
    t2 = dist.reshard(t, mesh, [dist.Replicate(), dist.Shard(1)])
    np.testing.assert_array_equal(t2.numpy(), x)
    t3 = dist.reshard(t2, mesh, [dist.Replicate(), dist.Replicate()])
    assert t3.placements[0] == dist.Replicate()
    np.testing.assert_array_equal(t3.numpy(), x)


def test_sharded_eager_math_propagates():
    # eager ops on DistTensors run through GSPMD with propagation —
    # the reference needed per-op SPMD rules for this (spmd_rules/*.cc)
    mesh = dist.ProcessMesh(list(range(8)), dim_names=["mp"])
    w = dist.shard_tensor(np.random.randn(16, 32).astype(np.float32),
                          mesh, [dist.Shard(1)])
    x = paddle.to_tensor(np.random.randn(4, 16).astype(np.float32))
    y = paddle.matmul(x, w)
    np.testing.assert_allclose(y.numpy(), x.numpy() @ w.numpy(), rtol=2e-5)


def test_dist_matmul_grad():
    mesh = dist.ProcessMesh(list(range(8)), dim_names=["mp"])
    wn = np.random.randn(16, 32).astype(np.float32)
    w = dist.shard_tensor(wn, mesh, [dist.Shard(1)], stop_gradient=False)
    x = paddle.to_tensor(np.random.randn(4, 16).astype(np.float32))
    y = paddle.matmul(x, w)
    y.sum().backward()
    np.testing.assert_allclose(
        w.grad.numpy(), x.numpy().sum(0)[:, None] @ np.ones((1, 32)),
        rtol=2e-5)


def test_all_reduce():
    g = dist.new_group(list(range(8)))
    x = paddle.to_tensor(np.ones((4,), np.float32))
    out = dist.all_reduce(x, group=g)
    np.testing.assert_array_equal(out.numpy()[0], 8 * np.ones(4))
    # mutated in place like the reference API
    np.testing.assert_array_equal(x.numpy(), 8 * np.ones(4))


def test_all_reduce_max():
    g = dist.new_group(list(range(4)))
    x = paddle.to_tensor(np.array([3.0, -1.0], np.float32))
    out = dist.all_reduce(x, op=dist.ReduceOp.MAX, group=g)
    np.testing.assert_array_equal(out.numpy()[0], [3.0, -1.0])


def test_all_gather():
    g = dist.new_group(list(range(8)))
    x = paddle.to_tensor(np.arange(3, dtype=np.float32))
    outs = dist.all_gather(x, group=g)
    assert len(outs) == 8
    np.testing.assert_array_equal(outs[0].numpy(), x.numpy())


def test_broadcast():
    g = dist.new_group(list(range(8)))
    x = paddle.to_tensor(np.full((2,), 7.0, np.float32))
    out = dist.broadcast(x, src=0, group=g)
    np.testing.assert_array_equal(out.numpy(), 7.0 * np.ones(2))


def test_reduce_scatter():
    g = dist.new_group(list(range(4)))
    # every rank holds the same (4*2,) local; sum then scatter 2-chunks
    x = paddle.to_tensor(np.arange(8, dtype=np.float32))
    out = dist.reduce_scatter(x, group=g)
    # rank r chunk = 4 * x[2r:2r+2]; rank-major result shape (4, 2)
    got = out.numpy()
    np.testing.assert_array_equal(got[0], 4 * np.arange(2))
    np.testing.assert_array_equal(got[3], 4 * np.arange(6, 8))


def test_barrier_and_group():
    g = dist.new_group(list(range(8)))
    dist.barrier(g)
    assert g.world_size == 8
    assert dist.get_world_size() >= 1
    assert dist.get_rank() == 0


def test_shard_layer_and_optimizer():
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt

    mesh = dist.ProcessMesh(list(range(8)), dim_names=["mp"])

    def col_shard(name, sub, m):
        params = getattr(sub, "_parameters", {})
        for pname, p in list(params.items()):
            if p is None or p.ndim != 2:
                continue
            sharded = dist.shard_tensor(p, m, [dist.Shard(1)],
                                        stop_gradient=False)
            from paddle_tpu.core.tensor import Parameter
            np_ = Parameter(sharded._value, trainable=True)
            np_.name = p.name
            params[pname] = np_

    layer = nn.Linear(16, 32)
    dist.shard_layer(layer, mesh, col_shard)
    assert layer.weight.is_dist()

    optimizer = dist.shard_optimizer(
        opt.AdamW(learning_rate=1e-3, parameters=layer.parameters()))
    x = paddle.to_tensor(np.random.randn(4, 16).astype(np.float32))
    loss = layer(x).sum()
    loss.backward()
    optimizer.step()
    # moment states inherited the parameter sharding
    from jax.sharding import NamedSharding
    checked = 0
    for st in optimizer._states.values():
        for k, v in st.items():
            if hasattr(v, "ndim") and v.ndim == 2:
                assert isinstance(v.sharding, NamedSharding)
                checked += 1
    assert checked > 0


def test_dtensor_from_fn_and_unshard():
    mesh = dist.ProcessMesh(list(range(8)), dim_names=["x"])
    t = dist.dtensor_from_fn(lambda: paddle.ones([8, 4]), mesh,
                             [dist.Shard(0)])
    assert t.is_dist()
    full = dist.unshard_dtensor(t)
    np.testing.assert_array_equal(full.numpy(), np.ones((8, 4)))


def test_northstar_config_compiles_without_involuntary_remat():
    """The dp x fsdp x mp ring-CP north-star step must compile with ZERO
    '[SPMD] Involuntary full rematerialization' warnings: the embedding
    cotangent's fsdp move from batch tile to hidden tile is handled by
    the two-step reshard in nn/functional/common.py:_vocab_take_op
    (VERDICT r2 item 2). Runs the compile in a subprocess because the
    warning is emitted from XLA's C++ stderr."""
    import subprocess
    import sys

    code = """
import numpy as np
import paddle_tpu
import paddle_tpu.optimizer as opt
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.distributed.mesh import init_mesh
from paddle_tpu.parallel import Trainer, TrainStepConfig, llama_sharding_plan

mesh = init_mesh({"dp": 2, "fsdp": 2, "mp": 2, "sp": 1})
cfg = tiny_llama_config(num_hidden_layers=2, recompute=True)
model = LlamaForCausalLM(cfg)
optimizer = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
trainer = Trainer(model, optimizer, mesh=mesh,
                  plan=llama_sharding_plan(mesh.jax_mesh.axis_names),
                  config=TrainStepConfig(compute_dtype="bfloat16",
                                         grad_accum_steps=2,
                                         context_parallel="ring"))
ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (16, 32))
loss = trainer.step({"input_ids": ids.astype(np.int32),
                     "labels": ids.astype(np.int32)})
assert np.isfinite(float(loss))
print("COMPILED_OK")
"""
    import os
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-3000:]
    assert "COMPILED_OK" in out
    assert "Involuntary full rematerialization" not in out, out[-3000:]
