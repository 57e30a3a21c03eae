"""Hang/failure detection wiring (reference:
paddle/phi/core/distributed/comm_task_manager.cc per-collective watch +
abort, fleet/elastic/manager.py:598 etcd membership watch): the watchdog
observes store barriers and eager collectives, and a dead rank is
detected by the store heartbeat so the SURVIVOR aborts a barrier with an
actionable diagnostic instead of hanging."""
import multiprocessing as mp
import time

import numpy as np
import pytest

from paddle_tpu.distributed import watchdog
from paddle_tpu.distributed.elastic import (ElasticManager, StoreHeartbeat,
                                            safe_barrier)
from paddle_tpu.distributed.store import TCPStore


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_watchdog_expires_and_completes():
    watchdog.enable(poll_ms=50)
    with watchdog.watch("quick-op", timeout_ms=10_000):
        pass                                     # completes in time
    before = watchdog.expired_count()
    with watchdog.watch("slow-op rank=0", timeout_ms=50):
        time.sleep(0.4)                          # blows the deadline
    assert watchdog.expired_count() == before + 1
    assert "slow-op" in watchdog.last_expired()


def test_collective_registers_with_watchdog():
    import paddle_tpu
    import paddle_tpu.distributed as dist

    watchdog.enable(poll_ms=50)
    before = watchdog.expired_count()
    t = paddle_tpu.to_tensor(np.arange(8, dtype="float32"))
    dist.all_reduce(t)                           # 8-device CPU mesh
    # completes well inside the default timeout: no new expirations
    assert watchdog.expired_count() == before


def _dead_rank(port, ready):
    """Fake rank 1: heartbeats once, then DIES before the barrier."""
    store = TCPStore("127.0.0.1", port, is_master=False, world_size=2)
    hb = StoreHeartbeat(store, rank=1, world_size=2, interval=0.2)
    hb.beat()
    ready.set()
    # exits without ever calling barrier => rank is dead


def test_dead_rank_mid_barrier_aborts_survivor():
    """VERDICT item 7 criterion: kill a fake rank mid-barrier; the
    survivor aborts with a diagnostic naming the dead rank, within the
    timeout."""
    port = _free_port()
    store = TCPStore("127.0.0.1", port, is_master=True, world_size=2)
    try:
        ctx = mp.get_context("fork")
        ready = ctx.Event()
        p = ctx.Process(target=_dead_rank, args=(port, ready), daemon=True)
        p.start()
        assert ready.wait(timeout=10)
        p.join(timeout=10)                       # rank 1 is now dead

        hb = StoreHeartbeat(store, rank=0, world_size=2,
                            interval=0.2, grace=0.8)
        hb.start()
        time.sleep(1.0)                          # let rank 1's beat expire
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError,
                           match=r"rank\(s\) \[1\] stopped heartbeating"):
            safe_barrier(store, "trainsync", rank=0, world_size=2,
                         timeout=2.0, heartbeat=hb)
        assert time.perf_counter() - t0 < 10.0   # aborted, not hung
        hb.stop()
    finally:
        store.close()


def test_elastic_manager_membership():
    port = _free_port()
    store = TCPStore("127.0.0.1", port, is_master=True, world_size=2)
    try:
        em = ElasticManager()
        em.attach_store(store, rank=0, world_size=2,
                        interval=0.2, grace=0.8)
        # rank 1 never joined: immediately stale
        assert em.dead_ranks() == [1]
        # once rank 1 beats, membership is clean
        StoreHeartbeat(store, rank=1, world_size=2).beat()
        assert em.dead_ranks() == []
        em.close()
    finally:
        store.close()


def test_store_barrier_timeout_diagnostic():
    port = _free_port()
    store = TCPStore("127.0.0.1", port, is_master=True, world_size=2)
    try:
        with pytest.raises(RuntimeError, match="1/2 ranks arrived"):
            store.barrier("lonely", rank=0, world_size=2, timeout=1.0)
    finally:
        store.close()


_ELASTIC_WORKER = r'''
import json
import os
import sys

import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import paddle_tpu as paddle
from paddle_tpu import nn
import paddle_tpu.tensor as T
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.distributed.elastic import ElasticManager, StoreHeartbeat

rank = int(os.environ["PADDLE_TRAINER_ID"])
world = int(os.environ["PADDLE_TRAINERS_NUM"])
host, port = os.environ["PADDLE_MASTER"].rsplit(":", 1)
attempt = int(os.environ["PADDLE_ELASTIC_ATTEMPT"])
ckdir, kill_at, total = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])

store = TCPStore(host, int(port), world_size=world, prefix=f"a{attempt}/")
hb = StoreHeartbeat(store, rank, world, interval=0.3)
hb.start()

paddle.seed(0)
net = nn.Linear(8, 1)
opt_ = paddle.optimizer.SGD(learning_rate=0.05,
                            parameters=net.parameters())
rng = np.random.RandomState(0)
X = rng.randn(64, 8).astype("float32")
Y = X @ rng.randn(8, 1).astype("float32")


def save_fn(step):
    if rank == 0:
        paddle.save(net.state_dict(), os.path.join(ckdir, "model.pd"))


mgr = ElasticManager(save_fn=save_fn, checkpoint_dir=ckdir)
start = mgr.last_step() + 1
if start > 0:
    net.set_state_dict(paddle.load(os.path.join(ckdir, "model.pd")))

for step in range(start, total):
    store.barrier(f"step{step}", rank, world, timeout=60)
    loss = T.mean((net(paddle.to_tensor(X)) - paddle.to_tensor(Y)) ** 2)
    loss.backward()
    opt_.step()
    opt_.clear_grad()
    if rank == 0:
        with open(os.path.join(ckdir, "losses.jsonl"), "a") as f:
            f.write(json.dumps({"step": step, "loss": float(loss)}) + "\n")
    if rank == 1 and attempt == 0 and step == kill_at:
        os._exit(17)                       # simulated preemption
    mgr.checkpoint(step)
hb.stop()
os._exit(0)       # skip interpreter teardown: native store/jax threads
                  # abort on exit in this environment (harmless, but the
                  # supervisor must see rc 0)
'''


def test_supervisor_relaunches_dead_rank_and_completes(tmp_path):
    """VERDICT r2 item 8 criterion: the supervisor detects a rank dying
    mid-training, relaunches the whole job with rewritten env, and the
    job completes from the last checkpoint with EXACTLY the loss curve
    an uninterrupted run produces (SGD + fixed seed = deterministic
    replay)."""
    import json
    import os
    import subprocess
    import sys

    from paddle_tpu.distributed.elastic import ElasticSupervisor

    worker = tmp_path / "worker.py"
    worker.write_text(_ELASTIC_WORKER)
    total, kill_at = 8, 4

    # uninterrupted reference run (single rank, fresh dir)
    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    import paddle_tpu
    repo = os.path.dirname(os.path.dirname(
        os.path.abspath(paddle_tpu.__file__)))
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PADDLE_TRAINER_ID": "0",
                "PADDLE_TRAINERS_NUM": "1",
                "PADDLE_ELASTIC_ATTEMPT": "0", "PYTHONPATH": repo})
    from paddle_tpu.distributed.store import TCPStore
    ref_store = TCPStore(is_master=True, world_size=1)
    env["PADDLE_MASTER"] = f"{ref_store.host}:{ref_store.port}"
    subprocess.run([sys.executable, str(worker), str(ref_dir), "-1",
                    str(total)], env=env, check=True, timeout=300)
    ref = {}
    with open(ref_dir / "losses.jsonl") as f:
        for line in f:
            d = json.loads(line)
            ref[d["step"]] = d["loss"]

    # supervised 2-rank run; rank 1 dies at step 4 on attempt 0
    job_dir = tmp_path / "job"
    job_dir.mkdir()
    sup_env = dict(os.environ)
    sup_env["JAX_PLATFORMS"] = "cpu"
    sup_env["PYTHONPATH"] = repo
    sup = ElasticSupervisor(
        [sys.executable, str(worker), str(job_dir), str(kill_at),
         str(total)],
        world_size=2, env=sup_env, max_restarts=2, poll_interval=0.3)
    try:
        restarts = sup.run()
    finally:
        sup.close()
    assert restarts == 1, restarts

    got = {}
    with open(job_dir / "losses.jsonl") as f:
        for line in f:
            d = json.loads(line)
            got[d["step"]] = d["loss"]     # resumed steps: last wins
    assert sorted(got) == list(range(total))
    for s in range(total):
        assert abs(got[s] - ref[s]) < 1e-6, (s, got[s], ref[s])
    # the curve itself is a real training curve
    assert got[total - 1] < got[0] * 0.9


def test_supervisor_exhausts_restarts(tmp_path):
    """A worker that always fails must exhaust max_restarts and raise
    with the failing rank named."""
    import subprocess
    import sys

    from paddle_tpu.distributed.elastic import ElasticSupervisor

    bad = tmp_path / "bad.py"
    bad.write_text("import sys; sys.exit(3)\n")
    sup = ElasticSupervisor([sys.executable, str(bad)], world_size=2,
                            max_restarts=1, poll_interval=0.2)
    try:
        with pytest.raises(RuntimeError, match="max_restarts"):
            sup.run()
        assert sup.restarts == 2
    finally:
        sup.close()
