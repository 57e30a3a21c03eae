"""Multi-process environment bootstrap.

TPU-native rebuild of the reference's parallel environment + launcher glue
(reference: python/paddle/distributed/parallel.py init_parallel_env,
ParallelEnv; rendezvous via TCPStore store/tcp_store.h:121 and
launch/controllers/master.py). JAX's coordination service
(`jax.distributed.initialize`) plays the TCPStore/master role over DCN; ICI
collectives need no bootstrap at all (they're compiled).
"""
from __future__ import annotations

import os

import jax

_initialized = [False]


def _env_int(*names, default=0):
    for n in names:
        v = os.environ.get(n)
        if v is not None:
            return int(v)
    return default


def get_rank(group=None):
    if group is not None:
        return 0 if not hasattr(group, "ranks") else group.ranks.index(
            get_rank())
    return _env_int("PADDLE_TRAINER_ID", "RANK",
                    default=jax.process_index() if _initialized[0] else 0)


def get_world_size(group=None):
    if group is not None and hasattr(group, "nranks"):
        return group.nranks
    return _env_int("PADDLE_TRAINERS_NUM", "WORLD_SIZE",
                    default=jax.process_count() if _initialized[0] else 1)


def init_parallel_env():
    """Initialise multi-process JAX (reference: parallel.py:init_parallel_env
    → ProcessGroup + TCPStore; here → jax.distributed coordination service).

    Single-process (incl. single-host multi-chip) needs no init — returns
    immediately, mirroring the reference's is_initialized short-circuit."""
    if _initialized[0]:
        return
    # PADDLE_JAX_COORDINATOR wins when set: under the elastic supervisor
    # PADDLE_MASTER is the supervisor's heartbeat/rendezvous store, and
    # the jax coordination service needs its own (per-attempt) address
    coord = (_coordinator_from_store()
             or os.environ.get("PADDLE_JAX_COORDINATOR")
             or os.environ.get("PADDLE_MASTER")
             or os.environ.get("MASTER_ADDR"))
    nprocs = _env_int("PADDLE_TRAINERS_NUM", "WORLD_SIZE", default=1)
    if nprocs > 1 and not _jax_distributed_active():
        port = os.environ.get("MASTER_PORT", "8476")
        addr = coord if coord and ":" in str(coord) else f"{coord}:{port}"
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=nprocs,
            process_id=_env_int("PADDLE_TRAINER_ID", "RANK", default=0))
    _initialized[0] = True


def _coordinator_from_store():
    """Rank-0-publishes-port handshake (PADDLE_JAX_COORDINATOR_FROM_
    STORE=1, set by ElasticSupervisor(jax_coordinator=True)): the
    supervisor picking a free port ahead of time is a TOCTOU race —
    another process can claim it before rank 0's coordination service
    binds, burning a restart for a non-worker fault. Instead rank 0
    allocates the port IN-PROCESS (microseconds before initialize binds
    it) and publishes the address under an attempt-scoped key in the
    rendezvous store; peers wait for it."""
    if os.environ.get("PADDLE_JAX_COORDINATOR_FROM_STORE") != "1":
        return None
    from paddle_tpu.distributed.store import TCPStore
    host, port = os.environ["PADDLE_MASTER"].rsplit(":", 1)
    attempt = os.environ.get("PADDLE_ELASTIC_ATTEMPT", "")
    key = (f"a{attempt}/" if attempt != "" else "") + "jax_coord"
    rank = _env_int("PADDLE_TRAINER_ID", "RANK", default=0)
    store = TCPStore(host, int(port))
    try:
        if rank == 0:
            import socket
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            addr = f"127.0.0.1:{s.getsockname()[1]}"
            s.close()
            store.set(key, addr.encode())
            return addr
        store.wait(key, timeout=300)
        return store.get(key).decode()
    finally:
        store.close()


def _jax_distributed_active():
    """True when jax.distributed.initialize already ran in this process
    (e.g. the launcher did it before handing control to the script) —
    a second initialize raises."""
    return bool(jax.distributed.is_initialized())


def is_initialized():
    return _initialized[0]


def parallel_device_count():
    return jax.device_count()


class ParallelEnv:
    """reference: python/paddle/distributed/parallel.py ParallelEnv."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def device_id(self):
        return 0

    @property
    def current_endpoint(self):
        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "127.0.0.1:0")

    @property
    def trainer_endpoints(self):
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        return eps.split(",") if eps else ["127.0.0.1:0"]
