"""The environment names `paddle_tpu/` reads, all of them, each with the
reason it is a deployment or safety setting and not a choice of kernel.

A name read from the environment is a second configuration of whatever
reads it, one that no cell of the benchmark measures: PR 31 took fourteen
of them out of the kernels, the train step, generation and the ring
(block sizes, byte budgets and three rejected experiments of the flash
kernel, the CE vocab block, the optimizer barrier's name list, the
generation step cache's cap, the ring's flash switch). A new one has to be
written down here, with its reason, before this test passes again.
"""
import ast
import os

_PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "paddle_tpu")

ALLOWED = {
    # -- where things are kept, and whether side systems are on -----------
    "PADDLE_TPU_AUTOTUNE": "0 pins block sizes to the seeded table: what "
                           "makes a benchmark run repeatable",
    "PADDLE_TPU_OBS": "turns the metrics plane on for a process",
    "PADDLE_TPU_PROFILE_DIR": "where the profiler writes: a path",
    "PADDLE_TPU_FLIGHT_DIR": "where crash bundles go: a path",
    "PADDLE_TPU_FLIGHT_KEEP": "how many crash bundles that path keeps",
    "PADDLE_EXTENSION_DIR": "where built C++ extensions are cached: a path",
    "PADDLE_TPU_DISABLE_NATIVE": "safety: run without the native library",
    "JAX_PLATFORMS": "set to cpu around the start of data workers (and "
                     "restored), so that no worker claims the chip",
    # -- fault injection and fault handling --------------------------------
    "PADDLE_TPU_CHAOS": "fault injection, off unless set",
    "PADDLE_TPU_CHAOS_SEED": "fault injection",
    "PADDLE_TPU_CHAOS_RATES": "fault injection",
    "PADDLE_TPU_CHAOS_DELAY_MS": "fault injection",
    "PADDLE_TPU_CHAOS_HANG_MS": "fault injection",
    "PADDLE_TPU_COMM_TIMEOUT_MS": "safety: the collective watchdog's limit",
    "PADDLE_TPU_RETRY_MAX_ATTEMPTS": "safety: the store's retry budget",
    "PADDLE_TPU_RETRY_DEADLINE_S": "safety: the store's retry budget",
    # -- the launcher's contract with its workers (addresses, ranks) -------
    "MASTER_ADDR": "rendezvous address",
    "MASTER_PORT": "rendezvous port",
    "PADDLE_MASTER": "rendezvous address, the reference's name",
    "PADDLE_JAX_COORDINATOR": "jax.distributed coordinator address",
    "PADDLE_JAX_COORDINATOR_FROM_STORE": "take that address from the store",
    "PADDLE_CURRENT_ENDPOINT": "this worker's endpoint",
    "PADDLE_TRAINER_ENDPOINTS": "every worker's endpoint",
    "PADDLE_TRAINER_ID": "this worker's rank",
    "PADDLE_TRAINERS_NUM": "the world size",
    "PADDLE_NNODES": "the launcher's node count",
    "PADDLE_ELASTIC_ATTEMPT": "which restart this is: keys the store",
    # -- distributed/launch/smoke.py: the launcher's own smoke worker, told
    # -- by its parent (tests/test_multiprocess_launch.py) what to run
    "SMOKE_MESH": "smoke worker input", "SMOKE_MICRO": "smoke worker input",
    "SMOKE_OUT": "smoke worker input", "SMOKE_OVERLAP": "smoke worker input",
    "SMOKE_STEPS": "smoke worker input",
    "SMOKE_STORE_PORT": "smoke worker input",
    "SMOKE_TRAINER": "smoke worker input",
}


def _is_environ(node):
    return (isinstance(node, ast.Attribute) and node.attr == "environ") \
        or (isinstance(node, ast.Name) and node.id == "environ")


def _env_key(node):
    """The expression naming the variable, where `node` reads the
    environment: `environ.get(k)`, `environ[k]`, `k in environ`,
    `getenv(k)`."""
    if isinstance(node, ast.Call) and node.args:
        fn = node.func
        if getattr(fn, "id", None) == "getenv" or (
                isinstance(fn, ast.Attribute) and (
                    fn.attr == "getenv"
                    or (fn.attr == "get" and _is_environ(fn.value)))):
            return node.args[0]
    if isinstance(node, ast.Subscript) and _is_environ(node.value):
        return node.slice
    if isinstance(node, ast.Compare) and len(node.ops) == 1 \
            and isinstance(node.ops[0], (ast.In, ast.NotIn)) \
            and _is_environ(node.comparators[0]):
        return node.left
    return None


def test_environment_names_the_package_reads():
    """A name held in a variable (core/flags.py's FLAGS_*,
    observability/requests.py's thresholds, compile_cache.ENV_VAR =
    JAX_COMPILATION_CACHE_DIR) is not a constant and is not looked at."""
    read = {}
    for dirpath, dirnames, filenames in os.walk(_PKG):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                key = _env_key(node)
                if isinstance(key, ast.Constant) \
                        and isinstance(key.value, str):
                    read.setdefault(key.value, []).append(
                        f"{os.path.relpath(path, _PKG)}:{node.lineno}")
    new = {k: v for k, v in read.items() if k not in ALLOWED}
    assert not new, f"environment names with no reason on record: {new}"
    gone = sorted(set(ALLOWED) - set(read))
    assert not gone, f"listed here and read nowhere: {gone}"
