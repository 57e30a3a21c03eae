"""Runtime kernel autotune cache (reference: phi/kernels/autotune/
cache.h:97 AlgorithmsCache + switch_autotune gating): sweep-once
measured block selection, seeded defaults, env override precedence —
and NO disk persistence (a winner one commit measured must not be read
back by the next from a file git never saw)."""
import os

import numpy as np
import pytest

from paddle_tpu.core import autotune


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    # HOME points at an empty dir for the test: anything the autotuner
    # wrote under it would show up in test_nothing_persists
    monkeypatch.setenv("HOME", str(tmp_path))
    autotune.clear_memory()
    yield str(tmp_path)
    autotune.clear_memory()


def test_nothing_persists(tmp_cache, monkeypatch):
    monkeypatch.chdir(tmp_cache)
    autotune.put("k", "s128_f32", (64, 128))
    assert autotune.get("k", "s128_f32") == (64, 128)
    # a fresh process (simulated by dropping memory) starts from the
    # seeds again, and no file was written anywhere
    autotune.clear_memory()
    assert autotune.get("k", "s128_f32") is None
    assert os.listdir(tmp_cache) == []
    assert not hasattr(autotune, "cache_path")


def test_choose_sweeps_once_then_caches(tmp_cache, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
    calls = []

    def measure(cfg):
        calls.append(cfg)
        return {(8,): 3.0, (16,): 1.0, (32,): 2.0}[cfg]

    got = autotune.choose("k", "shape_a", [(8,), (16,), (32,)], measure,
                          default=(8,))
    assert got == (16,) and len(calls) == 3
    # second call: cache hit, no measuring
    got2 = autotune.choose("k", "shape_a", [(8,), (16,), (32,)], measure,
                           default=(8,))
    assert got2 == (16,) and len(calls) == 3


def test_choose_disabled_returns_default(tmp_cache, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "0")
    got = autotune.choose("k", "shape_b", [(1,), (2,)],
                          lambda c: 0.0, default=(7,))
    assert got == (7,)
    assert autotune.get("k", "shape_b") is None


def test_choose_skips_failing_candidates(tmp_cache, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")

    def measure(cfg):
        if cfg == (1,):
            raise RuntimeError("mosaic rejects this block")
        return 1.0

    assert autotune.choose("k", "shape_c", [(1,), (2,)], measure,
                           default=(9,)) == (2,)
    # all candidates failing -> default, and the default is CACHED so
    # the failing sweep is not repeated every trace
    assert autotune.choose("k", "shape_d", [(1,)],
                           lambda c: (_ for _ in ()).throw(RuntimeError()),
                           default=(9,)) == (9,)
    assert autotune.get("k", "shape_d") == (9,)


def test_seeded_bench_shapes_present(tmp_cache):
    # the round-2 sweep results ship in the cache: the bench family
    # never pays a first-run sweep
    assert autotune.get("flash_fwd",
                        "q10240_s2048_d64_bf16_c1_g") == (512, 512)
    assert autotune.get("flash_bwd",
                        "q2048_s2048_d64_bf16_c1") == (512, 512)
    assert autotune.get("flash_stream_bk", "s16384_bf16") == 2048


def test_flash_block_selection_uses_cache(tmp_cache):
    """_tuned_blocks consults the cache; off-TPU uncached shapes fall
    back to the defaults without measuring."""
    import jax.numpy as jnp
    from paddle_tpu.kernels import flash_attention as fa

    # cached shape
    autotune.put("flash_fwd", "q4096_s4096_d64_bf16_c1", (256, 512))
    assert fa._tuned_blocks("flash_fwd", 2, 4, 4096, 4096, 64,
                            jnp.bfloat16, True) == (256, 512)
    # uncached on CPU -> defaults, no sweep
    assert fa._tuned_blocks("flash_fwd", 2, 4, 1536, 1536, 64,
                            jnp.bfloat16, True) == (fa._BLOCK_Q,
                                                    fa._BLOCK_K)


def test_choose_all_fail_caches_default(tmp_cache, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
    calls = []

    def measure(cfg):
        calls.append(cfg)
        raise RuntimeError("vmem")

    assert autotune.choose("k", "shape_f", [(1,), (2,)], measure,
                           default=(9,)) == (9,)
    assert len(calls) == 2
    # the default is cached: no re-sweep on the next call
    assert autotune.choose("k", "shape_f", [(1,), (2,)], measure,
                           default=(9,)) == (9,)
    assert len(calls) == 2


def test_stream_block_k_tuned_target(tmp_cache):
    from paddle_tpu.kernels import flash_attention as fa
    import jax.numpy as jnp
    # seeded target 2048 at 16k bf16, still VMEM-capped
    assert fa._stream_block_k(16384, 64, 2, jnp.bfloat16) == 2048
    # un-seeded shape falls back to the default target
    autotune.put("flash_stream_bk", "s65536_bf16", 1024)
    assert fa._stream_block_k(65536, 64, 2, jnp.bfloat16) == 1024
