"""The single import site for jax names that have moved between releases,
and the one platform probe.

`shard_map`, `axis_size` and the Pallas TPU compiler params have each
lived under more than one name. paddle_tpu supports exactly the
installed jax (0.9.x), so these are plain aliases — but every module
imports them from HERE, so the next rename is a one-file change, and
the analyzer's `jax-compat` pass (tools/analyze/passes/jax_compat.py)
fails CI when a bare spelling sneaks back in.

`on_tpu()` is the one answer to "is the default backend a TPU": the
Pallas auto-dispatch gates, the serving engine's kernel choice, the
autotune sweep gate and the MFU gauge all ask it. It does not catch:
a backend that fails to initialise raises here, where the cause is
visible, instead of reading as "not a TPU" and silently taking the
CPU path.
"""
from __future__ import annotations

import jax
from jax import shard_map
from jax.experimental.pallas import tpu as _pltpu

__all__ = ["shard_map", "axis_size", "tpu_compiler_params", "on_tpu"]

axis_size = jax.lax.axis_size
tpu_compiler_params = _pltpu.CompilerParams


def on_tpu() -> bool:
    """True when jax's default backend is a TPU."""
    return jax.devices()[0].platform == "tpu"
