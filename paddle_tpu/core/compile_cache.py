"""Where compiled programs are kept between processes — one rule.

`Trainer`, `PagedKVEngine`, `inference.Predictor`, benchmarks/run.py
and chip_smoke.py all call `ensure()` before they build a program:

- where `JAX_COMPILATION_CACHE_DIR` is set, jax reads it itself and this
  module sets NO cache directory in code — whoever runs the program
  places the cache;
- where it is not, the cache goes to ONE fixed path inside the checkout
  (`<repo>/.jax_cache`, git-ignored). Never `$HOME`, a temp name, a pid
  or a time: the directory is part of jax's cache key handling, and a
  directory that moves never hits.

jax's own persistence thresholds stay as they are (programs that
compile in under a second are not written); they too are the runner's
to move, through jax's own environment variables.
"""
from __future__ import annotations

import os

import jax

__all__ = ["ENV_VAR", "IN_CHECKOUT_DIR", "ensure"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
IN_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure() -> str:
    """Make jax's persistent compilation cache active; returns the
    directory in use. Idempotent and cheap: safe at every constructor."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    if jax.config.jax_compilation_cache_dir != IN_CHECKOUT_DIR:
        os.makedirs(IN_CHECKOUT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", IN_CHECKOUT_DIR)
    return IN_CHECKOUT_DIR
