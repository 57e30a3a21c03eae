"""Where compiled programs are cached (core/compile_cache.py): placed
from outside through JAX_COMPILATION_CACHE_DIR, else ONE fixed path
inside the checkout — never $HOME, a temp name, a pid or a time."""
import os

import jax
import pytest

from paddle_tpu.core import compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def updates(monkeypatch):
    """Record every jax.config.update instead of applying it, starting
    from a process that has no cache directory yet."""
    real, prev = jax.config.update, jax.config.jax_compilation_cache_dir
    real("jax_compilation_cache_dir", None)
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    yield seen
    real("jax_compilation_cache_dir", prev)


def _build_everything(tmp_path):
    """Every constructor that calls ensure(): Trainer, PagedKVEngine,
    Predictor."""
    import numpy as np
    import paddle_tpu
    import paddle_tpu.optimizer as opt
    from paddle_tpu.inference import (Config, PagedKVEngine,
                                      create_predictor)
    from paddle_tpu.models.llama import LlamaForCausalLM, tiny_llama_config
    from paddle_tpu.parallel import Trainer
    model = LlamaForCausalLM(tiny_llama_config(num_hidden_layers=1))
    Trainer(model, opt.AdamW(parameters=model.parameters()))
    PagedKVEngine(model, max_slots=2, num_pages=8)
    net = paddle_tpu.nn.Linear(4, 2)
    prefix = str(tmp_path / "m")
    paddle_tpu.jit.save(net, prefix, input_spec=[
        paddle_tpu.static.InputSpec([2, 4], "float32")])
    create_predictor(Config(prefix + ".pdmodel")).run(
        [np.zeros((2, 4), "float32")])


def test_placed_from_outside_sets_nothing_in_code(monkeypatch, updates,
                                                  tmp_path):
    placed = str(tmp_path / "placed")
    monkeypatch.setenv(compile_cache.ENV_VAR, placed)
    assert compile_cache.ensure() == placed
    _build_everything(tmp_path)
    assert not [u for u in updates if u[0] == "jax_compilation_cache_dir"]
    assert not os.path.exists(placed)       # nor created: jax's business


def test_unplaced_goes_to_the_fixed_dir_in_the_checkout(monkeypatch,
                                                        updates, tmp_path):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setenv("HOME", str(tmp_path / "home"))
    fixed = os.path.join(_ROOT, ".jax_cache")
    assert compile_cache.IN_CHECKOUT_DIR == fixed
    assert compile_cache.ensure() == fixed
    _build_everything(tmp_path)
    dirs = {v for k, v in updates if k == "jax_compilation_cache_dir"}
    assert dirs == {fixed}
    assert not os.path.exists(tmp_path / "home")    # nothing under $HOME
    with open(os.path.join(_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_old_knobs_are_gone():
    import inspect

    import paddle_tpu.inference as inf
    src = inspect.getsource(inf)
    assert "PADDLE_TPU_EXEC_CACHE" not in src
    assert not hasattr(inf.Config, "enable_executable_cache")
