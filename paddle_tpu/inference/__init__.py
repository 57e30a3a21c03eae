"""`paddle.inference` — deployment predictor (reference:
paddle/fluid/inference/ AnalysisPredictor, api/analysis_predictor.h:100;
Python surface python/paddle/inference/).

TPU-native: the reference's analysis passes + memory-reuse + TensorRT
subgraphing are what XLA's compiler does to a StableHLO module; deployment
is therefore (1) `jit.save` -> serialized StableHLO + params, (2) this
Predictor, which deserializes and runs it through XLA with zero-copy
device arrays. The handle-based API (get_input_names/get_input_handle/
run/get_output_handle) mirrors the reference so serving code ports 1:1.
"""
from __future__ import annotations

import numpy as np

from paddle_tpu.core import compile_cache
from paddle_tpu.core.tensor import Tensor

__all__ = ["Config", "Predictor", "create_predictor", "PrecisionType",
           "PlaceType", "PagedKVEngine", "PredictorServer", "serve",
           "overload", "ReplicaRouter", "tenancy", "TenantPolicy",
           "TenantTable"]


def __getattr__(name):
    # lazy: the paged serving engine pulls in models/generation helpers
    if name == "PagedKVEngine":
        from paddle_tpu.inference.paged import PagedKVEngine
        return PagedKVEngine
    if name in ("PredictorServer", "serve"):
        from paddle_tpu.inference import serving
        return getattr(serving, name)
    if name == "ReplicaRouter":
        from paddle_tpu.inference.router import ReplicaRouter
        return ReplicaRouter
    if name in ("TenantPolicy", "TenantTable"):
        from paddle_tpu.inference import tenancy as _tenancy
        return getattr(_tenancy, name)
    if name in ("overload", "tenancy"):
        # importlib, not `from ... import`: a from-import of a not-yet-
        # loaded submodule re-enters this __getattr__ and recurses
        import importlib
        return importlib.import_module(f"paddle_tpu.inference.{name}")
    raise AttributeError(name)


class PrecisionType:
    Float32 = "float32"
    Bfloat16 = "bfloat16"
    Half = "float16"
    Int8 = "int8"


class PlaceType:
    CPU = "cpu"
    TPU = "tpu"
    GPU = "gpu"  # accepted, mapped to whatever jax default backend is


class Config:
    """Predictor configuration (reference:
    paddle/fluid/inference/api/paddle_analysis_config.h). Model path +
    precision; the pass/optimization knobs of the reference are XLA's job
    and accepted as no-ops for compatibility."""

    def __init__(self, prog_file=None, params_file=None):
        # reference uses (model_dir) or (prog_file, params_file);
        # ours: the jit.save path prefix
        self._path_prefix = None
        if prog_file is not None:
            p = str(prog_file)
            for suf in (".pdmodel", ".json"):
                if p.endswith(suf):
                    p = p[: -len(suf)]
            self._path_prefix = p
        self._precision = PrecisionType.Float32
        self._device = None
        self._memory_optim = True

    def _set_path(self, prog_file):
        p = str(prog_file)
        for suf in (".pdmodel", ".json"):
            if p.endswith(suf):
                p = p[: -len(suf)]
        self._path_prefix = p

    def set_prog_file(self, path):
        self._set_path(path)

    def set_model(self, prog_file, params_file=None):
        self._set_path(prog_file)

    def model_dir(self):
        return self._path_prefix

    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0,
                       precision=PrecisionType.Float32):
        self._device = "gpu"
        self._precision = precision

    def enable_xpu(self, *a, **k):
        self._device = "xpu"

    def disable_gpu(self):
        self._device = "cpu"

    def switch_ir_optim(self, flag=True):
        return None  # XLA always optimizes

    def enable_memory_optim(self, flag=True):
        """Input-buffer donation (reference: the memory-reuse analysis
        pass, inference/analysis/passes/memory_optimize_pass.cc): the
        staged input device buffers are donated to XLA so outputs can
        alias them. Default ON — predictor inputs are freshly staged
        per run, so donation is free."""
        self._memory_optim = bool(flag)

    def set_cpu_math_library_num_threads(self, n):
        return None

    def summary(self):
        return f"paddle_tpu.inference.Config(path={self._path_prefix})"


class _IOHandle:
    """Zero-copy-ish tensor handle (reference: ZeroCopyTensor,
    paddle/fluid/inference/api/details/zero_copy_tensor.cc)."""

    def __init__(self, name):
        self.name = name
        self._arr = None

    def copy_from_cpu(self, arr):
        self._arr = np.ascontiguousarray(arr)

    def reshape(self, shape):
        pass  # shape comes from the array in copy_from_cpu

    def copy_to_cpu(self):
        return np.asarray(self._arr)

    def shape(self):
        return list(np.shape(self._arr))


class Predictor:
    """AnalysisPredictor equivalent: deserialize StableHLO, run via XLA
    (reference: analysis_predictor.h:100 Run/GetInputNames/
    GetInputTensor/GetOutputNames/GetOutputTensor)."""

    def __init__(self, config):
        import jax
        import jax.numpy as jnp
        if isinstance(config, str):
            cfg = Config(config)
        else:
            cfg = config
        if cfg._path_prefix is None:
            raise ValueError("inference.Config has no model path")
        # a RESTARTED serving process skips re-jit: compiled executables
        # persist where core/compile_cache.py says
        compile_cache.ensure()
        from paddle_tpu.jit import load as jit_load
        self._layer = jit_load(cfg._path_prefix)
        # in_tree is ((state, *inputs), {}) — count the positional inputs
        args_tree = self._layer._exported.in_tree.children()[0]
        n_in = len(args_tree.children()) - 1
        self._in_names = [f"x{i}" for i in range(max(n_in, 0))]
        self._inputs = {n: _IOHandle(n) for n in self._in_names}
        # output arity is part of the exported signature: name the
        # handles up front so serving metadata works before first run
        try:
            n_out = self._layer._exported.out_tree.num_leaves
        except Exception:
            n_out = 0
        self._out_names = [f"out{i}" for i in range(n_out)]
        self._outputs = {n: _IOHandle(n) for n in self._out_names}
        # weights live on device ONCE (the loaded layer keeps numpy and
        # would re-stage the whole state dict every call)
        self._state = jax.tree.map(jnp.asarray, self._layer._state)
        exported = self._layer._exported
        donate = (tuple(range(1, n_in + 1))
                  if cfg._memory_optim and n_in > 0 else ())
        self._call = jax.jit(lambda state, *xs: exported.call(state, *xs),
                             donate_argnums=donate)

    def get_input_names(self):
        return list(self._in_names)

    def input_shapes(self):
        """Static shapes of the positional inputs (the exported program
        is shape-monomorphic; servers use this to pad dynamic batches to
        the exported leading dim)."""
        exported = self._layer._exported
        import jax
        n_state = len(jax.tree.leaves(self._layer._state))
        avals = list(exported.in_avals)[n_state:]
        return [tuple(a.shape) for a in avals]

    def get_input_handle(self, name):
        return self._inputs[name]

    def run(self, inputs=None):
        """Either pass a list of numpy arrays (new API) or pre-fill input
        handles via copy_from_cpu (handle API)."""
        import jax
        import jax.numpy as jnp
        if inputs is not None:
            arrs = [np.asarray(a) for a in inputs]
        else:
            arrs = [self._inputs[n].copy_to_cpu() for n in self._in_names]
        # stage fresh device buffers (donate-able: nothing else holds them)
        out = self._call(self._state, *[jnp.asarray(a) for a in arrs])
        outs = jax.tree.leaves(out)
        outs_np = [np.asarray(o) for o in outs]
        self._out_names = [f"out{i}" for i in range(len(outs_np))]
        self._outputs = {}
        for n, a in zip(self._out_names, outs_np):
            h = _IOHandle(n)
            h.copy_from_cpu(a)
            self._outputs[n] = h
        if inputs is not None:
            return outs_np
        return True

    def get_output_names(self):
        return list(self._out_names)

    def get_output_handle(self, name):
        return self._outputs[name]


def create_predictor(config):
    return Predictor(config)
