"""Inference depth (reference: analysis_predictor.h:100 + capi_exp/
pd_inference_api.h): input-buffer donation, the persisted executable
cache (restart without re-jit), and the ctypes-consumable C API."""
import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn


def _save_tiny_model(tmp_path):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    net.eval()
    path = str(tmp_path / "m")
    paddle.jit.save(net, path,
                    input_spec=[paddle.jit.InputSpec((4, 8), "float32")])
    x = np.random.RandomState(0).randn(4, 8).astype("float32")
    ref = net(paddle.to_tensor(x)).numpy()
    return path, x, ref


def test_predictor_donation_and_device_state(tmp_path):
    """enable_memory_optim donates staged inputs; weights are staged to
    device once, not per call."""
    from paddle_tpu.inference import Config, create_predictor
    path, x, ref = _save_tiny_model(tmp_path)
    cfg = Config(path + ".pdmodel", path + ".pdiparams")
    cfg.enable_memory_optim(True)
    pred = create_predictor(cfg)
    import jax
    assert all(isinstance(v, jax.Array) for v in pred._state.values())
    for _ in range(3):                 # donation safe across repeat runs
        outs = pred.run([x])
    np.testing.assert_allclose(outs[0], ref, rtol=1e-5, atol=1e-5)

    cfg2 = Config(path + ".pdmodel", path + ".pdiparams")
    cfg2.enable_memory_optim(False)
    np.testing.assert_allclose(create_predictor(cfg2).run([x])[0], ref,
                               rtol=1e-5, atol=1e-5)


def test_executable_cache_restart_without_recompile(tmp_path):
    """VERDICT r2 item 7 criterion: a RESTARTED serving process hits the
    persisted executable cache instead of re-jitting. Two fresh
    subprocesses: the first populates the cache dir, the second must
    log a cache hit (and the dir must be non-empty in between). The
    cache is placed from OUTSIDE, through jax's own environment
    variables (core/compile_cache.py sets nothing when
    JAX_COMPILATION_CACHE_DIR is set); the zeroed threshold lets the
    tiny model's sub-second compile persist."""
    path, x, ref = _save_tiny_model(tmp_path)
    cache = str(tmp_path / "xla_cache")
    code = f"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import logging
logging.basicConfig(level=logging.DEBUG)
logging.getLogger("jax._src.compilation_cache").setLevel(logging.DEBUG)
import numpy as np
from paddle_tpu.inference import Config, create_predictor
pred = create_predictor(Config({path!r} + ".pdmodel",
                               {path!r} + ".pdiparams"))
out = pred.run([np.zeros((4, 8), "float32")])
print("RAN_OK", out[0].shape)
"""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    r1 = subprocess.run([sys.executable, "-c", code], env=env,
                        capture_output=True, text=True, timeout=300)
    assert "RAN_OK" in r1.stdout, r1.stdout + r1.stderr[-2000:]
    entries = os.listdir(cache)
    assert entries, "first run wrote no executables to the cache"
    r2 = subprocess.run([sys.executable, "-c", code], env=env,
                        capture_output=True, text=True, timeout=300)
    assert "RAN_OK" in r2.stdout, r2.stdout + r2.stderr[-2000:]
    blob = r2.stdout + r2.stderr
    assert ("cache hit" in blob.lower()
            or "persistent compilation cache hit" in blob.lower()), \
        blob[-3000:]


def test_c_api_end_to_end(tmp_path):
    """Build the C shim, ctypes-load it, and drive create/run/output —
    results must match the python Predictor."""
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.inference import capi

    path, x, ref = _save_tiny_model(tmp_path)
    so = capi.build(str(tmp_path / "capi"))
    assert os.path.exists(capi.header_path(str(tmp_path / "capi")))

    lib = ctypes.CDLL(so)
    lib.PT_PredictorCreate.restype = ctypes.c_void_p
    lib.PT_PredictorCreate.argtypes = [ctypes.c_char_p]
    lib.PT_PredictorDestroy.argtypes = [ctypes.c_void_p]
    lib.PT_PredictorNumInputs.argtypes = [ctypes.c_void_p]
    lib.PT_PredictorRun.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int), ctypes.c_int]
    lib.PT_PredictorOutput.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    lib.PT_LastError.restype = ctypes.c_char_p

    p = lib.PT_PredictorCreate(path.encode())
    assert p, lib.PT_LastError()
    assert lib.PT_PredictorNumInputs(p) == 1

    xc = np.ascontiguousarray(x)
    in_data = (ctypes.c_void_p * 1)(xc.ctypes.data)
    in_shape = (ctypes.c_int64 * 2)(*xc.shape)
    in_ndim = (ctypes.c_int * 1)(2)
    in_dt = (ctypes.c_int * 1)(0)          # float32
    n_out = lib.PT_PredictorRun(p, in_data, in_shape, in_ndim, in_dt, 1)
    assert n_out == 1, lib.PT_LastError()

    data = ctypes.c_void_p()
    shape = (ctypes.c_int64 * 8)()
    ndim = ctypes.c_int()
    dtype = ctypes.c_int()
    rc = lib.PT_PredictorOutput(p, 0, ctypes.byref(data), shape,
                                ctypes.byref(ndim), ctypes.byref(dtype))
    assert rc == 0, lib.PT_LastError()
    assert dtype.value == 0 and ndim.value == 2
    out_shape = tuple(shape[i] for i in range(ndim.value))
    out = np.ctypeslib.as_array(
        ctypes.cast(data, ctypes.POINTER(ctypes.c_float)),
        shape=out_shape).copy()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    lib.PT_PredictorDestroy(p)


def test_c_api_generator_streaming(tmp_path):
    """PT_GeneratorCreate/Stream: callback receives one token batch per
    generated position (parity with live generate) and a nonzero
    callback return cancels the stream."""
    from paddle_tpu.models import LlamaForCausalLM, generate
    from paddle_tpu.models.llama import tiny_llama_config
    from paddle_tpu.models.generation import export_generation_bundle
    from paddle_tpu.inference import capi

    paddle.seed(0)
    m = LlamaForCausalLM(tiny_llama_config(num_hidden_layers=2))
    m.eval()
    prompt = np.ascontiguousarray(
        np.random.RandomState(0).randint(0, 256, (2, 8)), dtype=np.int32)
    path = str(tmp_path / "g")
    export_generation_bundle(m, path, batch_size=2, prompt_len=8,
                             max_new_tokens=5)
    ref = generate(m, paddle.to_tensor(prompt),
                   max_new_tokens=5).numpy()[:, 8:]

    so = capi.build(str(tmp_path / "capi"))
    lib = ctypes.CDLL(so)
    CB = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.POINTER(ctypes.c_int32),
                          ctypes.c_int, ctypes.c_int, ctypes.c_void_p)
    lib.PT_GeneratorCreate.restype = ctypes.c_void_p
    lib.PT_GeneratorCreate.argtypes = [ctypes.c_char_p]
    lib.PT_GeneratorDestroy.argtypes = [ctypes.c_void_p]
    lib.PT_GeneratorStream.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_double,
        ctypes.c_int, ctypes.c_double, ctypes.c_int, ctypes.c_longlong,
        CB, ctypes.c_void_p]
    lib.PT_LastError.restype = ctypes.c_char_p

    g = lib.PT_GeneratorCreate(path.encode())
    assert g, lib.PT_LastError()

    got, steps_seen = [], []

    @CB
    def on_tok(toks, batch, step, user):
        got.append([toks[i] for i in range(batch)])
        steps_seen.append(step)
        return 0

    pp = prompt.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    n = lib.PT_GeneratorStream(g, pp, 2, 8, 5, 0, 1.0, 0, 1.0, -1, -1,
                               on_tok, None)
    assert n == 5, (n, lib.PT_LastError())
    assert steps_seen == [0, 1, 2, 3, 4]
    np.testing.assert_array_equal(np.array(got, np.int32).T, ref)

    # cancel from the callback
    count = []

    @CB
    def cancel(toks, batch, step, user):
        count.append(step)
        return 1 if step >= 1 else 0

    n2 = lib.PT_GeneratorStream(g, pp, 2, 8, 5, 0, 1.0, 0, 1.0, -1, -1,
                                cancel, None)
    assert n2 == 2 and count == [0, 1]

    # bad bundle path reports through PT_LastError
    assert not lib.PT_GeneratorCreate(b"/nonexistent/bundle")
    assert lib.PT_LastError()
    lib.PT_GeneratorDestroy(g)


def test_c_api_generator_streaming_masked(tmp_path):
    """PT_GeneratorStreamMasked: a left-padded prompt through the C API
    matches live padded generation; NULL mask equals the unmasked
    entry."""
    from paddle_tpu.models import LlamaForCausalLM, generate
    from paddle_tpu.models.llama import tiny_llama_config
    from paddle_tpu.models.generation import export_generation_bundle
    from paddle_tpu.inference import capi

    paddle.seed(0)
    m = LlamaForCausalLM(tiny_llama_config(num_hidden_layers=2))
    m.eval()
    rng = np.random.RandomState(1)
    prompt = np.ascontiguousarray(rng.randint(0, 256, (2, 8)),
                                  dtype=np.int32)
    mask = np.ones((2, 8), np.uint8)
    mask[1, :3] = 0                       # row 1 left-padded by 3
    path = str(tmp_path / "gm")
    export_generation_bundle(m, path, batch_size=2, prompt_len=8,
                             max_new_tokens=4)
    ref = generate(m, paddle.to_tensor(prompt), max_new_tokens=4,
                   attention_mask=mask.astype("int32")).numpy()[:, 8:]

    so = capi.build(str(tmp_path / "capi"))
    lib = ctypes.CDLL(so)
    CB = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.POINTER(ctypes.c_int32),
                          ctypes.c_int, ctypes.c_int, ctypes.c_void_p)
    lib.PT_GeneratorCreate.restype = ctypes.c_void_p
    lib.PT_GeneratorCreate.argtypes = [ctypes.c_char_p]
    lib.PT_GeneratorDestroy.argtypes = [ctypes.c_void_p]
    lib.PT_GeneratorStreamMasked.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_double, ctypes.c_int,
        ctypes.c_double, ctypes.c_int, ctypes.c_longlong, CB,
        ctypes.c_void_p]
    lib.PT_LastError.restype = ctypes.c_char_p

    g = lib.PT_GeneratorCreate(path.encode())
    assert g, lib.PT_LastError()
    got = []

    @CB
    def on_tok(toks, batch, step, user):
        got.append([toks[i] for i in range(batch)])
        return 0

    pp = prompt.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    mp = mask.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    n = lib.PT_GeneratorStreamMasked(g, pp, mp, 2, 8, 4, 0, 1.0, 0, 1.0,
                                     -1, -1, on_tok, None)
    assert n == 4, (n, lib.PT_LastError())
    np.testing.assert_array_equal(np.array(got, np.int32).T, ref)
    lib.PT_GeneratorDestroy(g)
