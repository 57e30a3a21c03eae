"""Device management (reference: python/paddle/device/__init__.py).

Placement is owned by PjRt/XLA; these APIs report the TPU topology instead
of steering allocations. CUDA/XPU/custom-device predicates exist for API
parity and report False — there is exactly one backend family here: XLA
(tpu on hardware, cpu for tests).
"""
from __future__ import annotations

import jax

_current_device = [None]


def get_all_devices():
    return jax.devices()


def device_count():
    return jax.device_count()


def local_device_count():
    return jax.local_device_count()


def set_device(device):
    _current_device[0] = device
    return device


def get_device():
    if _current_device[0] is not None:
        return _current_device[0]
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_cinn():
    return False


def is_compiled_with_custom_device(name=None):
    return False


def is_compiled_with_distribute():
    return True


def is_compiled_with_tpu():
    return any(d.platform == "tpu" for d in jax.devices())


class cuda:
    """Namespace shim for paddle.device.cuda."""

    @staticmethod
    def device_count():
        return 0

    @staticmethod
    def is_available():
        return False


def synchronize(device=None):
    import jax
    (jax.device_put(0) + 0).block_until_ready()


class Stream:
    """No-op stream shim: XLA orders execution itself; exposed for API
    parity with paddle.device.Stream."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()


class Event:
    def __init__(self, enable_timing=False):
        pass

    def record(self, stream=None):
        pass

    def synchronize(self):
        synchronize()


# -- remaining reference surface (reference: python/paddle/device/__init__)

class _Place:
    def __init__(self, kind, dev_id=0):
        self._kind, self._dev_id = kind, dev_id

    def __repr__(self):
        return f"Place({self._kind}:{self._dev_id})"


class XPUPlace(_Place):
    def __init__(self, dev_id=0):
        super().__init__("xpu", dev_id)


class IPUPlace(_Place):
    def __init__(self, dev_id=0):
        super().__init__("ipu", dev_id)


def get_all_device_type():
    import jax
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return []


def get_available_device():
    import jax
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return []


def get_cudnn_version():
    return None  # no cuDNN on the TPU backend


_current_stream = Stream()


def current_stream(device=None):
    return _current_stream


def set_stream(stream):
    global _current_stream
    prev = _current_stream
    _current_stream = stream
    return prev


class stream_guard:
    """(reference: device/__init__.py stream_guard) — XLA owns ordering;
    the guard swaps the bookkeeping object only."""

    def __init__(self, stream):
        self._stream = stream
        self._prev = None

    def __enter__(self):
        self._prev = set_stream(self._stream)
        return self._stream

    def __exit__(self, *exc):
        set_stream(self._prev)
        return False


def is_compiled_with_ipu():
    return False
