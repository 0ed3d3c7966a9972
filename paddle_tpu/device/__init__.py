"""paddle.device parity (set_device/get_device/cuda namespace-alikes).

Reference: python/paddle/device/. TPU-native: device selection is JAX's
(platform + ordinal); streams/events collapse into XLA's async dispatch, so
Stream/Event keep API shape with barrier semantics.
"""
from __future__ import annotations

import os

import jax

__all__ = ["set_device", "get_device", "get_all_device_type",
           "get_available_device", "is_compiled_with_cinn", "cuda",
           "Stream", "Event", "synchronize", "device_count", "memory_stats",
           "enable_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache():
    """Place JAX's persistent compilation cache; call before the first
    compile in any script meant for the chip. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
    sets nothing; otherwise the cache lives at ``<checkout>/.jax_cache``
    — a fixed path, because the path is part of the cache key and a
    directory that moves never hits. Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def set_device(device):
    """paddle.device.set_device parity. JAX owns placement, so there is
    nothing to switch — but naming a platform this process does not
    have (``set_device("tpu")`` on a CPU box) is an error, not a
    no-op that leaves the caller believing it runs on a chip."""
    platform = str(device).split(":")[0]
    have = {d.platform for d in jax.devices()}
    if platform not in have:
        raise ValueError(
            f"set_device({device!r}): JAX has no {platform!r} device "
            f"here (platforms: {sorted(have)})")
    return device


def get_device():
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_all_custom_device_type():
    return []


def is_compiled_with_cinn():
    return False


def device_count():
    return jax.device_count()


def synchronize(device=None):
    import jax.numpy as jnp
    jnp.zeros(()).block_until_ready()


def memory_stats(device=None):
    d = jax.devices()[0]
    try:
        return d.memory_stats() or {}
    except Exception:
        return {}


class Stream:
    """API-shape parity: XLA orders work itself; wait_* are barriers."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        synchronize()

    def wait_stream(self, stream):
        synchronize()

    def record_event(self, event=None):
        e = event or Event()
        e.record(self)
        return e


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        self._recorded = False

    def record(self, stream=None):
        self._recorded = True

    def query(self):
        return True

    def synchronize(self):
        synchronize()


class _CudaNamespace:
    """paddle.device.cuda shim — reports absence of CUDA, maps memory APIs
    to the TPU device where meaningful."""

    Stream = Stream
    Event = Event

    @staticmethod
    def device_count():
        return 0

    @staticmethod
    def is_available():
        return False

    @staticmethod
    def max_memory_allocated(device=None):
        stats = memory_stats()
        return stats.get("peak_bytes_in_use", 0)

    @staticmethod
    def memory_allocated(device=None):
        stats = memory_stats()
        return stats.get("bytes_in_use", 0)

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def synchronize(device=None):
        synchronize()


cuda = _CudaNamespace()


# ------------------------------------------------ reference device shims


def get_cudnn_version():
    return None          # no cuDNN in the TPU build


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_mlu():
    return False


def is_compiled_with_custom_device(device_type=None):
    return device_type == "tpu"


def get_available_custom_device():
    import jax
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


class XPUPlace:
    def __init__(self, device_id=0):
        self.device_id = device_id


class IPUPlace(XPUPlace):
    pass


class MLUPlace(XPUPlace):
    pass


class _Stream:
    """Stream facade: XLA orders work per device; sync == block."""

    def __init__(self, device=None, priority=None):
        self.device = device

    def synchronize(self):
        import jax
        (jax.device_put(0) + 0).block_until_ready()

    def wait_stream(self, stream):
        self.synchronize()

    def wait_event(self, event):
        self.synchronize()

    def record_event(self, event=None):
        return event


_current_stream = _Stream()


def current_stream(device=None):
    return _current_stream


def set_stream(stream):
    global _current_stream
    _current_stream = stream
    return stream


def stream_guard(stream):
    import contextlib

    @contextlib.contextmanager
    def guard():
        global _current_stream
        old, _cur = _current_stream, stream
        set_stream(stream)
        try:
            yield
        finally:
            set_stream(old)

    return guard()


Stream = _Stream
