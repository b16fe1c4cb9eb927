"""Device API (parity: python/paddle/device/). On TPU the device set is
fixed by the runtime (libtpu is the 'driver' — the reference's
Place/DeviceManager, paddle/phi/backends/device_manager.h, collapses to
jax.devices())."""
from __future__ import annotations

import jax

_CURRENT_DEVICE = [None]


def get_all_devices():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_device():
    if _CURRENT_DEVICE[0] is not None:
        return _CURRENT_DEVICE[0]
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def set_device(device: str):
    _CURRENT_DEVICE[0] = device
    return device


def get_device_count():
    return jax.device_count()


def device_count():
    return jax.device_count()


def is_compiled_with_cuda() -> bool:
    return False


def memory_stats(device=None) -> dict:
    """Raw allocator statistics of the accelerator (parity:
    paddle/fluid/memory/stats.h surface): the XLA allocator's
    bytes_in_use / peak_bytes_in_use / bytes_limit / num_allocs counters.
    Empty dict on platforms whose client reports none (the CPU client
    returns None); a backend that fails to initialize raises."""
    del device
    return jax.devices()[0].memory_stats() or {}


class cuda:
    """Namespace parity shim: paddle.device.cuda.* memory statistics map to
    the XLA allocator's memory_stats on the TPU device."""

    @staticmethod
    def device_count():
        return 0

    @staticmethod
    def max_memory_allocated(device=None):
        return memory_stats().get("peak_bytes_in_use", 0)

    @staticmethod
    def memory_allocated(device=None):
        return memory_stats().get("bytes_in_use", 0)

    @staticmethod
    def max_memory_reserved(device=None):
        return memory_stats().get("peak_bytes_in_use", 0)

    @staticmethod
    def memory_reserved(device=None):
        return memory_stats().get("bytes_limit", 0)

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def synchronize(device=None):
        synchronize(device)


def get_cudnn_version():
    """(parity: paddle.device.get_cudnn_version — no cuDNN on TPU)"""
    return None


class XPUPlace:
    """(parity stub: paddle.device.XPUPlace — no XPU backend)"""

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"Place(xpu:{self.device_id})"


class IPUPlace:
    """(parity stub: paddle.device.IPUPlace)"""

    def __repr__(self):
        return "Place(ipu)"


def is_compiled_with_xpu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    """XLA plays CINN's role on this substrate."""
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_distribute():
    """Collectives are compiled into programs — always available."""
    return True


def is_compiled_with_custom_device(device_type="tpu"):
    return device_type == "tpu"


def get_all_device_type():
    """(parity: paddle.device.get_all_device_type)"""
    kinds = []
    for d in jax.devices():
        if d.platform not in kinds:
            kinds.append(d.platform)
    return kinds


def get_all_custom_device_type():
    return [d.platform for d in jax.devices()
            if d.platform not in ("cpu", "gpu")][:1]


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [d for d in get_available_device()
            if not d.startswith(("cpu", "gpu"))]


class Event:
    """Stream-event parity stub (XLA owns scheduling; events are points
    the runtime already orders)."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self._t = None

    def record(self, stream=None):
        import time
        self._t = time.perf_counter()

    def query(self):
        return True

    def synchronize(self):
        pass

    def elapsed_time(self, end_event):
        if self._t is None or end_event._t is None:
            return 0.0
        return (end_event._t - self._t) * 1000.0


class Stream:
    """Stream parity stub — XLA programs are the scheduling unit."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        import jax
        try:
            (jax.device_put(0.0) + 0).block_until_ready()
        except Exception:
            pass

    def record_event(self, event=None):
        e = event or Event()
        e.record()
        return e

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass


def current_stream(device=None):
    return Stream(device)


def set_stream(stream):
    return stream


def stream_guard(stream):
    import contextlib

    @contextlib.contextmanager
    def _guard():
        yield stream
    return _guard()


def synchronize(device=None):
    """(parity: paddle.device.synchronize)"""
    import jax
    try:
        (jax.device_put(0.0) + 0).block_until_ready()
    except Exception:
        pass
