"""Framework-level utilities: save/load, mode queries.

Parity: python/paddle/framework/io.py paddle.save/paddle.load (pickle-based
state_dict serialization) — numpy payloads so checkpoints are portable.
"""
from __future__ import annotations

import os
import pickle

import jax.numpy as jnp
import numpy as np

from .core.tensor import Tensor


def _to_saveable(obj):
    if isinstance(obj, Tensor):
        return {"__tensor__": True, "data": np.asarray(obj._data),
                "stop_gradient": obj.stop_gradient, "name": obj.name}
    if isinstance(obj, dict):
        return {k: _to_saveable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        t = type(obj)
        return t(_to_saveable(v) for v in obj)
    return obj


def _from_saved(obj):
    if isinstance(obj, dict):
        if obj.get("__tensor__"):
            t = Tensor(jnp.asarray(obj["data"]),
                       stop_gradient=obj.get("stop_gradient", True))
            t.name = obj.get("name", "")
            return t
        return {k: _from_saved(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        t = type(obj)
        return t(_from_saved(v) for v in obj)
    return obj


def save(obj, path, protocol=4, **configs):
    """Save a (nested) state_dict / object (parity: paddle.save)."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "wb") as f:
        pickle.dump(_to_saveable(obj), f, protocol=protocol)


def load(path, **configs):
    """Load an object saved by ``save`` (parity: paddle.load)."""
    with open(path, "rb") as f:
        return _from_saved(pickle.load(f))


def in_dynamic_mode() -> bool:
    return True


def in_dynamic_or_pir_mode() -> bool:
    return True


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str = "tpu") -> bool:
    return device_type == "tpu"


class iinfo:
    """Integer dtype info (parity: paddle.iinfo)."""

    def __init__(self, dtype):
        import numpy as np
        from .core.dtype import convert_dtype
        i = np.iinfo(np.dtype(convert_dtype(dtype)))
        self.min = int(i.min)
        self.max = int(i.max)
        self.bits = int(i.bits)
        self.dtype = str(i.dtype)


class finfo:
    """Floating dtype info (parity: paddle.finfo)."""

    def __init__(self, dtype):
        import numpy as np
        from .core.dtype import convert_dtype
        dt = np.dtype(convert_dtype(dtype))
        try:
            f = np.finfo(dt)
        except Exception:
            import ml_dtypes
            f = ml_dtypes.finfo(dt)
        self.min = float(f.min)
        self.max = float(f.max)
        self.eps = float(f.eps)
        self.tiny = float(f.tiny)
        self.smallest_normal = float(f.smallest_normal)
        self.resolution = float(f.resolution)
        self.bits = int(f.bits)
        self.dtype = str(f.dtype)


class CPUPlace:
    def __repr__(self):
        return "Place(cpu)"

    def __eq__(self, other):
        return isinstance(other, CPUPlace)


class CUDAPlace:
    """GPU place stub — accepted for API compatibility; tensors live where
    XLA puts them (the TPU). (parity: paddle.CUDAPlace)"""

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"Place(gpu:{self.device_id})"

    def __eq__(self, other):
        return isinstance(other, CUDAPlace) and \
            other.device_id == self.device_id


class CUDAPinnedPlace:
    def __repr__(self):
        return "Place(gpu_pinned)"

    def __eq__(self, other):
        return isinstance(other, CUDAPinnedPlace)


class TPUPlace:
    """The native place of this framework."""

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"Place(tpu:{self.device_id})"

    def __eq__(self, other):
        return isinstance(other, TPUPlace) and \
            other.device_id == self.device_id


_PRINT_OPTIONS = {"precision": 8, "threshold": 1000, "edgeitems": 3,
                  "linewidth": 80, "sci_mode": None}


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """(parity: paddle.set_printoptions — applies to Tensor repr via numpy)"""
    import numpy as np
    kw = {}
    if precision is not None:
        _PRINT_OPTIONS["precision"] = precision
        kw["precision"] = precision
    if threshold is not None:
        _PRINT_OPTIONS["threshold"] = threshold
        kw["threshold"] = threshold
    if edgeitems is not None:
        _PRINT_OPTIONS["edgeitems"] = edgeitems
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        _PRINT_OPTIONS["linewidth"] = linewidth
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        _PRINT_OPTIONS["sci_mode"] = sci_mode
        kw["suppress"] = not sci_mode
    np.set_printoptions(**kw)


def disable_signal_handler():
    """No-op (parity: paddle.disable_signal_handler — the reference
    unhooks its C++ signal handlers; this build installs none)."""


def check_shape(shape):
    """Validate a shape argument (parity helper used by static APIs)."""
    if shape is None:
        raise ValueError("shape must not be None")
    for s in (shape if isinstance(shape, (list, tuple)) else [shape]):
        if isinstance(s, int) and s < -1:
            raise ValueError(f"invalid dim {s} in shape {shape}")
    return True


class LazyGuard:
    """Context that defers parameter initialization (parity:
    paddle.LazyGuard, python/paddle/fluid/lazy_init.py). On this substrate
    parameter arrays are cheap host-side inits, so the guard only marks
    layers constructed inside it; ``layer.to()``-time re-init is a no-op."""

    _active = False

    def __enter__(self):
        LazyGuard._active = True
        return self

    def __exit__(self, *exc):
        LazyGuard._active = False
        return False


def batch(reader, batch_size, drop_last=False):
    """Wrap a sample reader into a batch reader (parity: paddle.batch)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched
