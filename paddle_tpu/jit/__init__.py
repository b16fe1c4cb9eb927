"""paddle_tpu.jit — dygraph-to-static + program save/load.

Capability parity: python/paddle/jit/ (to_static/dy2static + SOT,
jit.save/api.py, translated_layer.py).

TPU-native design: "static graph capture" IS jax.jit tracing — no AST
rewriting or bytecode hooks are needed because the op funnel (run_op)
already emits pure-functional jax computations. to_static wraps a Layer
(or function) so no-grad calls execute through one cached compiled XLA
program; jit.save exports that program as serialized StableHLO
(portable, version-stable — the reference's pdmodel analog) alongside a
params npz (pdiparams analog); jit.load rebuilds a callable
TranslatedLayer from the pair without the original Python class.
"""
from __future__ import annotations

import json
import os
from typing import Optional, Sequence

import jax
import jax.export  # jax>=0.4.30 lazy submodule: save/load need it imported
import jax.numpy as jnp
import numpy as np

from ..core.autograd import is_tape_active, tape_paused
from ..core.tensor import Tensor
from ..nn.layer.layers import Layer, _swapped_state, functional_state

__all__ = ["InputSpec", "to_static", "save", "load", "not_to_static",
           "TranslatedLayer", "StaticFunction"]


class InputSpec:
    """Parity: paddle.static.InputSpec(shape, dtype, name). None dims mean
    dynamic in the reference; StableHLO export needs concrete dims, so
    None is accepted but must be resolved by a real example before save."""

    def __init__(self, shape: Sequence[Optional[int]], dtype="float32",
                 name=None):
        self.shape = list(shape)
        self.dtype = dtype
        self.name = name

    def to_sds(self) -> jax.ShapeDtypeStruct:
        if any(d is None or (isinstance(d, int) and d < 0)
               for d in self.shape):
            raise ValueError(
                f"InputSpec {self.name or ''} has dynamic dims "
                f"{self.shape}: provide concrete shapes for export")
        import jax.numpy as jnp
        return jax.ShapeDtypeStruct(tuple(self.shape),
                                    jnp.dtype(self.dtype))

    def __repr__(self):
        return (f"InputSpec(shape={self.shape}, dtype={self.dtype}, "
                f"name={self.name})")


def _unwrap(x):
    return x._data if isinstance(x, Tensor) else x


class StaticFunction:
    """A Layer (or function) with a jitted no-grad fast path.

    Training calls (tape active) fall through to eager execution so
    autograd/hooks keep working — the jitted-training path is
    models.create_train_step, which compiles fwd+bwd+opt as one program.
    """

    def __init__(self, obj, input_spec=None, full_graph=True,
                 donate_argnums=()):
        del full_graph
        self._input_spec = input_spec
        # indices into the USER arrays (the ``*arrays`` of the traced
        # fn — state and key are never donatable): XLA then aliases
        # those input buffers to outputs, which is how the serving
        # decode engine updates its KV pools in place instead of
        # copying them every step. Donated buffers are dead after the
        # call — only for callers that re-feed the outputs (the AOT
        # ``compile_for`` path); the live ``__call__`` path donates too,
        # so don't set this on a function whose caller keeps its inputs.
        self._donate = tuple(donate_argnums)
        if isinstance(obj, Layer):
            self._layer: Optional[Layer] = obj
            self._fn = None
        else:
            self._layer = None
            self._fn = obj
        self._jitted = None

    # -- compiled path ----------------------------------------------------
    def _build(self):
        if self._jitted is not None:
            return self._jitted
        from ..core import random as _random
        if self._layer is not None:
            layer = self._layer

            def fn(state, key, *arrays):
                # key is a traced argument: dropout draws differ per call
                # instead of being constant-folded into the program
                with _random.key_context(key):
                    with _swapped_state(layer, state):
                        with tape_paused():
                            out = layer(*[Tensor(a) for a in arrays])
                if isinstance(out, (tuple, list)):
                    return tuple(_unwrap(o) for o in out)
                return _unwrap(out)
        else:
            raw = self._fn

            def fn(state, key, *arrays):
                del state
                with _random.key_context(key):
                    with tape_paused():
                        out = raw(*[Tensor(a) for a in arrays])
                if isinstance(out, (tuple, list)):
                    return tuple(_unwrap(o) for o in out)
                return _unwrap(out)
        # user array i sits at jit position i + 2 (after state, key)
        self._jitted = jax.jit(
            fn, donate_argnums=tuple(i + 2 for i in self._donate)) \
            if self._donate else jax.jit(fn)
        return self._jitted

    def _state(self):
        return functional_state(self._layer) if self._layer is not None \
            else {}

    def __call__(self, *args, **kwargs):
        if is_tape_active() or kwargs:
            # training / kwargs path: eager (autograd-capable)
            target = self._layer if self._layer is not None else self._fn
            return target(*args, **kwargs)
        from ..core import random as _random
        arrays = [_unwrap(a) for a in args]
        out = self._build()(self._state(),
                            _random.default_generator.next_key(), *arrays)
        if isinstance(out, tuple):
            return tuple(Tensor(o, stop_gradient=True) for o in out)
        return Tensor(out, stop_gradient=True)

    # -- AOT path (serving) ----------------------------------------------
    def compile_for(self, *arg_specs):
        """AOT-compile the no-grad fast path for ONE concrete input
        signature and return the compiled executable: call it as
        ``compiled(state, key, *arrays)`` with ``state = self._state()``
        at call time (weight updates between calls are picked up; shapes/
        dtypes must match the compiled signature).

        This is the signature-reuse integration for ``paddle_tpu.serving``:
        the server's executable cache holds one of these per shape bucket,
        so the number of XLA compiles is exactly the bucket count, and the
        same traced function backs both the live ``__call__`` cache and
        the AOT executables.
        """
        sds = []
        for s in arg_specs:
            if isinstance(s, InputSpec):
                sds.append(s.to_sds())
            elif isinstance(s, jax.ShapeDtypeStruct):
                sds.append(s)
            else:
                arr = _unwrap(s) if isinstance(s, Tensor) else np.asarray(s)
                sds.append(jax.ShapeDtypeStruct(arr.shape, arr.dtype))
        state = self._state()
        state_sds = {k: jax.ShapeDtypeStruct(np.shape(v),
                                             jnp.asarray(v).dtype)
                     for k, v in state.items()}
        key0 = jax.random.key(0)
        key_sds = jax.ShapeDtypeStruct(key0.shape, key0.dtype)
        # the compile is counted and traced where every compile in the
        # process is: profiler.tracing's jax.monitoring listener
        return self._build().lower(state_sds, key_sds, *sds).compile()

    def cache_size(self) -> int:
        """Number of signatures traced by the live jit cache."""
        if self._jitted is None:
            return 0
        return self._jitted._cache_size()

    # Layer-protocol passthrough so to_static(layer) drops into model code
    def __getattr__(self, name):
        target = object.__getattribute__(self, "_layer")
        if target is None:
            target = object.__getattribute__(self, "_fn")
        return getattr(target, name)

    @property
    def forward(self):
        return self.__call__


def to_static(obj=None, input_spec=None, full_graph=True, backend=None,
              **kwargs):
    """Parity: paddle.jit.to_static — decorator or direct call."""
    del backend, kwargs

    def wrap(o):
        return StaticFunction(o, input_spec, full_graph)

    if obj is None:
        return wrap
    return wrap(obj)


def not_to_static(fn):
    """Parity: paddle.jit.not_to_static — marker passthrough (eager-first
    execution means nothing needs excluding)."""
    return fn


# -- save / load ------------------------------------------------------------

_MODEL_SUFFIX = ".pdmodel"       # serialized StableHLO
_PARAMS_SUFFIX = ".pdiparams"    # npz of the functional state
_META_SUFFIX = ".pdmeta.json"


def save(layer, path, input_spec=None, **configs):
    """Export layer.forward as StableHLO + params (parity: paddle.jit.save).

    ``input_spec``: list of InputSpec / example Tensors / arrays defining
    the traced signature.
    """
    del configs
    sf = layer if isinstance(layer, StaticFunction) else StaticFunction(layer)
    if sf._layer is None:
        raise TypeError("jit.save requires a Layer (or to_static(Layer))")
    spec = input_spec or sf._input_spec
    if not spec:
        raise ValueError("jit.save requires input_spec (shapes to trace)")
    sds = []
    for s in spec:
        if isinstance(s, InputSpec):
            sds.append(s.to_sds())
        else:
            arr = _unwrap(s) if isinstance(s, Tensor) else np.asarray(s)
            sds.append(jax.ShapeDtypeStruct(arr.shape, arr.dtype))

    state = sf._state()
    state_sds = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in state.items()}
    # export takes the RNG key as RAW uint32 bits, not a typed key array:
    # typed key dtypes (key<fry>) are not serializable by jax.export, and
    # raw bits keep the artifact loadable across jax versions
    base = sf._build()

    def _export_fn(st, raw_key, *arrays):
        return base(st, jax.random.wrap_key_data(raw_key), *arrays)

    raw0 = jax.random.key_data(jax.random.key(0))
    key_sds = jax.ShapeDtypeStruct(raw0.shape, raw0.dtype)
    exported = jax.export.export(jax.jit(_export_fn))(state_sds, key_sds,
                                                      *sds)

    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path + _MODEL_SUFFIX, "wb") as f:
        f.write(exported.serialize())
    with open(path + _PARAMS_SUFFIX, "wb") as f:  # np.savez would append
        np.savez(f, **{k: np.asarray(v) for k, v in state.items()})  # .npz
    with open(path + _META_SUFFIX, "w") as f:
        json.dump({
            "inputs": [{"shape": list(s.shape), "dtype": str(s.dtype)}
                       for s in sds],
            "state_keys": sorted(state.keys()),
            "key_format": "raw_uint32",
        }, f)


class TranslatedLayer:
    """A loaded program: callable without the original Python class
    (parity: paddle.jit.TranslatedLayer)."""

    def __init__(self, exported, state, meta):
        self._exported = exported
        self._state = state
        self._meta = meta
        self.training = False

    def __call__(self, *args):
        from ..core import random as _random
        arrays = [_unwrap(a) for a in args]
        state = self._state
        orig = getattr(self, "_orig_dtypes", None)
        if orig:
            # params stored reduced (convert_params): cast back to the
            # program's baked dtypes at the call boundary
            state = {k: (jnp.asarray(v).astype(orig[k]) if k in orig
                         else v) for k, v in state.items()}
        key = _random.default_generator.next_key()
        if self._meta.get("key_format") == "raw_uint32":
            key = jax.random.key_data(key)
        out = self._exported.call(state, key, *arrays)
        if isinstance(out, (tuple, list)):
            return tuple(Tensor(o, stop_gradient=True) for o in out)
        return Tensor(out, stop_gradient=True)

    forward = __call__

    def convert_params(self, dtype, black_list=None):
        """Store floating params in ``dtype`` (halving their steady HBM/
        host footprint), casting back to the program's baked dtypes at
        call time — the in-memory form of
        inference.convert_to_mixed_precision (the re-export path there is
        the on-disk form). ``black_list`` names params kept at full
        precision."""
        bl = set(black_list or ())
        self._orig_dtypes = dict(getattr(self, "_orig_dtypes", {}))
        new_state = dict(self._state)
        for k, v in self._state.items():
            arr = jnp.asarray(v)
            if k in bl or not jnp.issubdtype(arr.dtype, jnp.floating) \
                    or arr.dtype == jnp.dtype(dtype):
                continue
            self._orig_dtypes.setdefault(k, arr.dtype)
            new_state[k] = arr.astype(dtype)
        self._state = new_state
        return self

    def eval(self):
        self.training = False
        return self

    def state_dict(self):
        return {k: Tensor(v) for k, v in self._state.items()}

    @property
    def input_spec(self):
        return [InputSpec(m["shape"], m["dtype"])
                for m in self._meta.get("inputs", [])]


def load(path, **configs):
    """Parity: paddle.jit.load — rebuild a callable from pdmodel+pdiparams."""
    del configs
    with open(path + _MODEL_SUFFIX, "rb") as f:
        exported = jax.export.deserialize(f.read())
    npz = np.load(path + _PARAMS_SUFFIX)
    state = {k: npz[k] for k in npz.files}
    meta = {}
    if os.path.exists(path + _META_SUFFIX):
        with open(path + _META_SUFFIX) as f:
            meta = json.load(f)
    # non-numpy dtypes (bfloat16) are serialized as uint16 bits with the
    # true dtype recorded in the meta (inference.convert_to_mixed_precision)
    for k, dt in (meta.get("param_dtypes") or {}).items():
        if k in state:
            import ml_dtypes
            state[k] = state[k].view(np.dtype(getattr(ml_dtypes, dt)))
    return TranslatedLayer(exported, state, meta)


_IGNORED_MODULES = []
_CODE_LEVEL = 0
_VERBOSITY = 0
_TO_STATIC_ENABLED = True


def ignore_module(modules):
    """Mark modules whose calls to_static should not trace into (parity:
    paddle.jit.ignore_module — the SOT skip list). Tracing here is
    jax.jit, which inlines everything; the list is honored by to_static's
    fallback check."""
    global _IGNORED_MODULES
    _IGNORED_MODULES += list(modules)


def set_code_level(level=100, also_to_stdout=False):
    """(parity: paddle.jit.set_code_level — controls transformed-code
    logging)."""
    global _CODE_LEVEL
    _CODE_LEVEL = level


def set_verbosity(level=0, also_to_stdout=False):
    """(parity: paddle.jit.set_verbosity)"""
    global _VERBOSITY
    _VERBOSITY = level


def enable_to_static(enable=True):
    """Globally toggle to_static tracing (parity:
    paddle.jit.enable_to_static). When off, to_static returns the
    original callable."""
    global _TO_STATIC_ENABLED
    _TO_STATIC_ENABLED = bool(enable)


def _to_static_enabled():
    return _TO_STATIC_ENABLED
