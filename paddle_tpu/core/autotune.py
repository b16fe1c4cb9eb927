"""Kernel autotune cache (parity: paddle/phi/kernels/autotune/ — the
reference measures candidate kernels per op+shape key and caches the
winner; switch_autotune.h exposes enable/disable).

Here the candidates are the registered impls of a fused op ("pallas" vs
"xla"). A call with CONCRETE arrays and a new (op, shapes, dtypes) key
times every candidate on the live device and caches the fastest; calls
under tracing (jit, or inside the autograd tape's jax.vjp — i.e. any
forward that needs grads) consult the cache without measuring. The
measurement therefore happens on no-grad eager calls: run one eval/
warmup batch per shape (or preload a cache file) before training, and
the jitted train step picks up the cached winners. The cache can persist
to a JSON file so later processes skip the measurement, like the
reference's serialized autotune cache.
"""
from __future__ import annotations

import json
import os
import time
import warnings
from typing import Any, Dict, Optional

import jax

__all__ = ["enable_autotune", "disable_autotune", "autotune_status",
           "set_autotune_cache_file", "clear_autotune_cache",
           "use_artifacts_cache", "load_measured_defaults",
           "set_measured_defaults", "class_default", "shape_bucket"]


def use_artifacts_cache(repo_root: str) -> str:
    """Enable autotune against the checkout's on-chip tile cache
    (<root>/artifacts/autotune_tpu.json, written by bench_kernels.py at
    run time, git-ignored) plus the shape-CLASS measured-defaults table
    (measured_defaults.json, tools/seed_defaults.py). Returns the cache
    path."""
    path = os.path.join(repo_root, "artifacts", "autotune_tpu.json")
    enable_autotune()
    set_autotune_cache_file(path)
    defaults = os.path.join(repo_root, "artifacts",
                            "measured_defaults.json")
    if os.path.exists(defaults):
        load_measured_defaults(defaults)
    return path

_CACHE: Dict[str, str] = {}
_CACHE_FILE: Optional[str] = None
# shape-CLASS -> winner (VERDICT r4 #6): consulted when a traced call
# misses the exact-shape cache, so jitted paths get measured winners
# without an eager pre-tune in the same session. Seeded from on-chip
# captures by tools/seed_defaults.py; coarser than the exact cache
# (power-of-two seq buckets), finer than the hand heuristics.
_DEFAULTS: Dict[str, str] = {}
_STATS = {"hits": 0, "misses": 0, "measured": 0, "class_hits": 0}
# cache key -> {impl: "ExcType: message"} for every candidate that raised
# while being measured. A refused candidate (e.g. a tile too large for
# VMEM) cannot win, but it is never dropped silently: a swallowed
# AttributeError once disqualified EVERY Pallas candidate unnoticed.
_FAILED: Dict[str, Dict[str, str]] = {}


def shape_bucket(n: int) -> int:
    """Round up to the next power of two: the shape-class granularity of
    the measured-defaults table."""
    return 1 << max(0, (int(n) - 1).bit_length())


# Class-key builders — THE single source of the shape-class format, used
# by both the consult path (ops/pallas call sites) and the capture seeder
# (tools/seed_defaults.py). A format drift between the two would silently
# zero class_hits and reopen the cold-cache cliff, so neither side is
# allowed its own f-string.

def flash_class_key(tag: str, sq: int, sk: int, gqa: bool, head_dim: int,
                    dtype) -> str:
    return (f"{tag}_class_g{int(bool(gqa))}_d{int(head_dim)}"
            f"_sq{shape_bucket(sq)}_sk{shape_bucket(sk)}_{dtype}")


def ce_class_key(rows: int, vocab: int, dtype) -> str:
    return (f"softmax_xent_dir_class_r{shape_bucket(rows)}"
            f"_v{shape_bucket(vocab)}_{dtype}")


def norm_class_key(tag: str, rows: int, cols: int, dtype) -> str:
    return f"{tag}_class_r{shape_bucket(rows)}_c{int(cols)}_{dtype}"


def load_measured_defaults(path: str) -> int:
    """Load (or merge) a measured-defaults table; returns the number of
    entries loaded from THIS file (0 + a logged warning on failure, so a
    truncated capture write is not mistaken for a clean empty table)."""
    try:
        with open(path) as f:
            data = json.load(f)
        entries = {str(k): str(v)
                   for k, v in data.get("defaults", data).items()
                   if not str(k).startswith("_")}
    except Exception as e:  # noqa: BLE001
        import logging
        logging.getLogger(__name__).warning(
            "measured-defaults load failed for %s: %r", path, e)
        return 0
    _DEFAULTS.update(entries)
    return len(entries)


def set_measured_defaults(entries: Dict[str, str]) -> None:
    _DEFAULTS.clear()
    _DEFAULTS.update(entries)


def class_default(class_key: Optional[str]):
    if class_key is None:
        return None
    return _DEFAULTS.get(class_key)


def _flag_on() -> bool:
    from . import flags as _flags
    return bool(_flags.get_flag("use_autotune"))


def enable_autotune() -> None:
    from . import flags as _flags
    _flags.set_flags({"use_autotune": True})


def disable_autotune() -> None:
    from . import flags as _flags
    _flags.set_flags({"use_autotune": False})


def autotune_status() -> dict:
    """(parity: paddle.incubate.autotune status surface)"""
    return {"use_autotune": _flag_on(), "cache_size": len(_CACHE),
            "defaults_size": len(_DEFAULTS), **_STATS,
            "failed": {k: dict(v) for k, v in _FAILED.items()}}


def set_autotune_cache_file(path: Optional[str]) -> None:
    """Persist decisions to ``path`` (JSON) and preload existing ones."""
    global _CACHE_FILE
    _CACHE_FILE = path
    if path and os.path.exists(path):
        try:
            with open(path) as f:
                _CACHE.update(json.load(f))
        except Exception:
            pass


def clear_autotune_cache() -> None:
    _CACHE.clear()
    _DEFAULTS.clear()
    _FAILED.clear()
    _STATS.update(hits=0, misses=0, measured=0, class_hits=0)


def _key(name: str, arrays) -> str:
    parts = [name]
    for a in arrays:
        if hasattr(a, "shape"):
            parts.append(f"{tuple(a.shape)}:{a.dtype}")
        else:
            parts.append(repr(a)[:20])
    return "|".join(parts)


def _save() -> None:
    if _CACHE_FILE:
        try:
            with open(_CACHE_FILE, "w") as f:
                json.dump(_CACHE, f, indent=0)
        except Exception:
            pass


def record_meta(name: str, key_arrays, meta: str) -> None:
    """Attach a side note to a cache key (stored under ``<key>__meta``).
    Used e.g. to record the REAL batch size behind a batch-stripped
    surrogate key, so a later sweep can spot and re-measure entries whose
    serving batch drifted far from the measured one."""
    _CACHE[_key(name, key_arrays) + "__meta"] = str(meta)
    _save()


def get_meta(name: str, key_arrays):
    return _CACHE.get(_key(name, key_arrays) + "__meta")


def _measure(fn, args, warmup: int = 1, iters: int = 3):
    out = fn(*args)
    jax.tree_util.tree_map(
        lambda x: jax.device_get(x) if hasattr(x, "shape") else x, out)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree_util.tree_map(
            lambda x: jax.device_get(x) if hasattr(x, "shape") else x, out)
        best = min(best, time.perf_counter() - t0)
    return best, out


def pick_impl(name: str, impls: Dict[str, Any], arrays, call,
              key_arrays=None, class_key=None):
    """Return ``(winner_name, winner_output)`` for this call, measuring
    candidates on a cache miss (concrete arrays only). ``call(impl_name)``
    must run the op with the given impl and return its outputs. Returns
    ``(None, None)`` when autotuning does not apply (disabled, single
    impl, or tracing with an empty cache); a cache hit returns
    ``(name, None)`` — the caller runs the winner itself.
    ``key_arrays``: optional shape surrogates for the cache key when the
    op's optimum is invariant to a dim of the real arrays (e.g. flash
    attention tiles vs batch); tracer detection always uses ``arrays``.
    ``class_key``: optional shape-CLASS key into the measured-defaults
    table — a traced call that misses the exact cache falls back to the
    class winner (from a prior capture) before the hand heuristic, so
    jitted results stop depending on same-session pre-tune ordering."""
    if not _flag_on() or len(impls) < 2:
        return None, None
    if any(isinstance(a, jax.core.Tracer) for a in arrays):
        # traced call (jit or inside jax.vjp): consult-only
        k = _key(name, key_arrays if key_arrays is not None else arrays)
        choice = _CACHE.get(k)
        if choice is not None:
            _STATS["hits"] += 1
            return choice, None
        choice = class_default(class_key)
        if choice is not None:
            _STATS["class_hits"] += 1
        return choice, None
    k = _key(name, key_arrays if key_arrays is not None else arrays)
    if k in _CACHE:
        _STATS["hits"] += 1
        return _CACHE[k], None
    _STATS["misses"] += 1
    best_name, best_t, best_out = None, float("inf"), None
    for impl_name in impls:
        try:
            t, out = _measure(lambda *a: call(impl_name), arrays)
        except Exception as e:  # noqa: BLE001 -- recorded and warned below
            reason = f"{type(e).__name__}: {e}"[:300]
            _FAILED.setdefault(k, {})[impl_name] = reason
            warnings.warn(f"autotune: candidate {impl_name!r} of {name} "
                          f"raised and cannot win: {reason}",
                          RuntimeWarning, stacklevel=2)
            continue
        _STATS["measured"] += 1
        if t < best_t:
            best_name, best_t, best_out = impl_name, t, out
    if best_name is not None:
        _CACHE[k] = best_name
        _save()  # one small JSON per NEW key; misses are one-time per shape
    return best_name, best_out
