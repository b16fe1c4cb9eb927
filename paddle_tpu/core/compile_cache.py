"""Where XLA's persistent compile cache lives.

One rule for every entry point that compiles for a device
(``chip_smoke.py``, ``benchmarks/run.py``, ``serving/host.py``): when
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this module
sets nothing; otherwise the cache goes to ONE fixed directory inside the
checkout. A cache that moves between runs never hits, so the path is a
constant: no temp name, pid or timestamp.
"""
from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache", "CHECKOUT_CACHE_DIR"]

# <checkout>/.jax_cache (git-ignored): paddle_tpu/core/ -> two levels up
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache somewhere stable and
    return the directory in use. Call before the first large compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
