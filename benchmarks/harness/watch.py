"""Counts the compile requests jax sends to the backend while a block runs.

Copied from ``chip_smoke.py::compile_watch`` (PR 21), so that the benchmark's
yardstick does not move when the program's scripts do. Every trace that
reaches XLA counts, persistent-cache hit or not; the measured window must
count 0.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def compile_watch():
    import jax
    counts = {"compiles": 0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event, duration_secs, **kw):
        del duration_secs, kw
        if event == "/jax/core/compile/backend_compile_duration":
            counts["compiles"] += 1

    def on_event(event, **kw):
        del kw
        if event == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield counts
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
