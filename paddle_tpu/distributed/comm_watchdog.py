"""Host-side communication watchdog.

Capability parity with the reference's CommTaskManager
(reference: paddle/phi/core/distributed/comm_task_manager.cc:67 +
nccl_comm_task.cc): background threads poll in-flight collectives for
timeout and abort the job with a diagnosable error instead of hanging.

TPU-native design: collectives are compiled into XLA programs, so there is
no per-collective task object to poll — the observable hang surface is a
device sync (``block_until_ready`` / host barrier) that never returns
(e.g. a peer host died mid all-reduce on a pod, or a chip was lost).
The watchdog runs the sync on a worker thread with a deadline;
on expiry it fires the hang callback (elastic integration: mark the node
unhealthy so the launcher relaunches) and raises ``CommTimeoutError``.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax

from ..core import flags as _flags

__all__ = ["CommTimeoutError", "CommTaskManager",
           "get_comm_task_manager", "set_comm_task_manager"]

_flags.define_flag("comm_timeout_s", 0.0,
                   "watchdog deadline (seconds) for device syncs/barriers; "
                   "0 disables")


class CommTimeoutError(RuntimeError):
    """A device sync did not complete within the watchdog deadline
    (the reference aborts via the comm task's error state)."""


class CommTaskManager:
    def __init__(self, timeout_s: Optional[float] = None,
                 on_hang: Optional[Callable[[str, float], None]] = None):
        self._timeout = timeout_s
        self._on_hang = on_hang
        self._hang_count = 0
        self._pool = None  # one persistent watchdog worker, not per-call

    def _submit(self, fn):
        from concurrent.futures import ThreadPoolExecutor
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="comm-watchdog")
        return self._pool.submit(fn)

    @property
    def hang_count(self) -> int:
        return self._hang_count

    def _deadline(self, timeout_s):
        if timeout_s is not None:
            return timeout_s
        if self._timeout is not None:
            return self._timeout
        return float(_flags.get_flag("comm_timeout_s") or 0.0)

    def wait(self, value, desc: str = "collective",
             timeout_s: Optional[float] = None, waiter=None):
        """Block until ``value``'s device work completes, bounded by the
        deadline. ``waiter`` overrides the sync callable (tests / custom
        transports). Deadline <= 0 degrades to an unbounded sync."""
        deadline = self._deadline(timeout_s)
        sync = waiter if waiter is not None \
            else (lambda: jax.block_until_ready(value))
        if deadline <= 0:
            return sync()
        injected = _injected_hang(desc)
        if injected is not None:
            # fault harness: this sync "hangs" like a dead peer — only
            # consulted under a deadline, so it can never wedge a wait
            sync = injected

        from concurrent.futures import TimeoutError as FuturesTimeout
        start = time.monotonic()
        fut = self._submit(sync)
        try:
            return fut.result(deadline)  # device errors re-raise here
        except FuturesTimeout:
            self._hang_count += 1
            elapsed = time.monotonic() - start
            # the worker is stuck inside the sync: abandon this pool so the
            # next wait gets a fresh worker instead of queueing behind it
            pool, self._pool = self._pool, None
            pool.shutdown(wait=False)
            if self._on_hang is not None:
                try:
                    self._on_hang(desc, elapsed)
                except Exception:
                    pass
            self._notify_elastic(desc)
            raise CommTimeoutError(
                f"'{desc}' did not complete within {deadline:.1f}s "
                f"(waited {elapsed:.1f}s) — a peer may be down or the "
                "device link hung (reference: CommTaskManager watchdog)"
            ) from None

    def barrier(self, desc: str = "barrier",
                timeout_s: Optional[float] = None):
        """Deadline-bounded host barrier: a trivial device round-trip."""
        import jax.numpy as jnp
        return self.wait(jnp.zeros(()) + 0, desc=desc, timeout_s=timeout_s)

    def close(self) -> None:
        """Release the watchdog worker pool. Never waits: a worker stuck
        inside a hung sync would block a clean shutdown forever — the
        pool is abandoned exactly like the hang path abandons it."""
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=False)

    def __enter__(self) -> "CommTaskManager":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _notify_elastic(self, desc: str) -> None:
        """Elastic integration (reference: watchdog error propagation aborts
        training so the elastic manager relaunches): flag the local agent
        unhealthy if one is running."""
        try:
            from .fleet.elastic.manager import notify_comm_hang
        except Exception:
            return
        try:
            notify_comm_hang(desc)
        except Exception:
            pass


def _injected_hang(desc: str):
    """Fault-harness hook: a parked waiter when a sync-hang is armed for
    ``desc``, else None. Import is lazy and failure-proof — the watchdog
    must work even if the resilience package is unavailable."""
    try:
        from .resilience.faults import get_fault_injector
    except Exception:
        return None
    inj = get_fault_injector()
    if not inj.armed:
        return None
    return inj.sync_hang_waiter(desc)


_GLOBAL = CommTaskManager()


def get_comm_task_manager() -> CommTaskManager:
    return _GLOBAL


def set_comm_task_manager(m: CommTaskManager) -> None:
    global _GLOBAL
    _GLOBAL = m
