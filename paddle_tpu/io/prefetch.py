"""Async device feed: prefetch-to-device ahead of the training loop.

The jitted train step made device time one XLA program per step
(models/trainer.py); this module closes the gaps BETWEEN programs. A
background thread pulls batches from any DataLoader/iterable, optionally
stacks K microbatches into the ``[K, B, ...]`` layout
``create_multistep_train_step`` expects, and places them on device ahead
of consumption — so host batch assembly and the H2D transfer overlap
with device compute instead of serializing in front of it. Paired with
``models.trainer.run_steps`` (which fetches metrics one step behind),
the host never sits inside the step loop waiting on either side.

Observability rides ``paddle_tpu.profiler.pipeline_stats()`` (mirroring
``serving_stats()``): queue-depth gauge, per-batch transfer latency, and
the host-blocked vs device-blocked time split that answers "am I
input-bound or compute-bound?" in one call.
"""
from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Any, Callable, Iterable, Optional, Union

import numpy as np

from ..core.tensor import Tensor
from ..profiler.metrics import MetricsBase

__all__ = ["DevicePrefetcher", "PipelineMetrics", "prefetch_to_device"]


class PipelineMetrics(MetricsBase):
    """Thread-safe counters/histograms/time-totals for one input pipeline
    (the io analog of serving.ServingMetrics; snapshot retrievable through
    ``profiler.pipeline_stats()``).

    Counters: batches_in (pulled from the source iterator), batches_out
    (handed to the consumer), stacks (K-stacked super-batches built),
    producer_exceptions.
    Histograms: transfer_ms (device placement latency per emitted batch),
    queue_depth (observed at each consumer get).
    Time totals (seconds): host_blocked_s (consumer waited on an empty
    queue — input-bound), device_blocked_s (consumer waited inside a
    lagged ``device_get`` — compute-bound; fed by ``run_steps``),
    producer_blocked_s (producer waited on a full queue — healthy
    backpressure), producer_busy_s (pull + stack + transfer work),
    dispatch_s (the consumer's own time inside the call of the step: key
    fold, schedule, program launches; fed by ``run_steps``).

    The trainer loop's own record (``run_steps``, one entry per complete
    iteration: feed_wait, dispatch, checkpoint, the lagged fetch): the
    histogram loop_ms (the iteration less the caller's ``on_log``),
    callback_s (time inside ``on_log``), starved_steps / starved_by /
    starved_s (dispatches that found the previous step already done, so
    the device had run dry, by the host phase that took longest since
    the previous launch, and a lower bound on the idle seconds),
    gc_pause_s / gc_collections / gc_gen2 (Python's collector, any
    thread), and the records of the ``SLOWEST`` longest iterations and
    of the last ``STARVED_KEPT`` starved ones (``run_steps`` documents a
    record's keys).
    """

    COUNTERS = ("batches_in", "batches_out", "stacks",
                "producer_exceptions", "starved_steps", "gc_collections",
                "gc_gen2")
    HISTS = ("transfer_ms", "queue_depth", "loop_ms")
    TIMES = ("host_blocked_s", "device_blocked_s", "producer_blocked_s",
             "producer_busy_s", "dispatch_s", "callback_s", "starved_s",
             "gc_pause_s")
    SLOWEST = 3
    STARVED_KEPT = 8

    def __init__(self, name: str):
        super().__init__(name)
        self._slowest: list = []
        self._starved = collections.deque(maxlen=self.STARVED_KEPT)
        self._starved_by: dict = {}

    def add_iteration(self, record: dict, starved_s: float,
                      gc: tuple) -> None:
        """One complete iteration of ``run_steps``: its record, the
        seconds the device stood idle in it at least, and the collector's
        ``(pause_s, collections, gen2)`` since the last call."""
        ms, cause = record["loop_ms"], record["starved"]
        with self._lock:
            self._hists["loop_ms"].observe(ms)
            slowest = self._slowest
            if len(slowest) < self.SLOWEST or ms > slowest[-1]["loop_ms"]:
                slowest.append(record)
                slowest.sort(key=lambda r: -r["loop_ms"])
                del slowest[self.SLOWEST:]
            if cause is not None:
                self._counters["starved_steps"] += 1
                self._starved_by[cause] = self._starved_by.get(cause, 0) + 1
                self._times["starved_s"] += starved_s
                self._starved.append(record)
        if gc[1]:
            self.add_gc(gc)

    def add_gc(self, gc: tuple) -> None:
        """The collector's ``(pause_s, collections, gen2)``."""
        pause_s, n, gen2 = gc
        with self._lock:
            self._times["gc_pause_s"] += pause_s
            self._counters["gc_collections"] += n
            self._counters["gc_gen2"] += gen2

    def snapshot(self) -> dict:
        with self._lock:
            out: dict = dict(self._counters)
            out["name"] = self.name
            out.update({k: round(v, 6) for k, v in self._times.items()})
            for k, h in self._hists.items():
                out[k] = h.snapshot()
            # records are never changed once added: a shallow copy is a
            # consistent one
            out["slowest"] = list(self._slowest)
            out["starved"] = list(self._starved)
            out["starved_by"] = dict(self._starved_by)
        out["queue_depth_now"] = self._read_gauge()
        host, dev = out["host_blocked_s"], out["device_blocked_s"]
        # the one-word answer: where did the step loop actually wait?
        out["bound"] = ("input" if host > dev else
                        "compute" if dev > host else "balanced")
        return out


def _strip_tensors(item):
    """Tensor leaves -> their jax arrays, so pytree ops see raw leaves."""
    import jax
    return jax.tree_util.tree_map(
        lambda x: x._data if isinstance(x, Tensor) else x, item,
        is_leaf=lambda x: isinstance(x, Tensor))


def _stack_items(items):
    """Stack K same-structure batches leafwise into [K, ...] arrays (host
    side, numpy — the single H2D transfer then moves the super-batch)."""
    import jax
    return jax.tree_util.tree_map(
        lambda *leaves: np.stack([np.asarray(l) for l in leaves]), *items)


class DevicePrefetcher:
    """Iterator over device-resident batches, filled by a background
    thread ``depth`` ahead of consumption.

    - ``sharding=None``: plain ``jax.device_put`` (default device).
    - ``sharding=<jax.sharding.Sharding>``: every leaf placed with it.
    - ``sharding=<callable>``: applied per leaf (e.g. the ``shard_batch``
      returned by ``create_sharded_train_step`` — batch dim over the data
      axis, scan/microbatch dims replicated).
    - ``stack=K``: K source batches are stacked leafwise into the
      ``[K, B, ...]`` layout ``create_multistep_train_step(steps=K)``
      checks at trace time; a trailing ragged remainder (< K batches) is
      dropped, mirroring ``drop_last`` semantics.

    Ordering is deterministic (single producer thread, FIFO queue).
    Backpressure is the bounded queue: the producer blocks once ``depth``
    batches wait unconsumed. A producer exception is re-raised in the
    consumer thread at the point the failing batch would have been
    yielded. ``close()`` (or ``with``-exit, or garbage collection) stops
    the producer promptly even mid-epoch.
    """

    _END = object()

    def __init__(self, iterator: Iterable, depth: int = 2,
                 sharding: Union[None, Callable, Any] = None,
                 stack: Optional[int] = None, name: str = "prefetch",
                 timeout: float = 120.0):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if stack is not None and stack < 1:
            raise ValueError(f"stack must be >= 1, got {stack}")
        self._source = iterator
        self._depth = depth
        self._sharding = sharding
        self._stack = stack
        self._timeout = timeout
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._exhausted = False
        self.metrics = PipelineMetrics(name)
        self.metrics.set_depth_gauge(self._q.qsize)
        from .. import profiler
        profiler.register_pipeline_source(name, self.metrics)
        self._thread = threading.Thread(
            target=self._produce, daemon=True,
            name=f"paddle_tpu-prefetch-{name}")
        self._thread.start()

    # -- producer ----------------------------------------------------------
    def _place(self, item):
        item = _strip_tensors(item)
        import jax
        if callable(self._sharding):   # shard_batch-style placement fn
            return jax.tree_util.tree_map(self._sharding, item)
        return jax.device_put(item, self._sharding)

    def _put(self, obj) -> bool:
        """Blocking put that stays responsive to close(); returns False
        when the prefetcher was closed while waiting."""
        t0 = time.perf_counter()
        while not self._stop.is_set():
            try:
                self._q.put(obj, timeout=0.05)
                waited = time.perf_counter() - t0
                if waited > 0.001:   # an uncontended put is ~free
                    self.metrics.add_time("producer_blocked_s", waited)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self):
        try:
            it = iter(self._source)
            while not self._stop.is_set():
                t0 = time.perf_counter()
                if self._stack is None:
                    try:
                        item = next(it)
                    except StopIteration:
                        break
                    self.metrics.inc("batches_in")
                else:
                    items = []
                    while len(items) < self._stack:
                        try:
                            items.append(next(it))
                        except StopIteration:
                            break
                    self.metrics.inc("batches_in", len(items))
                    if len(items) < self._stack:
                        break   # ragged tail dropped (drop_last)
                    item = _stack_items(items)
                    self.metrics.inc("stacks")
                t1 = time.perf_counter()
                placed = self._place(item)
                self.metrics.observe(
                    "transfer_ms", (time.perf_counter() - t1) * 1e3)
                self.metrics.add_time("producer_busy_s",
                                      time.perf_counter() - t0)
                if not self._put(placed):
                    return
            self._put(self._END)
        except BaseException as e:  # noqa: BLE001 — propagated to consumer
            self.metrics.inc("producer_exceptions")
            self._put(e)

    # -- consumer ----------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted or self._stop.is_set():
            raise StopIteration   # finished, or close()d mid-epoch
        self.metrics.observe("queue_depth", self._q.qsize())
        t0 = time.perf_counter()
        while True:
            # short-poll so a concurrent close() ends the iteration
            # promptly instead of stranding this thread for the full
            # timeout on a drained queue
            if self._stop.is_set():
                self._exhausted = True
                raise StopIteration
            try:
                item = self._q.get(timeout=0.05)
                break
            except queue.Empty:
                if time.perf_counter() - t0 > self._timeout:
                    # the producer is hung: terminate the iterator so a
                    # retry fails fast instead of blocking another full
                    # timeout
                    self._stop.set()
                    self._exhausted = True
                    raise TimeoutError(
                        f"prefetcher {self.metrics.name!r}: no batch "
                        f"within {self._timeout}s (producer alive="
                        f"{self._thread.is_alive()})") from None
        self.metrics.add_time("host_blocked_s",
                              time.perf_counter() - t0)
        if item is self._END:
            self._exhausted = True
            raise StopIteration
        if isinstance(item, BaseException):
            self._exhausted = True
            raise item
        self.metrics.inc("batches_out")
        return item

    def close(self):
        """Stop the producer and release the queue. Idempotent; safe
        mid-epoch (the in-flight batch is discarded). "Promptly" is
        bounded by the source: a thread can't be interrupted inside a
        blocking ``next(source)``, so the join waits up to 5 s for the
        iterator to yield control (the daemon thread never blocks
        process exit either way)."""
        self._stop.set()
        try:
            while True:   # unblock a producer stuck on a full queue
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)
        from .. import profiler
        profiler.unregister_pipeline_source(self.metrics.name,
                                            self.metrics)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            if not self._stop.is_set():
                self._stop.set()
        except Exception:
            pass


def prefetch_to_device(iterator: Iterable, depth: int = 2,
                       sharding: Union[None, Callable, Any] = None,
                       stack: Optional[int] = None,
                       name: str = "prefetch") -> DevicePrefetcher:
    """Wrap any DataLoader/iterable in a background prefetcher that keeps
    ``depth`` batches resident on device ahead of the consumer.

        feed = prefetch_to_device(loader, depth=2)
        for ids, labels in feed:          # already jax.Arrays on device
            loss, params, opt_state = step(params, opt_state, k,
                                           ids, labels, lr)

    ``stack=K`` auto-stacks K source batches into the ``[K, B, ...]``
    layout of ``create_multistep_train_step(steps=K)``; ``sharding``
    takes a ``jax.sharding.Sharding`` or the ``shard_batch`` callable
    from ``create_sharded_train_step``. Build multichip shardings from
    the canonical vocabulary rather than inline specs::

        layout = paddle_tpu.distributed.default_layout()
        feed = prefetch_to_device(
            loader, sharding=NamedSharding(mesh, layout.batch()))

    Stats (queue depth, transfer latency, host/device-blocked split)
    ride ``paddle_tpu.profiler.pipeline_stats(name)``.
    """
    return DevicePrefetcher(iterator, depth=depth, sharding=sharding,
                            stack=stack, name=name)
