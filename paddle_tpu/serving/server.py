"""In-process dynamic-batching inference server.

The Server wraps one compiled model behind a thread-safe request queue, a
micro-batcher, a bucketed executable cache, and backpressure:

- ``submit()`` enqueues ONE example (input arrays WITHOUT the batch dim)
  and returns a Future; a worker thread coalesces pending requests of the
  same bucketed signature up to ``max_batch_size`` or ``batch_timeout_ms``.
- Shapes are padded to a small bucket set (powers of two on the batch axis
  and, optionally, each example's leading axis), so XLA compiles a bounded
  number of executables; compiled executables live in an LRU cache keyed
  on the padded signature.
- The queue is bounded: a full queue rejects with ServerOverloaded (load
  shedding), expired requests fail with DeadlineExceeded, and
  ``shutdown(drain=True)`` completes queued work before the worker exits.

Model kinds accepted:
- ``nn.Layer`` / ``jit.StaticFunction``: AOT-compiled per bucket via
  ``StaticFunction.compile_for`` (the jit signature-reuse path).
- ``jit.TranslatedLayer`` (a ``jit.save``d artifact, or a ``Predictor``
  via ``Config.enable_serving()``): the exported program's baked batch
  size is the single batch bucket; partial batches pad up to it.
- any plain callable mapping batched arrays -> batched array(s): counted
  per distinct signature but compiled by whatever the callable does.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import OrderedDict
from typing import List, Optional, Sequence

import numpy as np

from ..profiler import tracing
from .batcher import (DeadlineExceeded, Future, Request, RequestQueue,
                      ServerClosed, ServerOverloaded, ServingError)
from .bucketing import (bucket_example, next_bucket_strict, pow2_buckets,
                        stack_and_pad)
from .lifecycle import ServerLifecycleMixin
from .metrics import ServingMetrics

__all__ = ["Server", "ServingError", "ServerOverloaded", "DeadlineExceeded",
           "ServerClosed", "Future"]

_server_ids = itertools.count()


def _to_numpy(out):
    import jax

    from ..core.tensor import Tensor

    outs = list(out) if isinstance(out, (tuple, list)) else [out]
    # Tensor unwraps to its device buffer; any OTHER wrapper exposing
    # .numpy() (foreign tensor types a wrapped callable may return)
    # converts through it — device_get of an arbitrary object would
    # hand the client a 0-d object array around the wrapper
    outs = [o._data if isinstance(o, Tensor)
            else o.numpy() if not isinstance(o, np.ndarray)
            and callable(getattr(o, "numpy", None))
            else o for o in outs]
    # ONE batched D2H for the whole output list: a per-output np.asarray
    # is one serial blocking transfer each (what graft_lint GL505 flags)
    fetched = jax.device_get(outs)
    return [np.asarray(o) for o in fetched]


class _AotExecutor:
    """Per-bucket AOT compilation of a StaticFunction with an LRU
    executable cache — the compile count is exactly the number of cache
    misses, so a bounded bucket set provably bounds XLA work."""

    def __init__(self, static_fn, cache_size: int, metrics: ServingMetrics):
        self._sf = static_fn
        self._cache: "OrderedDict[tuple, object]" = OrderedDict()
        self._cache_size = max(1, cache_size)
        self._metrics = metrics
        self._lock = threading.Lock()   # warmup() may race the worker

    def run(self, stacked: List[np.ndarray]) -> List[np.ndarray]:
        import jax

        from ..core import random as _random

        key = tuple((a.shape, str(a.dtype)) for a in stacked)
        # The lock intentionally covers compile AND execute, not just the
        # cache dict: jax tracing is not thread-safe against concurrent
        # eager ops in this runtime — an eager key/array created on one
        # thread while another thread is mid-trace leaks into that trace
        # (UnexpectedTracerError, observed empirically with a warmup
        # compile racing a served batch). A warmup therefore delays
        # in-flight batches by one compile; that is the safe trade.
        with self._lock:
            compiled = self._cache.get(key)
            if compiled is None:
                with tracing.trace_span("serving::compile", cat="serving"):
                    compiled = self._sf.compile_for(
                        *[jax.ShapeDtypeStruct(a.shape, a.dtype)
                          for a in stacked])
                self._metrics.inc("compile_count")
                self._cache[key] = compiled
                while len(self._cache) > self._cache_size:
                    self._cache.popitem(last=False)
                    self._metrics.inc("cache_evictions")
            else:
                self._cache.move_to_end(key)
                self._metrics.inc("cache_hits")
            out = compiled(self._sf._state(),
                           _random.default_generator.next_key(), *stacked)
        # D2H of the finished batch happens OUTSIDE the lock: compiled()
        # dispatches async, so the download inside _to_numpy is where the
        # device wait actually lands — holding the lock through it would
        # serialize warmup compiles and concurrent callers behind the
        # whole batch execution
        return _to_numpy(out)


class _CallableExecutor:
    """Wraps a TranslatedLayer or plain callable. Compilation happens
    inside the callee (e.g. the exported program compiled at load), so
    'compile_count' counts first-seen signatures — still the quantity a
    bounded bucket set must keep bounded."""

    def __init__(self, fn, metrics: ServingMetrics):
        self._fn = fn
        self._seen = set()
        self._metrics = metrics
        self._lock = threading.Lock()

    def run(self, stacked: List[np.ndarray]) -> List[np.ndarray]:
        key = tuple((a.shape, str(a.dtype)) for a in stacked)
        # lock covers the call too: the callee may trace (exported.call
        # stages on first use), and tracing races eager ops on other
        # threads in this runtime — see _AotExecutor.run
        with self._lock:
            if key in self._seen:
                self._metrics.inc("cache_hits")
            else:
                self._seen.add(key)
                self._metrics.inc("compile_count")
            out = self._fn(*stacked)
        # conversion (the blocking D2H wait) deliberately OUTSIDE the
        # lock, as in _AotExecutor.run: converting under the lock
        # serialized every concurrent caller behind this batch's entire
        # device execution, not just its trace
        return _to_numpy(out)


class Server(ServerLifecycleMixin):
    """Dynamic-batching inference server over one model.

    Example::

        layer = paddle.jit.load(prefix)          # or an eval-mode Layer
        with serving.Server(layer, max_batch_size=8,
                            batch_timeout_ms=2.0) as srv:
            fut = srv.submit(ids)                # ONE example, no batch dim
            logits = fut.result(timeout=5.0)

    Parameters
    ----------
    model: Layer | StaticFunction | TranslatedLayer | callable.
    max_batch_size: largest number of requests coalesced per dispatch.
    batch_timeout_ms: how long a forming batch waits for stragglers.
    max_queue_size: bound on queued requests; beyond it submit() raises
        ServerOverloaded.
    batch_buckets: admissible padded batch sizes (default: powers of two
        up to max_batch_size).
    seq_buckets: admissible axis-0 lengths for each example array; None
        disables sequence padding (requests then group by exact shape).
        Right-padding the sequence axis is output-preserving for causal
        models only — see bucketing.py.
    pad_value: fill for padded positions (e.g. a pad token id).
    output_seq_axis: axis of each per-request OUTPUT that follows the
        input's axis-0 length; sliced back to the real length when
        sequence padding was applied (None disables).
    unpad_outputs: which output indices that slicing applies to; None
        (default) means every output whose ``output_seq_axis`` dim equals
        the padded length. Pass explicit indices for models with outputs
        whose dims can coincide with a sequence bucket (e.g. a pooled
        embedding of hidden size 32 next to seq_buckets=[32]) — the
        default shape test cannot tell those apart.
    executable_cache_size: LRU capacity for compiled executables.
    default_deadline_ms: per-request deadline applied when submit() gets
        none; None means requests wait indefinitely.
    """

    def __init__(self, model, *, max_batch_size: int = 8,
                 batch_timeout_ms: float = 2.0, max_queue_size: int = 128,
                 batch_buckets: Optional[Sequence[int]] = None,
                 seq_buckets: Optional[Sequence[int]] = None,
                 pad_value=0, output_seq_axis: Optional[int] = 0,
                 unpad_outputs: Optional[Sequence[int]] = None,
                 executable_cache_size: int = 16,
                 default_deadline_ms: Optional[float] = None,
                 name: Optional[str] = None):
        from ..jit import StaticFunction, TranslatedLayer
        from ..nn.layer.layers import Layer

        self.name = name or f"serving_server_{next(_server_ids)}"
        self._metrics = ServingMetrics(self.name)
        self._fixed_example_shapes = None

        if isinstance(model, TranslatedLayer):
            # the exported program's shapes are baked: its batch dim is
            # the one (and only) batch bucket, partial batches pad to it
            specs = model.input_spec
            if not specs:
                raise ValueError(
                    "TranslatedLayer has no input metadata; re-save with "
                    "this framework's jit.save")
            baked_batch = int(specs[0].shape[0])
            for s in specs:
                if int(s.shape[0]) != baked_batch:
                    raise ValueError(
                        "serving requires every input's leading dim to be "
                        f"the batch dim; got {[s.shape for s in specs]}")
            if seq_buckets is not None:
                raise ValueError(
                    "seq_buckets is not supported for a loaded "
                    "TranslatedLayer (its shapes are baked at export); "
                    "serve the Layer itself to get sequence bucketing")
            max_batch_size = baked_batch
            batch_buckets = [baked_batch]
            self._fixed_example_shapes = [tuple(s.shape[1:]) for s in specs]
            self._executor = _CallableExecutor(model, self._metrics)
        elif isinstance(model, StaticFunction):
            self._executor = _AotExecutor(model, executable_cache_size,
                                          self._metrics)
        elif isinstance(model, Layer):
            self._executor = _AotExecutor(StaticFunction(model),
                                          executable_cache_size,
                                          self._metrics)
        elif callable(model):
            self._executor = _CallableExecutor(model, self._metrics)
        else:
            raise TypeError(
                f"cannot serve a {type(model).__name__}: expected a Layer, "
                "StaticFunction, TranslatedLayer, or callable")

        if max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        self.max_batch_size = int(max_batch_size)
        self.batch_timeout_s = float(batch_timeout_ms) / 1e3
        self._batch_buckets = sorted(batch_buckets) if batch_buckets \
            else pow2_buckets(self.max_batch_size)
        if max(self._batch_buckets) < self.max_batch_size:
            raise ValueError(
                f"largest batch bucket {max(self._batch_buckets)} < "
                f"max_batch_size {self.max_batch_size}")
        self._seq_buckets = sorted(seq_buckets) if seq_buckets else None
        self._pad_value = pad_value
        self._output_seq_axis = output_seq_axis
        self._unpad_outputs = (None if unpad_outputs is None
                               else set(unpad_outputs))
        self._default_deadline_s = (None if default_deadline_ms is None
                                    else float(default_deadline_ms) / 1e3)

        self._queue = RequestQueue(max_queue_size)
        self._metrics.set_depth_gauge(self._queue.qsize)
        self._stop = threading.Event()
        self._drained = threading.Event()
        self._closed = False
        self._lock = threading.Lock()
        from ..profiler import register_serving_source
        register_serving_source(self.name, self._metrics)
        self._worker = threading.Thread(target=self._run_loop,
                                        name=self.name, daemon=True)
        self._worker.start()

    # -- client API --------------------------------------------------------
    def submit(self, *args, deadline_ms: Optional[float] = None) -> Future:
        """Enqueue one request. Each positional arg is ONE example (no
        batch dim). Returns a Future; full queue raises ServerOverloaded,
        a closed server raises ServerClosed."""
        # _closed is guarded by _lock (shutdown() writes it under the
        # lock); an unguarded read here was the check-then-act race
        # graft_lint GL202 was built to catch — the queue's own closed
        # check would still reject the request, but only after this
        # thread had already counted it into "submitted", skewing the
        # drain invariant on the shutdown path
        if self._is_closed():
            raise ServerClosed("server is shutting down")
        if not args:
            raise ValueError("submit() needs at least one input array")
        # graft-lint: disable=GL505 -- admission-side host staging:
        # client examples arrive host-resident and must be host-stacked
        # and padded (stack_and_pad) before the ONE batched upload
        arrs = tuple(np.asarray(a.numpy() if hasattr(a, "numpy") else a)
                     for a in args)
        if self._fixed_example_shapes is not None:
            if len(arrs) != len(self._fixed_example_shapes):
                raise ValueError(
                    f"model takes {len(self._fixed_example_shapes)} "
                    f"inputs, got {len(arrs)}")
            for a, want in zip(arrs, self._fixed_example_shapes):
                if tuple(a.shape) != want:
                    raise ValueError(
                        f"example shape {tuple(a.shape)} != exported "
                        f"example shape {want} (submit per-example arrays "
                        "without the batch dim)")
        key = tuple((bucket_example(a, self._seq_buckets), str(a.dtype))
                    for a in arrs)
        deadline_s = (float(deadline_ms) / 1e3 if deadline_ms is not None
                      else self._default_deadline_s)
        req = Request(arrs, key,
                      None if deadline_s is None
                      else time.monotonic() + deadline_s)
        req.real_len = int(arrs[0].shape[0]) if arrs[0].ndim else 0
        req.padded_len = key[0][0][0] if arrs[0].ndim else 0
        # trace_id rides in from the caller's TraceContext (the wire
        # handler enters one per frame) — the enqueue instant is the
        # server-side start of this request's timeline
        tracing.trace_event("serving::submit", cat="serving",
                            server=self.name)
        # counted BEFORE put so drain()'s submitted==settled invariant
        # never transiently undercounts an in-flight request
        self._metrics.inc("submitted")
        try:
            self._queue.put(req)
        except ServerOverloaded:
            self._metrics.inc("submitted", -1)
            self._metrics.inc("rejected_overload")
            raise
        except ServerClosed:
            self._metrics.inc("submitted", -1)
            raise
        return req.future

    def run(self, *args, timeout: Optional[float] = None,
            deadline_ms: Optional[float] = None):
        """Synchronous submit + wait."""
        if timeout is not None and deadline_ms is None:
            deadline_ms = timeout * 1e3
        return self.submit(*args, deadline_ms=deadline_ms).result(timeout)

    def warmup(self, *example_args,
               batch_sizes: Optional[Sequence[int]] = None) -> int:
        """Pre-compile executables: pads ``example_args`` (one example,
        no batch dim) to its sequence bucket and runs it at every batch
        bucket (or the given ``batch_sizes``). Returns the number of new
        compiles this warmup caused."""
        arrs = [np.asarray(a.numpy() if hasattr(a, "numpy") else a)
                for a in example_args]
        before = self._metrics["compile_count"]
        for b in (batch_sizes or self._batch_buckets):
            stacked = []
            for a in arrs:
                shp = bucket_example(a, self._seq_buckets)
                arr, _ = stack_and_pad([a], shp, b, self._pad_value)
                stacked.append(arr)
            self._executor.run(stacked)
        return self._metrics["compile_count"] - before

    def stats(self) -> dict:
        """Current metrics snapshot (also available via
        ``paddle_tpu.profiler.serving_stats()``)."""
        return self._metrics.snapshot()

    @property
    def metrics(self) -> ServingMetrics:
        return self._metrics

    def queue_depth(self) -> int:
        return self._queue.qsize()

    def bucket_config(self) -> dict:
        """The shape-bucket configuration requests execute under. The
        serving router requires identical configs across its backends —
        that is what makes a failed-over request land on an executable
        the target already compiled."""
        return {"batch_buckets": list(self._batch_buckets),
                "seq_buckets": (list(self._seq_buckets)
                                if self._seq_buckets else None),
                "max_batch_size": self.max_batch_size,
                "pad_value": self._pad_value}

    # -- lifecycle ---------------------------------------------------------
    # drain/close/__enter__/__exit__/__del__ come from ServerLifecycleMixin
    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None):
        """Stop admitting requests; with ``drain`` finish queued work,
        otherwise abort queued requests with ServerClosed. Idempotent."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._queue.close()
        if drain:
            self.drain(timeout)
        else:
            for r in self._queue.flush():
                r.future.set_exception(
                    ServerClosed("server shut down before execution"))
                self._metrics.inc("failed")
        self._stop.set()
        self._worker.join(timeout if timeout is not None else 10.0)
        from ..profiler import unregister_serving_source
        # identity-checked: a newer server reusing this name keeps its
        # registry entry when this one shuts down
        unregister_serving_source(self.name, self._metrics)

    # -- worker ------------------------------------------------------------
    def _run_loop(self):
        while True:
            batch, expired = self._queue.next_batch(
                self.max_batch_size, self.batch_timeout_s, self._stop)
            now = time.monotonic()
            for r in expired:
                self._metrics.observe("queue_wait_ms",
                                      (now - r.t_submit) * 1e3)
                r.future.set_exception(DeadlineExceeded(
                    "deadline passed while queued "
                    f"({(now - r.t_submit) * 1e3:.1f} ms in queue)"))
                self._metrics.inc("expired")   # after set: drain invariant
            if batch is None:           # idle and stop requested
                if self._queue.qsize() == 0:
                    return
                continue
            if not batch:
                continue
            try:
                self._execute(batch)
            except Exception as e:  # noqa: BLE001 — the worker must survive
                for r in batch:
                    if not r.future.done():
                        r.future.set_exception(
                            ServingError(f"batch processing failed: {e!r}"))
                        self._metrics.inc("failed")

    def _execute(self, batch: List[Request]):
        n = len(batch)
        # invariant: n <= max_batch_size <= max bucket; a violation is a
        # bug and raises BucketOverflow loudly (the old silent
        # None-fallback masked it as a mis-sized batch)
        bb = next_bucket_strict(n, self._batch_buckets,
                                "coalesced batch size")
        t0 = time.monotonic()
        for r in batch:
            self._metrics.observe("queue_wait_ms",
                                  (t0 - r.t_submit) * 1e3)
        example_shapes = [shape for shape, _ in batch[0].key]
        stacked, real, padded = [], 0, 0
        for i, shp in enumerate(example_shapes):
            arr, real_i = stack_and_pad([r.args[i] for r in batch], shp,
                                        bb, self._pad_value)
            stacked.append(arr)
            real += real_i
            padded += int(arr.size)
        try:
            with tracing.trace_span("serving::execute", cat="serving",
                                    batch=n, bucket=bb):
                outs = self._executor.run(stacked)
        except Exception as e:  # noqa: BLE001 — fail the batch, not the server
            for r in batch:
                r.future.set_exception(
                    ServingError(f"batch execution failed: {e!r}"))
                self._metrics.inc("failed")
            return
        self._metrics.inc("batches")
        self._metrics.observe("batch_size", n)
        if padded:
            self._metrics.observe("pad_waste", 1.0 - real / padded)
        t1 = time.monotonic()
        for i, r in enumerate(batch):
            rows = [o[i] for o in outs]
            if (self._output_seq_axis is not None
                    and r.padded_len != r.real_len):
                ax = self._output_seq_axis
                rows = [row[(slice(None),) * ax + (slice(0, r.real_len),)]
                        if (self._unpad_outputs is None
                            or j in self._unpad_outputs)
                        and row.ndim > ax and row.shape[ax] == r.padded_len
                        else row for j, row in enumerate(rows)]
            r.future.set_result(rows[0] if len(rows) == 1 else tuple(rows))
            self._metrics.inc("completed")
            self._metrics.observe("latency_ms", (t1 - r.t_submit) * 1e3)
