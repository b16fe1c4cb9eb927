"""Fault-tolerant front-end router over N serving backends.

One ``Router`` fans two request kinds over a fleet of ``Backend``s
(in-process today, remote transports later):

- one-shots (``submit`` → Future), the ``serving.Server`` contract;
- token streams (``submit_decode`` → DecodeStream), the
  ``serving.decode.DecodeServer`` contract.

Robustness machinery, per backend: health state from active heartbeat
probes + passive request accounting (HEALTHY/DEGRADED/DOWN), a circuit
breaker (closed → open on consecutive failures, half-open single-probe
recovery), and deadline-aware retries under a global retry budget.
Routing is **sticky by shape bucket**: requests of one (seq bucket,
page bucket) signature keep landing on the same backend, and because
every backend shares one bucket config (validated at construction), a
failover re-lands on an executable the target has already compiled —
never a cold XLA compile in the middle of an outage. When the sticky
target is unusable, placement falls back to weighted-least-loaded among
non-DOWN backends (DEGRADED capacity is de-weighted 3x, not excluded).

**Loss-free decode failover**: the router relays backend stream tokens
into the client stream and checks backend liveness between tokens. When
a backend dies mid-stream, the already-relayed tokens are folded into
the effective prompt (the same preemption trick the decode scheduler
uses) and the request is re-admitted on another backend — the resumed
greedy stream is bit-identical to an uninterrupted one, and no token is
lost or double-emitted.

Overload behavior: the router's own admission queue is bounded
(``RouterOverloaded`` at submit — load shedding), per-backend
``ServerOverloaded`` rejections rotate the request across the fleet,
and when EVERY backend stays saturated until the deadline (or the
shed timeout) the request is shed with ``RouterOverloaded`` rather than
queued unboundedly.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import OrderedDict
from typing import List, Optional, Sequence

import numpy as np

from ...profiler import tracing
from ..batcher import (DeadlineExceeded, Future, ServerClosed,
                       ServerOverloaded, ServingError)
from ..bucketing import BucketOverflow, bucket_example, next_bucket_strict
from ..decode.kvcache import pages_for
from ..decode.scheduler import AdmissionQueue, DecodeStream
from ..lifecycle import ServerLifecycleMixin
from .backend import Backend
from .breaker import BreakerState, CircuitBreaker
from .errors import BackendDied, BackendUnavailable, RouterOverloaded
from .health import BackendHealth, HealthState
from .metrics import RouterMetrics
from .retry import RetryPolicy

__all__ = ["Router"]

_router_ids = itertools.count()


class _RouterRequest:
    """One queued routed request (either kind). The dispatch worker that
    pops it is its sole owner — settlement needs no locking beyond what
    Future/DecodeStream already do."""

    __slots__ = ("kind", "args", "key", "prompt", "max_new_tokens",
                 "eos_id", "deadline", "future", "stream", "t_submit",
                 "settled", "trace_id", "fo_span")

    def __init__(self, kind: str, key: tuple, deadline: Optional[float]):
        self.kind = kind
        self.key = key
        self.deadline = deadline        # absolute monotonic or None
        self.args = None
        self.prompt = None
        self.max_new_tokens = 0
        self.eos_id = None
        self.future = Future() if kind == "oneshot" else None
        self.stream = DecodeStream() if kind == "decode" else None
        self.t_submit = time.monotonic()
        self.settled = False
        self.trace_id = None
        # open while a decode failover is in progress: starts at the
        # mid-stream death, ends at the successful re-admission
        # elsewhere — the merged timeline shows the failover GAP as one
        # explicit span. One that never re-admits is dropped unrecorded.
        self.fo_span = None

    def expired(self, now: Optional[float] = None) -> bool:
        return (self.deadline is not None
                and (now if now is not None else time.monotonic())
                > self.deadline)

    def remaining_s(self, now: Optional[float] = None) -> Optional[float]:
        if self.deadline is None:
            return None
        return self.deadline - (now if now is not None
                                else time.monotonic())

    # -- settlement (exactly once; owner thread only) ----------------------
    def settle_result(self, value) -> None:
        self.settled = True
        self.future.set_result(value)

    def settle_exc(self, exc: BaseException) -> None:
        if self.settled:
            return
        self.settled = True
        if self.future is not None:
            self.future.set_exception(exc)
        else:
            self.stream._fail(exc)

    def finish_stream(self, reason: str) -> None:
        self.settled = True
        self.stream._finish(reason)


class _BackendEntry:
    """One backend plus its router-side robustness state."""

    __slots__ = ("index", "backend", "health", "breaker")

    def __init__(self, index: int, backend: Backend,
                 health: BackendHealth, breaker: CircuitBreaker):
        self.index = index
        self.backend = backend
        self.health = health
        self.breaker = breaker


class Router(ServerLifecycleMixin):
    """Fault-tolerant request router over N serving backends.

    Example::

        backends = [InProcessBackend(f"host{i}", decode_server=srv_i)
                    for i, srv_i in enumerate(servers)]
        with Router(backends) as router:
            stream = router.submit_decode(prompt, max_new_tokens=32)
            tokens = stream.result(timeout=30)

    Parameters
    ----------
    backends: the fleet. Every backend must expose an IDENTICAL
        ``bucket_config()`` — shared buckets are what keep failover on
        warm executables (mismatch raises ValueError).
    max_queue_size: router admission bound; beyond it submit raises
        ``RouterOverloaded``.
    num_workers: dispatch threads. A decode stream occupies its worker
        for the stream's lifetime, so size this at least the expected
        concurrent stream count.
    default_deadline_ms: applied when submit passes none (None = wait
        forever — discouraged behind a router).
    probe_interval_ms / probe_timeout_ms: active health-probe cadence
        and per-probe answer deadline (a blackholed backend fails
        probes by timeout).
    down_after / degrade_error_rate / degrade_latency_ms: health knobs
        (see ``health.BackendHealth``).
    failure_threshold / breaker_reset_ms: circuit-breaker knobs (see
        ``breaker.CircuitBreaker``).
    retry: a ``RetryPolicy`` (default: 4 attempts, 5 ms base backoff,
        20% retry budget).
    hedge_after_ms: when set, a one-shot still unanswered after this
        long is duplicated onto a second healthy backend and the first
        answer wins (tail-latency insurance; off by default).
    shed_timeout_ms: how long a request with NO deadline may wait for
        any backend to become available before it is shed.
    max_decode_failovers: bound on mid-stream failovers per request
        (each failover re-prefills elsewhere; the deadline is the
        primary bound, this the belt-and-braces one).
    close_backends: when True, ``shutdown`` also closes the backends.
    """

    def __init__(self, backends: Sequence[Backend], *,
                 max_queue_size: int = 256, num_workers: int = 8,
                 default_deadline_ms: Optional[float] = None,
                 probe_interval_ms: float = 50.0,
                 probe_timeout_ms: float = 250.0,
                 down_after: int = 2, degrade_error_rate: float = 0.5,
                 degrade_latency_ms: Optional[float] = None,
                 failure_threshold: int = 3,
                 breaker_reset_ms: float = 1000.0,
                 retry: Optional[RetryPolicy] = None,
                 hedge_after_ms: Optional[float] = None,
                 shed_timeout_ms: float = 5000.0,
                 max_decode_failovers: int = 8,
                 relay_poll_ms: float = 2.0, poll_ms: float = 5.0,
                 close_backends: bool = False,
                 name: Optional[str] = None):
        backends = list(backends)
        if not backends:
            raise ValueError("Router needs at least one backend")
        ids = [b.backend_id for b in backends]
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate backend ids: {ids}")
        cfg0 = backends[0].bucket_config()
        for b in backends[1:]:
            if b.bucket_config() != cfg0:
                raise ValueError(
                    "all backends must share one bucket config so "
                    "failover lands on warm executables; "
                    f"{backends[0].backend_id!r} has {cfg0} but "
                    f"{b.backend_id!r} has {b.bucket_config()}")
        self._cfg = cfg0

        self.name = name or f"serving_router_{next(_router_ids)}"
        self._metrics = RouterMetrics(self.name)
        self._retry = retry if retry is not None else RetryPolicy()
        self._default_deadline_s = (None if default_deadline_ms is None
                                    else float(default_deadline_ms) / 1e3)
        self._probe_interval_s = float(probe_interval_ms) / 1e3
        self._probe_timeout_s = float(probe_timeout_ms) / 1e3
        self._hedge_after_s = (None if hedge_after_ms is None
                               else float(hedge_after_ms) / 1e3)
        self._shed_timeout_s = float(shed_timeout_ms) / 1e3
        self._max_decode_failovers = int(max_decode_failovers)
        self._relay_poll_s = float(relay_poll_ms) / 1e3
        self._poll_s = float(poll_ms) / 1e3
        self._close_backends = bool(close_backends)

        def _transition_counter():
            m = self._metrics

            def on_transition(old, new):
                m.inc({BreakerState.OPEN: "breaker_open",
                       BreakerState.HALF_OPEN: "breaker_half_open",
                       BreakerState.CLOSED: "breaker_close"}[new])
            return on_transition

        self._backends: List[_BackendEntry] = []
        for i, b in enumerate(backends):
            self._backends.append(_BackendEntry(
                i, b,
                BackendHealth(down_after=down_after,
                              degrade_error_rate=degrade_error_rate,
                              degrade_latency_ms=degrade_latency_ms),
                CircuitBreaker(failure_threshold=failure_threshold,
                               reset_timeout_s=breaker_reset_ms / 1e3,
                               on_transition=_transition_counter())))

        # LRU-bounded: with no seq buckets a one-shot key embeds the
        # exact example shape, so an unbounded dict would grow one
        # permanent entry per distinct length for the router's lifetime
        self._sticky: "OrderedDict[tuple, str]" = OrderedDict()
        self._sticky_cap = 256
        self._sticky_lock = threading.Lock()
        self._queue = AdmissionQueue(max_queue_size)
        self._metrics.set_depth_gauge(self._queue.qsize)
        self._metrics.set_backends_fn(self._backend_states)

        self._stop = threading.Event()
        self._abort = False
        self._closed = False
        self._lock = threading.Lock()
        from ...profiler import register_router_source
        register_router_source(self.name, self._metrics)
        self._workers = [
            threading.Thread(target=self._dispatch_loop,
                             name=f"{self.name}_w{i}", daemon=True)
            for i in range(max(1, int(num_workers)))]
        for w in self._workers:
            w.start()
        # one prober per backend: a blackholed host parks only ITS
        # prober for the probe timeout, never delaying DOWN detection
        # or half-open recovery probes of the other backends
        self._probers = [
            threading.Thread(target=self._health_loop, args=(e,),
                             name=f"{self.name}_health{e.index}",
                             daemon=True)
            for e in self._backends]
        for p in self._probers:
            p.start()

    # -- client API --------------------------------------------------------
    def _deadline(self, deadline_ms: Optional[float]) -> Optional[float]:
        s = (float(deadline_ms) / 1e3 if deadline_ms is not None
             else self._default_deadline_s)
        return None if s is None else time.monotonic() + s

    def _enqueue(self, rr: _RouterRequest):
        # counted BEFORE put: drain()'s submitted==settled invariant
        self._metrics.inc("submitted")
        try:
            self._queue.put(rr)
        except ServerOverloaded:
            self._metrics.inc("submitted", -1)
            self._metrics.inc("rejected_overload")
            raise RouterOverloaded(
                f"router queue full ({self._queue.max_depth}); "
                "retry with backoff") from None
        except ServerClosed:
            self._metrics.inc("submitted", -1)
            raise

    def _stamp_trace(self, rr: _RouterRequest) -> None:
        """Flight-recorder admission stamp: the router is the trace ROOT
        for routed requests. The id minted (or inherited from the
        caller's ``TraceContext``) here rides the request through every
        downstream hop — dispatch spans, wire frame meta, host-side
        decode lifecycle — so ``tools/trace_merge.py`` can stitch one
        request's timeline across processes. No-cost when tracing is
        disabled (``trace_id`` stays None, nothing is stamped)."""
        tid = tracing.current_trace_id()
        if tid is None and tracing.tracing_enabled():
            tid = tracing.new_trace_id()
        rr.trace_id = tid
        tracing.trace_event("router::submit", cat="router", trace_id=tid,
                            kind=rr.kind)

    def submit(self, *args, deadline_ms: Optional[float] = None) -> Future:
        """Route one one-shot request (per-example arrays, no batch dim —
        the ``Server.submit`` contract). Returns a Future; a full router
        queue raises ``RouterOverloaded``, a closed router
        ``ServerClosed``."""
        if self._is_closed():
            raise ServerClosed("router is shutting down")
        if "oneshot" not in self._cfg:
            raise TypeError("no backend serves one-shot requests")
        if not args:
            raise ValueError("submit() needs at least one input array")
        # graft-lint: disable=GL505 -- admission-side host staging:
        # client examples arrive host-resident and are host-stacked by
        # the chosen backend's Server before its ONE batched upload
        arrs = tuple(np.asarray(a.numpy() if hasattr(a, "numpy") else a)
                     for a in args)
        seq_buckets = self._cfg["oneshot"]["seq_buckets"]
        key = ("oneshot",) + tuple(
            (bucket_example(a, seq_buckets), str(a.dtype)) for a in arrs)
        rr = _RouterRequest("oneshot", key, self._deadline(deadline_ms))
        rr.args = arrs
        self._stamp_trace(rr)
        self._retry.on_request()
        self._enqueue(rr)
        return rr.future

    def run(self, *args, timeout: Optional[float] = None,
            deadline_ms: Optional[float] = None):
        """Synchronous submit + wait."""
        if timeout is not None and deadline_ms is None:
            deadline_ms = timeout * 1e3
        return self.submit(*args, deadline_ms=deadline_ms).result(timeout)

    def submit_decode(self, prompt, *,
                      max_new_tokens: Optional[int] = None,
                      eos_id: Optional[int] = None,
                      deadline_ms: Optional[float] = None) -> DecodeStream:
        """Route one generation request. Returns a DecodeStream whose
        tokens keep flowing across backend failovers (loss-free: resumed
        greedy output is bit-identical, nothing re-emitted)."""
        if self._is_closed():
            raise ServerClosed("router is shutting down")
        if "decode" not in self._cfg:
            raise TypeError("no backend serves decode requests")
        cfg = self._cfg["decode"]
        # graft-lint: disable=GL505 -- admission-side host staging:
        # prompts arrive host-resident; the device upload is the chosen
        # backend's prefill step itself
        arr = np.asarray(prompt.numpy() if hasattr(prompt, "numpy")
                         else prompt).reshape(-1).astype(np.int32)
        if arr.size == 0:
            raise ValueError("prompt must contain at least one token")
        mnt = int(max_new_tokens if max_new_tokens is not None
                  else cfg["max_context"] - arr.size)
        if mnt < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # fail over-budget requests here, with the backends' own checks
        sb = next_bucket_strict(int(arr.size), cfg["prefill_buckets"],
                                "prompt length")
        if arr.size + mnt > cfg["max_context"]:
            raise BucketOverflow(
                f"prompt ({arr.size}) + max_new_tokens ({mnt}) exceeds "
                f"max_context {cfg['max_context']}")
        pb = next_bucket_strict(
            pages_for(min(arr.size + mnt, cfg["max_context"]),
                      cfg["page_len"]),
            cfg["page_buckets"], "page count")
        rr = _RouterRequest("decode", ("decode", sb, pb),
                            self._deadline(deadline_ms))
        rr.prompt = arr
        rr.max_new_tokens = mnt
        rr.eos_id = eos_id
        self._stamp_trace(rr)
        self._retry.on_request()
        self._enqueue(rr)
        return rr.stream

    def generate(self, prompt, *, max_new_tokens: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 timeout: Optional[float] = None) -> np.ndarray:
        """Synchronous submit_decode + wait; the generated token ids."""
        deadline_ms = None if timeout is None else timeout * 1e3
        return self.submit_decode(
            prompt, max_new_tokens=max_new_tokens, eos_id=eos_id,
            deadline_ms=deadline_ms).result(timeout)

    def stats(self) -> dict:
        """Metrics snapshot (also via ``profiler.router_stats()``)."""
        return self._metrics.snapshot()

    @property
    def metrics(self) -> RouterMetrics:
        return self._metrics

    def queue_depth(self) -> int:
        return self._queue.qsize()

    def backends(self) -> List[Backend]:
        return [e.backend for e in self._backends]

    def scrape_fleet(self, timeout_s: float = 1.0) -> str:
        """One Prometheus-style text scrape over the whole fleet: the
        router's own metrics plus every backend's ``host_stats()``
        (one-shot/decode server snapshots incl. the latency histograms,
        transport counters), flattened to ``name value`` exposition
        lines under ``paddle_tpu_backend_<id>_...``. A backend that
        cannot answer within ``timeout_s`` (dead, blackholed) scrapes
        as its ``..._up 0`` line alone — a down host must not wedge or
        empty the fleet scrape. Names pass through the collision-safe
        sanitizer, so hostile backend ids cannot collapse onto one
        series."""
        from ...profiler import _flatten_scrape, _sanitize
        lines: list = []
        _flatten_scrape(f"paddle_tpu_router_{self.name}",
                        self._metrics.snapshot(), lines)
        for e in self._backends:
            prefix = f"paddle_tpu_backend_{e.backend.backend_id}"
            try:
                st = e.backend.host_stats(timeout=timeout_s)
            except Exception:
                lines.append(f"{_sanitize(prefix)}_up 0")
                continue
            lines.append(f"{_sanitize(prefix)}_up 1")
            _flatten_scrape(prefix, st, lines)
        return "\n".join(lines) + "\n"

    def _backend_states(self) -> dict:
        out = {}
        for e in self._backends:
            st = {"health": e.health.snapshot(),
                  "breaker": e.breaker.state,
                  "breaker_transitions":
                      [[round(t, 3), a, b]
                       for t, a, b in e.breaker.transitions()]}
            try:
                st["load"] = float(e.backend.load())
            except Exception:
                st["load"] = -1.0
            out[e.backend.backend_id] = st
        return out

    # -- lifecycle ---------------------------------------------------------
    # drain/close/__enter__/__exit__/__del__ come from ServerLifecycleMixin
    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None):
        """Stop admitting; with ``drain`` finish queued and in-flight
        work, otherwise abort it with ServerClosed. Idempotent."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._queue.close()
        if drain:
            self.drain(timeout)
        else:
            self._abort = True
        self._stop.set()
        for p in self._probers:
            p.join(max(1.0, self._probe_timeout_s
                       + self._probe_interval_s * 3))
        for w in self._workers:
            w.join(timeout if timeout is not None else 10.0)
        for r in self._queue.flush():
            r.settle_exc(ServerClosed("router shut down before execution"))
            self._metrics.inc("failed")
        if self._close_backends:
            for e in self._backends:
                try:
                    e.backend.close()
                except Exception:
                    pass
        from ...profiler import unregister_router_source
        unregister_router_source(self.name, self._metrics)

    # -- health loop (graft_lint hot-path root) ----------------------------
    def _health_loop(self, e: _BackendEntry):
        """Active prober for ONE backend: a trivial round-trip per tick.
        An OPEN breaker suppresses probes until its reset dwell, at
        which point the probe itself becomes the half-open trial."""
        while not self._stop.wait(self._probe_interval_s):
            br = e.breaker
            if br.state != BreakerState.CLOSED and not br.allow():
                continue
            self._metrics.inc("probes")
            try:
                lat = e.backend.probe(self._probe_timeout_s)
            except Exception:
                self._metrics.inc("probe_failures")
                e.health.record_probe(False)
                br.record_failure()
                continue
            e.health.record_probe(True, lat * 1e3)
            br.record_success()

    # -- dispatch (graft_lint hot-path root) -------------------------------
    def _dispatch_loop(self):
        """One worker: pop a request, drive it to settlement (including
        retries and failovers), repeat. A decode stream holds its worker
        until the stream finishes."""
        while True:
            rr, dropped = self._queue.pop_ready()
            now = time.monotonic()
            for r in dropped:
                r.settle_exc(DeadlineExceeded("deadline passed in router "
                                              "queue"))
                self._metrics.inc("expired")
            if rr is None:
                if self._stop.is_set():
                    return
                self._queue.wait_nonempty(self._poll_s)
                continue
            if self._abort:
                rr.settle_exc(
                    ServerClosed("router shut down before execution"))
                self._metrics.inc("failed")
                continue
            self._metrics.observe("queue_wait_ms",
                                  (now - rr.t_submit) * 1e3)
            try:
                # the dispatch worker runs under the request's trace id:
                # every backend call below (and the wire client's frame
                # meta) picks it up from the thread context
                with tracing.TraceContext(rr.trace_id):
                    if rr.kind == "decode":
                        self._dispatch_decode(rr)
                    else:
                        self._dispatch_oneshot(rr)
            except Exception as e:  # noqa: BLE001 — the worker must survive
                if not rr.settled:
                    rr.settle_exc(
                        ServingError(f"router dispatch failed: {e!r}"))
                    self._metrics.inc("failed")
            finally:
                if rr.fo_span is not None:
                    rr.fo_span.drop()
                    rr.fo_span = None

    # -- placement ---------------------------------------------------------
    def _pick_backend(self, key: tuple,
                      excluded: set) -> Optional[_BackendEntry]:
        """Sticky-first placement among usable backends; least-loaded
        fallback reassigns the sticky key (so the NEXT request of this
        bucket lands warm on the same target). Returns None when no
        backend is usable right now.

        Breaker subtlety: candidates are primarily those with CLOSED
        breakers — ``allow()`` is only consulted when no closed backend
        exists, because on an OPEN-but-eligible breaker it admits the
        single half-open trial, and a candidate we then did not pick
        would have consumed that trial for nothing."""
        usable = [e for e in self._backends
                  if e.backend.backend_id not in excluded
                  and e.health.state != HealthState.DOWN]
        closed = [e for e in usable
                  if e.breaker.state == BreakerState.CLOSED]
        with self._sticky_lock:
            sid = self._sticky.get(key)
        if closed:
            pool = closed
        else:
            # no closed breaker: offer the request as the half-open
            # trial of exactly ONE open breaker (sticky owner first) —
            # calling allow() on every candidate would consume the
            # single trial of backends we then don't dispatch to,
            # wedging them in HALF_OPEN for a full dwell
            pool = None
            for e in sorted(usable,
                            key=lambda e: (e.backend.backend_id != sid,
                                           e.index)):
                if e.breaker.allow():
                    pool = [e]
                    break
            if pool is None:
                return None
        for e in pool:
            if e.backend.backend_id == sid:
                self._touch_sticky(key)
                return e

        def score(e: _BackendEntry):
            w = 3.0 if e.health.state == HealthState.DEGRADED else 1.0
            try:
                load = float(e.backend.load())
            except Exception:
                load = float("inf")
            return (w * (load + 1.0), e.index)

        chosen = min(pool, key=score)
        with self._sticky_lock:
            prev = self._sticky.get(key)
            self._sticky[key] = chosen.backend.backend_id
            self._sticky.move_to_end(key)
            while len(self._sticky) > self._sticky_cap:
                self._sticky.popitem(last=False)
        if prev is not None and prev != chosen.backend.backend_id:
            self._metrics.inc("sticky_moves")
        return chosen

    def _touch_sticky(self, key: tuple) -> None:
        with self._sticky_lock:
            if key in self._sticky:
                self._sticky.move_to_end(key)

    def _record_backend_failure(self, entry: _BackendEntry,
                                exc: BaseException) -> None:
        """Classify one backend failure into the health model: a
        transport death (host gone) is a reachability signal that can
        mark the backend DOWN; anything else is a quality signal for
        the DEGRADED error-rate window. Both count against the
        breaker."""
        if isinstance(exc, (BackendDied, ServerClosed)):
            entry.health.record_death()
        else:
            entry.health.record_request(False)
        entry.breaker.record_failure()

    def sticky_assignment(self) -> dict:
        """Snapshot of the sticky (bucket -> backend id) table."""
        with self._sticky_lock:
            return dict(self._sticky)

    # -- retry/shed helpers ------------------------------------------------
    def _backoff_for_retry(self, rr: _RouterRequest, attempt: int) -> bool:
        """Gate + sleep before retry ``attempt``; False means the caller
        must settle the request with a typed error instead."""
        if not self._retry.allows_attempt(attempt):
            return False
        delay = self._retry.backoff_s(attempt - 1)
        if not self._retry.fits_deadline(delay, rr.remaining_s()):
            return False     # never retry past the deadline
        if not self._retry.try_acquire():
            self._metrics.inc("retry_budget_exhausted")
            return False
        self._metrics.inc("retries")
        self._metrics.observe("backoff_ms", delay * 1e3)
        time.sleep(delay)
        return True

    def _settle_unserved(self, rr: _RouterRequest, last_exc,
                         overload_only: bool, attempt: int) -> None:
        """Typed terminal error for a request no backend could serve."""
        if rr.expired():
            rr.settle_exc(DeadlineExceeded(
                f"deadline passed in router after {attempt} attempt(s); "
                f"last error: {last_exc!r}"))
            self._metrics.inc("expired")
            return
        if overload_only and last_exc is not None:
            rr.settle_exc(RouterOverloaded(
                "every backend is saturated; request shed after "
                f"{attempt} attempt(s): {last_exc}"))
            self._metrics.inc("shed")
        else:
            rr.settle_exc(BackendUnavailable(
                f"no backend could serve the request after {attempt} "
                f"attempt(s); last error: {last_exc!r}"))
        self._metrics.inc("failed")

    def _wait_for_backend(self, rr: _RouterRequest,
                          waiting_since: float) -> bool:
        """Nothing usable right now: poll briefly (budget-exempt — no
        backend op is spent). False once the deadline or the shed
        timeout says to give up."""
        now = time.monotonic()
        if rr.expired(now):
            return False
        if now - waiting_since >= self._shed_timeout_s:
            return False
        remaining = rr.remaining_s(now)
        if remaining is not None and remaining <= 0:
            return False
        time.sleep(self._poll_s if remaining is None
                   else min(self._poll_s, remaining))
        return not self._abort

    # -- one-shot dispatch -------------------------------------------------
    def _dispatch_oneshot(self, rr: _RouterRequest) -> None:
        attempt = 0
        excluded: set = set()
        last_exc = None
        overload_only = True
        waiting_since = None
        while True:
            if self._abort:
                rr.settle_exc(ServerClosed("router aborted"))
                self._metrics.inc("failed")
                return
            now = time.monotonic()
            if rr.expired(now):
                self._settle_unserved(rr, last_exc, overload_only,
                                      attempt)
                return
            entry = self._pick_backend(rr.key, excluded)
            if entry is None and excluded:
                # widen: previously failed backends may have recovered
                excluded = set()
                entry = self._pick_backend(rr.key, excluded)
            if entry is None:
                if waiting_since is None:
                    waiting_since = now
                if self._wait_for_backend(rr, waiting_since):
                    continue
                self._settle_unserved(rr, last_exc, overload_only,
                                      attempt)
                return
            waiting_since = None
            attempt += 1
            t0 = time.monotonic()
            try:
                remaining = rr.remaining_s(t0)
                handle = entry.backend.submit(
                    rr.args, deadline_ms=None if remaining is None
                    else max(1e-3, remaining) * 1e3)
                res, winner = self._await_oneshot(rr, entry, handle,
                                                  excluded)
            except ServerOverloaded as exc:
                last_exc = exc
                self._metrics.inc("backend_overloads")
                excluded.add(entry.backend.backend_id)
                if len(excluded) >= len(self._backends):
                    excluded = set()   # full rotation: all saturated
                    if not self._backoff_for_retry(rr, attempt + 1):
                        self._settle_unserved(rr, last_exc,
                                              overload_only, attempt)
                        return
                continue
            except DeadlineExceeded:
                self._settle_unserved(rr, last_exc, overload_only,
                                      attempt)
                return
            except ServingError as exc:   # BackendDied, ServerClosed, ...
                if self._abort:
                    # our own abort, not the backend's fault: settle
                    # without blaming its breaker/health
                    rr.settle_exc(ServerClosed("router aborted"))
                    self._metrics.inc("failed")
                    return
                last_exc = exc
                overload_only = False
                self._record_backend_failure(entry, exc)
                self._metrics.inc("failovers")
                excluded.add(entry.backend.backend_id)
                if not self._backoff_for_retry(rr, attempt + 1):
                    self._settle_unserved(rr, last_exc, overload_only,
                                          attempt)
                    return
                continue
            winner.health.record_request(
                True, (time.monotonic() - t0) * 1e3)
            winner.breaker.record_success()
            rr.settle_result(res)
            self._metrics.inc("completed")
            self._metrics.observe("latency_ms",
                                  (time.monotonic() - rr.t_submit) * 1e3)
            self._metrics.observe("attempts", attempt)
            return

    def _await_handle(self, rr: _RouterRequest, handle):
        """Wait for one backend future in abort/deadline-sliced polls —
        a worker must never ride out an unbounded backend wait that
        ``shutdown`` or the request deadline wants to interrupt."""
        while True:
            if handle.done():
                # terminal: returns the payload or raises the REAL
                # error (including a backend-side DeadlineExceeded)
                return handle.result(0)
            if self._abort:
                raise ServerClosed("router aborted")
            remaining = rr.remaining_s()
            if remaining is not None and remaining <= 0:
                raise DeadlineExceeded(
                    "deadline passed waiting for a backend answer")
            wait = (self._poll_s if remaining is None
                    else min(self._poll_s, remaining))
            try:
                return handle.result(max(wait, 1e-4))
            except DeadlineExceeded:
                # poll tick. A timed-out result() is NOT evidence of a
                # terminal deadline — the future may have settled in the
                # race window, or its terminal state may itself be a
                # DeadlineExceeded; the next iteration's done() check
                # re-reads the real outcome via result(0) either way.
                continue

    def _await_oneshot(self, rr: _RouterRequest, entry: _BackendEntry,
                       handle, excluded: set):
        """Wait for one backend answer, optionally hedging onto a second
        backend after ``hedge_after_ms``. Returns (result, winning
        entry); raises the primary's error."""
        remaining = rr.remaining_s()
        if self._hedge_after_s is None:
            return self._await_handle(rr, handle), entry
        first_wait = (self._hedge_after_s if remaining is None
                      else min(self._hedge_after_s, remaining))
        try:
            return handle.result(max(1e-4, first_wait)), entry
        except DeadlineExceeded:
            if handle.done():
                # settled in the race window: take the REAL outcome
                # (result(0) re-raises a genuine terminal deadline)
                return handle.result(0), entry
            if rr.expired():
                raise
        hedge_excluded = set(excluded)
        hedge_excluded.add(entry.backend.backend_id)
        h_entry = self._pick_backend(rr.key, hedge_excluded)
        if h_entry is None:
            return self._await_handle(rr, handle), entry
        try:
            h_handle = h_entry.backend.submit(
                rr.args, deadline_ms=None if rr.remaining_s() is None
                else max(1e-3, rr.remaining_s()) * 1e3)
        except ServingError:
            return self._await_handle(rr, handle), entry
        self._metrics.inc("hedges")
        hedge_exc = None
        while True:
            if self._abort:
                raise ServerClosed("router aborted")
            if rr.expired():
                raise DeadlineExceeded("deadline passed while hedging")
            if handle.done():
                return handle.result(0), entry   # real outcome/raise
            if hedge_exc is None and h_handle.done():
                try:
                    res = h_handle.result(0)
                except ServingError as exc:
                    hedge_exc = exc    # hedge lost; keep the primary
                    self._record_backend_failure(h_entry, exc)
                else:
                    self._metrics.inc("hedge_wins")
                    return res, h_entry
            time.sleep(self._relay_poll_s)

    # -- decode dispatch + loss-free failover ------------------------------
    def _dispatch_decode(self, rr: _RouterRequest) -> None:
        attempt = 0
        failovers = 0
        excluded: set = set()
        last_exc = None
        overload_only = True
        waiting_since = None
        while True:
            if self._abort:
                rr.settle_exc(ServerClosed("router aborted"))
                self._metrics.inc("failed")
                return
            now = time.monotonic()
            if rr.expired(now):
                self._settle_unserved(rr, last_exc, overload_only,
                                      attempt)
                return
            entry = self._pick_backend(rr.key, excluded)
            if entry is None and excluded:
                excluded = set()
                entry = self._pick_backend(rr.key, excluded)
            if entry is None:
                if waiting_since is None:
                    waiting_since = now
                if self._wait_for_backend(rr, waiting_since):
                    continue
                self._settle_unserved(rr, last_exc, overload_only,
                                      attempt)
                return
            waiting_since = None
            attempt += 1
            # fold already-relayed tokens into the effective prompt (the
            # decode scheduler's preemption trick, applied across hosts):
            # the dispatch worker is the client stream's only writer, so
            # the unlocked read is single-threaded
            emitted = list(rr.stream._tokens)
            eff = (rr.prompt if not emitted
                   else np.concatenate([rr.prompt,
                                        np.asarray(emitted, np.int32)]))
            budget = rr.max_new_tokens - len(emitted)
            if budget <= 0:     # finished during a failover window
                rr.finish_stream("length")
                self._metrics.inc("completed")
                return
            t0 = time.monotonic()
            try:
                bs = entry.backend.submit_decode(
                    eff, max_new_tokens=budget, eos_id=rr.eos_id)
            except BucketOverflow as exc:
                # the failover-grown effective prompt outgrew the SHARED
                # prefill buckets — no backend can re-admit it (a
                # ValueError, so it must not fall through to the opaque
                # dispatch-failed handler): settle with the typed error,
                # mirroring the decode engine's preemption-grown case
                rr.settle_exc(exc)
                self._metrics.inc("failed")
                return
            except ServerOverloaded as exc:
                last_exc = exc
                self._metrics.inc("backend_overloads")
                excluded.add(entry.backend.backend_id)
                if len(excluded) >= len(self._backends):
                    excluded = set()
                    if not self._backoff_for_retry(rr, attempt + 1):
                        self._settle_unserved(rr, last_exc,
                                              overload_only, attempt)
                        return
                continue
            except ServingError as exc:
                if self._abort:
                    rr.settle_exc(ServerClosed("router aborted"))
                    self._metrics.inc("failed")
                    return
                last_exc = exc
                overload_only = False
                self._record_backend_failure(entry, exc)
                self._metrics.inc("failovers")
                excluded.add(entry.backend.backend_id)
                if not self._backoff_for_retry(rr, attempt + 1):
                    self._settle_unserved(rr, last_exc, overload_only,
                                          attempt)
                    return
                continue
            if rr.fo_span is not None:  # re-admitted: failover complete
                rr.fo_span.end()
                rr.fo_span = None
            with tracing.trace_span("router::relay", cat="router",
                                    trace_id=rr.trace_id,
                                    backend=entry.backend.backend_id):
                outcome, exc = self._relay(rr, entry, bs)
            if outcome == "done":
                entry.health.record_request(
                    True, (time.monotonic() - t0) * 1e3)
                entry.breaker.record_success()
                rr.finish_stream(bs.finish_reason or "eos")
                self._metrics.inc("completed")
                self._metrics.observe(
                    "latency_ms", (time.monotonic() - rr.t_submit) * 1e3)
                self._metrics.observe("attempts", attempt)
                return
            if outcome == "expired":
                rr.settle_exc(DeadlineExceeded(
                    "deadline passed mid-generation "
                    f"({rr.stream.token_count()} tokens in)"))
                self._metrics.inc("expired")
                return
            if outcome == "aborted":
                rr.settle_exc(ServerClosed("router aborted"))
                self._metrics.inc("failed")
                return
            # backend died mid-stream: loss-free failover. The relayed
            # tokens stay with the client; the next attempt re-admits
            # elsewhere with them folded into the prompt. Failover of
            # accepted in-flight work is deadline-bounded (plus a hard
            # failover cap) but retry-budget-exempt: dropping a
            # partially-streamed response to save budget would turn a
            # recoverable fault into a client-visible one.
            last_exc = exc
            overload_only = False
            self._record_backend_failure(entry, exc)
            emitted_now = list(rr.stream._tokens)
            if rr.eos_id is not None and emitted_now \
                    and emitted_now[-1] == rr.eos_id:
                # eos was already relayed: the death merely beat the
                # stream's finish signal. The request is COMPLETE —
                # re-admitting would append post-eos tokens and break
                # the bit-identical guarantee
                rr.finish_stream("eos")
                self._metrics.inc("completed")
                self._metrics.observe(
                    "latency_ms", (time.monotonic() - rr.t_submit) * 1e3)
                self._metrics.observe("attempts", attempt)
                return
            failovers += 1
            self._metrics.inc("failovers")
            self._metrics.inc("decode_failovers")
            self._metrics.inc("tokens_resumed", rr.stream.token_count())
            rr.fo_span = tracing.trace_span(
                "router::failover", cat="router", trace_id=rr.trace_id,
                from_backend=entry.backend.backend_id,
                tokens_resumed=rr.stream.token_count())
            excluded = {entry.backend.backend_id}
            if failovers > self._max_decode_failovers:
                self._settle_unserved(rr, last_exc, overload_only,
                                      attempt)
                return

    def _relay(self, rr: _RouterRequest, entry: _BackendEntry, bs):
        """Copy tokens from the backend stream into the client stream
        until finish / death / expiry. Liveness is checked between
        tokens: a token from a host that died before handing it over is
        never relayed (the failover re-derives it bit-identically).
        Returns (outcome, exc): "done" | "died" | "expired" |
        "aborted"."""
        i = 0
        while True:
            if self._abort:
                return "aborted", None
            if rr.expired():
                return "expired", None
            try:
                entry.backend.check_alive()
            except ServingError as exc:
                return "died", exc
            try:
                tok = bs.next_token(i, timeout=self._relay_poll_s)
            except DeadlineExceeded as exc:
                if bs.done():
                    # the BACKEND stream's terminal state is itself a
                    # DeadlineExceeded (host-side deadline config,
                    # server-side cancel) — a backend failure to the
                    # router, which owns the request deadline: fail
                    # over instead of spinning on the settled stream
                    return "died", exc
                continue            # poll tick: re-check liveness/expiry
            except ServingError as exc:
                return "died", exc  # stream failed terminally host-side
            if tok is None:
                return "done", None
            rr.stream._put(tok)
            i += 1
