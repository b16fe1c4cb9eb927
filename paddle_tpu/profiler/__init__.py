"""Unified profiler (parity: python/paddle/profiler/profiler.py —
Profiler:346, make_scheduler:117, export_chrome_tracing:215, RecordEvent;
statistics tables in profiler_statistic.py).

TPU-native design: the device side delegates to jax.profiler (XPlane —
TensorBoard-consumable traces of XLA executions); the host side is
``tracing``'s one span call: a recording Profiler is a sink of every
``trace_span``/``trace_event``/``RecordEvent`` in the program and of every
``run_op`` dispatch via the core hook (the reference emits RecordEvent
from every generated op function). The same spans enter a
``jax.profiler.TraceAnnotation``, so with a non-CPU target they sit in the
``.xplane.pb`` beside the device's ops, on one clock. The
schedule(wait/warmup/active) state machine and chrome-trace export keep the
reference API.
"""
from __future__ import annotations

import collections
import json
import os
import re
import threading
import time
import weakref
import zlib
from enum import Enum
from typing import Callable, Iterable, List, Optional

from . import tracing as _tracing
from .tracing import (TraceContext, trace_span, trace_step, trace_event,
                      new_trace_id, current_trace_id, enable_tracing, disable_tracing,
                      tracing_enabled, snapshot_events, export_trace,
                      start_trace_writer, stop_trace_writer,
                      set_clock_offset, set_trace_metadata, record_compile,
                      compile_count, reset_tracing)

__all__ = ["ProfilerState", "ProfilerTarget", "make_scheduler",
           "export_chrome_tracing", "RecordEvent", "Profiler",
           "load_profiler_result", "SummaryView", "serving_stats",
           "register_serving_source", "unregister_serving_source",
           "pipeline_stats", "register_pipeline_source",
           "unregister_pipeline_source", "record_placement_fallback",
           "decode_stats", "register_decode_source",
           "unregister_decode_source", "resilience_stats",
           "register_resilience_source", "unregister_resilience_source",
           "router_stats", "register_router_source",
           "unregister_router_source", "transport_stats",
           "register_transport_source", "unregister_transport_source",
           "export_stats",
           # flight-recorder tracing (profiler.tracing re-exports)
           "TraceContext", "trace_span", "trace_step", "trace_event",
           "new_trace_id", "current_trace_id", "enable_tracing", "disable_tracing",
           "tracing_enabled", "snapshot_events", "export_trace",
           "start_trace_writer", "stop_trace_writer", "set_clock_offset",
           "set_trace_metadata", "record_compile", "compile_count",
           "reset_tracing"]


class ProfilerState(Enum):
    """Parity: profiler.ProfilerState."""
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1      # accepted for API parity; maps to the device target
    TPU = 2
    CUSTOM_DEVICE = 3


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Step-number -> state schedule (parity: make_scheduler:117):
    skip_first CLOSED steps, then cycles of closed/ready/record, the last
    record step of each cycle returning RECORD_AND_RETURN."""
    num_steps = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        cycle = step // num_steps
        if repeat > 0 and cycle >= repeat:
            return ProfilerState.CLOSED
        pos = step % num_steps
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == num_steps - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def _default_state_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD


class _HostEvent:
    """One record of ``tracing`` (a span, an op, an instant) as a
    recording ``Profiler`` keeps it: ``start``/``end`` in seconds on the
    wall clock, like the flight recorder's ring."""

    __slots__ = ("rec", "tid")

    def __init__(self, rec, tid):
        self.rec = rec
        self.tid = tid

    @property
    def name(self):
        return self.rec[0]

    @property
    def category(self):
        return self.rec[1]

    @property
    def start(self):
        return self.rec[3]

    @property
    def end(self):
        return self.rec[3] + (self.rec[4] or 0.0)


class _HostTracer:
    """Collects host events; ``tracing``'s sink while a Profiler is
    RECORD-ing."""

    def __init__(self):
        self.events: List[_HostEvent] = []
        self._lock = threading.Lock()

    def add(self, rec, tid):
        ev = _HostEvent(rec, tid)
        with self._lock:
            self.events.append(ev)


def _op_span(name: str):
    return trace_span(name, cat="op")


class RecordEvent:
    """User scope annotation (parity: paddle.profiler.RecordEvent):

        with profiler.RecordEvent("data_loading"):
            ...

    A thin form of ``trace_span``: the scope lands in a recording
    ``Profiler``, in the flight recorder's ring when tracing is enabled,
    and in the profiler's ``.xplane.pb`` when a device trace runs.
    """

    def __init__(self, name: str, event_type: str = "UserDefined"):
        self.name = name
        self.event_type = event_type
        self._span = None

    def begin(self):
        self._span = trace_span(self.name, cat=self.event_type)

    def end(self):
        span, self._span = self._span, None
        if span is not None:
            span.end()

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None
                          ) -> Callable:
    """on_trace_ready handler writing chrome://tracing JSON
    (parity: export_chrome_tracing:215)."""
    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        worker = worker_name or f"host_{os.getpid()}"
        path = os.path.join(
            dir_name, f"{worker}_time_{int(time.time() * 1000)}"
                      f".paddle_trace.json")
        prof._export_chrome(path)
        prof.last_export_path = path
    return handler


class Profiler:
    """Parity: paddle.profiler.Profiler (profiler.py:346).

    with Profiler(scheduler=(2, 5), on_trace_ready=...) as p:
        for batch in loader:
            train_step(batch)
            p.step()
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 timer_only: bool = False, record_shapes: bool = False,
                 profile_memory: bool = False, with_flops: bool = False,
                 emit_nvtx: bool = False, custom_device_types=None):
        del record_shapes, profile_memory, with_flops, emit_nvtx
        del custom_device_types
        self.targets = list(targets) if targets else [ProfilerTarget.CPU]
        if isinstance(scheduler, tuple):
            start, end = scheduler
            self.scheduler = make_scheduler(closed=max(start, 0), ready=0,
                                            record=end - start, repeat=1)
        elif scheduler is None:
            self.scheduler = _default_state_scheduler
        else:
            self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._tracer: Optional[_HostTracer] = None
        self._all_events: List[_HostEvent] = []
        self._device_tracing = False
        self._step_t0 = None
        self._step_durations: List[float] = []
        self.last_export_path = None

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        self.current_state = self.scheduler(self.step_num)
        self._transition(ProfilerState.CLOSED, self.current_state)
        self._step_t0 = time.perf_counter()
        return self

    def stop(self):
        self._transition(self.current_state, ProfilerState.CLOSED,
                         final=True)
        self.current_state = ProfilerState.CLOSED

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    def step(self, num_samples: Optional[int] = None):
        del num_samples
        now = time.perf_counter()
        if self._step_t0 is not None:
            self._step_durations.append(now - self._step_t0)
        self._step_t0 = now
        prev = self.current_state
        self.step_num += 1
        self.current_state = self.scheduler(self.step_num)
        self._transition(prev, self.current_state)

    # -- state machine -----------------------------------------------------
    def _recording(self, state) -> bool:
        return state in (ProfilerState.RECORD,
                         ProfilerState.RECORD_AND_RETURN)

    def _transition(self, prev, new, final=False):
        was, now = self._recording(prev), self._recording(new) and not final
        if not was and now:
            self._begin_record()
        elif was and (not now or prev == ProfilerState.RECORD_AND_RETURN):
            self._end_record()
            if now and prev == ProfilerState.RECORD_AND_RETURN:
                self._begin_record()

    def _begin_record(self):
        from ..core import dispatch as _dispatch
        self._tracer = _HostTracer()
        _tracing.set_profiler_sink(self._tracer.add)
        if not self.timer_only:
            _dispatch.set_op_profile_hook(_op_span)
            self._maybe_device_trace(True)

    def _end_record(self):
        from ..core import dispatch as _dispatch
        if self._tracer is None:
            return
        _dispatch.set_op_profile_hook(None)
        _tracing.set_profiler_sink(None)
        self._maybe_device_trace(False)
        self._all_events.extend(self._tracer.events)
        self._tracer = None
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def _maybe_device_trace(self, start: bool):
        """Device side = jax.profiler XPlane trace (TensorBoard format)."""
        want_device = any(t != ProfilerTarget.CPU for t in self.targets)
        if not want_device:
            return
        import jax
        try:
            if start and not self._device_tracing:
                d = os.environ.get("PADDLE_PROFILER_TRACE_DIR",
                                   "/tmp/paddle_tpu_xplane")
                jax.profiler.start_trace(d)
                self._device_tracing = True
            elif not start and self._device_tracing:
                jax.profiler.stop_trace()
                self._device_tracing = False
        except Exception:
            self._device_tracing = False  # device tracer unavailable (CPU CI)

    # -- results -----------------------------------------------------------
    def _export_chrome(self, path: str):
        pid = os.getpid()
        events = [_tracing.chrome_event(ev.rec, pid, ev.tid)
                  for ev in self._all_events or (self._tracer.events
                                                 if self._tracer else [])]
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)

    def export(self, path: str, format: str = "json"):
        del format
        self._export_chrome(path)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms") -> str:
        """Op statistic table (parity: profiler_statistic summary)."""
        del sorted_by, op_detail, thread_sep
        unit = {"s": 1.0, "ms": 1e3, "us": 1e6}[time_unit]
        stats = {}
        for ev in self._all_events:
            tot, cnt, mx = stats.get(ev.name, (0.0, 0, 0.0))
            d = ev.end - ev.start
            stats[ev.name] = (tot + d, cnt + 1, max(mx, d))
        rows = sorted(stats.items(), key=lambda kv: -kv[1][0])
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(' + time_unit + ')':>14}"
                 f"{'Avg(' + time_unit + ')':>12}{'Max(' + time_unit + ')':>12}"]
        for name, (tot, cnt, mx) in rows:
            lines.append(f"{name[:39]:<40}{cnt:>8}{tot * unit:>14.3f}"
                         f"{tot / cnt * unit:>12.3f}{mx * unit:>12.3f}")
        if self._step_durations:
            import numpy as np
            sd = np.asarray(self._step_durations)
            lines.append(f"steps: {len(sd)}  avg "
                         f"{sd.mean() * unit:.3f}{time_unit}  p50 "
                         f"{np.percentile(sd, 50) * unit:.3f}{time_unit}")
        text = "\n".join(lines)
        print(text)
        return text

    @property
    def events(self):
        return list(self._all_events)


# -- metrics-source registries -----------------------------------------------
# Subsystems (serving servers, input-pipeline prefetchers/runners) register
# their live metrics objects here so counters and latency histograms are
# retrievable through the profiler API (the framework's one observability
# surface) without holding the owners alive: entries are weak references,
# pruned on read.
class _SourceRegistry:
    """name -> weakref(metrics object with .snapshot())."""

    def __init__(self, kind: str):
        self._kind = kind
        self._sources: "dict[str, weakref.ref]" = {}
        self._lock = threading.Lock()

    def register(self, name: str, metrics) -> None:
        with self._lock:
            self._sources[name] = weakref.ref(metrics)

    def unregister(self, name: str, metrics=None) -> None:
        # when ``metrics`` is given, only remove if the registry still
        # points at THAT object — a later owner that reused the name must
        # not lose its metrics to the older owner's shutdown
        with self._lock:
            ref = self._sources.get(name)
            if ref is None:
                return
            if metrics is not None and ref() is not None \
                    and ref() is not metrics:
                return
            del self._sources[name]

    def stats(self, name: Optional[str] = None):
        with self._lock:
            live = {}
            for n, ref in list(self._sources.items()):
                m = ref()
                if m is None:
                    del self._sources[n]
                else:
                    live[n] = m
        if name is not None:
            if name not in live:
                raise KeyError(
                    f"no live {self._kind} source named {name!r}")
            return live[name].snapshot()
        return {n: m.snapshot() for n, m in live.items()}


_serving_registry = _SourceRegistry("serving")
_pipeline_registry = _SourceRegistry("pipeline")
_decode_registry = _SourceRegistry("decode")
_resilience_registry = _SourceRegistry("resilience")
_router_registry = _SourceRegistry("router")
_transport_registry = _SourceRegistry("transport")


def register_serving_source(name: str, metrics) -> None:
    """Register a serving metrics source (an object with .snapshot()).
    Called by serving.Server on construction."""
    _serving_registry.register(name, metrics)


def unregister_serving_source(name: str, metrics=None) -> None:
    """Remove a source (only if it still points at ``metrics``, when
    given). Called by serving.Server on shutdown."""
    _serving_registry.unregister(name, metrics)


def serving_stats(name: Optional[str] = None):
    """Snapshot of serving metrics: queue depth, batch-size histogram,
    compile count, queue-wait/latency p50/p99 — per registered server.

    Returns ``{server_name: snapshot_dict}``, or one snapshot when
    ``name`` is given (KeyError when that server is gone)."""
    return _serving_registry.stats(name)


def register_pipeline_source(name: str, metrics) -> None:
    """Register an input-pipeline metrics source (an object with
    .snapshot()). Called by io.prefetch.DevicePrefetcher and
    models.trainer.run_steps on construction."""
    _pipeline_registry.register(name, metrics)


def unregister_pipeline_source(name: str, metrics=None) -> None:
    """Remove a pipeline source (only if it still points at ``metrics``,
    when given)."""
    _pipeline_registry.unregister(name, metrics)


# place_by_spec replication fallbacks: silent de-sharding is a real bug
# class (a renamed param whose spec no longer divides quietly replicates
# and eats HBM/bandwidth), so every fallback is recorded here with a
# one-line reason and surfaced through pipeline_stats(). Bounded deque —
# a long run cannot accumulate unbounded state.
_placement_fallbacks = collections.deque(maxlen=100)
_placement_lock = threading.Lock()


def record_placement_fallback(reason: str) -> None:
    """Record a one-line reason for a sharding->replication fallback
    (called by models.trainer.place_by_spec)."""
    with _placement_lock:
        _placement_fallbacks.append(str(reason))


def pipeline_stats(name: Optional[str] = None):
    """Snapshot of input-pipeline metrics: queue-depth gauge/histogram,
    per-batch transfer latency, and the host-blocked vs device-blocked
    time split ("am I input-bound or compute-bound?") — per registered
    prefetcher/runner (mirrors ``serving_stats``).

    Returns ``{pipeline_name: snapshot_dict}`` plus a
    ``"placement_fallbacks"`` entry listing recent
    ``place_by_spec`` sharding->replication fallback reasons, or one
    snapshot when ``name`` is given (KeyError when that source is
    gone)."""
    if name is not None:
        return _pipeline_registry.stats(name)
    out = _pipeline_registry.stats()
    with _placement_lock:
        out["placement_fallbacks"] = list(_placement_fallbacks)
    return out


def register_decode_source(name: str, metrics) -> None:
    """Register a decode-server metrics source (an object with
    .snapshot()). Called by serving.decode.DecodeServer on
    construction."""
    _decode_registry.register(name, metrics)


def unregister_decode_source(name: str, metrics=None) -> None:
    """Remove a decode source (only if it still points at ``metrics``,
    when given)."""
    _decode_registry.unregister(name, metrics)


def decode_stats(name: Optional[str] = None):
    """Snapshot of continuous-batching decode metrics: slot occupancy,
    page utilization, prefill vs decode step time, preemptions,
    time-to-first-token — per registered DecodeServer.

    Returns ``{server_name: snapshot_dict}``, or one snapshot when
    ``name`` is given (KeyError when that server is gone)."""
    return _decode_registry.stats(name)


def register_resilience_source(name: str, metrics) -> None:
    """Register a resilience metrics source (an object with
    .snapshot()). Called by distributed.resilience.CheckpointManager on
    construction."""
    _resilience_registry.register(name, metrics)


def unregister_resilience_source(name: str, metrics=None) -> None:
    """Remove a resilience source (only if it still points at
    ``metrics``, when given)."""
    _resilience_registry.unregister(name, metrics)


def resilience_stats(name: Optional[str] = None):
    """Snapshot of preemption-tolerance metrics: snapshot/commit latency,
    write-behind queue depth, comm-watchdog hang count, restarts, last
    committed step — per registered CheckpointManager.

    Returns ``{manager_name: snapshot_dict}``, or one snapshot when
    ``name`` is given (KeyError when that manager is gone)."""
    return _resilience_registry.stats(name)


def register_router_source(name: str, metrics) -> None:
    """Register a serving-router metrics source (an object with
    .snapshot()). Called by serving.router.Router on construction."""
    _router_registry.register(name, metrics)


def unregister_router_source(name: str, metrics=None) -> None:
    """Remove a router source (only if it still points at ``metrics``,
    when given)."""
    _router_registry.unregister(name, metrics)


def router_stats(name: Optional[str] = None):
    """Snapshot of serving-router metrics: per-backend health/breaker
    state and breaker transitions, retry/failover/shed/hedge counts,
    latency and attempt histograms — per registered Router.

    Returns ``{router_name: snapshot_dict}``, or one snapshot when
    ``name`` is given (KeyError when that router is gone)."""
    return _router_registry.stats(name)


def register_transport_source(name: str, metrics) -> None:
    """Register a wire-transport metrics source (an object with
    .snapshot()). Called by serving.transport.RemoteBackend /
    BackendServer on construction."""
    _transport_registry.register(name, metrics)


def unregister_transport_source(name: str, metrics=None) -> None:
    """Remove a transport source (only if it still points at
    ``metrics``, when given)."""
    _transport_registry.unregister(name, metrics)


def transport_stats(name: Optional[str] = None):
    """Snapshot of wire-transport metrics: bytes in/out, connects /
    reconnects / disconnects, frame errors, per-RPC round-trip latency,
    streamed tokens, deadline sheds — per registered transport endpoint
    (RemoteBackend clients and BackendServer hosts).

    Returns ``{endpoint_name: snapshot_dict}``, or one snapshot when
    ``name`` is given (KeyError when that endpoint is gone)."""
    return _transport_registry.stats(name)


# the one table of metrics-source scrapes: export_stats() and the
# registry introspection below both derive from it, so adding a stats
# source is ONE entry here — and tests derive their expected registry
# set instead of hardcoding a count that breaks on every new subsystem
_STATS_SCRAPES = {
    "pipeline": pipeline_stats,
    "serving": serving_stats,
    "decode": decode_stats,
    "resilience": resilience_stats,
    "router": router_stats,
    "transport": transport_stats,
}


def stats_registries() -> tuple:
    """Names of every metrics-source registry ``export_stats()``
    scrapes (sorted). The introspection surface consumers (dashboards,
    tests) use to stay correct as stats sources are added."""
    return tuple(sorted(_STATS_SCRAPES))


def _flatten_scrape(prefix: str, value, out: list) -> None:
    """dict/number tree -> ``name value`` exposition lines (labels are
    flattened into the metric name; non-numeric leaves are dropped —
    a scrape is numbers, not strings)."""
    if isinstance(value, dict):
        for k, v in value.items():
            _flatten_scrape(f"{prefix}_{k}", v, out)
    elif isinstance(value, (list, tuple)):
        out.append(f"{_sanitize(prefix)}_count {len(value)}")
    elif isinstance(value, bool):
        out.append(f"{_sanitize(prefix)} {int(value)}")
    elif isinstance(value, (int, float)):
        out.append(f"{_sanitize(prefix)} {value}")


def _sanitize(name: str) -> str:
    """Prometheus-legal metric name: every char outside ``[a-zA-Z0-9_]``
    becomes ``_`` (ASCII-only — ``isalnum`` would wave unicode through),
    a leading digit gets a ``_`` prefix, and — collision safety — any
    name the rewrite CHANGED gets a short stable hash of the original
    appended, so distinct hostile names ("a.b" vs "a-b") cannot collapse
    onto the same series."""
    clean = re.sub(r"[^a-zA-Z0-9_]", "_", name)
    if clean[:1].isdigit():
        clean = "_" + clean
    if clean != name:
        clean = f"{clean}_{zlib.crc32(name.encode('utf-8')):08x}"
    return clean


def export_stats(format: str = "dict"):
    """One scrape over every metrics registry — the fleet-dashboard
    endpoint payload combining ``pipeline_stats()``, ``serving_stats()``
    and ``decode_stats()``.

    format="dict" returns the nested dict, "json" a JSON string, and
    "text" a Prometheus-style exposition (one ``name value`` line per
    numeric leaf, names prefixed ``paddle_tpu_<registry>_<source>_``).
    The registry set is ``stats_registries()`` — one scrape per entry
    in ``_STATS_SCRAPES``.
    """
    data = {name: scrape() for name, scrape in _STATS_SCRAPES.items()}
    if format == "dict":
        return data
    if format == "json":
        return json.dumps(data, sort_keys=True, default=str)
    if format == "text":
        lines: list = []
        _flatten_scrape("paddle_tpu", data, lines)
        return "\n".join(lines) + "\n"
    raise ValueError(
        f"unknown export_stats format {format!r}: expected 'dict', "
        "'json', or 'text'")


class SummaryView(Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def load_profiler_result(filename: str) -> dict:
    with open(filename) as f:
        return json.load(f)


class SortedKeys:
    """Sort keys for summary tables (parity: paddle.profiler.SortedKeys,
    python/paddle/profiler/profiler_statistic.py)."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7
