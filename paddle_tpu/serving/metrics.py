"""Serving observability: counters + latency/size histograms.

Every Server owns a ServingMetrics; the snapshot is retrievable through
``paddle_tpu.profiler.serving_stats()`` (the profiler is the framework's
one observability surface — reference parity: the predictor's
memory/latency stats also surface through the profiler tables). Batch
executions are ``trace_span``s (``serving::execute``), which a recording
Profiler receives, so serving work shows up in chrome traces next to op
events.

The thread-safe scaffolding (Histogram, counters/gauge plumbing) lives
in ``paddle_tpu.profiler.metrics``, shared with the input-pipeline
metrics in ``paddle_tpu.io.prefetch``.
"""
from __future__ import annotations

from ..profiler.metrics import Histogram, MetricsBase

__all__ = ["Histogram", "ServingMetrics"]


class ServingMetrics(MetricsBase):
    """Thread-safe counters/histograms for one Server.

    Counters: submitted, completed, rejected_overload, expired, failed,
    batches, compile_count, cache_hits, cache_evictions.
    Histograms: batch_size, queue_wait_ms, latency_ms, pad_waste
    (fraction of executed elements that were padding).
    Gauge: queue_depth (pulled from the server at snapshot time).
    """

    COUNTERS = ("submitted", "completed", "rejected_overload", "expired",
                "failed", "batches", "compile_count", "cache_hits",
                "cache_evictions")
    HISTS = ("batch_size", "queue_wait_ms", "latency_ms", "pad_waste")

    def snapshot(self) -> dict:
        with self._lock:
            out = dict(self._counters)
            out["name"] = self.name
            for k, h in self._hists.items():
                out[k] = h.snapshot()
        out["queue_depth"] = self._read_gauge()
        return out
