"""Profiler, timers, amp tensor-checker tests (reference test models:
test/legacy_test/test_profiler.py, test_newprofiler.py)."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.distributed.fleet.utils import get_timers, set_timers
from paddle_tpu.profiler import (Profiler, ProfilerState, RecordEvent,
                                 export_chrome_tracing, make_scheduler)


class TestScheduler:
    def test_make_scheduler_cycle(self):
        sched = make_scheduler(closed=1, ready=1, record=2, repeat=1)
        states = [sched(i) for i in range(6)]
        assert states == [ProfilerState.CLOSED, ProfilerState.READY,
                          ProfilerState.RECORD,
                          ProfilerState.RECORD_AND_RETURN,
                          ProfilerState.CLOSED, ProfilerState.CLOSED]

    def test_skip_first(self):
        sched = make_scheduler(closed=0, ready=0, record=1, repeat=2,
                               skip_first=3)
        assert sched(0) == ProfilerState.CLOSED
        assert sched(2) == ProfilerState.CLOSED
        assert sched(3) == ProfilerState.RECORD_AND_RETURN
        assert sched(4) == ProfilerState.RECORD_AND_RETURN
        assert sched(5) == ProfilerState.CLOSED


class TestProfiler:
    def _work(self):
        x = paddle.to_tensor(np.random.RandomState(0).randn(8, 8)
                             .astype(np.float32))
        y = paddle.matmul(x, x)
        return (y * 2).sum()

    def test_records_op_events(self):
        with Profiler() as p:
            with RecordEvent("user_scope"):
                self._work()
        names = {e.name for e in p.events}
        assert "matmul" in names
        assert "user_scope" in names

    def test_hook_cleared_after_stop(self):
        from paddle_tpu.core import dispatch
        with Profiler():
            self._work()
        assert dispatch._op_profile_hook is None
        self._work()  # ops run after stop() must not crash or record

    def test_chrome_export(self, tmp_path):
        handler = export_chrome_tracing(str(tmp_path))
        with Profiler(scheduler=make_scheduler(closed=0, ready=0, record=1,
                                               repeat=1),
                      on_trace_ready=handler) as p:
            self._work()
            p.step()
        assert p.last_export_path and os.path.exists(p.last_export_path)
        trace = json.load(open(p.last_export_path))
        assert any(ev["name"] == "matmul" for ev in trace["traceEvents"])
        assert all({"ph", "ts", "dur", "pid", "tid"} <= set(ev)
                   for ev in trace["traceEvents"])

    def test_summary_table(self):
        with Profiler() as p:
            for _ in range(3):
                self._work()
                p.step()
        text = p.summary(time_unit="us")
        assert "matmul" in text
        assert "steps: 3" in text

    def test_scheduled_window_only(self):
        # record only step 1 (0-indexed): events from step 0 are dropped
        sched = make_scheduler(closed=1, ready=0, record=1, repeat=1)
        with Profiler(scheduler=sched) as p:
            self._work()   # step 0: CLOSED
            p.step()       # -> RECORD_AND_RETURN window opens
            self._work()
            p.step()
        assert any(e.name == "matmul" for e in p.events)
        # exactly one window's worth: fewer events than two full steps
        matmuls = [e for e in p.events if e.name == "matmul"]
        assert len(matmuls) == 1


class TestTimers:
    def test_start_stop_elapsed(self):
        set_timers()
        t = get_timers()("fwd")
        t.start()
        t.stop()
        e = t.elapsed(reset=False)
        assert e >= 0.0
        t.reset()
        assert t.elapsed() == 0.0

    def test_log_format(self, capsys):
        set_timers()
        tm = get_timers()
        tm("a").start(); tm("a").stop()  # noqa: E702
        tm("b").start(); tm("b").stop()  # noqa: E702
        text = tm.log(["a", "b"], normalizer=2.0)
        assert text.startswith("time (ms) |")
        assert "a:" in text and "b:" in text


class TestTensorChecker:
    def test_checker_catches_nan(self):
        cfg = paddle.amp.debugging.TensorCheckerConfig(enable=True)
        paddle.amp.debugging.enable_tensor_checker(cfg)
        try:
            x = paddle.to_tensor(np.array([1.0, 0.0], np.float32))
            with pytest.raises(FloatingPointError):
                _ = x / 0.0
        finally:
            paddle.amp.debugging.disable_tensor_checker()
        # disabled again: no raise
        _ = paddle.to_tensor(np.array([1.0], np.float32)) / 0.0


class TestSanitize:
    def test_legal_names_pass_through_unchanged(self):
        from paddle_tpu.profiler import _sanitize
        assert _sanitize("paddle_tpu_decode_ttft_ms_p99") == \
            "paddle_tpu_decode_ttft_ms_p99"
        assert _sanitize("A_z0_9") == "A_z0_9"

    def test_hostile_names_stay_distinct(self):
        """Collision safety: distinct hostile names must NOT collapse
        onto one series after sanitization ("a.b" and "a-b" both rewrote
        to "a_b" before the hash suffix existed)."""
        from paddle_tpu.profiler import _sanitize
        import re
        hostile = ["a.b", "a-b", "a b", "a/b", "héllo", "hèllo",
                   "0lead", "_lead", "x:y", "x;y"]
        cleaned = [_sanitize(n) for n in hostile]
        assert len(set(cleaned)) == len(hostile), cleaned
        pat = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
        for c in cleaned:
            assert pat.match(c), c
        # stability: the suffix is a pure function of the input
        assert _sanitize("a.b") == _sanitize("a.b")

    def test_export_stats_text_lines_are_prometheus_legal(self):
        import re
        text = profiler.export_stats(format="text")
        pat = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
        for line in text.strip().splitlines():
            name, _, value = line.rpartition(" ")
            assert pat.match(name), line
            float(value)


class TestFlightRecorder:
    @pytest.fixture(autouse=True)
    def _clean(self):
        from paddle_tpu.profiler import tracing
        tracing.reset_tracing()
        tracing.disable_tracing()
        yield
        tracing.reset_tracing()
        tracing.disable_tracing()

    def test_disabled_mode_records_nothing(self):
        """Disabled, a span is its profiler annotation and one branch:
        no clock read, nothing in the ring, usable as ``with`` or
        handle."""
        from paddle_tpu.profiler import tracing
        s1 = tracing.trace_span("x")
        s2 = tracing.trace_span("y", cat="z", k=1)
        assert s1._t0 is None and s2._t0 is None
        with s1:
            tracing.trace_event("e", k=2)
        s2.end()
        assert s1._ann is None and s2._ann is None   # annotations left
        assert tracing.snapshot_events() == []

    def test_span_and_event_record_with_context_trace_id(self):
        from paddle_tpu.profiler import tracing
        tracing.enable_tracing()
        with tracing.TraceContext("tid1"):
            with tracing.trace_span("outer", cat="t", k=1):
                tracing.trace_event("inner", cat="t")
            with tracing.TraceContext("tid2"):
                tracing.trace_event("nested")
            tracing.trace_event("restored")
        evs = {e["name"]: e for e in tracing.snapshot_events()}
        assert evs["outer"]["args"]["trace_id"] == "tid1"
        assert evs["outer"]["ph"] == "X" and evs["outer"]["dur"] >= 0
        assert evs["outer"]["args"]["k"] == 1
        assert evs["inner"]["args"]["trace_id"] == "tid1"
        assert evs["inner"]["ph"] == "i"
        assert evs["nested"]["args"]["trace_id"] == "tid2"
        assert evs["restored"]["args"]["trace_id"] == "tid1"  # unwound
        assert tracing.current_trace_id() is None

    def test_explicit_trace_id_wins_over_context(self):
        from paddle_tpu.profiler import tracing
        tracing.enable_tracing()
        with tracing.TraceContext("ctx"):
            with tracing.trace_span("s", trace_id="explicit"):
                pass
        (ev,) = tracing.snapshot_events()
        assert ev["args"]["trace_id"] == "explicit"

    def test_ring_is_bounded_and_keeps_newest(self):
        from paddle_tpu.profiler import tracing
        tracing.enable_tracing(ring_size=8)
        for i in range(50):
            tracing.trace_event(f"e{i}")
        evs = tracing.snapshot_events()
        assert len(evs) == 8
        assert [e["name"] for e in evs] == [f"e{i}" for i in range(42, 50)]

    def test_span_end_is_idempotent(self):
        from paddle_tpu.profiler import tracing
        tracing.enable_tracing()
        span = tracing.trace_span("once")
        span.end()
        span.end()
        with span:      # a later with-block must not re-record either
            pass
        assert len(tracing.snapshot_events()) == 1

    def test_compile_watcher_counts_and_emits(self):
        from paddle_tpu.profiler import tracing
        tracing.enable_tracing()
        assert tracing.compile_count() == 0
        tracing.record_compile("fwd")
        tracing.record_compile("bwd")
        assert tracing.compile_count() == 2
        names = [e["name"] for e in tracing.snapshot_events()]
        assert names.count("jit::compile") == 2

    def test_export_schema_and_metadata(self, tmp_path):
        from paddle_tpu.profiler import tracing
        tracing.enable_tracing()
        tracing.set_trace_metadata(backend_id="hA", role="host")
        tracing.set_clock_offset("peer0", 0.25)
        with tracing.trace_span("s", cat="t"):
            pass
        path = str(tmp_path / "sub" / "t.json")
        assert tracing.export_trace(path) == path
        doc = json.load(open(path))
        assert doc["displayTimeUnit"] == "ms"
        pt = doc["paddleTrace"]
        assert pt["pid"] == os.getpid()
        assert pt["metadata"] == {"backend_id": "hA", "role": "host"}
        assert pt["clock_offsets"] == {"peer0": 0.25}
        phs = {e["ph"] for e in doc["traceEvents"]}
        assert "M" in phs and "X" in phs    # thread names + the span
        span = [e for e in doc["traceEvents"] if e["ph"] == "X"][0]
        assert span["ts"] > 1e15            # wall-clock µs, not perf_counter
        assert span["dur"] >= 0

    def test_background_writer_survives_and_flushes(self, tmp_path):
        import time as _time
        from paddle_tpu.profiler import tracing
        tracing.enable_tracing()
        path = str(tmp_path / "flight.json")
        tracing.start_trace_writer(path, interval_s=0.02)
        tracing.trace_event("before_kill")
        end = _time.monotonic() + 5
        seen = False
        while _time.monotonic() < end and not seen:
            if os.path.exists(path):
                names = [e["name"]
                         for e in json.load(open(path))["traceEvents"]]
                seen = "before_kill" in names
            _time.sleep(0.02)
        assert seen     # flushed WITHOUT stop: the SIGKILL property
        tracing.trace_event("at_stop")
        tracing.stop_trace_writer()
        names = [e["name"] for e in json.load(open(path))["traceEvents"]]
        assert "at_stop" in names           # final flush on stop

    def test_enable_rejects_bad_ring_size(self):
        from paddle_tpu.profiler import tracing
        with pytest.raises(ValueError):
            tracing.enable_tracing(ring_size=0)
