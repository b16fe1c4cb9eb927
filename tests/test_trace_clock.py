"""One span call, three sinks (ISSUE 25): every ``trace_span`` /
``trace_event`` / ``RecordEvent`` lands on the profiler's clock (the
``/host:CPU`` plane of the ``.xplane.pb``), in the flight recorder's ring
when tracing is enabled, and in a recording ``Profiler``; the names the
device trace needs come from the program; ``tools/trace_gaps.py`` puts a
device gap down to the span the host was in.

No sleeps and no thread races: the traced session is one module fixture
(starting a trace costs seconds) and everything else is arithmetic on plain
lists or a look at lowered text.
"""
import gc
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.io import prefetch_to_device
from paddle_tpu.profiler import Profiler, RecordEvent, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools import trace_gaps  # noqa: E402


@jax.jit
def _toy_step(params, opt_state, key, ids, labels, lr):
    del key, labels
    loss = ids.astype(jnp.float32).mean() + params["w"].sum()
    return loss, {"w": params["w"] - lr}, opt_state


def _batches(n):
    for i in range(n):
        ids = np.full((2, 4), i, np.int32)
        yield ids, ids


def _run_toy(feed):
    return models.run_steps(_toy_step, {"w": jnp.ones((4,))}, {}, feed,
                            lr=0.1)


@pytest.fixture(autouse=True)
def _clean():
    tracing.reset_tracing()
    tracing.disable_tracing()
    yield
    tracing.reset_tracing()
    tracing.disable_tracing()


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """ONE profiler session with every kind of span in it. Returns the host
    plane as ``{name: [stats dict, ...]}`` (each with its ``start_ns`` and
    ``duration_ns``), the profile's origin on ``time.time_ns``, what the
    ring held, what the recording Profiler held, and the feed's snapshot
    of a ``run_steps`` whose ``on_log`` collects garbage once."""
    _run_toy(_batches(2))               # compile outside the session
    d = str(tmp_path_factory.mktemp("xplane"))
    tracing.reset_tracing()
    tracing.enable_tracing()
    jax.profiler.start_trace(d)
    try:
        with tracing.trace_span("clock::span", cat="t", step=7, k="v"):
            pass
        with tracing.trace_step("clock::step", 3, cat="t", batch=2):
            pass
        tracing.trace_event("clock::instant", cat="t", k=1)
        handle = tracing.trace_span("clock::dropped", cat="t")
        handle.drop()
        with Profiler(timer_only=True) as prof:
            with RecordEvent("clock::record_event"):
                pass
            ev = RecordEvent("clock::begin_end")
            ev.begin()
            ev.end()
        feed = prefetch_to_device(_batches(3), depth=2, name="clock_loop")
        try:
            models.run_steps(
                _toy_step, {"w": jnp.ones((4,))}, {}, feed, lr=0.1,
                log_every=1,
                on_log=lambda i, v: gc.collect() if i == 1 else None)
            loop = feed.metrics.snapshot()
        finally:
            feed.close()
        tracing.disable_tracing()       # ring off, annotation still there
        with tracing.trace_span("clock::ring_off", cat="t"):
            pass
    finally:
        jax.profiler.stop_trace()
    ring = tracing.snapshot_events()
    tracing.reset_tracing()
    tracing.disable_tracing()
    data = jax.profiler.ProfileData.from_file(trace_gaps.find_xplane(d))
    host = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if trace_gaps.PROGRAM_SPAN.match(e.name):
                    host.setdefault(e.name, []).append(
                        dict(e.stats, start_ns=e.start_ns,
                             duration_ns=e.duration_ns))
    return {"dir": d, "host": host, "origin_ns": trace_gaps.origin_ns(data),
            "ring": ring, "profiler": prof.events, "dropped": handle,
            "loop": loop}


# -- the profiler's clock ----------------------------------------------------

def test_span_is_in_the_host_plane_with_its_step_stat(session):
    (stats,) = session["host"]["clock::span"]
    assert stats["step"] == 7 and stats["k"] == "v"


def test_step_span_is_the_profilers_step_marker(session):
    (stats,) = session["host"]["clock::step"]
    # StepTraceAnnotation: _r marks a step event, step_num numbers it
    assert stats["_r"] == 1 and stats["step_num"] == 3
    assert stats["step"] == 3 and stats["batch"] == 2
    ring = {e["name"]: e for e in session["ring"]}
    assert ring["clock::step"]["args"] == {"step": 3, "batch": 2}


def test_instant_event_is_in_the_host_plane_and_the_ring(session):
    (stats,) = session["host"]["clock::instant"]
    assert stats["k"] == 1
    ring = {e["name"]: e for e in session["ring"]}
    assert ring["clock::instant"]["ph"] == "i"


def test_dropped_handle_records_nothing_and_leaves_no_open_annotation(
        session):
    assert session["dropped"]._ann is None
    # the annotation was entered and left: a whole event, not a dangling one
    (stats,) = session["host"]["clock::dropped"]
    assert stats["duration_ns"] >= 0
    assert "clock::dropped" not in {e["name"] for e in session["ring"]}
    assert "clock::dropped" not in {e.name for e in session["profiler"]}


@pytest.mark.parametrize("name", ["clock::record_event", "clock::begin_end"])
def test_record_event_reaches_all_three_sinks(session, name):
    assert name in {e.name for e in session["profiler"]}
    assert name in {e["name"] for e in session["ring"]}
    assert name in session["host"]


def test_ring_off_still_reaches_the_profilers_clock(session):
    assert "clock::ring_off" in session["host"]
    assert "clock::ring_off" not in {e["name"] for e in session["ring"]}


def test_run_steps_spans_are_on_the_profilers_clock(session):
    host = session["host"]
    assert [s["step"] for s in host["train::dispatch"]] == [0, 1, 2]
    assert [s["step_num"] for s in host["train::dispatch"]] == [0, 1, 2]
    assert [s["step"] for s in host["train::fetch"]] == [0, 1, 2]
    # the fourth feed_wait met StopIteration: dropped, yet a whole event
    assert [s["step"] for s in host["train::feed_wait"]] == [0, 1, 2, 3]
    # the caller's on_log, timed apart from the loop's own phases
    assert [s["step"] for s in host["train::callback"]] == [0, 1, 2]


def test_loop_record_stamps_are_on_the_profilers_clock(session):
    """``profile_start_time + start_ns`` is ``time.time_ns()``: each whole
    iteration's dispatch stamp lies within 1 ms of its ``train::dispatch``
    span (dispatch 0 fetched nothing, so it has no record)."""
    origin = session["origin_ns"]
    starts = {s["step"]: s["start_ns"]
              for s in session["host"]["train::dispatch"]}
    records = session["loop"]["slowest"]
    assert origin is not None
    assert sorted(r["step"] for r in records) == [1, 2]
    for r in records:
        assert abs(origin + starts[r["step"]] - r["t_ns"]["dispatch"]) < 1e6


def test_a_collection_is_counted_and_spanned(session):
    loop = session["loop"]
    assert loop["gc_collections"] >= 1 and loop["gc_gen2"] >= 1
    assert loop["gc_pause_s"] > 0
    # on_log(1)'s gc.collect(): generation 2, with what it collected
    assert any(s["generation"] == 2 and s["collected"] >= 0
               for s in session["host"]["gc::collect"])
    assert any(e["args"]["generation"] == 2 and "collected" in e["args"]
               for e in session["ring"] if e["name"] == "gc::collect")


def test_trace_gaps_loads_the_program_spans_of_a_real_profile(session):
    data = trace_gaps.load(session["dir"])
    names = {r[0] for rows in data["host"].values() for r in rows}
    assert {"train::feed_wait", "train::dispatch", "train::fetch",
            "clock::span"} <= names
    # no runtime scope (``PjRtCpuExecutable::Execute``) passes for a span
    assert all(trace_gaps.PROGRAM_SPAN.match(n) for n in names)
    assert data["devices"] == {}        # a CPU profile has no device plane


# -- off, and the two writers -------------------------------------------------

def test_disabled_records_nothing_and_balances_the_annotation():
    span = tracing.trace_span("off::span", step=1)
    assert span._ann is not None and span._t0 is None
    span.end()
    span.end()                          # idempotent
    assert span._ann is None
    with tracing.trace_step("off::step", 5):
        tracing.trace_event("off::event")
    assert tracing.snapshot_events() == []


def test_profiler_export_and_export_trace_give_the_same_event_shape(
        tmp_path):
    tracing.enable_tracing()
    with Profiler(timer_only=True) as prof:
        with tracing.TraceContext("tid9"):
            with tracing.trace_span("same::span", cat="t", k=1):
                tracing.trace_event("same::event", cat="t")
    prof.export(str(tmp_path / "p.json"))
    tracing.export_trace(str(tmp_path / "r.json"))
    mine = json.load(open(tmp_path / "p.json"))["traceEvents"]
    ring = [e for e in json.load(open(tmp_path / "r.json"))["traceEvents"]
            if e["ph"] != "M"]
    key = lambda e: e["ts"]             # noqa: E731
    assert sorted(mine, key=key) == sorted(ring, key=key)
    span = next(e for e in mine if e["name"] == "same::span")
    assert span["ph"] == "X" and span["cat"] == "t" and span["dur"] >= 0
    assert span["args"] == {"trace_id": "tid9", "k": 1}
    assert span["ts"] > 1e15            # wall-clock µs, like the ring


def test_profiler_detaches_its_sink_on_stop():
    with Profiler(timer_only=True):
        assert tracing._recording
    assert tracing._sink is None and not tracing._recording


# -- counters at the same boundaries ------------------------------------------

@pytest.mark.parametrize("prefetch", [False, True],
                         ids=["plain_iterable", "device_prefetcher"])
def test_run_steps_fills_dispatch_s(prefetch):
    if prefetch:
        feed = prefetch_to_device(_batches(3), depth=2, name="clock_feed")
        try:
            _, _, losses = _run_toy(feed)
            snap = feed.metrics.snapshot()
        finally:
            feed.close()
    else:
        seen = {}
        orig = paddle.profiler.unregister_pipeline_source

        def keep(name, metrics=None):
            seen["snap"] = metrics.snapshot()
            orig(name, metrics)
        paddle.profiler.unregister_pipeline_source = keep
        try:
            _, _, losses = _run_toy(_batches(3))
        finally:
            paddle.profiler.unregister_pipeline_source = orig
        snap = seen["snap"]
    assert len(losses) == 3
    assert snap["dispatch_s"] > 0.0
    assert {"host_blocked_s", "device_blocked_s"} <= set(snap)


# -- names on the device ------------------------------------------------------

def test_lowered_train_step_names_optimizer_and_attention():
    cfg = models.gpt2_tiny()
    paddle.seed(0)
    model = models.GPTForCausalLM(cfg)
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step, params, opt_state = models.create_train_step(model, opt)
    ids = np.zeros((2, 16), np.int32)
    text = step.lower(params, opt_state, jax.random.key(0), ids, ids,
                      1e-3).as_text(debug_info=True)
    names = [ln for ln in text.splitlines() if ln.startswith("#loc")]
    # forward, backward and optimizer: three disjoint prefixes
    assert any("/jvp(attention)/" in ln for ln in names)
    assert any("/transpose(jvp(attention))/" in ln for ln in names)
    opt_lines = [ln for ln in names if "/optimizer/" in ln]
    assert opt_lines and not any("jvp(" in ln for ln in opt_lines)


_KERNEL_NAMES = {"fa_mha": {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"},
                 "rms_norm": {"rms_norm_fwd"},
                 "layer_norm": {"layer_norm_fwd"},
                 "softmax_ce": {"softmax_ce_fwd", "softmax_ce_bwd"}}


@pytest.mark.parametrize("family", sorted(_KERNEL_NAMES))
def test_pallas_kernels_carry_their_names_to_the_tpu_lowering(family):
    import chip_smoke
    cases = [c for c in chip_smoke.kernel_cases(chip_smoke.TINY["kernels"],
                                                interpret=False)
             if c[0].startswith(family) and "bwd_xla" not in c[0]]
    _, pallas_fn, _, args, n_diff = cases[0]
    text = chip_smoke.fwd_and_vjp(pallas_fn, n_diff).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    for name in _KERNEL_NAMES[family]:
        assert f"/{name}/" in text or f'"{name}"' in text, name


# -- the compile watcher ------------------------------------------------------

def test_every_backend_compile_is_counted_with_its_name():
    x = jnp.ones((3,))                  # its own program compiles here
    tracing.enable_tracing()
    before = tracing.compile_count()

    def never_compiled_before_25(x):
        return x * 3 + 1
    jax.jit(never_compiled_before_25)(x).block_until_ready()
    assert tracing.compile_count() == before + 1
    (ev,) = [e for e in tracing.snapshot_events()
             if e["name"] == "jit::compile"]
    assert ev["args"]["fn"] == "jit(never_compiled_before_25)"
    assert ev["ph"] == "X" and ev["dur"] > 0


# -- tools/trace_gaps.py on synthetic events ---------------------------------

def _ops():
    # two whole step programs [100, 200) and [210, 300); gaps at
    # [140, 150) inside the first, [200, 210) between, [260, 262) inside
    return [["%fusion.1 = f32[] fusion()", 100.0, 40.0],
            ["%flash_fwd.1 = f32[] custom-call()", 150.0, 50.0],
            ["%copy-start = f32[] copy-start()", 210.0, 50.0],
            ["%fusion.2 = f32[] fusion()", 262.0, 38.0]]


def _modules():
    return [["jit_train_step", 0.0, 90.0],          # cut by the start
            ["jit_train_step", 100.0, 100.0],
            ["jit__threefry_fold_in", 201.0, 1.0],
            ["jit_train_step", 210.0, 90.0],
            ["jit_train_step", 310.0, 20.0]]        # cut by the stop


def test_trace_gaps_window_leaves_out_the_cut_runs():
    assert trace_gaps.step_window(_modules()) == \
        ("jit_train_step", 100.0, 300.0, 2)
    assert trace_gaps.step_window(_modules(), r"fold_in") is None
    assert trace_gaps.step_window([]) is None


def test_trace_gaps_finds_the_gaps_between_and_inside_programs():
    gaps = trace_gaps.device_gaps(_ops(), 100.0, 300.0)
    assert [(s, e) for s, e, _, _ in gaps] == \
        [(140.0, 150.0), (200.0, 210.0), (260.0, 262.0)]
    assert gaps[1][2].startswith("%flash_fwd.1")
    assert gaps[1][3].startswith("%copy-start")


def test_trace_gaps_puts_each_gap_down_to_the_open_span():
    host = {"main#0": [["train::fetch", 90.0, 30.0],
                       ["train::feed_wait", 135.0, 20.0],
                       ["train::dispatch", 205.0, 10.0]],
            "feeder#1": [["feed::produce", 195.0, 10.0]]}
    rows, totals = trace_gaps.attribute(
        trace_gaps.device_gaps(_ops(), 100.0, 300.0), host)
    # 140: inside train::feed_wait. 200: only the feeder has a span open
    # (dispatch starts at 205). 260: no span on any thread
    assert rows[0]["spans"] == {"main#0": "train::feed_wait",
                                "feeder#1": "none"}
    assert rows[1]["spans"] == {"main#0": "none",
                                "feeder#1": "feed::produce"}
    assert rows[2]["spans"] == {"main#0": "none", "feeder#1": "none"}
    assert totals == {"train::feed_wait": (1, 10.0),
                      "feed::produce": (1, 10.0), "none": (1, 2.0)}


def test_trace_gaps_counts_a_gap_for_each_thread_with_a_span_open():
    host = {"main#0": [["train::dispatch", 199.0, 5.0]],
            "feeder#1": [["feed::produce", 195.0, 10.0]]}
    _, totals = trace_gaps.attribute([(200.0, 210.0, "a", "b")], host)
    assert totals == {"train::dispatch": (1, 10.0),
                      "feed::produce": (1, 10.0)}


def test_trace_gaps_takes_the_innermost_of_nested_spans():
    host = {"main#0": [["decode::step", 0.0, 100.0],
                       ["jit::compile", 40.0, 20.0]]}
    assert trace_gaps.open_spans(host, 50.0) == {"main#0": "jit::compile"}
    assert trace_gaps.open_spans(host, 70.0) == {"main#0": "decode::step"}
    assert trace_gaps.open_spans(host, 100.0) == {"main#0": "none"}


def test_trace_gaps_report_on_plain_data():
    data = {"devices": {"/device:TPU:0": {"modules": _modules(),
                                          "ops": _ops()}},
            "host": {"main#0": [["train::fetch", 90.0, 115.0]]}}
    rep = trace_gaps.report(data, min_us=5e-3)      # 5 ns
    r = rep["/device:TPU:0"]
    assert r["program"] == "jit_train_step" and r["steps"] == 2
    assert r["idle_ns"] == 22.0 and len(r["gaps"]) == 2
    assert r["totals"] == {"train::fetch": {"gaps": 2, "ns": 20.0}}
