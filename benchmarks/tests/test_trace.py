"""The trace reduction on a small synthetic trace, and the reader of
``.xplane.pb`` on a trace recorded here on the CPU."""
import pytest

from benchmarks.harness import trace


def _events():
    mods = [["jit_train_step(1)", 20.0, 70.0],       # cut by the trace's start
            ["jit_train_step(1)", 100.0, 90.0],
            ["jit_train_step(1)", 200.0, 90.0],
            ["jit_other", 300.0, 5.0],
            ["jit_train_step(1)", 310.0, 90.0],
            ["jit_train_step(1)", 410.0, 30.0]]      # cut by its end
    ops = [["flash_fwd", 60.0, 30.0], ["fusion.1", 410.0, 30.0],
           ["fusion.1", 100.0, 40.0], ["flash_fwd", 140.0, 30.0],
           ["fusion.1", 200.0, 40.0], ["flash_fwd", 230.0, 30.0],
           ["while.2", 240.0, 50.0],                 # overlaps: nested
           ["fusion.1", 310.0, 40.0], ["flash_fwd", 350.0, 30.0],
           ["copy.3", 395.0, 20.0]]                  # runs past the window
    return {"/device:TPU:0|XLA Modules": mods, "/device:TPU:0|XLA Ops": ops,
            "/device:TPU:0|Steps": [["0", 100.0, 300.0]]}


def test_reduce_window_busy_and_steps():
    r = trace.reduce(_events(), r"jit_train_step")
    assert r["steps"] == 3 and r["chips"] == 1
    assert r["window_s"] == pytest.approx(300e-9)     # 100 .. 400
    # busy: [100,170] + [200,290] + [310,380] + [395,400] = 70+90+70+5
    assert r["busy_s"] == pytest.approx(235e-9)
    top = dict(r["device_ops"])
    assert top["fusion.1"] == pytest.approx(120e-9)
    gaps = dict(r["idle_gaps"])
    assert gaps["after flash_fwd before fusion.1"] == pytest.approx(30e-9)
    assert gaps["after while.2 before fusion.1"] == pytest.approx(20e-9)
    assert gaps["after flash_fwd before copy.3"] == pytest.approx(15e-9)
    assert sum(gaps.values()) == pytest.approx(65e-9)
    secs, n = trace.op_seconds(r, r"flash")
    assert n == 3 and secs == pytest.approx(90e-9)


def test_reduce_finds_nothing():
    assert trace.reduce(_events(), r"jit_serve_step") is None
    assert trace.reduce({}, r"jit_train_step") is None


def test_union_and_gaps():
    assert trace._union([(0, 10), (5, 12), (20, 21)]) == 13
    assert trace._gaps([(2, 4, "a"), (3, 8, "b")], 0, 10) == [
        (0, 2, "window_start", "a"), (8, 10, "b", "window_end")]


def test_xplane_reader_on_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    events = trace.load_events(path, planes_prefix="/host:CPU")
    assert events and all(len(r) == 3 for rows in events.values()
                          for r in rows)
    assert trace.load_events(path) == {}              # no device plane here
    saved = tmp_path / "e.json.gz"
    trace.save_events(events, str(saved))
    assert trace.read_events(str(saved)) == events
    assert trace.describe(path)["planes"]


def test_reduce_on_a_trace_recorded_on_the_chip():
    """Three steps of mistral7b-l2-train-s4096 on a TPU v5e (the probe of
    PR 24), kept as plain events: the names are whole HLO instructions."""
    import os
    events = trace.read_events(os.path.join(
        os.path.dirname(__file__), "data",
        "trace_events_mistral_3steps.json.gz"))
    r = trace.reduce(events, r"jit_train_step")
    # three step programs in the trace: the middle one is counted
    assert r["steps"] == 1 and r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.956347104, rel=1e-9)
    assert 0.9999 < r["busy_s"] / r["window_s"] <= 1.0
    pattern = r'\[128,4096,128\].*custom_call_target="tpu_custom_call"'
    secs, n = trace.op_seconds(r, pattern)
    assert n == 6 and secs == pytest.approx(0.317634529, rel=1e-6)
    assert r["device_ops"][0][0] == \
        "transpose_jvp___.4 custom-call:tpu_custom_call"
    assert all(len(name) <= 200 for name, _ in r["idle_gaps"])


def test_short_name():
    assert trace.short_name(
        '%fusion.12 = bf16[4]{0} fusion(bf16[4]{0} %p), kind=kLoop') == \
        "fusion.12"
    assert trace.short_name(
        '%jvp__.3 = (f32[2]{0}, f32[2]{0}) custom-call(f32[2]{0} %a), '
        'custom_call_target="tpu_custom_call"') == \
        "jvp__.3 custom-call:tpu_custom_call"
    assert trace.short_name("window_start") == "window_start"
