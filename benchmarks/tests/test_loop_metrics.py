"""The trainer loop's own records read by the three metrics of PR 36, each on
plain snapshots: a parent's (no such keys: nothing), fewer than 20
iterations, the device run dry by the caller's callback alone (the runner's
profile start and stop: 0), and one stall; then the three on a traced
rehearsal of the runner on the CPU."""
import io
import json
import os
from contextlib import redirect_stdout

import pytest

from benchmarks import run
from benchmarks.harness import spec
from conftest import PRESET, ROOT

NEW = ("loop_stall_ms.train", "starved_steps_pct", "gc_pause_ms_per_step")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(os.path.join(ROOT, "BENCHMARK.json"))


def _ctx(feed, steps=40):
    logged = []
    return {"counters": {"feed": feed, "steps": steps}, "trace": None,
            "log": logged.append}, logged


def _loop(count, p50=400.0, mx=401.0):
    return {"count": count, "mean": p50, "max": mx, "p50": p50, "p99": mx}


def _snap(**kw):
    """A window's feed snapshot as ``PipelineMetrics`` gives it."""
    out = {"host_blocked_s": 0.001, "dispatch_s": 0.1,
           "loop_ms": _loop(39), "slowest": [], "starved": [],
           "starved_steps": 0, "starved_by": {}, "starved_s": 0.0,
           "gc_pause_s": 0.0, "gc_collections": 0, "gc_gen2": 0}
    out.update(kw)
    return out


@pytest.mark.parametrize("metric", NEW)
def test_a_parent_without_the_records_reads_nothing(bench, metric):
    read = bench.module("metrics", metric).read
    parent = {"host_blocked_s": 0.001, "dispatch_s": 0.1,
              "device_blocked_s": 12.0}
    assert read(_ctx(parent)[0]) is None
    assert read(_ctx({})[0]) is None
    if metric != "loop_stall_ms.train":         # the two shares of steps
        assert read(_ctx(_snap(), steps=0)[0]) is None


def test_loop_stall_is_the_slowest_over_the_typical(bench):
    read = bench.module("metrics", "loop_stall_ms.train").read
    rec = {"step": 57, "ms": {"fetch_wait": 1320.4}}
    ctx, logged = _ctx(_snap(loop_ms=_loop(39, 471.3, 1791.7),
                             slowest=[rec]))
    assert read(ctx) == pytest.approx(1320.4)
    assert "slowest 57" in logged[0] and "fetch_wait" in logged[0]
    assert read(_ctx(_snap(loop_ms=_loop(19)))[0]) is None


def test_starved_by_the_callback_alone_reads_zero(bench):
    read = bench.module("metrics", "starved_steps_pct").read
    ctx, logged = _ctx(_snap(starved_steps=2, starved_by={"callback": 2},
                             starved_s=0.0004))
    assert read(ctx) == 0.0
    assert "{'callback': 2}" in logged[0]
    one = _snap(starved_steps=3, starved_by={"callback": 2, "gc": 1})
    assert read(_ctx(one, steps=40)[0]) == pytest.approx(2.5)


def test_gc_pause_per_step(bench):
    read = bench.module("metrics", "gc_pause_ms_per_step").read
    ctx, logged = _ctx(_snap(gc_pause_s=0.12, gc_collections=31, gc_gen2=1))
    assert read(ctx) == pytest.approx(3.0)
    assert "31 collections, 1 of generation 2" in logged[0]


def test_the_three_entries_are_in_the_benchmark_for_every_cell(bench):
    by_name = {m["name"]: m for m in bench.per_layer}
    for name in NEW:
        m = by_name[name]
        assert (m["layer"], m["moves"], m["source"], m["better"]) == (
            "trainer loop", "train_tokens_per_s", "program_counter", "lower")
        assert "workloads" not in m
        for cell in bench.cells:
            assert m in bench.metrics_for(cell, "per_layer")


def test_a_traced_rehearsal_reads_all_three(tmp_path):
    """The runner hands ``run_steps``' own snapshot to the readers: on the
    CPU preset with the three appended, a traced run reads each."""
    with open(PRESET) as f:
        preset = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        ours = {m["name"]: m for m in json.load(f)["per_layer"]}
    preset["per_layer"] += [ours[n] for n in NEW]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(preset))
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main(["--workload", "gpt2-tiny-train", "--seed", "36",
                         "--seconds", "2", "--trace", "1"],
                        benchmark_json=str(path), rehearsal=True) == 0
    metrics = json.loads(out.getvalue().strip().splitlines()[-1])["metrics"]
    assert set(NEW) <= set(metrics)
    assert metrics["loop_stall_ms.train"]["value"] >= 0
    assert metrics["gc_pause_ms_per_step"]["value"] >= 0
