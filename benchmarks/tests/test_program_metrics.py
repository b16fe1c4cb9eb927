"""The readers of the metrics that come from inside the program (PR 25), each
on a synthetic ``ctx``: ``dispatch_ms_per_step`` and ``feed_busy_ms_per_step``
read the feed's counters, ``attn_device_ms_per_step`` finds attention in the
trace by the program's names and by nothing else."""
import os

import pytest

from benchmarks.harness import spec, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(os.path.join(ROOT, "BENCHMARK.json"))


def _ctx(feed=None, steps=10, ops=None, trace_steps=2):
    logged = []
    reduced = None if ops is None else {"steps": trace_steps, "ops": ops}
    return {"counters": {"feed": feed or {}, "steps": steps},
            "trace": reduced, "log": logged.append}, logged


@pytest.mark.parametrize("metric, counter", [
    ("dispatch_ms_per_step", "dispatch_s"),
    ("feed_busy_ms_per_step", "producer_busy_s")])
def test_counter_readers(bench, metric, counter):
    read = bench.module("metrics", metric).read
    ctx, _ = _ctx({counter: 0.025}, steps=10)
    assert read(ctx) == pytest.approx(2.5)
    # a program that does not count it (the parent commit), a window with
    # no step, a run whose feed gave no snapshot: nothing, and no raise
    assert read(_ctx({"host_blocked_s": 1.0})[0]) is None
    assert read(_ctx({counter: 0.025}, steps=0)[0]) is None
    assert read({"counters": {"steps": 3}, "trace": None,
                 "log": print}) is None


def _hlo(head, operands="bf16[8,128,64]{2,1,0} %bitcast.1"):
    return (f"%{head} = bf16[8,128,64]{{2,1,0}} custom-call({operands}), "
            'custom_call_target="tpu_custom_call"')


def test_attention_is_found_by_name_and_split(bench):
    read = bench.module("metrics", "attn_device_ms_per_step").read
    ops = [(0.0, 2e6, _hlo("flash_fwd.1")),
           (2e6, 5e6, _hlo("flash_bwd_dq.1")),
           (5e6, 9e6, _hlo("flash_bwd_dkv.1")),
           # an XLA route under the scope, forward and backward
           (9e6, 10e6, "%jvp_attention__fusion.3 = f32[4]{0} fusion(%p)"),
           (10e6, 12e6, "%transpose_jvp_attention__.7 = f32[4]{0} dot(%p)"),
           # not attention: consumes a flash kernel's result, or is a Mosaic
           # call of attention's shape that the program gave no name
           (12e6, 20e6, "%fusion.9 = bf16[8]{0} fusion(%flash_fwd.1)"),
           (20e6, 30e6, _hlo("jvp__.3"))]
    ctx, logged = _ctx(ops=ops, trace_steps=2)
    assert read(ctx) == pytest.approx(12.0 / 2)
    assert "5 events by name" in logged[0]
    assert "forward 3.000 ms, backward 9.000 ms" in logged[0]


def test_attention_reads_nothing_without_the_names(bench):
    read = bench.module("metrics", "attn_device_ms_per_step").read
    assert read(_ctx()[0]) is None                      # no trace
    assert read(_ctx(ops=[], trace_steps=0)[0]) is None
    ctx, logged = _ctx(ops=[(0.0, 1e6, _hlo("jvp__.3"))])
    assert read(ctx) is None and not logged


def test_attention_reads_nothing_on_a_trace_from_before_the_names(bench):
    """The recorded Mistral trace predates the names: its flash kernels are
    ``%jvp__.N``/``%transpose_jvp___.N``. The reader by shape finds six of
    them; the reader by name must find none."""
    events = trace.read_events(os.path.join(
        os.path.dirname(__file__), "data",
        "trace_events_mistral_3steps.json.gz"))
    reduced = trace.reduce(events, r"jit_train_step")
    _, by_shape = trace.op_seconds(
        reduced, r'\[128,4096,128\].*custom_call_target="tpu_custom_call"')
    assert by_shape == 6
    read = bench.module("metrics", "attn_device_ms_per_step").read
    assert read({"counters": {}, "trace": reduced, "log": print}) is None


def test_the_three_entries_are_appended_for_both_cells(bench):
    names = [m["name"] for m in bench.per_layer]
    assert names[-3:] == ["dispatch_ms_per_step", "feed_busy_ms_per_step",
                          "attn_device_ms_per_step"]
    for cell in ("gpt2s-train-s1024", "mistral7b-l2-train-s4096"):
        assert set(names[-3:]) <= {m["name"] for m in
                                   bench.metrics_for(cell, "per_layer")}
