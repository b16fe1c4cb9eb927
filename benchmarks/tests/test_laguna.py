"""The Laguna family through ``runners/train.py`` at a tiny preset on the CPU
(the published structure: 5 layers of the pattern, window and full attention,
4 of 16 experts held, top-4): ``correct`` comes out true, and false for the
float8 control and for each planted fault: half of the batch left out under
the timed path, and in the reference put in the program's place one expert
fewer chosen, and a capacity of 1.0 x the mean load with the overflow
dropped."""
import io
import json
import os
from contextlib import redirect_stdout

import pytest

from benchmarks import run
from benchmarks.harness import compare, spec
from benchmarks.tools import readings_moe
from conftest import ROOT
from test_run import _half_batch

PRESET = os.path.join(ROOT, "benchmarks", "tests", "preset_laguna",
                      "BENCHMARK.json")
CELL = "laguna-tiny-train"


def rehearse(seed, trace=0, wrap_step=None):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "1", "--trace", str(trace)], benchmark_json=PRESET,
                      rehearsal=True, wrap_step=wrap_step)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [2**31 + 5, 7])
def test_the_family_runs_through_the_train_runner_and_is_correct(seed):
    line = rehearse(seed)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 3
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(c["value"] <= c["limit"] for c in line["compared"])


def test_a_traced_run_on_the_cpu_reads_no_device_metric():
    line = rehearse(17, trace=1)
    assert line["correct"] is True
    # no device plane on the CPU: the three new readers find no event and
    # return nothing, as they do on a program without the names
    # (the step times' p95 is read only from 20 intervals on)
    assert {"feed_wait_ms_per_step"} <= set(line["metrics"]) <= {
        "feed_wait_ms_per_step", "step_ms_p95.train"}


def test_half_of_the_batch_left_out_is_not_correct():
    line = rehearse(23, wrap_step=_half_batch)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["compared"])


@pytest.fixture(scope="module")
def followed():
    """seed -> (the reference's first steps, a function that follows them
    again with something planted)."""
    bench = spec.load_benchmark(PRESET, root=ROOT)
    cell = bench.cell(CELL)
    runner = bench.module("runners", "train")
    reference = bench.module("reference", "laguna")
    out = {}
    for seed in (3, 5, 6):
        batches = runner.batch_fn(bench, cell, seed)

        def again(seed=seed, batches=batches, **kw):
            return runner.follow_reference(bench, cell, seed, batches, **kw)
        out[seed] = (again(), again)
    return bench, cell, reference, out


@pytest.mark.parametrize("fault", list(readings_moe.FAULTS))
def test_a_fault_of_the_expert_layer_is_not_correct(followed, fault):
    _, cell, reference, runs = followed
    limits = cell.params["check"]["limits"]
    for seed, (want, again) in runs.items():
        with readings_moe.planted(reference, readings_moe.FAULTS[fault]):
            rows = compare.training(again(), want, limits)
        assert any(r["value"] > r["limit"] for r in rows), (seed, rows)
    assert reference.route.__name__ == "route"      # and it is taken out


def test_the_float8_control_is_not_correct_and_bfloat16_is(followed):
    bench, cell, _, runs = followed
    numerics = bench.module("reference", "numerics")
    limits = cell.params["check"]["limits"]
    for seed, (want, again) in runs.items():
        rows = compare.training(again(math=numerics.Fp8()), want, limits)
        assert any(r["value"] > r["limit"] for r in rows), (seed, rows)
        rows = compare.training(again(math=numerics.Bf16()), want, limits)
        assert all(r["value"] <= r["limit"] for r in rows), (seed, rows)


def test_required_flops_of_the_cell_by_hand():
    """``dims`` through the unedited ``flops.train_flops_per_token``: every
    layer's own projections, the dense MLP or router + shared expert + one
    routed expert in expectation, the window layers' attention at its band,
    the full layers' at the causal half, and the head."""
    from benchmarks.harness import flops
    real = spec.load_benchmark(os.path.join(ROOT, "BENCHMARK.json"),
                               root=ROOT)
    cell = real.cell("laguna-xs2-train-s8192")
    v = cell.config.values
    d = real.module("reference", "laguna").dims(v)
    h, hd, s, w = 2048, 128, 8192, 512

    def attn(heads):
        return 2 * h * heads * hd + 2 * h * 8 * hd + h * heads
    expert = 3 * h * 512
    sparse = h * 256 + expert + 8 * 32 / 256 * expert
    params = (attn(48) + 3 * h * 8192) + 3 * (attn(64) + sparse) \
        + (attn(48) + sparse) + 12544 * h
    band = w - w * (w - 1) / (2 * s)
    want = 6 * params + 3 * 12 * 64 * hd * band + 2 * 6 * s * 48 * hd
    got = flops.train_flops_per_token(d, cell.params["seq"])
    assert got == pytest.approx(want, rel=1e-12)
    assert 2.40e9 < got < 2.41e9
