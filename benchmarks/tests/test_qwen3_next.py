"""The Qwen3-Next family through ``runners/train.py`` at a tiny preset on the
CPU (the published structure: one period, three DeltaNet layers and a full
one, 4 value heads over 2 key heads, softmax top-4 of 16 experts with 8 held
and a gated shared expert, 256 tokens: two blocks of the recurrence):
``correct`` comes out true, and false for the float8 control and, in the
reference put in the program's place, for each of the six faults of
``tools/readings_qwen3_next.py``; a traced run on the CPU reads no device
metric."""
import io
import json
import os
from contextlib import redirect_stdout

import pytest

from benchmarks import run
from benchmarks.harness import compare, spec
from benchmarks.tools import readings_qwen3_next
from conftest import ROOT

PRESET = os.path.join(ROOT, "benchmarks", "tests", "preset_qwen3_next",
                      "BENCHMARK.json")
CELL = "qwen3-next-tiny-train"
FAULTS = ["fault_no_carry", "fault_beta_one", "fault_neighbour_decay",
          "fault_no_output_gate", "fault_no_shared_gate",
          "fault_sigmoid_router"]


def rehearse(seed, trace=0):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "1", "--trace", str(trace)], benchmark_json=PRESET,
                      rehearsal=True)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [2**31 + 5, 7])
def test_the_family_runs_through_the_runner_and_is_correct(seed):
    line = rehearse(seed)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_a_traced_run_on_the_cpu_reads_no_device_metric():
    line = rehearse(17, trace=1)
    assert line["correct"] is True
    assert not {"gdn_device_ms_per_step", "gdn_roofline", "step_mfu"} & \
        set(line["metrics"])


@pytest.fixture(scope="module")
def followed():
    bench = spec.load_benchmark(PRESET, root=ROOT)
    runner = bench.module("runners", "train_lean")
    reference = bench.module("reference", "qwen3_next")
    cell = bench.cell(CELL)
    out = {}
    for seed in (3, 5):
        batches = runner.batch_fn(bench, cell, seed)

        def again(seed=seed, batches=batches, **kw):
            return runner.follow_reference(bench, cell, seed, batches, **kw)
        out[seed] = (again(), again)
    return bench, cell, reference, out


def _fails(rows):
    return any(r["value"] > r["limit"] for r in rows
               if r["limit"] is not None)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_of_the_family_is_not_correct(followed, fault):
    _, cell, reference, runs = followed
    for seed, (want, again) in runs.items():
        with readings_qwen3_next.faults(reference)[fault]():
            rows = compare.training(again(), want,
                                    cell.params["check"]["limits"])
        assert _fails(rows), (fault, seed, rows)
    for name in ("carry", "write_strength", "head_decay", "output_gate",
                 "shared_gate", "router_probs"):
        assert getattr(reference, name).__name__ == name


def test_an_unchanged_state_is_not_correct(followed):
    """Weights that never moved: the losses of the later steps and the
    gradient at step 1 may read as the reference's, the change reads 1."""
    _, cell, _, runs = followed
    limits = cell.params["check"]["limits"]
    for seed, (want, _) in runs.items():
        still = dict(want, change_norm={k: 0.0 for k in want["change_norm"]})
        rows = compare.training(still, want, limits)
        assert [r["name"] for r in rows if r["limit"] is not None
                and r["value"] > r["limit"]] == ["change_norm_gap"], seed
        assert not _fails(compare.training(want, want, limits)), seed


def test_the_faults_read_over_one_step(capsys):
    """``--fault-steps 1``: the first step's loss and the gradient are
    compared, the change is not read, and beta 1 still fails."""
    assert readings_qwen3_next.main(
        [CELL, "--fault", "3", "--kinds", "fault_beta_one",
         "--fault-steps", "1"], benchmark_json=PRESET) == 0
    lines = [json.loads(x.split(" ", 1)[1]) for x in
             capsys.readouterr().out.splitlines()
             if x.startswith("READING ")]
    assert [(x["kind"], x["steps"]) for x in lines] == [("fault_beta_one", 1)]
    assert set(lines[0]["numbers"]) == {"loss_gap_step1", "grad_norm_gap"}
    assert lines[0]["fails"] == ["grad_norm_gap"]


def test_the_float8_control_is_not_correct_and_bfloat16_is(followed):
    bench, cell, _, runs = followed
    numerics = bench.module("reference", "numerics")
    limits = cell.params["check"]["limits"]
    for seed, (want, again) in runs.items():
        assert _fails(compare.training(again(math=numerics.Fp8()), want,
                                       limits)), seed
        assert not _fails(compare.training(again(math=numerics.Bf16()), want,
                                           limits)), seed
