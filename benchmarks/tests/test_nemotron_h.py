"""The Nemotron-H family through ``runners/train_lean.py`` at a tiny preset on
the CPU (the pattern's first attention period ``MEMEM*EME``, 8 Mamba-2 heads
of 8 over 2 groups of state 16, relu^2 top-4 of 16 experts with 8 held, a
correction bias and a shared expert, 256 tokens: eight chunks of the scan):
``correct`` comes out true, and false for the float8 control and, in the
reference put in the program's place, for each of the six faults of
``tools/readings_nemotron_h.py``; a traced run on the CPU reads no device
metric."""
import io
import json
import os
from contextlib import redirect_stdout

import pytest

from benchmarks import run
from benchmarks.harness import compare, spec
from benchmarks.tools import readings_nemotron_h
from conftest import ROOT

PRESET = os.path.join(ROOT, "benchmarks", "tests", "preset_nemotron",
                      "BENCHMARK.json")
CELL = "nemotron-tiny-train"
FAULTS = ["fault_no_carry", "fault_neighbour_group", "fault_neighbour_decay",
          "fault_no_skip", "fault_gate_after_norm", "fault_relu"]


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
    assert not {"ssd_device_ms_per_step", "ssd_roofline", "step_mfu"} & \
        set(line["metrics"])


@pytest.fixture(scope="module")
def followed():
    bench = spec.load_benchmark(PRESET, root=ROOT)
    runner = bench.module("runners", "train_lean")
    reference = bench.module("reference", "nemotron_h")
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
        with readings_nemotron_h.faults(reference)[fault]():
            rows = compare.training(again(), want,
                                    cell.params["check"]["limits"])
        assert _fails(rows), (fault, seed, rows)
    for name in ("carry", "group_of", "head_decay", "skip", "gated_norm",
                 "expert_act", "state_precision"):
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


def test_the_controls_read_over_one_step(capsys):
    """``--control`` reads the float8 control over the cell's steps and the
    bfloat16 state over ``--fault-steps``; the float8 control fails."""
    assert readings_nemotron_h.main(
        [CELL, "--control", "3", "--fault-steps", "1"],
        benchmark_json=PRESET) == 0
    lines = [json.loads(x.split(" ", 1)[1]) for x in
             capsys.readouterr().out.splitlines()
             if x.startswith("READING ")]
    assert [(x["kind"], x["steps"]) for x in lines] == [
        ("control_fp8", 3), ("control_bf16_state", 1)]
    assert lines[0]["fails"] == ["grad_norm_gap"]
    assert set(lines[1]["numbers"]) == {"loss_gap_step1", "grad_norm_gap"}


def test_the_choice_reading_counts_the_tokens_choosing_otherwise(capsys):
    """``--choice`` counts, by expert layer, the tokens whose chosen experts
    differ between the program (bfloat16 storage) and the float32
    reference: a few, not none and not most; a program reading lists its
    worst leaves, the first the one ``grad_norm_gap`` names."""
    assert readings_nemotron_h.main(
        [CELL, "--choice", "3", "--program", "3"],
        benchmark_json=PRESET) == 0
    lines = [json.loads(x.split(" ", 1)[1]) for x in
             capsys.readouterr().out.splitlines()
             if x.startswith("READING ")]
    assert [x["kind"] for x in lines] == ["choice", "program"]
    choice, program = lines
    assert sorted(choice["tokens_choosing_otherwise"]) == ["1", "3", "6", "8"]
    counts = choice["tokens_choosing_otherwise"].values()
    assert 0 < sum(counts) and max(counts) < choice["tokens"] // 4
    assert all(0 <= choice["of_those_a_held_expert"][k] <= n for k, n in
               choice["tokens_choosing_otherwise"].items())
    worst = program["worst_grad_leaves"]
    assert worst[0][0] == program["leaves"]["grad_norm_gap"]
    assert worst[0][1] == program["numbers"]["grad_norm_gap"]
    assert [g for _, g, _ in worst] == sorted((g for _, g, _ in worst),
                                              reverse=True)
