"""The tiny-preset rehearsal of ``run.py`` on the CPU: the result line's
shape, the refusal without a chip, the control that has to come out not
correct, and the faults planted under the timed path."""
import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import pytest

from benchmarks import run
from benchmarks.harness import compare, spec
from conftest import PRESET, ROOT

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def rehearse(cell, seed, trace=0, wrap_step=None):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       "1", "--trace", str(trace)], benchmark_json=PRESET,
                      rehearsal=True, wrap_step=wrap_step)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["gpt2-tiny-train", "llama-tiny-train"])
def test_result_line_has_the_contracts_keys(cell):
    line = rehearse(cell, 2**31 + 5)
    assert list(line) == CONTRACT_KEYS + ["compared"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 3
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert [c["name"] for c in line["compared"]] == [
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
        "grad_norm_gap", "change_norm_gap", "compiles_in_window"]
    assert all(c["value"] <= c["limit"] for c in line["compared"])


def test_traced_run_reports_per_layer_metrics_and_no_device_metric():
    line = rehearse("gpt2-tiny-train", 17, trace=1)
    assert line["correct"] is True
    # the CPU has no device plane: readers that find nothing return nothing
    assert set(line["metrics"]) == {"feed_wait_ms_per_step",
                                    "step_ms_p95.train"}
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_refuses_to_run_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "gpt2s-train-s1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env, cwd=ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_unknown_device_kind_is_an_error():
    bench = spec.load_benchmark(PRESET, root=ROOT)
    assert bench.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        bench.peaks("TPU v9 imaginary")


# -- faults under the timed path: correct has to come out false -------------

def _state_unchanged(step):
    def broken(params, opt_state, key, ids, labels, lr):
        copy = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
        loss, _, _ = step(*copy, key, ids, labels, lr)
        return loss, params, opt_state
    return broken


def _half_batch(step):
    def broken(params, opt_state, key, ids, labels, lr):
        half = ids.shape[0] // 2
        return step(params, opt_state, key, ids[:half], labels[:half], lr)
    return broken


@pytest.mark.parametrize("fault,number", [
    (_state_unchanged, "change_norm_gap"), (_half_batch, "grad_norm_gap")],
    ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("cell", ["gpt2-tiny-train", "llama-tiny-train"])
def test_a_fault_under_the_timed_path_is_not_correct(cell, fault, number):
    line = rehearse(cell, 23, wrap_step=fault)
    assert line["correct"] is False
    failed = {c["name"] for c in line["compared"] if c["value"] > c["limit"]}
    assert number in failed
    if fault is _state_unchanged:
        got = {c["name"]: c["value"] for c in line["compared"]}
        assert got["change_norm_gap"] == pytest.approx(1.0)
        assert got["grad_norm_gap"] == pytest.approx(1.0)


# -- the control: the reference in float8 in the program's place -------------

@pytest.mark.parametrize("cell_name", ["gpt2-tiny-train", "llama-tiny-train"])
def test_the_float8_control_is_not_correct(cell_name):
    bench = spec.load_benchmark(PRESET, root=ROOT)
    cell = bench.cell(cell_name)
    runner = bench.module("runners", "train")
    numerics = bench.module("reference", "numerics")
    limits = cell.params["check"]["limits"]
    for seed in (3, 5, 6):
        batches = runner.batch_fn(bench, cell, seed)
        want = runner.follow_reference(bench, cell, seed, batches)
        control = runner.follow_reference(bench, cell, seed, batches,
                                          math=numerics.Fp8())
        rows = compare.training(control, want, limits)
        assert any(r["value"] > r["limit"] for r in rows), (seed, rows)
        # and the stated precision, in the same place, passes
        stated = runner.follow_reference(bench, cell, seed, batches,
                                         math=numerics.Bf16())
        rows = compare.training(stated, want, limits)
        assert all(r["value"] <= r["limit"] for r in rows), (seed, rows)


def test_dead_gradients_are_left_out_by_rule_not_by_name():
    want = {"a": 1.0, "b": 2.0, "c": 1e-9, "d": 1.5}
    assert compare.live_leaves(want) == ["a", "b", "d"]
    gap, leaf = compare.worst_leaf({"a": 1.1, "b": 2.0, "c": 5.0, "d": 1.5},
                                   want, ["a", "b", "d"])
    assert leaf == "a" and gap == pytest.approx(0.1 / 1.5)
    gap, leaf = compare.worst_leaf({"a": float("nan"), "b": 2.0}, 
                                   {"a": 1.0, "b": 2.0})
    assert leaf == "a"
