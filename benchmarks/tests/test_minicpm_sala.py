"""The MiniCPM-SALA family through ``runners/train_lean.py`` at a tiny preset
on the CPU (the published structure: one period of the mixers, blocks of 8
with top-4, a window of 2 blocks, dense_len 32), past ``dense_len`` and under
it: ``correct`` comes out true, and false for the float8 control, for half of
the batch left out under the timed path, and in the reference put in the
program's place for half the blocks chosen, the forced local blocks left out,
a neighbouring head's decay and the output gate left out. The work functions
and the four readers of the cell's own metrics."""
import io
import json
import os
from contextlib import redirect_stdout

import pytest

from benchmarks import run
from benchmarks.harness import compare, flops, sala_work, spec
from benchmarks.tools import readings_sala
from conftest import ROOT
from test_run import _half_batch

PRESET = os.path.join(ROOT, "benchmarks", "tests", "preset_sala",
                      "BENCHMARK.json")
CELL, DENSE = "sala-tiny-train", "sala-tiny-dense-train"
REAL = "minicpm-sala-train-s12288"
KERNEL_METRICS = ["sparse_attn_device_ms_per_step",
                  "linear_attn_device_ms_per_step", "sparse_attn_roofline",
                  "linear_attn_roofline"]


def rehearse(cell, seed, trace=0, wrap_step=None):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       "1", "--trace", str(trace)], benchmark_json=PRESET,
                      rehearsal=True, wrap_step=wrap_step)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell,seed", [(CELL, 2**31 + 5), (CELL, 7),
                                       (DENSE, 4)])
def test_the_family_runs_through_the_lean_runner_and_is_correct(cell, seed):
    line = rehearse(cell, seed)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 3
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    compared = {c["name"] for c in line["compared"]
                if c["limit"] is not None}
    assert compared >= {"grad_norm_gap", "change_norm_gap",
                        "compiles_in_window"}
    # the choice is compared where there is one: past dense_len
    assert ("choice_gap" in compared) == (cell == CELL)


def test_a_traced_run_on_the_cpu_reads_no_device_metric():
    line = rehearse(CELL, 17, trace=1)
    assert line["correct"] is True
    assert {"feed_wait_ms_per_step"} <= set(line["metrics"]) <= {
        "feed_wait_ms_per_step", "step_ms_p95.train"}


def test_half_of_the_batch_left_out_is_not_correct():
    line = rehearse(CELL, 23, wrap_step=_half_batch)
    assert line["correct"] is False


@pytest.fixture(scope="module")
def followed():
    bench = spec.load_benchmark(PRESET, root=ROOT)
    runner = bench.module("runners", "train_lean")
    reference = bench.module("reference", "minicpm_sala")
    out = {}
    for name in (CELL, DENSE):
        cell = bench.cell(name)
        for seed in (3, 5):
            batches = runner.batch_fn(bench, cell, seed)

            def again(cell=cell, seed=seed, batches=batches, **kw):
                return runner.follow_reference(bench, cell, seed, batches,
                                               **kw)
            out[name, seed] = (cell, again(), again)
    return bench, reference, out


def _fails(rows):
    return any(r["value"] > r["limit"] for r in rows
               if r["limit"] is not None)


@pytest.mark.parametrize("fault", ["fault_top32", "fault_no_local",
                                   "fault_neighbour_decay", "fault_no_gate"])
def test_a_fault_of_the_family_is_not_correct(followed, fault):
    _, reference, runs = followed
    for (name, seed), (cell, want, again) in runs.items():
        if name == DENSE and fault in ("fault_top32", "fault_no_local"):
            continue          # under dense_len there is no choice to spoil
        values = cell.config.values
        before = json.dumps(values, sort_keys=True)
        with readings_sala.faults(reference, values)[fault]():
            rows = compare.training(again(), want,
                                    cell.params["check"]["limits"])
        assert _fails(rows), (name, seed, rows)
        assert json.dumps(values, sort_keys=True) == before
    assert reference.output_gate.__name__ == "output_gate"
    assert reference.decay_rates.__name__ == "decay_rates"


@pytest.mark.parametrize("fault", readings_sala.CHOICE_FAULTS)
def test_a_fault_of_the_choice_fails_choice_gap_alone(followed, fault):
    bench, reference, _ = followed
    runner = bench.module("runners", "train_lean")
    cell = bench.cell(CELL)
    limit = cell.params["check"]["limits"]["choice_gap"]
    for seed in (3, 5):
        ids = runner.batch_fn(bench, cell, seed)(0)[0]
        want = runner.reference_choices(bench, cell, seed, ids)
        mine = runner.program_choices(bench, cell, seed, ids)
        assert runner.choice_gap(mine, want) <= limit
        with readings_sala.faults(reference, cell.config.values)[fault]():
            spoiled = runner.reference_choices(bench, cell, seed, ids)
        assert runner.choice_gap(spoiled, want) > limit, (fault, seed)


def test_choice_gap_counts_what_either_side_chose_alone():
    import numpy as np
    bench = spec.load_benchmark(PRESET, root=ROOT)
    gap = bench.module("runners", "train_lean").choice_gap
    want = {0: np.array([[1, 1, 0, 0], [1, 0, 1, 0]], bool)}
    assert gap(want, want) == 0.0
    # a swapped pair is two of four choices; a superset does not pass free
    assert gap({0: np.array([[1, 0, 1, 0], [1, 0, 1, 0]], bool)}, want) == 0.5
    assert gap({0: np.ones((2, 4), bool)}, want) == 1.0
    assert gap({}, want) == float("inf") == gap(want, {1: want[0]})


def test_the_float8_control_is_not_correct_and_bfloat16_is(followed):
    bench, _, runs = followed
    numerics = bench.module("reference", "numerics")
    for (name, seed), (cell, want, again) in runs.items():
        limits = cell.params["check"]["limits"]
        assert _fails(compare.training(again(math=numerics.Fp8()), want,
                                       limits)), (name, seed)
        assert not _fails(compare.training(again(math=numerics.Bf16()), want,
                                           limits)), (name, seed)


@pytest.fixture(scope="module")
def real():
    return spec.load_benchmark(os.path.join(ROOT, "BENCHMARK.json"),
                               root=ROOT)


def test_required_flops_of_the_cell_by_hand(real):
    cell = real.cell(REAL)
    d = real.module("reference", "minicpm_sala").dims(cell.config.values)
    h, inner, hd = 4096, 16384, 32 * 128
    mlp = 3 * h * inner
    sparse = 3 * h * hd + 2 * h * 256 + mlp + 2 * hd * 3392.5 \
        + hd * 382.563720703125 / 3
    lightning = 5 * h * hd + mlp + 2 * 32 * 128 * 128
    want = 6 * (sparse + 3 * lightning + 9216 * h)
    got = flops.train_flops_per_token(d, cell.params["seq"])
    assert got == pytest.approx(want, rel=1e-12)
    assert 7.07e9 < got < 7.08e9 and d["heads"] == 0 and d["layers"] == 4


def test_the_kernels_required_work_by_hand(real):
    v = real.cell(REAL).config.values
    ops, moved = sala_work.sparse_attention(v, 1, 12288, 3392.5)
    assert ops == 12 * 32 * 128 * 3392.5 * 12288
    q, kv = 12288 * 32 * 128 * 2, 12288 * 2 * 128 * 2
    assert moved == (2 * q + 2 * kv) + (3 * q + 2 * kv) + (q + 2 * kv) \
        + 2 * 4 * 2 * 12288 * 64
    ops, moved = sala_work.linear_attention(v, 1, 12288)
    assert ops == 12 * 32 * 128 * 128 * 12288 and moved == 11 * q
    peaks = real.peaks("TPU v5 lite")
    assert sala_work.least_seconds(ops, moved, peaks)[1] == "memory"
    assert sala_work.layers_of(v, "minicpm4") == 1
    assert sala_work.layers_of(v, "lightning-attn") == 3


def test_the_configuration_keeps_the_published_widths(real):
    v = real.cell(REAL).config.values
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"] == "MiniCPM-SALA")["config"]
    changed = {k for k, x in published.items() if v.get(k, "absent") != x}
    assert changed == {"num_hidden_layers", "mixer_types", "vocab_size"}
    assert {k: published[k] for k in changed} == v["published"]
    assert set(v["reduced"]) == changed
    assert v["mixer_types"] == published["mixer_types"][:4]
    assert v["sparse_config"]["topk"] == 64 and v["residual_scale_layers"] == 32


def _ops(per_step):
    """A reduced trace of two steps holding the named kernels' events."""
    ops, t = [], 0.0
    for _ in range(2):
        for name, ms in per_step:
            ops.append((t, t + ms * 1e6, f"%{name} = bf16[32,12288,128]"
                        "{2,1,0} custom-call(...), "
                        'custom_call_target="tpu_custom_call"'))
            t += ms * 1e6
    return {"steps": 2, "window_s": 2.4, "busy_s": 2.4, "ops": ops}


def test_the_four_readers_on_a_made_up_trace(real):
    cell = real.cell(REAL)
    step = [("sparse_attn_fwd.1", 10.0), ("sparse_attn_bwd_dq.1", 13.0),
            ("sparse_attn_bwd_dkv.1", 16.0), ("fusion.7", 400.0)] \
        + [("linear_attn_fwd.%d" % i, 1.0) for i in range(6)] \
        + [("linear_attn_bwd.%d" % i, 1.5) for i in range(3)]
    logged = []
    ctx = {"trace": _ops(step), "cell": cell, "bench": real,
           "peaks": real.peaks("TPU v5 lite"), "log": logged.append}
    read = {n: real.module("metrics", n).read(ctx) for n in KERNEL_METRICS}
    assert read["sparse_attn_device_ms_per_step"] == pytest.approx(39.0)
    assert read["linear_attn_device_ms_per_step"] == pytest.approx(10.5)
    v = cell.config.values
    ops, moved = sala_work.sparse_attention(v, 1, 12288, 3392.5)
    assert read["sparse_attn_roofline"] == pytest.approx(
        100 * (ops / 197e12) / 0.039, rel=1e-6)
    ops, moved = sala_work.linear_attention(v, 1, 12288)
    assert read["linear_attn_roofline"] == pytest.approx(
        100 * 3 * (moved / 819e9) / 0.0105, rel=1e-6)
    assert any("compute-bound" in line for line in logged)
    assert any("memory-bound" in line for line in logged)
    assert any("sparse_attn_bwd_dkv 16.000 ms a step" in line
               for line in logged)
    # nothing to read: no trace, a trace without the events (the parent
    # commit has no such kernel)
    for changed in ({"trace": None},
                    {"trace": _ops([("fusion.7", 400.0)])}):
        for name in KERNEL_METRICS:
            assert real.module("metrics", name).read(
                dict(ctx, **changed)) is None


def test_the_new_entries_are_for_the_new_cell_alone(real):
    # by name, not by place: the next configuration appends after these
    mine = {m["name"]: m for m in real.per_layer
            if m["name"] in KERNEL_METRICS}
    assert sorted(mine) == sorted(KERNEL_METRICS)
    for m in mine.values():
        assert m["workloads"] == [REAL] and m["layer"] == "kernels"
        assert m["moves"] == "train_tokens_per_s"
    assert real.cell(REAL).chips == 1
    assert real.cell(REAL).params["runner"] == "train_lean"
    assert real.cell(REAL).params["check"]["limits"]["choice_gap"] > 0
