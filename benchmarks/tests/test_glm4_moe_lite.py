"""The GLM MoE lite family through ``runners/train.py`` at a tiny preset on
the CPU (the published structure: a dense layer and two sparse ones, latent
attention, 4 of 16 bias-corrected experts held, top-4, one MTP module):
``correct`` comes out true, and false for the float8 control, for half of the
batch left out under the timed path, and in the reference put in the program's
place for one expert fewer chosen, the MTP term left out of the loss, and the
choice made without the bias. The two readers of the cell's own metrics on a
trace recorded on the chip."""
import io
import json
import os
from contextlib import redirect_stdout

import pytest

from benchmarks import run
from benchmarks.harness import compare, flops, spec, trace
from benchmarks.tools import readings_glm
from conftest import ROOT
from test_run import _half_batch

PRESET = os.path.join(ROOT, "benchmarks", "tests", "preset_glm",
                      "BENCHMARK.json")
CELL = "glm-tiny-train"
REAL = "glm47-flash-train-s8192"


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
    assert all(c["value"] <= c["limit"] for c in line["compared"]
               if c["limit"] is not None)
    assert {c["name"] for c in line["compared"] if c["limit"] is not None} \
        >= {"grad_norm_gap", "change_norm_gap", "compiles_in_window"}


def test_a_traced_run_on_the_cpu_reads_no_device_metric():
    line = rehearse(17, trace=1)
    assert line["correct"] is True
    # no device plane on the CPU: the two new readers find no event and
    # return nothing, as they do on a program without the model
    assert {"feed_wait_ms_per_step"} <= set(line["metrics"]) <= {
        "feed_wait_ms_per_step", "step_ms_p95.train"}


def test_half_of_the_batch_left_out_is_not_correct():
    line = rehearse(23, wrap_step=_half_batch)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["compared"]
               if c["limit"] is not None)


@pytest.fixture(scope="module")
def followed():
    """seed -> (the reference's first steps, a function that follows them
    again with something planted)."""
    bench = spec.load_benchmark(PRESET, root=ROOT)
    cell = bench.cell(CELL)
    runner = bench.module("runners", "train")
    reference = bench.module("reference", "glm4_moe_lite")
    out = {}
    for seed in (3, 5, 6):
        batches = runner.batch_fn(bench, cell, seed)

        def again(seed=seed, batches=batches, **kw):
            return runner.follow_reference(bench, cell, seed, batches, **kw)
        out[seed] = (again(), again)
    return bench, cell, reference, out


def _fails(rows):
    return any(r["value"] > r["limit"] for r in rows
               if r["limit"] is not None)


@pytest.mark.parametrize("fault", ["fault_top3", "fault_no_mtp",
                                   "control_no_bias"])
def test_a_fault_of_the_family_is_not_correct(followed, fault):
    _, cell, reference, runs = followed
    limits = cell.params["check"]["limits"]
    values = cell.config.values
    before = json.dumps(values, sort_keys=True)
    for seed, (want, again) in runs.items():
        with readings_glm.faults(reference, values)[fault]():
            rows = compare.training(again(), want, limits)
        assert _fails(rows), (seed, rows)
    # and it is taken out
    assert reference.route.__name__ == "route"
    assert json.dumps(values, sort_keys=True) == before


def test_the_float8_control_is_not_correct_and_bfloat16_is(followed):
    bench, cell, _, runs = followed
    numerics = bench.module("reference", "numerics")
    limits = cell.params["check"]["limits"]
    for seed, (want, again) in runs.items():
        assert _fails(compare.training(again(math=numerics.Fp8()), want,
                                       limits)), seed
        assert not _fails(compare.training(again(math=numerics.Bf16()), want,
                                           limits)), seed


@pytest.fixture(scope="module")
def real():
    return spec.load_benchmark(os.path.join(ROOT, "BENCHMARK.json"),
                               root=ROOT)


def test_required_flops_of_the_cell_by_hand(real):
    """``dims`` through the unedited ``flops.train_flops_per_token``: six
    attention calls of 20 heads at 256; the five latent projections in each;
    the dense MLP, or router + shared expert + half a routed expert in
    expectation (top-4, 8 of 64 held); ``Weh``; and the head twice."""
    cell = real.cell(REAL)
    d = real.module("reference", "glm4_moe_lite").dims(cell.config.values)
    h, s = 2048, 8192
    attn = h * 768 + 768 * 20 * 256 + h * (512 + 64) + 512 * 20 * (192 + 256) \
        + 20 * 256 * h
    assert attn == 21_757_952
    expert = 3 * h * 1536
    sparse = attn + h * 64 + expert + 4 * 8 / 64 * expert
    params = (attn + 3 * h * 10240) + 4 * sparse + (sparse + 2 * h * h) \
        + 2 * 19360 * h
    want = 6 * params + 6 * 6 * s * 20 * 256
    got = flops.train_flops_per_token(d, cell.params["seq"])
    assert got == pytest.approx(want, rel=1e-12)
    assert 3.62e9 < got < 3.63e9
    assert d["layers"] == 6 and d["head_dim"] == 256


def test_the_configuration_keeps_the_published_widths(real):
    v = real.cell(REAL).config.values
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"] == "GLM-4.7-Flash")["config"]
    changed = {k for k, x in published.items() if v.get(k, "absent") != x}
    assert changed == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert {k: published[k] for k in changed} == v["published"]
    assert set(v["reduced"]) == changed
    assert v["expert_share"] == {"first": 0, "held": 8, "of": 64}


def test_the_two_readers_on_a_trace_recorded_on_the_chip(real):
    """Three steps of glm47-flash-train-s8192 on a TPU v5e (the sizing probe
    of PR 32), the Mosaic calls' events kept: 12 forward calls (the layer
    body is recomputed), 6 dq and 6 dkv a step at [40, 8192, 256]."""
    cell = real.cell(REAL)
    events = trace.read_events(os.path.join(
        os.path.dirname(__file__), "data", "trace_events_glm_3steps.json.gz"))
    reduced = trace.reduce(events, r"jit_train_step")
    assert reduced["steps"] == 1
    logged = []
    ctx = {"trace": reduced, "cell": cell, "bench": real,
           "peaks": real.peaks("TPU v5 lite"), "log": logged.append}
    ms = real.module("metrics", "mla_attn_device_ms_per_step").read(ctx)
    assert ms == pytest.approx(292.768051, rel=1e-9)
    assert "24 events by shape" in logged[-1]
    share = real.module("metrics", "mla_attn_roofline").read(ctx)
    # 6 calls x 6 x 2 x 2 x 20 x 8192^2 x 256 / 2 FLOPs at 197 TFLOP/s
    least = 6 * 6 * 2 * 2 * 20 * 8192 ** 2 * 256 / 2 / 197e12
    assert least == pytest.approx(0.125579, rel=1e-5)
    assert share == pytest.approx(100 * least / 0.292768051, rel=1e-9)
    assert "compute-bound" in logged[-1]
    # the grouped products are not attention, though Mosaic calls too
    assert sum("moe_gmm" in name for _, _, name in reduced["ops"]) == 40
    # nothing to read: no trace, another family's cell, a trace without
    # the events (the parent commit has no such model)
    for changed in ({"trace": None},
                    {"cell": real.cell("laguna-xs2-train-s8192")},
                    {"trace": dict(reduced, ops=[
                        op for op in reduced["ops"] if "moe_gmm" in op[2]])}):
        for name in ("mla_attn_device_ms_per_step", "mla_attn_roofline"):
            assert real.module("metrics", name).read(
                dict(ctx, **changed)) is None


def test_the_new_entries_are_appended_for_the_new_cell_alone(real):
    names = [m["name"] for m in real.per_layer]
    assert names[-2:] == ["mla_attn_roofline", "mla_attn_device_ms_per_step"]
    for m in real.per_layer[-2:]:
        assert m["workloads"] == [REAL] and m["layer"] == "kernels"
        assert m["moves"] == "train_tokens_per_s"
    assert list(real.cells)[-1] == REAL and real.cell(REAL).chips == 1
