"""The Nemotron-3 Nano cell's part of the benchmark, without a chip: the
required FLOPs a token through ``dims()`` and the SSD kernels' required work
(``harness/ssd_work.py``) against hand counts, the other cells' counts as
they were, the new readers on a made-up trace (and nothing read where the
trace holds no such event), the configuration's widths against the
published ones, the parameters held, and the new entries of
``BENCHMARK.json`` scoped to the new cell."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.harness import flops, sala_work, spec, ssd_work  # noqa: E402

CELL = "nemotron3-nano-train-s16384"
METRICS = ["ssd_device_ms_per_step", "ssd_roofline"]
# accepted metrics whose readers find this cell's events as they are
APPENDED = ["attn_device_ms_per_step", "moe_device_ms_per_step"]
# the catalog's config of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, the keys
# that shape the model
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mlp_hidden_act": "relu2", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 52, "num_key_value_heads": 2,
    "routed_scaling_factor": 2.5, "ssm_state_size": 128,
    "tie_word_embeddings": False, "topk_group": 1, "use_conv_bias": True,
    "vocab_size": 131072}
# the other cells' required FLOPs a token, as the parent commit counts them
BEFORE = {"gpt2s-train-s1024": 798031872.0,
          "mistral7b-l2-train-s4096": 3605004288.0,
          "laguna-xs2-train-s8192": 2405311488.0,
          "glm47-flash-train-s8192": 3625451520.0,
          "minicpm-sala-train-s12288": 7071609354.0,
          "qwen3-next-train-s16384": 1582153728.0}


@pytest.fixture(scope="module")
def real():
    return spec.load_benchmark(os.path.join(ROOT, "BENCHMARK.json"),
                               root=ROOT)


def _per_token(real, name):
    cell = real.cell(name)
    v = cell.config.values
    return flops.train_flops_per_token(
        real.module("reference", v["family"]).dims(v), cell.params["seq"])


def test_required_flops_of_the_cell_by_hand(real):
    h = 2688
    mamba = h * (4096 + 6144 + 64) + 4096 * h + 12 * 64 * 64 * 128 / 6
    moe = h * 128 + 2 * h * 3712 + (6 * 8 / 128) * 2 * h * 1856
    attention = h * 4096 + 2 * h * 256 + 4096 * h
    want = 6 * (4 * (mamba + moe) + attention + 16384 * h) \
        + 6 * 16384 * 32 * 128
    got = _per_token(real, CELL)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(2_338_406_400, rel=1e-12)
    # 38.3 TFLOP a step of 16,384 tokens
    assert 38.3e12 < got * 16384 < 38.4e12


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_the_other_cells_count_as_before(real, name):
    assert _per_token(real, name) == BEFORE[name]


def test_the_kernels_required_work_by_hand(real):
    v = real.cell(CELL).config.values
    ops, moved = ssd_work.ssd(v, 1, 16384)
    assert ops == 12 * 64 * 64 * 128 * 16384
    tokens = 16384
    xy = 2 * tokens * 64 * 64 * 2
    bc = 2 * tokens * 8 * 128 * 2
    dt = tokens * 64 * 4
    assert moved == 2 * (xy + bc + dt)
    assert sala_work.least_seconds(ops, moved, real.peaks(
        "TPU v5 lite"))[1] == "memory"
    assert ssd_work.mamba_layers(v) == 4
    assert ssd_work.mamba_layers(dict(
        v, hybrid_override_pattern=PUBLISHED["hybrid_override_pattern"])) \
        == 23


def _ops(per_step):
    """A reduced trace of two steps holding the named kernels' events."""
    ops, t = [], 0.0
    for _ in range(2):
        for name, ms in per_step:
            ops.append((t, t + ms * 1e6, f"%{name} = bf16[64,16384,64]"
                        "{2,1,0} custom-call(...), "
                        'custom_call_target="tpu_custom_call"'))
            t += ms * 1e6
    return {"steps": 2, "window_s": 1.2, "busy_s": 1.2, "ops": ops}


def test_the_readers_on_a_made_up_trace(real):
    cell = real.cell(CELL)
    step = [("fusion.7", 400.0), ("flash_fwd.1", 9.0),
            ("flash_bwd_dq.1", 11.0), ("flash_bwd_dkv.1", 12.0),
            ("moe_gmm_fwd.1", 2.0), ("moe_gmm_bwd_w.1", 3.0)] \
        + [("ssd_fwd.%d" % i, 2.0) for i in range(4)] \
        + [("ssd_bwd.%d" % i, 4.0) for i in range(4)]
    logged = []
    ctx = {"trace": _ops(step), "cell": cell, "bench": real,
           "peaks": real.peaks("TPU v5 lite"), "log": logged.append}
    read = {n: real.module("metrics", n).read(ctx)
            for n in METRICS + APPENDED}
    assert read["ssd_device_ms_per_step"] == pytest.approx(24.0)
    ops, moved = ssd_work.ssd(cell.config.values, 1, 16384)
    assert read["ssd_roofline"] == pytest.approx(
        100 * 4 * (moved / 819e9) / 0.024, rel=1e-6)
    assert 0 < read["ssd_roofline"] < 100
    assert read["attn_device_ms_per_step"] == pytest.approx(32.0)
    assert read["moe_device_ms_per_step"] == pytest.approx(5.0)
    assert any("memory-bound" in line for line in logged)
    assert any("ssd_bwd 16.000 ms a step" in line for line in logged)
    # nothing to read: no trace, a trace without the events (the parent
    # commit has no such kernel)
    for changed in ({"trace": None},
                    {"trace": _ops([("fusion.7", 400.0)])}):
        for name in METRICS:
            assert real.module("metrics", name).read(
                dict(ctx, **changed)) is None


def test_the_configuration_keeps_the_published_widths(real):
    v = real.cell(CELL).config.values
    changed = {k for k, x in PUBLISHED.items() if v.get(k, "absent") != x}
    assert changed == {"num_hidden_layers", "hybrid_override_pattern",
                       "n_routed_experts", "vocab_size"}
    assert {k: PUBLISHED[k] for k in changed} == v["published"]
    assert set(v["reduced"]) == changed
    assert v["hybrid_override_pattern"] == \
        PUBLISHED["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert v["num_hidden_layers"] == 9
    assert v["expert_share"] == {"first": 0, "held": 8, "of": 128}
    assert v["n_routed_experts"] == 8 and v["vocab_size"] * 8 == 131072
    assert len(v["ssm_decay_init"]["A"]) == 64
    for key in ("rotary", "decay_init", "D", "conv_init", "score_bias",
                "dtype", "recompute_layers"):
        assert key in v["assumed"], key
    assert sorted(real.configs["nemotron3-nano-l9-e8"].reduced) == \
        sorted(changed)
    # every number of the catalog's config is in the file, the same but
    # for what ``reduced`` names
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron3-nano-l9-e8.json")) as f:
        raw = json.load(f)
    for k, x in PUBLISHED.items():
        assert k in raw and (raw[k] == x or k in changed), k


def test_the_parameters_held_by_hand(real):
    """4 Mamba-2 layers of 38,744,896, 4 expert layers of 100,125,312 (8
    relu^2 experts held: no gate matrix), one attention layer of 23,399,040,
    embedding, head and final norm 88,083,072; beside them 4 x 128 frozen
    correction biases, no trained leaf."""
    import numpy as np
    v = real.cell(CELL).config.values
    shapes = real.module("reference", "nemotron_h").param_shapes(v)
    count = {k: int(np.prod(s)) for k, (s, _) in shapes.items()}
    layer = [sum(n for k, n in count.items()
                 if k.startswith(f"layers.{i}.")) for i in range(9)]
    assert layer == [38_744_896, 100_125_312] * 2 + [38_744_896,
                                                      23_399_040,
                                                      100_125_312,
                                                      38_744_896,
                                                      100_125_312]
    n = sum(count.values())
    assert n == 666_962_944
    assert n + 4 * 128 == 666_963_456
    # 12 bytes a parameter: bfloat16 weight and gradient, float32 moments
    assert 8.00e9 < 12 * n < 8.01e9


def test_the_new_entries_are_for_the_new_cell_alone(real):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert entry["chips"] == 1 and entry["config"] == "nemotron3-nano-l9-e8"
    assert len(entry["why"]) <= 200
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in METRICS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["layer"] == "kernels"
        assert metrics[name]["moves"] == "train_tokens_per_s"
    for name in APPENDED:
        assert CELL in metrics[name]["workloads"]
    cell = real.cell(CELL)
    assert cell.params["runner"] == "train_lean"
    assert (cell.params["batch"], cell.params["seq"]) == (1, 16384)
    for other in real.cells:
        if other != CELL:
            got = {m["name"] for m in real.metrics_for(other, "per_layer")}
            assert not got & set(METRICS), other


def test_the_cells_limits_hold_an_unchanged_state_not_correct(real):
    """Weights that never moved read 1 in the change of every leaf whose
    reference change is at least the median's, and the cell's limit on the
    change catches that; the reference against itself passes."""
    from benchmarks.harness import compare
    limits = real.cell(CELL).params["check"]["limits"]
    names = ["layers.%d.%s" % (i, k) for i in range(9)
             for k in ("norm.weight", "out.weight")]
    want = {"loss": [10.24, 10.25, 10.19],
            "grad_norm": {k: 0.01 * (1 + i) for i, k in enumerate(names)},
            "change_norm": {k: 0.003 * (1 + i) for i, k in enumerate(names)}}
    still = dict(want, change_norm=dict.fromkeys(names, 0.0))
    rows = {r["name"]: r for r in compare.training(still, want, limits)}
    assert rows["change_norm_gap"]["value"] == pytest.approx(1.0)
    assert not compare.passes(list(rows.values()))
    assert compare.passes(compare.training(want, want, limits))
    # the lower-precision controls' smallest reading on the chip, 0.0287,
    # fails the gradient's limit; the program's largest, 0.01945 (a router
    # whose tokens choose otherwise under bfloat16 storage), passes
    assert 0.01945 < limits["grad_norm_gap"] < 0.0287
