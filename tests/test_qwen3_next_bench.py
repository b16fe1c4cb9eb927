"""The Qwen3-Next cell's part of the benchmark, without a chip: the required
FLOPs a token through ``dims()`` and the DeltaNet kernels' required work
(``harness/gdn_work.py``) against hand counts, the new readers on a made-up
trace (and nothing read where the trace holds no such event), the
configuration's widths against the published ones, and the new entries of
``BENCHMARK.json`` scoped to the new cell."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.harness import flops, gdn_work, spec  # noqa: E402

CELL = "qwen3-next-train-s16384"
METRICS = ["gdn_device_ms_per_step", "gdn_roofline", "gated_attn_roofline",
           "qwen_moe_gmm_roofline"]
# accepted metrics whose readers find this cell's events as they are
APPENDED = ["attn_device_ms_per_step", "moe_device_ms_per_step"]
# Qwen3-Next-80B-A3B-Instruct's config.json, the keys that shape the model
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


@pytest.fixture(scope="module")
def real():
    return spec.load_benchmark(os.path.join(ROOT, "BENCHMARK.json"),
                               root=ROOT)


def test_required_flops_of_the_cell_by_hand(real):
    cell = real.cell(CELL)
    d = real.module("reference", "qwen3_next").dims(cell.config.values)
    h = 2048
    moe = h * 512 + 3 * h * 512 + h + (10 * 32 / 512) * 3 * h * 512
    deltanet = h * 12288 + h * 64 + 4096 * h + 3 * 32 * 128 * 128
    full = h * 8192 + 2 * h * 512 + 4096 * h
    want = 6 * (3 * (deltanet + moe) + full + moe + 18992 * h) \
        + 6 * 16384 * 16 * 256
    got = flops.train_flops_per_token(d, cell.params["seq"])
    assert got == pytest.approx(want, rel=1e-12)
    assert 1.58e9 < got < 1.59e9
    assert d["layers"] == 4 and d["heads"] == 4 and d["head_dim"] == 256
    # 25.9 TFLOP a step of 16,384 tokens
    assert 25.9e12 < got * 16384 < 26.0e12


def test_the_kernels_required_work_by_hand(real):
    v = real.cell(CELL).config.values
    ops, moved = gdn_work.gated_delta(v, 1, 16384)
    assert ops == 18 * 32 * 128 * 128 * 16384
    tokens = 16384
    qk = 2 * tokens * 16 * 128 * 2
    vo = 2 * tokens * 32 * 128 * 2
    gb = 2 * tokens * 32 * 4
    assert moved == 2 * (qk + vo + gb)
    peaks = real.peaks("TPU v5 lite")
    from benchmarks.harness import sala_work
    assert sala_work.least_seconds(ops, moved, peaks)[1] == "memory"
    assert gdn_work.deltanet_layers(v) == 3
    assert gdn_work.deltanet_layers(dict(v, num_hidden_layers=48)) == 36


def _ops(per_step):
    """A reduced trace of two steps holding the named kernels' events."""
    ops, t = [], 0.0
    for _ in range(2):
        for name, ms in per_step:
            ops.append((t, t + ms * 1e6, f"%{name} = bf16[32,16384,128]"
                        "{2,1,0} custom-call(...), "
                        'custom_call_target="tpu_custom_call"'))
            t += ms * 1e6
    return {"steps": 2, "window_s": 1.2, "busy_s": 1.2, "ops": ops}


def test_the_two_readers_on_a_made_up_trace(real):
    cell = real.cell(CELL)
    step = [("fusion.7", 400.0), ("flash_fwd.1", 9.0)] \
        + [("gated_delta_fwd.%d" % i, 2.0) for i in range(6)] \
        + [("gated_delta_bwd.%d" % i, 4.0) for i in range(3)]
    logged = []
    ctx = {"trace": _ops(step), "cell": cell, "bench": real,
           "peaks": real.peaks("TPU v5 lite"), "log": logged.append}
    read = {n: real.module("metrics", n).read(ctx) for n in METRICS[:2]}
    assert read["gdn_device_ms_per_step"] == pytest.approx(24.0)
    ops, moved = gdn_work.gated_delta(cell.config.values, 1, 16384)
    assert read["gdn_roofline"] == pytest.approx(
        100 * 3 * (moved / 819e9) / 0.024, rel=1e-6)
    assert any("memory-bound" in line for line in logged)
    assert any("gated_delta_bwd 12.000 ms a step" in line for line in logged)
    # nothing to read: no trace, a trace without the events (the parent
    # commit has no such kernel)
    for changed in ({"trace": None},
                    {"trace": _ops([("fusion.7", 400.0)])}):
        for name in METRICS:
            assert real.module("metrics", name).read(
                dict(ctx, **changed)) is None


def test_the_attention_and_expert_readers_on_a_made_up_trace(real):
    """The full layer's flash calls and the grouped products of the four
    sparse layers, each against its least time at the cell's shapes."""
    from benchmarks.harness import sparse_flops
    cell = real.cell(CELL)
    v = cell.config.values
    step = [("fusion.7", 400.0), ("flash_fwd.1", 14.0),
            ("flash_bwd_dq.1", 19.0), ("flash_bwd_dkv.1", 24.0),
            ("gated_delta_fwd.1", 2.0)] \
        + [("moe_gmm_fwd.%d" % i, 1.0) for i in range(8)] \
        + [("moe_gmm_bwd_x.%d" % i, 1.5) for i in range(4)] \
        + [("moe_gmm_bwd_w.%d" % i, 0.5) for i in range(4)]
    logged = []
    peaks = real.peaks("TPU v5 lite")
    ctx = {"trace": _ops(step), "cell": cell, "bench": real, "peaks": peaks,
           "log": logged.append}
    read = {n: real.module("metrics", n).read(ctx)
            for n in METRICS[2:] + APPENDED}
    assert read["attn_device_ms_per_step"] == pytest.approx(57.0)
    assert read["moe_device_ms_per_step"] == pytest.approx(16.0)
    attn = max(flops.attention_flops(1, 16, 16384, 256) / 197e12,
               flops.attention_bytes(1, 16, 2, 16384, 256) / 819e9)
    assert read["gated_attn_roofline"] == pytest.approx(
        100 * attn / 0.057, rel=1e-6)
    assert 0 < read["gated_attn_roofline"] < 100
    ops, moved = sparse_flops.grouped_products(v, 16384)
    assert sparse_flops.expected_assignments(v, 16384) == 10240
    gmm = 4 * max(ops / 197e12, moved / 819e9)
    assert read["qwen_moe_gmm_roofline"] == pytest.approx(
        100 * gmm / 0.016, rel=1e-6)
    assert any("(1, 16, 2, 16384, 256): compute-bound" in line
               for line in logged)
    sparse = real.module("metrics", "qwen_moe_gmm_roofline").sparse_layers
    assert sparse(v) == 4
    assert sparse(dict(v, mlp_only_layers=[0])) == 3
    assert sparse(dict(v, decoder_sparse_step=2)) == 2
    # a family without Qwen's keys (Laguna's cell) reads nothing here
    laguna = dict(ctx, cell=real.cell("laguna-xs2-train-s8192"))
    for name in METRICS[2:]:
        assert real.module("metrics", name).read(laguna) is None


def test_the_cells_limits_hold_an_unchanged_state_not_correct(real):
    """Weights that never moved read 1 in the change of every leaf whose
    reference change is at least the median's, and the cell's limit on the
    change catches that; the reference against itself passes."""
    from benchmarks.harness import compare
    limits = real.cell(CELL).params["check"]["limits"]
    names = ["layers.%d.%s" % (i, k) for i in range(4)
             for k in ("A_log", "router.weight", "shared_gate.weight")]
    want = {"loss": [10.26, 10.258, 10.252],
            "grad_norm": {k: 0.01 * (1 + i) for i, k in enumerate(names)},
            "change_norm": {k: 0.003 * (1 + i) for i, k in enumerate(names)}}
    still = dict(want, change_norm=dict.fromkeys(names, 0.0))
    rows = {r["name"]: r for r in compare.training(still, want, limits)}
    assert rows["change_norm_gap"]["value"] == pytest.approx(1.0)
    assert not compare.passes(list(rows.values()))
    assert compare.passes(compare.training(want, want, limits))
    assert limits["change_norm_gap"] is not None


def test_the_configuration_keeps_the_published_widths(real):
    v = real.cell(CELL).config.values
    changed = {k for k, x in PUBLISHED.items() if v.get(k, "absent") != x}
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert {k: PUBLISHED[k] for k in changed} == v["published"]
    assert set(v["reduced"]) == changed
    assert v["expert_share"] == {"first": 0, "held": 32, "of": 512}
    assert v["num_hidden_layers"] == 4 and v["vocab_size"] * 8 == 151936
    assert len(v["linear_decay_init"]["A"]) == 32
    for key in ("decay_init", "norm_form", "rotary_pairing", "dtype",
                "recompute_layers", "mtp"):
        assert key in v["assumed"], key
    assert sorted(real.configs["qwen3-next-l4-e32"].reduced) == \
        sorted(changed)


def test_the_parameters_held_by_hand(real):
    import numpy as np
    v = real.cell(CELL).config.values
    shapes = real.module("reference", "qwen3_next").param_shapes(v)
    n = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert n == 625_667_136
    # 12 bytes a parameter: bfloat16 weight and gradient, float32 moments
    assert 7.50e9 < 12 * n < 7.51e9


def test_the_new_entries_are_for_the_new_cell_alone(real):
    mine = {m["name"]: m for m in real.per_layer if m["name"] in METRICS}
    assert sorted(mine) == sorted(METRICS)
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["layer"] == "kernels"
        assert m["moves"] == "train_tokens_per_s"
    for m in real.per_layer:
        if m["name"] in APPENDED:
            assert CELL in m["workloads"]
    cell = real.cell(CELL)
    assert cell.chips == 1 and cell.params["runner"] == "train_lean"
    assert (cell.params["batch"], cell.params["seq"]) == (1, 16384)
    assert cell.config.values["family"] == "qwen3_next"
    for other in real.cells:
        if other != CELL:
            got = {m["name"] for m in real.metrics_for(other, "per_layer")}
            assert not got & set(METRICS), other
