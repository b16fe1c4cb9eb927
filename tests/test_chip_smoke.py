"""chip_smoke.py off the chip: its legs at the TINY preset on the CPU mesh
(Pallas kernels in interpret mode), its refusal to run without a TPU, the
compile-cache placement rule, and — the part a CPU host CAN say about
Mosaic — that every kernel family and the sharded train step LOWER for a
TPU (``lowering_platforms=("tpu",)`` runs the jaxpr -> Mosaic MLIR
conversion, which is where an f64 constant or a GSPMD-partitioned Mosaic
call is refused)."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.core import compile_cache  # noqa: E402


@pytest.fixture
def interpret_kernels():
    """Off the chip the dispatch routes attention to XLA unless this flag
    is on; the legs should run the (interpreted) kernels, as the chip
    runs the compiled ones."""
    paddle.set_flags({"pallas_force_interpret": True})
    try:
        yield
    finally:
        paddle.set_flags({"pallas_force_interpret": False})


# -- the legs, tiny ---------------------------------------------------------
# Three of them take more than 5 s (first-use tracing and compiles), so by
# this suite's rule they carry the slow marker and run outside the tier-1
# budget: `python -m pytest tests/test_chip_smoke.py` (no -m filter).

@pytest.mark.slow
def test_leg_kernels_tiny():
    out = chip_smoke.leg_kernels(chip_smoke.TINY["kernels"])
    assert out["interpret"] and out["cases"] == 12


@pytest.mark.slow
def test_leg_train_tiny(interpret_kernels):
    out = chip_smoke.leg_train(chip_smoke.TINY["train"])
    assert out["compiles_after_first_step"] == 0
    assert out["losses"][-1] < out["losses"][0]


def test_leg_laguna_tiny(interpret_kernels):
    out = chip_smoke.leg_laguna(chip_smoke.TINY["laguna"])
    assert out["losses"][-1] < out["losses"][0]
    assert [r["layer"] for r in out["routing_stats"]] == [1, 2, 3, 4]
    # interpreted kernels leave no name in the lowered step; their plans
    # say that the windowed flash kernels and the expert layer were lowered
    from paddle_tpu.incubate.moe import MOE_PLAN_TALLY
    from paddle_tpu.ops.pallas.flash_attention import TILE_PLAN_TALLY
    assert set(out["kernels_in_step"]) >= {"flash_win_fwd", "moe_gmm_fwd"}
    assert {k[0] for k in TILE_PLAN_TALLY} >= {"flash_win_fwd",
                                               "flash_win_bwd"}
    # every layer's backward pass is the one kernel: its full layers and
    # its window layers
    assert out["flash_bwd_calls"] == {"flash_bwd": 2, "flash_win_bwd": 3}
    assert any(k[:3] == (4, 16, 4) for k in MOE_PLAN_TALLY)
    # five recomputed layers, each flash forward lowered once
    assert out["recompute_policy"] == "flash_saveable"
    assert out["flash_fwd_calls_in_step"] == 5


def test_leg_glm_tiny(interpret_kernels):
    out = chip_smoke.leg_glm(chip_smoke.TINY["glm"])
    assert out["losses"][-1] < out["losses"][0]
    # two sparse layers and the MTP module's
    assert [r["layer"] for r in out["routing_stats"]] == [1, 2, 3]
    assert out["mla_plan"]["route"] == "kernel"
    assert out["mla_plan"]["rule"] == "default"
    assert out["mla_plan"]["tiles"].startswith("fwd=32x32/")
    # three layers and the MTP module, each flash forward lowered once
    assert out["recompute_policy"] == "flash_saveable"
    assert out["flash_fwd_calls_in_step"] == 4


def test_leg_nemotron_h_tiny(interpret_kernels):
    out = chip_smoke.leg_nemotron_h(chip_smoke.TINY["nemotron_h"])
    assert out["losses"][-1] < out["losses"][0]
    # interpreted kernels leave no name in the lowered step; the plan says
    # that the Mamba-2 layers took the kernels' path, their forward kept
    assert set(out["kernels_in_step"]) == set(chip_smoke.NEMOTRON_H_KERNELS)
    assert (out["ssd_plan"]["path"], out["ssd_plan"]["chunk"],
            out["ssd_plan"]["chunks"], out["ssd_plan"]["forward"]) == (
        "kernel", 32, 2, "kept")
    assert (out["moe_plan"]["activation"], out["moe_plan"]["shared"],
            out["moe_plan"]["score_bias"]) == ("relu2", "Relu2MLP", True)
    assert out["recompute_policy"] == "flash_saveable"


def test_leg_qwen3_next_tiny(interpret_kernels):
    out = chip_smoke.leg_qwen3_next(chip_smoke.TINY["qwen3_next"])
    assert out["losses"][-1] < out["losses"][0]
    # interpreted kernels leave no name in the lowered step; the plan says
    # that the DeltaNet layers took the kernels' path
    assert set(out["kernels_in_step"]) == set(chip_smoke.QWEN3_NEXT_KERNELS)
    assert (out["gdn_plan"]["path"], out["gdn_plan"]["chunk"],
            out["gdn_plan"]["heads"]) == ("kernel", 64, 4)
    assert (out["moe_plan"]["score"], out["moe_plan"]["shared"]) == (
        "softmax", "GatedSharedExpert")
    assert out["recompute_policy"] == "flash_saveable"
    assert out["compiler_memory"]["argument_size_in_bytes"] > 0
    assert out["compile_requests"] == 1


def test_leg_sala_tiny(interpret_kernels):
    out = chip_smoke.leg_sala(chip_smoke.TINY["sala"])
    assert out["losses"][-1] < out["losses"][0]
    # interpreted kernels leave no name in the lowered step; the plans say
    # that both mixers took their kernels' path, past dense_len
    assert set(out["kernels_in_step"]) == set(chip_smoke.SALA_KERNELS)
    assert (out["sparse_plan"]["path"], out["sparse_plan"]["seq"],
            out["sparse_plan"]["topk"]) == ("kernel", 64, 4)
    assert (out["linear_plan"]["path"], out["linear_plan"]["chunk"]) == (
        "kernel", 64)
    assert out["recompute_policy"] == "sala_saveable"
    assert out["recompute_plan"]["kept_bytes"] > 0
    assert out["compiler_memory"]["argument_size_in_bytes"] > 0
    assert out["compile_requests"] == 1


def test_leg_serve_tiny():
    out = chip_smoke.leg_serve(chip_smoke.TINY["serve"])
    assert out["requests"] == 5 and not out["pools_donated"]


@pytest.mark.slow
@pytest.mark.skipif(jax.device_count() < 4, reason="needs 4 devices")
def test_leg_four_chip_tiny(interpret_kernels):
    out = chip_smoke.leg_four_chip(chip_smoke.TINY["four_chip"])
    assert set(out) >= {"oracle_losses", "tp_losses", "tp_fsdp_losses"}


# -- failure is loud --------------------------------------------------------

def test_script_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=REPO)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout       # no result line


def test_failed_leg_makes_exit_code_nonzero(monkeypatch, capsys):
    ran = []

    def boom(p):
        raise AssertionError("forced failure")

    def fine(p):
        ran.append(p)
        return {"n": p}

    class FakeTpu:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(chip_smoke, "LEGS", {"boom": boom, "fine": fine})
    monkeypatch.setattr(chip_smoke, "CHIP", {"boom": 0, "fine": 1})
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "stub")
    assert chip_smoke.main(legs=["boom", "fine"]) == 1
    out = capsys.readouterr().out
    assert ran == [1]                   # later legs still run
    assert "leg boom: FAILED" in out and "leg fine: ok" in out
    assert json.loads(out.strip().splitlines()[-1])["ok"] is False

    monkeypatch.setattr(chip_smoke, "LEGS", {"fine": fine})
    assert chip_smoke.main(legs=["fine"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


# -- compile cache placement ------------------------------------------------

@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    try:
        yield before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_left_alone_when_placed_from_outside(
        monkeypatch, cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == cache_dir_restored


def test_compile_cache_defaults_to_one_fixed_dir_in_checkout(
        monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert first == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# -- what a CPU host can say about Mosaic -----------------------------------

_CASES = list(chip_smoke.kernel_cases(chip_smoke.TINY["kernels"],
                                      interpret=False))


@pytest.mark.parametrize("case", _CASES, ids=[c[0] for c in _CASES])
def test_kernel_lowers_for_tpu(case):
    """Forward and backward of every kernel family convert to Mosaic MLIR
    under the package-wide jax_enable_x64 (a bare Python float handed to
    jnp.where used to enter the kernel as f64: "Unsupported cast")."""
    _, pallas_fn, _, args, n_diff = case
    text = chip_smoke.fwd_and_vjp(pallas_fn, n_diff).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.skipif(jax.device_count() < 4, reason="needs 4 devices")
def test_sharded_step_with_mosaic_flash_lowers_for_tpu(monkeypatch):
    """GSPMD refuses a Mosaic call with sharded operands; the flash
    dispatch must wrap it in a shard_map over the trainer's mesh."""
    from jax.sharding import Mesh

    from paddle_tpu import models
    from paddle_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "pallas_interpret", lambda: False)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    cfg = dataclasses.replace(models.llama_tiny(), num_layers=1,
                              hidden_size=256, num_heads=4, num_kv_heads=4,
                              max_position_embeddings=1024)
    paddle.seed(0)
    model = models.LlamaForCausalLM(cfg).bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step, params, opt_state, shard_batch = models.create_sharded_train_step(
        model, opt, mesh, models.llama_param_spec)
    ids = np.zeros((4, 1025), np.int32)     # seq 1024: the flash route
    with jax.set_mesh(mesh):
        text = step.jitted.trace(
            params, opt_state, jax.random.key(0), shard_batch(ids[:, :-1]),
            shard_batch(ids[:, 1:]), 1e-3).lower(
                lowering_platforms=("tpu",)).as_text()
    # the forward and the one backward kernel of one layer
    assert text.count("tpu_custom_call") == 2
