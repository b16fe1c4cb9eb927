"""models/glm_moe_lite.py through ``create_train_step`` / ``run_steps``, in
the cells' storage (bfloat16 leaves, a float32 router) against the plain
reference, with its plans and its ``routing_stats``. The float32 comparison
and the tiny preset are in tests/test_glm_moe_lite_model.py."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import models  # noqa: E402
from paddle_tpu.models.glm_moe_lite import MLA_PLAN_TALLY  # noqa: E402
from test_glm_moe_lite_model import (interpret_kernels,  # noqa: E402,F401
                                     program_and_reference)


def test_bfloat16_storage_stays_close_to_the_reference(interpret_kernels):  # noqa: F811
    """The cells' storage: bfloat16 leaves, a float32 router. A token whose
    4th and 5th biased scores are close can choose another expert than in
    the float32 reference, so the leaves are held to their norms and
    directions, not element by element: 10% and 0.95 as for the Laguna
    family, at 128 tokens a batch where one token is a percent of an
    expert's gradient."""
    loss, grads, ref_loss, ref_grads = program_and_reference(jnp.bfloat16)
    assert abs(float(loss) - float(ref_loss)) < 1e-4 * float(ref_loss)
    for k, want in ref_grads.items():
        got = grads[k].astype(jnp.float32).reshape(-1)
        want = want.astype(jnp.float32).reshape(-1)
        n_got, n_want = jnp.linalg.norm(got), jnp.linalg.norm(want)
        assert abs(float(n_got / n_want) - 1.0) < 0.1, k
        assert float(got @ want / (n_got * n_want)) > 0.95, k


def test_three_steps_lower_the_loss_and_touch_every_leaf_but_the_bias(
        interpret_kernels):  # noqa: F811
    from paddle_tpu.models import create_train_step, run_steps
    from paddle_tpu.nn.layer.layers import functional_state
    paddle.seed(3)
    model = models.GlmMoeLiteForCausalLM(models.glm_moe_lite_tiny(
        use_recompute=True, experts_held=(4, 8)))
    model.train()
    rng = np.random.default_rng(0)
    biases = {n: rng.normal(0, 0.02, 16).astype(np.float32)
              for n, _ in model.named_buffers()}
    assert sorted(biases) == [
        "model.layers.1.mlp.e_score_correction_bias",
        "model.layers.2.mlp.e_score_correction_bias",
        "mtp.block.mlp.e_score_correction_bias"]
    for n, b in model.named_buffers():
        b._data = jnp.asarray(biases[n])
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.01,
                                 parameters=model.parameters())
    step, params, opt_state = create_train_step(model, opt, donate=True)
    # the bias is no trained leaf: not in the step's tree, not in the
    # optimizer's
    assert not any("e_score_correction_bias" in k for k in params)
    assert not any("e_score_correction_bias" in k for k in opt_state)
    before = {k: np.asarray(v, np.float32) for k, v in params.items()}
    ids = rng.integers(0, 96, (2, 33)).astype(np.int32)
    batch = (ids[:, :-1], ids[:, 1:])
    params, opt_state, losses = run_steps(
        step, params, opt_state, [batch] * 3, key=jax.random.key(0), lr=1e-2)
    losses = [float(v) for v in losses]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.1
    untouched = [k for k, v in params.items()
                 if np.array_equal(np.asarray(v, np.float32), before[k])]
    assert untouched == []
    for n, b in functional_state(model).items():
        if n in biases:
            np.testing.assert_array_equal(np.asarray(b), biases[n])


def test_plans_are_recorded_once_a_lowered_layer_and_step(interpret_kernels):  # noqa: F811
    """``mla::plan`` once for each attention layer of a lowered step (three
    main layers and the MTP module's), ``mtp::plan`` once a step, and
    ``moe::plan`` says that the layers select with a bias."""
    from paddle_tpu.models import create_train_step
    from paddle_tpu.profiler import tracing
    paddle.seed(5)
    model = models.GlmMoeLiteForCausalLM(models.glm_moe_lite_tiny(
        use_recompute=True))
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())
    step, params, opt_state = create_train_step(model, opt)
    ids = np.zeros((2, 32), np.int32)
    key = (4, 12, 4, 16, 24, 16, 64, "kernel")
    before = MLA_PLAN_TALLY[key]
    tracing.reset_tracing()
    # another test of this process may have left a ring of 8 events behind
    tracing.enable_tracing(ring_size=tracing.DEFAULT_RING_SIZE)
    try:
        step.lower(params, opt_state, jax.random.key(0), ids, ids, 1e-2)
        events = tracing.snapshot_events()
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    assert MLA_PLAN_TALLY[key] == before + 4
    mla = [e["args"] for e in events if e["name"] == "mla::plan"]
    assert len(mla) == 4
    a = mla[0]
    assert (a["heads"], a["qk_nope_head_dim"], a["qk_rope_head_dim"],
            a["v_head_dim"], a["q_lora_rank"], a["kv_lora_rank"],
            a["tokens"]) == (4, 12, 4, 16, 24, 16, 64)
    assert (a["route"], a["rule"]) == ("kernel", "default")
    assert a["tiles"] == "fwd=32x32/32x32 bwd=32x32/32x32"
    mtp = [e["args"] for e in events if e["name"] == "mtp::plan"]
    assert len(mtp) == 1
    assert mtp[0] == {"depth": 1, "weight": 0.3, "positions": 64,
                      "positions_with_target": 62, "own_table": True,
                      "own_head": True}
    moe = [e["args"] for e in events if e["name"] == "moe::plan"]
    assert len(moe) == 3 and all(m["score_bias"] is True for m in moe)
    # off the chip and without the flag the same call goes to XLA, and the
    # plan says by which rule
    paddle.set_flags({"pallas_force_interpret": False})
    again, params, opt_state = create_train_step(model, opt)
    tracing.enable_tracing(ring_size=tracing.DEFAULT_RING_SIZE)
    try:
        again.lower(params, opt_state, jax.random.key(0), ids, ids, 1e-2)
        routes = {(e["args"]["route"], e["args"]["rule"])
                  for e in tracing.snapshot_events()
                  if e["name"] == "mla::plan"}
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    assert routes == {("xla", "interpret_not_forced")}


def test_routing_stats_counts_what_landed_here():
    paddle.seed(5)
    model = models.GlmMoeLiteForCausalLM(models.glm_moe_lite_tiny(
        experts_held=(4, 8)))
    ids = np.random.default_rng(1).integers(0, 96, (2, 32)).astype(np.int32)
    stats = model.routing_stats(ids)
    # the two sparse layers, then the MTP module's
    assert [s["layer"] for s in stats] == [1, 2, 3]
    for s in stats:
        # 64 tokens x top-4, half of the 16 experts held: about 128
        assert 64 <= s["assignments_here"] <= 192
        assert s["mean_load"] == pytest.approx(s["assignments_here"] / 8)
        assert s["max_load"] >= s["mean_load"]
    chosen = model.chosen_experts(ids)
    assert sorted(chosen) == [1, 2, 3] and chosen[3].shape == (2, 32, 4)
    # the uncut layer sees every assignment; without the module, two layers
    whole = models.GlmMoeLiteForCausalLM(models.glm_moe_lite_tiny(
        num_nextn_predict_layers=0))
    assert [s["assignments_here"] for s in whole.routing_stats(ids)] == \
        [2 * 32 * 4] * 2
