"""models/laguna.py in the cells' storage (bfloat16 leaves, a float32 router)
against the plain reference, through ``create_train_step`` / ``run_steps``,
and its ``routing_stats``. The float32 comparison and the tiny preset are in
tests/test_laguna_model.py."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import models  # noqa: E402
from test_laguna_model import program_and_reference  # noqa: E402


def test_bfloat16_storage_stays_close_to_the_reference():
    """The cells' storage: bfloat16 leaves, a float32 router. A token whose
    4th and 5th scores are close can choose another expert than in the
    float32 reference, so the expert leaves are held to their norms and
    directions, not element by element."""
    paddle.set_flags({"pallas_force_interpret": True})
    try:
        loss, grads, ref_loss, ref_grads = program_and_reference(
            jnp.bfloat16)
    finally:
        paddle.set_flags({"pallas_force_interpret": False})
    assert abs(float(loss) - float(ref_loss)) < 1e-4 * float(ref_loss)
    for k, want in ref_grads.items():
        got = grads[k].astype(jnp.float32).reshape(-1)
        want = want.astype(jnp.float32).reshape(-1)
        n_got, n_want = jnp.linalg.norm(got), jnp.linalg.norm(want)
        assert abs(float(n_got / n_want) - 1.0) < 0.1, k
        assert float(got @ want / (n_got * n_want)) > 0.95, k


def test_trains_through_create_train_step_and_run_steps():
    from paddle_tpu.models import create_train_step, run_steps
    paddle.seed(3)
    model = models.LagunaForCausalLM(models.laguna_tiny(
        use_recompute=True, experts_held=(4, 8)))
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.01,
                                 parameters=model.parameters())
    step, params, opt_state = create_train_step(model, opt, donate=True)
    ids = np.random.default_rng(0).integers(0, 96, (2, 33)).astype(np.int32)
    batch = (ids[:, :-1], ids[:, 1:])
    params, opt_state, losses = run_steps(
        step, params, opt_state, [batch] * 6, key=jax.random.key(0), lr=1e-2)
    losses = [float(v) for v in losses]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.3


def test_routing_stats_counts_what_landed_here():
    paddle.seed(5)
    model = models.LagunaForCausalLM(models.laguna_tiny(experts_held=(4, 8)))
    ids = np.random.default_rng(1).integers(0, 96, (2, 32)).astype(np.int32)
    stats = model.routing_stats(ids)
    assert [s["layer"] for s in stats] == [1, 2, 3, 4]
    for s in stats:
        # 64 tokens x top-4, half of the 16 experts held: about 128
        assert 64 <= s["assignments_here"] <= 192
        assert s["mean_load"] == pytest.approx(s["assignments_here"] / 8)
        assert s["max_load"] >= s["mean_load"]
    # the uncut layer sees every assignment
    whole = models.LagunaForCausalLM(models.laguna_tiny())
    assert all(s["assignments_here"] == 2 * 32 * 4
               for s in whole.routing_stats(ids))
