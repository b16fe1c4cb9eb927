"""models/minicpm_sala.py against the benchmark's plain reference on seeded
random weights at the tests' tiny preset (blocks of 8, top-4, a window of 2
blocks, dense_len 32): loss, gradients and three AdamW steps through
``create_train_step`` for each mixer alone and for one period of the model,
past ``dense_len`` and under it; the kernels' path against the XLA path; the
two plan events, what a replayed block keeps, and ``selection_stats``."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.models import create_train_step, run_steps, write_back
from paddle_tpu.models.minicpm_sala import (LIGHTNING, SPARSE,
                                            MiniCPMSALAConfig,
                                            lightning_decay_rates)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import sys  # noqa: E402
sys.path.insert(0, ROOT)
from benchmarks.families import minicpm_sala as family  # noqa: E402
from benchmarks.reference import minicpm_sala as reference  # noqa: E402
from benchmarks.reference import numerics, train as ref_train  # noqa: E402
from benchmarks.reference import train_lean  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "tests", "preset_sala", "configs",
                       "sala-tiny.json")) as f:
    TINY = json.load(f)
LR, WD, VOCAB = 1e-2, 0.01, 96


@pytest.fixture
def interpret_kernels():
    paddle.set_flags({"pallas_force_interpret": True})
    try:
        yield
    finally:
        paddle.set_flags({"pallas_force_interpret": False})


def _values(mixers, seq, dtype="float32"):
    return dict(TINY, mixer_types=list(mixers), num_hidden_layers=len(mixers),
                sequence_length=seq, dtype=dtype)


def _program(values, seed, init_range=0.2):
    paddle.seed(0)
    model = family.build_model(values)
    dtype = jnp.dtype(values["dtype"])
    if dtype == jnp.bfloat16:
        model = model.bfloat16()
    model.train()
    shapes = reference.param_shapes(values)
    names = {k: family.program_name(k) for k in shapes}
    made = ref_train.make_params(shapes, seed, dtype, init_range)
    write_back(model, {names[k]: v for k, v in made.items()}, strict=True)
    opt = paddle.optimizer.AdamW(learning_rate=LR, weight_decay=WD,
                                 parameters=model.parameters())
    step, params, state = create_train_step(model, opt)
    assert set(params) == set(names.values())
    return model, step, params, state, names, made


CASES = {
    "sparse_mixer_alone": ((SPARSE,), 64),
    "lightning_mixer_alone": ((LIGHTNING,), 64),
    "one_period_past_dense_len": ((SPARSE,) + (LIGHTNING,) * 3, 64),
    "one_period_under_dense_len": ((SPARSE,) + (LIGHTNING,) * 3, 32),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_loss_gradients_and_three_steps_follow_the_reference(case):
    mixers, seq = CASES[case]
    values = _values(mixers, seq)
    _, step, params, state, names, made = _program(values, seed=3)
    batches = [ref_train.make_batch(3, i, 2, seq, VOCAB) for i in range(3)]
    # the lean follower (one gradient of the whole batch), as the cell's
    # runner calls it
    want = train_lean.follow(
        reference.token_losses, values,
        lambda: ref_train.make_params(reference.param_shapes(values), 3,
                                      jnp.float32, 0.2),
        batches, lr=LR, weight_decay=WD, math=numerics.Exact(),
        store_dtype=jnp.float32)
    start = dict(params)
    p, st, losses = params, state, []
    for i, (ids, labels) in enumerate(batches):
        loss, p, st = step(p, st, jax.random.key(0), ids, labels, LR)
        losses.append(float(loss))
        if i == 0:
            grad = {k: float(jnp.linalg.norm(st[n]["moment1"])) / 0.1
                    for k, n in names.items()}
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-5)
    for k, n in names.items():
        assert grad[k] == pytest.approx(want["grad_norm"][k], rel=2e-3,
                                        abs=1e-7), k
        change = float(jnp.linalg.norm(p[n] - start[n]))
        assert change == pytest.approx(want["change_norm"][k], rel=2e-3), k


def test_gradients_match_the_reference_leaf_by_leaf():
    values = _values(CASES["one_period_past_dense_len"][0], 64)
    _, step, params, state, names, made = _program(values, seed=5)
    ids, labels = ref_train.make_batch(5, 0, 2, 64, VOCAB)
    grads = jax.grad(lambda q: jnp.mean(reference.token_losses(
        q, jnp.asarray(ids), jnp.asarray(labels), values,
        numerics.Exact())))(made)
    _, _, st = step(params, state, jax.random.key(0), ids, labels, LR)
    for k, n in names.items():
        got = st[n]["moment1"] / 0.1
        scale = float(jnp.abs(grads[k]).max()) + 1e-12
        assert float(jnp.abs(got - grads[k]).max()) / scale < 1e-3, k


def test_the_kernels_path_is_the_xla_path(interpret_kernels):
    """One period past dense_len with both mixers' kernels interpreted
    against the same model on the XLA paths: one loss, one set of
    gradients."""
    values = _values(CASES["one_period_past_dense_len"][0], 64)
    ids, labels = ref_train.make_batch(7, 0, 2, 64, VOCAB)
    out = {}
    for kernels in (True, False):
        paddle.set_flags({"pallas_force_interpret": kernels})
        _, step, params, state, names, _ = _program(values, seed=7)
        loss, _, st = step(params, state, jax.random.key(0), ids, labels, LR)
        out[kernels] = (float(loss), {n: st[n]["moment1"] for n in
                                      names.values()})
    assert out[True][0] == pytest.approx(out[False][0], rel=1e-6)
    for n, g in out[True][1].items():
        np.testing.assert_allclose(np.asarray(g), np.asarray(out[False][1][n]),
                                   atol=2e-6, err_msg=n)


def test_bfloat16_storage_stays_close_to_the_reference():
    """The cell's storage. A query whose last free block is close to the
    next may choose another than in the float32 reference, so the leaves are
    held to their norms and directions as the Laguna and GLM families are."""
    values = _values(CASES["one_period_past_dense_len"][0], 64, "bfloat16")
    _, step, params, state, names, made = _program(values, seed=11,
                                                   init_range=0.05)
    ids, labels = ref_train.make_batch(11, 0, 2, 64, VOCAB)
    want, grads = jax.value_and_grad(lambda q: jnp.mean(
        reference.token_losses(q, jnp.asarray(ids), jnp.asarray(labels),
                               values, numerics.Exact())))(made)
    loss, _, st = step(params, state, jax.random.key(0), ids, labels, LR)
    assert abs(float(loss) - float(want)) < 2e-3 * float(want)
    for k, n in names.items():
        got = (st[n]["moment1"] / 0.1).astype(jnp.float32).reshape(-1)
        ref = grads[k].astype(jnp.float32).reshape(-1)
        n_got, n_ref = jnp.linalg.norm(got), jnp.linalg.norm(ref)
        assert abs(float(n_got / n_ref) - 1.0) < 0.1, k
        assert float(got @ ref / (n_got * n_ref)) > 0.95, k


def test_plans_and_what_a_replayed_block_keeps(interpret_kernels):
    from paddle_tpu.distributed.fleet.recompute import RECOMPUTE_PLAN_TALLY
    from paddle_tpu.ops.pallas.linear_attention import LINEAR_PLAN_TALLY
    from paddle_tpu.ops.pallas.sparse_attention import SPARSE_PLAN_TALLY
    from paddle_tpu.profiler import tracing
    values = _values(CASES["one_period_past_dense_len"][0], 64)
    _, step, params, state, _, _ = _program(values, seed=13)
    ids = np.zeros((2, 64), np.int32)
    sparse_key, linear_key = (4, 2, 64, "kernel", 64, 64), \
        (3, 16, 64, 64, "kernel")
    before = SPARSE_PLAN_TALLY[sparse_key], LINEAR_PLAN_TALLY[linear_key]
    tracing.reset_tracing()
    tracing.enable_tracing(ring_size=tracing.DEFAULT_RING_SIZE)
    try:
        step.lower(params, state, jax.random.key(0), ids, ids, LR)
        events = tracing.snapshot_events()
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    assert SPARSE_PLAN_TALLY[sparse_key] == before[0] + 1
    assert LINEAR_PLAN_TALLY[linear_key] == before[1] + 3
    sparse = [e["args"] for e in events if e["name"] == "sparse_attn::plan"]
    assert sparse == [{
        "heads": 4, "kv_heads": 2, "seq": 64, "path": "kernel", "block": 8,
        "topk": 4, "forced_blocks": 3, "pooled_keys": 31,
        "mean_keys_per_query": 22.5, "tiles": "64x64",
        "replay_keeps": "sparse_choice,sparse_out,sparse_lse"}]
    linear = [e["args"] for e in events if e["name"] == "linear_attn::plan"]
    assert len(linear) == 3
    assert [(a["heads"], a["head_dim"], a["chunk"], a["chunks"])
            for a in linear] == [(3, 16, 64, 1)] * 3
    # layer 1's decays: exp(-2^(-8 (h + 1) / 3) (1 - 1 / 31 + 1e-5))
    rates = lightning_decay_rates(3, 1, 32)
    assert linear[0]["smallest_decay"] == pytest.approx(np.exp(-rates[0]))
    assert linear[0]["largest_decay"] == pytest.approx(np.exp(-rates[2]))
    assert linear[0]["largest_decay"] < linear[2]["largest_decay"] < 1
    plans = [e["args"] for e in events if e["name"] == "recompute::plan"]
    assert [p["policy"] for p in plans] == ["sala_saveable"] * 4
    # the sparse layer keeps the choice [2, 2, 64, 8] bool, out [2, 64, 4,
    # 16] float32 and lse [8, 64, 1] float32; a lightning layer nothing
    assert plans[0]["kept_bytes"] == 2 * 2 * 64 * 8 + 2 * 64 * 64 * 4 \
        + 8 * 64 * 4
    assert [p["kept_bytes"] for p in plans[1:]] == [0, 0, 0]
    assert ("sala_saveable", 0) in RECOMPUTE_PLAN_TALLY
    # under dense_len the same mixer is a flash call, and the plan says so
    _, dense, params, state, _, _ = _program(_values((SPARSE,), 32), seed=13)
    tracing.enable_tracing(ring_size=tracing.DEFAULT_RING_SIZE)
    try:
        dense.lower(params, state, jax.random.key(0), ids[:, :32],
                    ids[:, :32], LR)
        paths = [(e["args"]["path"], e["args"]["replay_keeps"])
                 for e in tracing.snapshot_events()
                 if e["name"] == "sparse_attn::plan"]
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    assert paths == [("dense", "flash_out,flash_lse")]


def test_selection_stats_reads_the_choice_off_the_steps_path():
    values = _values(CASES["one_period_past_dense_len"][0], 64, "bfloat16")
    model, _, _, _, _, _ = _program(values, seed=17, init_range=0.3)
    ids = ref_train.make_batch(17, 0, 2, 64, VOCAB)[0]
    stats = model.selection_stats(ids)
    assert [s["layer"] for s in stats] == [0]
    s = stats[0]
    # queries of blocks 0-3 see at most 4 blocks and take all (block 1 is
    # not forced for a query of block 3); of blocks 4-7 three are forced
    # and one is free: 40 of 64 queries hold one block that is not forced
    assert s["free_blocks_per_query"] == pytest.approx(40 / 64)
    assert 2 <= s["free_block_mean_distance"] <= 6
    # 2 rows x 2 kv heads x (8 x (1+2+3+4) + 32 x 4) choices
    assert s["choices"] == 2 * 2 * (8 * 10 + 32 * 4)
    assert 0.0 <= s["choices_differing_share"] < 0.2
    # under dense_len there is no choice to read
    assert model.selection_stats(ids[:, :32]) == []


def test_from_published_keeps_the_catalog_and_refuses_other_equations():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"] == "MiniCPM-SALA")["config"]
    cfg = MiniCPMSALAConfig.from_published(published)
    assert cfg == MiniCPMSALAConfig(residual_scale_layers=32)
    assert cfg.mixer_types.count(SPARSE) == 8 and cfg.num_layers == 32
    assert cfg.residual_scale == pytest.approx(1.4 / np.sqrt(32))
    for key, bad in (("attn_use_rope", True), ("lightning_use_rope", False),
                     ("qk_norm", False), ("use_output_gate", False),
                     ("tie_word_embeddings", True), ("lightning_nkv", 8)):
        with pytest.raises(ValueError, match=key):
            MiniCPMSALAConfig.from_published(dict(published, **{key: bad}))
    with pytest.raises(ValueError, match="unknown mixer"):
        models.minicpm_sala_tiny(mixer_types=("mamba",))


def test_three_steps_lower_the_loss_and_touch_every_leaf(interpret_kernels):
    paddle.seed(3)
    model = models.MiniCPMSALAForCausalLM(models.minicpm_sala_tiny(
        use_recompute=True))
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.01,
                                 parameters=model.parameters())
    step, params, opt_state = create_train_step(model, opt, donate=True)
    before = {k: np.asarray(v, np.float32) for k, v in params.items()}
    ids = np.random.default_rng(0).integers(0, 96, (2, 65)).astype(np.int32)
    params, opt_state, losses = run_steps(
        step, params, opt_state, [(ids[:, :-1], ids[:, 1:])] * 3,
        key=jax.random.key(0), lr=1e-2)
    losses = [float(v) for v in losses]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.1
    assert [k for k, v in params.items()
            if np.array_equal(np.asarray(v, np.float32), before[k])] == []
