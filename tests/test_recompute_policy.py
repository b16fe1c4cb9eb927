"""fleet.recompute's "flash_saveable": a recomputed block keeps each flash
call's output and softmax statistics, so the backward pass replays the
block without the forward kernel. Tiny Laguna and GLM steps through
``create_train_step``, the kernels interpreted; against "full", which
replays everything."""
import collections
import importlib
import re

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.models import create_train_step
from paddle_tpu.ops.pallas.flash_attention import TILE_PLAN_TALLY

# ``fleet.recompute`` the attribute is the function; this is its module
rc = importlib.import_module("paddle_tpu.distributed.fleet.recompute")

FAMILIES = ("laguna", "glm")
POLICIES = ("full", "flash_saveable")
B, S = 2, 32


@pytest.fixture
def interpret_kernels():
    paddle.set_flags({"pallas_force_interpret": True})
    try:
        yield
    finally:
        paddle.set_flags({"pallas_force_interpret": False})


def _config(family, **changed):
    if family == "laguna":
        return models.laguna_tiny(use_recompute=True, experts_held=(4, 8),
                                  **changed)
    return models.glm_moe_lite_tiny(use_recompute=True, experts_held=(4, 8),
                                    **changed)


def _step(family, **changed):
    paddle.seed(3)
    cfg = _config(family, **changed)
    model = (models.LagunaForCausalLM if family == "laguna"
             else models.GlmMoeLiteForCausalLM)(cfg)
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.01,
                                 parameters=model.parameters())
    return create_train_step(model, opt), cfg


def _batch(seed=0):
    ids = np.random.default_rng(seed).integers(0, 96, (B, S + 1))
    ids = ids.astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def _blocks(cfg):
    """(query heads, value head size) of each recomputed block's flash
    call, in the order the step lowers them."""
    if isinstance(cfg, models.LagunaConfig):
        return [(h, cfg.head_dim) for h in cfg.num_heads_per_layer]
    n = cfg.num_hidden_layers + cfg.num_nextn_predict_layers
    return [(cfg.num_attention_heads, cfg.v_head_dim)] * n


def _kept_bytes(heads, dim, itemsize=4):
    # out [B, S, Hq, D] in the activations' dtype + lse [B x Hq, S] float32
    return B * S * heads * dim * itemsize + 4 * B * heads * S


def _kernel_calls(jaxpr, inside=False, counts=None):
    """Counter of (inside a recomputed block, kernel name) over the
    ``pallas_call`` equations of a jaxpr and everything nested in it."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "pallas_call":
            counts[(inside, str(eqn.params["name"]))] += 1
        here = inside or prim in ("checkpoint", "remat2", "remat")
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernel_calls(sub, here, counts)
    return counts


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("family", FAMILIES)
def test_recomputed_blocks_hold_the_forward_kernel_only_under_full(
        family, policy, interpret_kernels):
    (step, params, opt_state), cfg = _step(family, recompute_policy=policy)
    ids, labels = _batch()
    jaxpr = jax.make_jaxpr(step)(params, opt_state, jax.random.key(0), ids,
                                 labels, 1e-2)
    calls = _kernel_calls(jaxpr.jaxpr)
    layers = len(_blocks(cfg))

    def count(inside, *names):
        return sum(calls[(inside, n)] for n in names)

    fwd = ("flash_fwd", "flash_win_fwd")
    # the forward pass runs each layer's kernel once, whatever is kept
    assert count(False, *fwd) == layers
    assert count(True, *fwd) == (layers if policy == "full" else 0)
    # the backward kernels are the same under both
    assert count(True, "flash_bwd_dq", "flash_win_bwd_dq") == layers
    assert count(True, "flash_bwd_dkv", "flash_win_bwd_dkv") == layers
    # everything else in the block is replayed as under "full"
    assert count(True, "moe_gmm_fwd") == count(False, "moe_gmm_fwd") > 0


@pytest.mark.parametrize("family", FAMILIES)
def test_the_new_policy_is_the_families_default(family):
    assert _config(family).recompute_policy == "flash_saveable"
    assert models.llama_tiny().recompute_policy == "full"
    assert models.gpt2_tiny().recompute_policy == "full"


@pytest.mark.parametrize("family", FAMILIES)
def test_three_steps_are_bit_equal_between_the_policies(family,
                                                        interpret_kernels):
    """The kept ``out`` and ``lse`` are the values the second call would
    have written: the loss, every leaf and both of Adam's moments of every
    leaf (the first, after one step, is a tenth of the gradient) are equal
    to the last bit after each of three steps."""
    runs = {}
    for policy in POLICIES:
        (step, params, opt_state), _ = _step(family, recompute_policy=policy)
        seen = []
        for i in range(3):
            ids, labels = _batch(i)
            loss, params, opt_state = step(
                params, opt_state, jax.random.fold_in(jax.random.key(0), i),
                ids, labels, 1e-2)
            seen.append((np.asarray(loss), jax.tree.map(np.asarray, params),
                         jax.tree.map(np.asarray, opt_state)))
        runs[policy] = seen
    for full, kept in zip(runs["full"], runs["flash_saveable"]):
        assert np.isfinite(full[0]) and full[0].tobytes() == kept[0].tobytes()
        for a, b in zip(jax.tree.leaves(full[1:]), jax.tree.leaves(kept[1:])):
            assert a.tobytes() == b.tobytes()
    first, last = runs["full"][0][1], runs["full"][-1][1]
    assert all(not np.array_equal(first[k], last[k]) for k in first)


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("family", FAMILIES)
def test_plan_is_recorded_once_a_lowered_block(family, policy,
                                               interpret_kernels):
    from paddle_tpu.profiler import tracing
    (step, params, opt_state), cfg = _step(family, recompute_policy=policy)
    ids, labels = _batch()
    before = dict(rc.RECOMPUTE_PLAN_TALLY)
    tracing.reset_tracing()
    tracing.enable_tracing(ring_size=tracing.DEFAULT_RING_SIZE)
    try:
        step.lower(params, opt_state, jax.random.key(0), ids, labels, 1e-2)
        events = [e["args"] for e in tracing.snapshot_events()
                  if e["name"] == "recompute::plan"]
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    blocks = _blocks(cfg)
    keeps = policy == "flash_saveable"
    want = [_kept_bytes(h, d) if keeps else 0 for h, d in blocks]
    assert [e["kept_bytes"] for e in events] == want
    assert {e["policy"] for e in events} == {policy}
    assert {e["names"] for e in events} == {
        "flash_out,flash_lse" if keeps else ""}
    # one flash call a block, two values named in it, kept or not
    assert [e["named_values"] for e in events] == [2] * len(blocks)
    added = {k: n - before.get(k, 0)
             for k, n in rc.RECOMPUTE_PLAN_TALLY.items()
             if n > before.get(k, 0)}
    assert added == {(policy, b): n
                     for b, n in collections.Counter(want).items()}


def test_an_unknown_policy_is_refused_with_the_names_there_are():
    with pytest.raises(ValueError, match="flash_saveable") as err:
        rc._resolve_policy("flash")
    assert "'full'" in str(err.value) and "'dots_saveable'" in str(err.value)
    # the table's other entries are what they were
    assert rc._resolve_policy("full") is None
    assert rc._resolve_policy("selective") is \
        jax.checkpoint_policies.dots_saveable


def _tiny_dense_step(family):
    paddle.seed(1)
    if family == "gpt2":
        model = models.GPTForCausalLM(models.gpt2_tiny())
    else:
        model = models.LlamaForCausalLM(models.llama_tiny())
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step, params, opt_state = create_train_step(model, opt)
    ids = np.zeros((2, 64), np.int32)
    text = step.lower(params, opt_state, jax.random.key(0), ids, ids,
                      1e-3).as_text()
    # MLIR numbers the private functions of a module as it makes them
    # (``@_where_94``); the number is no part of the program
    return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)


@pytest.mark.parametrize("family", ("gpt2", "llama"))
def test_outside_a_checkpoint_the_names_lower_to_nothing(
        family, interpret_kernels, monkeypatch):
    """Neither model recomputes by default, so its step never enters
    ``recompute`` and a named value is the value: the lowered text equals
    the text with ``checkpoint_name`` made the identity."""
    before = sum(TILE_PLAN_TALLY.values())
    with_names = _tiny_dense_step(family)
    # two layers' forward, dq and dkv: the step took the kernels
    assert sum(TILE_PLAN_TALLY.values()) == before + 6
    monkeypatch.setattr(rc, "checkpoint_name", lambda x, name: x)
    assert _tiny_dense_step(family) == with_names
