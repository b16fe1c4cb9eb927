"""fleet.recompute's "flash_saveable": a recomputed block keeps each flash
call's output and softmax statistics, and each gated delta rule's output
and chunk states, so the backward pass replays the block without the
forward kernel. Tiny Laguna, GLM and Qwen3-Next steps through
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
from paddle_tpu.models.qwen3_next import FULL
from paddle_tpu.ops.pallas.flash_attention import TILE_PLAN_TALLY
from paddle_tpu.ops.pallas.gated_delta import gated_delta_plan

# ``fleet.recompute`` the attribute is the function; this is its module
rc = importlib.import_module("paddle_tpu.distributed.fleet.recompute")

FAMILIES = ("laguna", "glm")
POLICIES = ("full", "flash_saveable")
# (family, policy): Qwen3-Next's DeltaNet blocks besides the flash calls
CASES = [pytest.param(f, p, id=f"{f}-{p}") for p in POLICIES
         for f in FAMILIES + ("qwen3_next",)]
B, S = 2, 32


@pytest.fixture
def interpret_kernels():
    paddle.set_flags({"pallas_force_interpret": True})
    try:
        yield
    finally:
        paddle.set_flags({"pallas_force_interpret": False})


_TINY = {"laguna": (models.laguna_tiny, models.LagunaForCausalLM),
         "glm": (models.glm_moe_lite_tiny, models.GlmMoeLiteForCausalLM),
         "qwen3_next": (models.qwen3_next_tiny,
                        models.Qwen3NextForCausalLM)}


def _config(family, **changed):
    return _TINY[family][0](use_recompute=True, experts_held=(4, 8),
                            **changed)


def _step(family, **changed):
    paddle.seed(3)
    cfg = _config(family, **changed)
    model = _TINY[family][1](cfg)
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.01,
                                 parameters=model.parameters())
    return create_train_step(model, opt), cfg


def _batch(seed=0):
    ids = np.random.default_rng(seed).integers(0, 96, (B, S + 1))
    ids = ids.astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def _blocks(cfg):
    """Each recomputed block's mixer, in the order the step lowers them:
    ("flash", query heads, value head size) of a flash call, ("gdn", value
    heads, dk, dv) of a delta rule."""
    if isinstance(cfg, models.LagunaConfig):
        return [("flash", h, cfg.head_dim) for h in cfg.num_heads_per_layer]
    if isinstance(cfg, models.Qwen3NextConfig):
        return [("flash", cfg.num_attention_heads, cfg.head_dim)
                if kind == FULL else
                ("gdn", cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim) for kind in cfg.layer_types]
    n = cfg.num_hidden_layers + cfg.num_nextn_predict_layers
    return [("flash", cfg.num_attention_heads, cfg.v_head_dim)] * n


def _kept_bytes(heads, dim, itemsize=4):
    # out [B, S, Hq, D] in the activations' dtype + lse [B x Hq, S] float32
    return B * S * heads * dim * itemsize + 4 * B * heads * S


def _gdn_kept_bytes(heads, dk, dv, itemsize=4):
    # out [B, S, Hv, dv] in the activations' dtype + the chunk states
    # [B x Hv, chunks, dk, dv] float32
    chunks = -(-S // gated_delta_plan(S))
    return B * S * heads * dv * itemsize + 4 * B * heads * chunks * dk * dv


def _kept(block, policy):
    """The bytes a block keeps under ``policy``."""
    kind, *dims = block
    if policy == "full":
        return 0
    return (_gdn_kept_bytes if kind == "gdn" else _kept_bytes)(*dims)


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


@pytest.mark.parametrize("family,policy", CASES)
def test_recomputed_blocks_hold_the_forward_kernel_only_under_full(
        family, policy, interpret_kernels):
    (step, params, opt_state), cfg = _step(family, recompute_policy=policy)
    ids, labels = _batch()
    jaxpr = jax.make_jaxpr(step)(params, opt_state, jax.random.key(0), ids,
                                 labels, 1e-2)
    calls = _kernel_calls(jaxpr.jaxpr)
    kinds = [block[0] for block in _blocks(cfg)]
    layers, deltanets = kinds.count("flash"), kinds.count("gdn")

    def count(inside, *names):
        return sum(calls[(inside, n)] for n in names)

    fwd = ("flash_fwd", "flash_win_fwd")
    # the forward pass runs each layer's kernel once, whatever is kept
    assert count(False, *fwd) == layers
    assert count(True, *fwd) == (layers if policy == "full" else 0)
    # the backward kernel is the same under both: one a layer
    assert count(True, "flash_bwd", "flash_win_bwd") == layers
    # the delta rule's forward kernel leaves the replay too, its output and
    # chunk states kept; its backward kernel stays
    assert count(False, "gated_delta_fwd") == deltanets
    assert count(True, "gated_delta_fwd") == (
        deltanets if policy == "full" else 0)
    assert count(True, "gated_delta_bwd") == deltanets
    # everything else in the block is replayed as under "full"
    assert count(True, "moe_gmm_fwd") == count(False, "moe_gmm_fwd") > 0


@pytest.mark.parametrize("family", FAMILIES + ("qwen3_next",))
def test_the_new_policy_is_the_families_default(family):
    assert _config(family).recompute_policy == "flash_saveable"
    assert models.llama_tiny().recompute_policy == "full"
    assert models.gpt2_tiny().recompute_policy == "full"


def _three_steps(family, policy):
    """(loss, leaves, AdamW state) after each of three steps."""
    (step, params, opt_state), _ = _step(family, recompute_policy=policy)
    seen = []
    for i in range(3):
        ids, labels = _batch(i)
        loss, params, opt_state = step(
            params, opt_state, jax.random.fold_in(jax.random.key(0), i),
            ids, labels, 1e-2)
        seen.append((np.asarray(loss), jax.tree.map(np.asarray, params),
                     jax.tree.map(np.asarray, opt_state)))
    first, last = seen[0][1], seen[-1][1]
    assert all(not np.array_equal(first[k], last[k]) for k in first)
    return seen


@pytest.mark.parametrize("family", FAMILIES)
def test_three_steps_are_bit_equal_between_the_policies(family,
                                                        interpret_kernels):
    """The kept ``out`` and ``lse`` are the values the second call would
    have written: the loss, every leaf and both of Adam's moments of every
    leaf (the first, after one step, is a tenth of the gradient) are equal
    to the last bit after each of three steps."""
    runs = [_three_steps(family, policy) for policy in POLICIES]
    for full, kept in zip(*runs):
        assert np.isfinite(full[0]) and full[0].tobytes() == kept[0].tobytes()
        for a, b in zip(jax.tree.leaves(full[1:]), jax.tree.leaves(kept[1:])):
            assert a.tobytes() == b.tobytes()


def test_the_kept_delta_rule_values_are_the_replayed_ones(interpret_kernels):
    """A recomputed delta-rule call, the kernels interpreted: its output
    and the gradients of all five operands are equal to the last bit
    whether the block keeps the forward kernel's output and chunk states
    ("flash_saveable") or runs the kernel again ("full")."""
    from paddle_tpu.ops.pallas import gated_delta as gd
    rng = np.random.default_rng(0)
    b, s, hk, hv, dk, dv = 2, 48, 2, 4, 16, 8

    def draw(*shape):
        return jax.numpy.asarray(rng.standard_normal(shape), np.float32)
    q, k = draw(b, s, hk, dk) / 4, draw(b, s, hk, dk)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v, cot = draw(b, s, hv, dv), draw(b, s, hv, dv)
    g = -np.abs(draw(b, s, hv)) / 10
    beta = jax.nn.sigmoid(draw(b, s, hv))
    operands = (q, k, v, g, beta)

    def block(*xs):
        # the operands are made inside the block, as projections are
        return jax.numpy.tanh(gd.gated_delta(*(x * 1.0 for x in xs)))
    seen = {}
    for policy in ("full", "flash_saveable"):
        body = jax.checkpoint(block, policy=rc._resolve_policy(policy))

        def out_and_grads(*xs):
            out, pullback = jax.vjp(body, *xs)
            return (out, *pullback(cot))
        calls = _kernel_calls(jax.make_jaxpr(out_and_grads)(*operands).jaxpr)
        seen[policy] = ([np.asarray(x) for x in
                         jax.jit(out_and_grads)(*operands)], calls)
    (replayed, calls), (kept, kept_calls) = seen.values()
    assert calls[(True, "gated_delta_fwd")] == 1
    assert kept_calls[(True, "gated_delta_fwd")] == 0
    assert all(np.isfinite(x).all() for x in replayed)
    assert [x.tobytes() for x in replayed] == [x.tobytes() for x in kept]


def test_three_qwen3_next_steps_agree_between_the_policies(
        interpret_kernels):
    """The model around the kept values: three steps of the tiny Qwen3-Next
    under "full" and "flash_saveable". The first loss is equal to the last
    bit (the forward pass is one program). The gradients are not, and the
    fault is the replay's: on the CPU the interpreted forward kernel is
    fused otherwise when it is replayed (the ops writing its output
    [B x Hv, S, dv] fall into other loop fusions), and under "full" the
    output the backward pass reads differs from the one the loss saw in
    the last bits (2.9e-10 in the last DeltaNet layer of the first step;
    its chunk states are bit-equal; the mixer alone, above, is bit-equal
    both ways). Under "flash_saveable"
    the backward pass reads the forward's own output. So the later losses
    agree within 3e-7 and each leaf and moment within 5e-4 of its largest
    magnitude (read: 1.04e-7 and 2.5e-4, the latter where AdamW's first
    steps divide a gradient near zero by its own size)."""
    replayed, kept = (_three_steps("qwen3_next", policy)
                      for policy in POLICIES)
    assert replayed[0][0].tobytes() == kept[0][0].tobytes()
    for full, ours in zip(replayed, kept):
        assert np.isfinite(full[0])
        np.testing.assert_allclose(ours[0], full[0], rtol=3e-7, atol=0)
        for a, b in zip(jax.tree.leaves(full[1:]), jax.tree.leaves(ours[1:])):
            if np.issubdtype(a.dtype, np.floating):
                assert np.abs(a - b).max() <= 5e-4 * np.abs(a).max()
            else:
                assert np.array_equal(a, b)


@pytest.mark.parametrize("family,policy", CASES)
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
    want = [_kept(block, policy) for block in blocks]
    assert [e["kept_bytes"] for e in events] == want
    assert {e["policy"] for e in events} == {policy}
    assert {e["names"] for e in events} == {{
        "full": "",
        "flash_saveable": "flash_out,flash_lse,gdn_out,gdn_states,ssd_out,"
                          "ssd_states"}[policy]}
    # one flash call or delta rule a block, two values named in it, kept
    # or not
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
    # two layers' forward and backward kernel: the step took the kernels
    assert sum(TILE_PLAN_TALLY.values()) == before + 4
    monkeypatch.setattr(rc, "checkpoint_name", lambda x, name: x)
    assert _tiny_dense_step(family) == with_names
