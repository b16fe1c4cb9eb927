"""Optimizer + LR scheduler + AMP tests."""
import numpy as np
import pytest

import paddle_tpu as paddle

RNG = np.random.RandomState(11)


def _quad_problem(opt_factory, steps=60):
    w = paddle.nn.Parameter(np.array([5.0, -3.0], np.float32))
    opt = opt_factory([w])
    for _ in range(steps):
        loss = (w * w).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
    return np.abs(w.numpy()).max()


@pytest.mark.parametrize("factory", [
    lambda ps: paddle.optimizer.SGD(0.1, parameters=ps),
    lambda ps: paddle.optimizer.Momentum(0.05, 0.9, parameters=ps),
    lambda ps: paddle.optimizer.Adam(0.3, parameters=ps),
    lambda ps: paddle.optimizer.AdamW(0.3, parameters=ps),
    lambda ps: paddle.optimizer.RMSProp(0.1, parameters=ps),
    lambda ps: paddle.optimizer.Adagrad(0.5, parameters=ps),
    lambda ps: paddle.optimizer.Lamb(0.1, parameters=ps),
])
def test_optimizers_converge(factory):
    assert _quad_problem(factory) < 0.5


def test_adam_matches_reference_formula():
    w = paddle.nn.Parameter(np.array([1.0], np.float32))
    opt = paddle.optimizer.Adam(learning_rate=0.1, beta1=0.9, beta2=0.999,
                                epsilon=1e-8, parameters=[w])
    w.grad = paddle.to_tensor([0.5])
    opt.step()
    # manual: m=0.05, v=2.5e-4*... bias-corrected step
    m = 0.1 * 0.5
    v = 0.001 * 0.25
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.999)
    ref = 1.0 - 0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(w.numpy(), [ref], rtol=1e-5)


def test_adamw_decoupled_decay():
    w = paddle.nn.Parameter(np.array([1.0], np.float32))
    opt = paddle.optimizer.AdamW(learning_rate=0.1, weight_decay=0.5,
                                 parameters=[w])
    w.grad = paddle.to_tensor([0.0])
    opt.step()
    # grad=0: only decay applies: w *= (1 - lr*wd)
    np.testing.assert_allclose(w.numpy(), [1.0 * (1 - 0.05)], rtol=1e-5)


def test_adam_name_positional_moment_dtype_kw_only():
    """Regression (ISSUE 2 satellite): moment_dtype was inserted
    positionally before ``name``, shifting the reference positional
    signature — a caller passing name positionally silently got a string
    as the moment STORAGE dtype. Now moment_dtype is keyword-only."""
    import jax.numpy as jnp

    w = paddle.nn.Parameter(np.array([1.0], np.float32))
    # reference positional order: ..., use_multi_tensor, amsgrad, name
    opt = paddle.optimizer.Adam(0.1, 0.9, 0.999, 1e-8, [w], None, None,
                                False, False, False, False, "my_adam")
    assert opt._moment_dtype == jnp.float32   # name did NOT land here
    w.grad = paddle.to_tensor([0.5])
    opt.step()                                # states build in f32

    w2 = paddle.nn.Parameter(np.array([1.0], np.float32))
    opt2 = paddle.optimizer.AdamW(0.1, 0.9, 0.999, 1e-8, [w2], 0.01,
                                  None, None, None, False, False, False,
                                  "my_adamw")
    assert opt2._moment_dtype == jnp.float32
    with pytest.raises(TypeError):            # 13th positional: rejected
        paddle.optimizer.Adam(0.1, 0.9, 0.999, 1e-8, [w], None, None,
                              False, False, False, False, "nm",
                              jnp.bfloat16)
    # the documented spelling still works
    opt3 = paddle.optimizer.Adam(0.1, parameters=[w],
                                 moment_dtype=jnp.bfloat16)
    assert opt3._moment_dtype == jnp.bfloat16


def test_apply_decay_param_fun():
    w = paddle.nn.Parameter(np.array([1.0], np.float32), name="layer.bias")
    opt = paddle.optimizer.AdamW(
        learning_rate=0.1, weight_decay=0.5, parameters=[w],
        apply_decay_param_fun=lambda n: "bias" not in n)
    w.grad = paddle.to_tensor([0.0])
    opt.step()
    np.testing.assert_allclose(w.numpy(), [1.0], rtol=1e-6)


def test_lamb_exclude_from_weight_decay():
    # excluded param with zero grad must stay exactly put (no decay)
    w = paddle.nn.Parameter(np.array([1.0], np.float32), name="norm.bias")
    v = paddle.nn.Parameter(np.array([1.0], np.float32), name="linear.weight")
    opt = paddle.optimizer.Lamb(
        0.1, lamb_weight_decay=0.5, parameters=[w, v],
        exclude_from_weight_decay_fn=lambda p: "bias" in (p.name or ""))
    w.grad = paddle.to_tensor([0.0])
    v.grad = paddle.to_tensor([0.0])
    opt.step()
    np.testing.assert_allclose(w.numpy(), [1.0], rtol=1e-6)
    assert v.numpy()[0] < 1.0  # non-excluded param does decay


def test_state_dict_survives_fused_step():
    # fused step donates state buffers; a state_dict captured before the
    # next step must remain readable (snapshot, not alias)
    w = paddle.nn.Parameter(np.array([1.0, 2.0], np.float32), name="w")
    opt = paddle.optimizer.Adam(0.1, parameters=[w])
    w.grad = paddle.to_tensor([0.1, 0.1])
    opt.step()
    sd = opt.state_dict()
    w.grad = paddle.to_tensor([0.1, 0.1])
    opt.step()  # donation would delete aliased buffers here
    for k, val in sd.items():
        if hasattr(val, "numpy"):
            np.asarray(val.numpy())  # must not raise "Array has been deleted"


def test_grad_clip_in_optimizer():
    w = paddle.nn.Parameter(np.array([1.0], np.float32))
    opt = paddle.optimizer.SGD(1.0, parameters=[w],
                               grad_clip=paddle.nn.ClipGradByGlobalNorm(0.1))
    w.grad = paddle.to_tensor([100.0])
    opt.step()
    np.testing.assert_allclose(w.numpy(), [0.9], rtol=1e-4)


def test_lr_schedulers():
    lr = paddle.optimizer.lr.StepDecay(0.1, step_size=2, gamma=0.5)
    vals = []
    for _ in range(5):
        vals.append(lr())
        lr.step()
    np.testing.assert_allclose(vals, [0.1, 0.1, 0.05, 0.05, 0.025], rtol=1e-6)

    warm = paddle.optimizer.lr.LinearWarmup(0.1, warmup_steps=10,
                                            start_lr=0.0, end_lr=0.1)
    assert warm() < 0.02
    for _ in range(12):
        warm.step()
    np.testing.assert_allclose(warm(), 0.1, rtol=1e-6)

    cos = paddle.optimizer.lr.CosineAnnealingDecay(1.0, T_max=10)
    for _ in range(10):
        cos.step()
    assert cos() < 0.01


def test_scheduler_drives_optimizer():
    sched = paddle.optimizer.lr.StepDecay(0.5, step_size=1, gamma=0.1)
    w = paddle.nn.Parameter(np.array([1.0], np.float32))
    opt = paddle.optimizer.SGD(sched, parameters=[w])
    w.grad = paddle.to_tensor([1.0])
    opt.step()
    np.testing.assert_allclose(w.numpy(), [0.5], rtol=1e-5)
    sched.step()
    w.grad = paddle.to_tensor([1.0])
    opt.step()
    np.testing.assert_allclose(w.numpy(), [0.45], rtol=1e-5)


def test_optimizer_state_dict_roundtrip():
    w = paddle.nn.Parameter(np.array([1.0, 2.0], np.float32), name="w")
    opt = paddle.optimizer.Adam(0.1, parameters=[w])
    w.grad = paddle.to_tensor([0.1, 0.1])
    opt.step()
    sd = opt.state_dict()
    opt2 = paddle.optimizer.Adam(0.1, parameters=[w])
    opt2.set_state_dict(sd)
    assert opt2._step_count == 1
    np.testing.assert_allclose(
        opt2._state_for(w)["moment1"], opt._state_for(w)["moment1"])


def test_amp_autocast_bf16():
    import jax.numpy as jnp
    x = paddle.randn([4, 4])
    y = paddle.randn([4, 4])
    with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
        z = paddle.matmul(x, y)
        assert z.dtype == jnp.bfloat16
        s = paddle.exp(x)   # black list: stays f32
        assert s.dtype == jnp.float32
    z = paddle.matmul(x, y)
    assert z.dtype == jnp.float32


def test_amp_grad_scaler_bf16_passthrough():
    scaler = paddle.amp.GradScaler()
    w = paddle.nn.Parameter(np.array([1.0], np.float32))
    opt = paddle.optimizer.SGD(0.1, parameters=[w])
    loss = (w * 2).sum()
    scaled = scaler.scale(loss)
    scaled.backward()
    scaler.step(opt)
    np.testing.assert_allclose(w.numpy(), [0.8], rtol=1e-5)


def test_multi_precision_master_weights():
    import jax.numpy as jnp
    w = paddle.nn.Parameter(np.array([1.0], np.float32))
    w._data = w._data.astype(jnp.bfloat16)
    opt = paddle.optimizer.AdamW(0.001, parameters=[w], multi_precision=True)
    for _ in range(3):
        w.grad = paddle.to_tensor(np.array([0.3], np.float32), dtype="bfloat16")
        opt.step()
    assert w.dtype == jnp.bfloat16
    assert id(w) in opt._master_weights


def test_adamw_bf16_moment_storage():
    """moment_dtype=bfloat16 halves optimizer-state bytes; arithmetic
    stays f32 (states cast up before the update, down on store), so a
    short training trajectory tracks the f32-moment one closely."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import GPTForCausalLM, create_train_step, gpt2_tiny

    def run(moment_dtype):
        paddle.seed(11)
        model = GPTForCausalLM(gpt2_tiny())
        model.eval()
        opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters(),
                                     moment_dtype=moment_dtype)
        step, params, state = create_train_step(model, opt)
        rng = np.random.RandomState(3)
        ids = jnp.asarray(rng.randint(0, 256, (2, 9)), jnp.int32)
        losses = []
        for i in range(6):
            loss, params, state = step(params, state,
                                       jax.random.fold_in(jax.random.key(0), i),
                                       ids[:, :-1], ids[:, 1:], 5e-3)
            losses.append(float(loss))
        return losses, state

    l32, s32 = run(None)
    lb16, sb16 = run(jnp.bfloat16)
    name = next(iter(sb16))
    assert sb16[name]["moment1"].dtype == jnp.bfloat16
    assert sb16[name]["moment2"].dtype == jnp.bfloat16
    assert sb16[name]["beta1_pow"].dtype == jnp.float32
    assert s32[name]["moment1"].dtype == jnp.float32
    # same descent, small numeric drift only
    assert lb16[-1] < lb16[0]
    np.testing.assert_allclose(lb16, l32, rtol=0.05, atol=0.05)


# -- the functional update holds a matrix's gradient apart (PR 35) ----------

def _parent_rule(opt, params, grads, states, lr, wd_mask):
    """``Optimizer.apply_gradients`` as it stood until PR 35: the update
    reads the gradient as ``jax.grad`` hands it over, no barrier between.
    There XLA may keep a bf16 gradient at the float32 it was accumulated in
    (``xla_allow_excess_precision``) and feed the update that; behind a
    barrier the gradient is the value its type declares. So the rule by
    hand rounds a bf16 gradient where its type says it is rounded, as the
    benchmark's float32 reference does; a float32 gradient it leaves."""
    import jax
    import jax.numpy as jnp
    new_params, new_states = {}, {}
    wd, decoupled = opt._wd_coeff(), opt._decoupled_weight_decay()
    for k, p in params.items():
        g, p32 = grads[k].astype(jnp.float32), p.astype(jnp.float32)
        if grads[k].dtype == jnp.bfloat16:
            g = jax.lax.reduce_precision(g, exponent_bits=8, mantissa_bits=7)
        decay = wd if wd_mask.get(k, True) else 0.0
        if decay and not decoupled:
            g = g + decay * p32
        new, new_states[k] = opt._update(p32, g, states[k], lr,
                                         wd=decay if decoupled else 0.0)
        new_params[k] = new.astype(p.dtype)
    return new_params, new_states


_FACTORIES = {
    "adamw": lambda ps: paddle.optimizer.AdamW(1e-2, weight_decay=0.01,
                                               parameters=ps),
    "sgd": lambda ps: paddle.optimizer.SGD(1e-2, weight_decay=0.01,
                                           parameters=ps),
    "momentum": lambda ps: paddle.optimizer.Momentum(1e-2, 0.9,
                                                     parameters=ps),
}


def _tiny(family, dtype):
    from paddle_tpu import models
    paddle.seed(35)
    if family == "llama":
        model = models.LlamaForCausalLM(models.llama_tiny())
    else:
        model = models.GPTForCausalLM(models.gpt2_tiny())
    model = model.bfloat16() if dtype == "bfloat16" else model
    model.train()
    return model


def _assert_same_leaves(got, want):
    """To the last bits, not bit for bit: XLA's CPU backend may order a
    float32 sum differently on the two sides of a fusion boundary (an
    embedding's scattered gradient added into ``wd * p``), so a leaf may
    differ by a few roundings of its largest element (of 0.01 for a leaf
    that is rounding noise throughout: GPT-2's key biases, whose gradient
    is zero but for rounding)."""
    import jax
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, path
        room = 8 * np.spacing(np.maximum(np.abs(b).max(), 1e-2).astype(
            b.dtype)).astype(np.float64)
        gap = np.abs(a.astype(np.float64) - b.astype(np.float64)).max()
        assert gap <= room, (jax.tree_util.keystr(path), gap, room)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("family", ["llama", "gpt2"])
@pytest.mark.parametrize("name", list(_FACTORIES))
def test_train_step_equals_the_parents_update_rule(name, family, dtype):
    """A barrier is an identity: three steps of ``create_train_step`` leave
    what the parent's rule, applied by hand to the same gradients, leaves:
    losses, parameters and state."""
    import jax

    from paddle_tpu.models import create_train_step, trainer

    model = _tiny(family, dtype)
    opt = _FACTORIES[name](model.parameters())
    step, params, state = create_train_step(model, opt)
    loss_call, by_hand, state_h, wd_mask = trainer._functional_pieces(
        model, opt, None)

    @jax.jit
    def parent_step(params, state, key, ids, labels, lr):
        loss, grads = jax.value_and_grad(
            lambda p: loss_call(p, ids, labels, key))(params)
        return (loss,) + _parent_rule(opt, params, grads, state, lr, wd_mask)

    key, rng = jax.random.key(0), np.random.RandomState(35)
    for i in range(3):
        data = rng.randint(0, 256, (2, 17))
        x, y = data[:, :-1], data[:, 1:]
        loss, params, state = step(params, state, key, x, y, 1e-2)
        loss_h, by_hand, state_h = parent_step(by_hand, state_h, key, x, y,
                                               1e-2)
        np.testing.assert_allclose(
            float(loss), float(loss_h),
            rtol=1e-6 if dtype == "float32" else 1e-3, err_msg=str(i))
    _assert_same_leaves((params, state), (by_hand, state_h))


@pytest.fixture
def no_barrier(monkeypatch):
    """A context in which a step is traced as the parent traced it: the
    barrier gone."""
    import contextlib

    import jax

    @contextlib.contextmanager
    def gone():
        with monkeypatch.context() as m:
            m.setattr(jax.lax, "optimization_barrier", lambda x: x)
            yield
    return gone


def test_accumulating_step_traces_and_gives_the_parents_losses(no_barrier):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import create_multistep_train_step

    data = np.random.RandomState(36).randint(0, 256, (2, 2, 2, 17))  # [K, M, B, S + 1]
    xs, ys = jnp.asarray(data[..., :-1]), jnp.asarray(data[..., 1:])

    def run(held):
        model = _tiny("llama", "float32")
        opt = _FACTORIES["adamw"](model.parameters())
        step, p, s = create_multistep_train_step(model, opt, steps=2,
                                                 accumulate=2)
        assert ("optimization_barrier" in step.lower(
            p, s, jax.random.key(2), xs, ys, 1e-2).as_text()) == held
        losses, p, s = step(p, s, jax.random.key(2), xs, ys, 1e-2)
        return np.asarray(losses), (p, s)

    losses, leaves = run(held=True)
    with no_barrier():
        losses_p, leaves_p = run(held=False)
    np.testing.assert_allclose(losses, losses_p, rtol=1e-6)
    _assert_same_leaves(leaves, leaves_p)


def test_sharded_step_traces_and_gives_the_parents_losses(no_barrier):
    """The barrier sits between a gradient's reduction over ``dp`` and its
    update: GSPMD has to carry the leaf's sharding through it."""
    import jax
    from jax.sharding import Mesh

    from paddle_tpu.models import create_sharded_train_step, llama_param_spec

    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    data = np.random.RandomState(37).randint(0, 256, (4, 17))
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))

    def run():
        model = _tiny("llama", "float32")
        opt = _FACTORIES["adamw"](model.parameters())
        step, p, s, shard = create_sharded_train_step(model, opt, mesh,
                                                      llama_param_spec)
        x, y = shard(data[:, :-1]), shard(data[:, 1:])
        losses = []
        for _ in range(2):
            loss, p, s = step(p, s, jax.random.key(3), x, y, 1e-2)
            losses.append(float(loss))
        return losses, (p, s)

    losses, leaves = run()
    with no_barrier():
        losses_p, leaves_p = run()
    np.testing.assert_allclose(losses, losses_p, rtol=1e-6)
    _assert_same_leaves(leaves, leaves_p)
    w = leaves[0]["model.layers.0.mlp.gate_proj.weight"]
    assert w.addressable_shards[0].data.shape[1] == w.shape[1] // 4


def test_update_plan_counts_the_leaves_held_apart_once_a_lowered_step():
    import jax

    from paddle_tpu.models import create_train_step
    from paddle_tpu.optimizer import UPDATE_PLAN_TALLY
    from paddle_tpu.profiler import tracing

    model = _tiny("llama", "bfloat16")
    opt = _FACTORIES["adamw"](model.parameters())
    step, params, state = create_train_step(model, opt)
    matrices = [v for v in params.values() if v.ndim >= 2]
    plan = (len(params), len(matrices), sum(v.size for v in matrices),
            sum(v.size * 2 for v in matrices))
    assert len(matrices) < len(params)      # the norms' weights stay as they are
    data = np.random.RandomState(38).randint(0, 256, (2, 17))
    x, y = data[:, :-1], data[:, 1:]
    UPDATE_PLAN_TALLY.clear()
    tracing.reset_tracing()
    tracing.enable_tracing()
    try:
        compiled = step.lower(params, state, jax.random.key(0), x, y,
                              1e-2).compile()
        for _ in range(3):
            _, params, state = compiled(params, state, jax.random.key(0), x,
                                        y, 1e-2)
        events = [e for e in tracing.snapshot_events()
                  if e["name"] == "optimizer::plan"]
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    assert dict(UPDATE_PLAN_TALLY) == {plan: 1}
    assert [e["args"] for e in events] == [dict(zip(
        ("leaves", "held", "held_params", "held_bytes"), plan))]
    assert "optimization_barrier" in step.lower(
        params, state, jax.random.key(0), x, y, 1e-2).as_text()
