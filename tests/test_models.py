"""Model-family tests: GPT + Llama eager/jit training, hybrid-sharded step
(model: reference end-to-end parallel tests, semi_auto_llama.py — loss
parity between parallel and single-device runs is the oracle)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (GPTForCausalLM, LlamaForCausalLM,
                               create_train_step, create_sharded_train_step,
                               gpt2_tiny, llama_param_spec, llama_tiny,
                               write_back)

RNG = np.random.RandomState(0)


def test_llama_forward_shapes():
    paddle.seed(0)
    cfg = llama_tiny()
    model = LlamaForCausalLM(cfg)
    ids = paddle.to_tensor(RNG.randint(0, cfg.vocab_size, (2, 16)))
    logits = model(ids)
    assert logits.shape == [2, 16, cfg.vocab_size]
    loss = model.loss(ids, ids)
    assert np.isfinite(float(loss))


def test_llama_gqa_heads():
    cfg = llama_tiny()
    assert cfg.num_kv_heads < cfg.num_heads  # GQA is actually exercised
    model = LlamaForCausalLM(cfg)
    att = model.model.layers[0].self_attn
    assert att.k_proj.weight.shape[1] == cfg.num_kv_heads * att.head_dim


def test_llama_jit_training_memorizes():
    paddle.seed(1)
    cfg = llama_tiny()
    model = LlamaForCausalLM(cfg)
    model.eval()
    opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters())
    step, params, opt_state = create_train_step(model, opt)
    key = jax.random.key(0)
    data = RNG.randint(0, cfg.vocab_size, (4, 17))
    losses = []
    for i in range(25):
        loss, params, opt_state = step(params, opt_state,
                                       jax.random.fold_in(key, i),
                                       data[:, :-1], data[:, 1:], 5e-3)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 1.5
    write_back(model, params)


def test_llama_recompute_matches_plain():
    paddle.seed(2)
    cfg = llama_tiny()
    model = LlamaForCausalLM(cfg)
    ids = paddle.to_tensor(RNG.randint(0, cfg.vocab_size, (2, 8)))
    model.eval()
    l1 = float(model.loss(ids, ids))
    model.cfg.use_recompute = True
    model.model.cfg.use_recompute = True
    model.train()
    l2 = float(model.loss(ids, ids))
    np.testing.assert_allclose(l1, l2, rtol=1e-5)


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")
def test_llama_hybrid_sharded_step_matches_unsharded():
    """dp=2 x tp=4 sharded step vs unsharded step: identical loss (the
    reference's acc-align oracle for semi-auto llama)."""
    from jax.sharding import Mesh
    paddle.seed(3)
    cfg = llama_tiny()
    model = LlamaForCausalLM(cfg)
    model.eval()

    opt1 = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step_plain, params0, opt_state0 = create_train_step(model, opt1)

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))
    opt2 = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step_shard, params_s, opt_state_s, shard_batch = \
        create_sharded_train_step(model, opt2, mesh, llama_param_spec)

    key = jax.random.key(0)
    data = RNG.randint(0, cfg.vocab_size, (4, 9))
    x, y = data[:, :-1], data[:, 1:]

    l1, params0, _ = step_plain(params0, opt_state0, key, x, y, 1e-3)
    l2, params_s, _ = step_shard(params_s, opt_state_s, key,
                                 shard_batch(x), shard_batch(y), 1e-3)
    np.testing.assert_allclose(float(l1), float(l2), rtol=2e-4)
    # params after one step also match
    k = "model.layers.0.self_attn.q_proj.weight"
    np.testing.assert_allclose(np.asarray(params0[k]),
                               np.asarray(params_s[k]), rtol=2e-3, atol=2e-5)
    # weights really are distributed
    sh = params_s[k].addressable_shards[0]
    assert sh.data.shape[1] == params_s[k].shape[1] // 4


def test_gpt_eager_vs_jit_loss_match():
    paddle.seed(4)
    cfg = gpt2_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    ids = RNG.randint(0, cfg.vocab_size, (2, 12))
    eager = float(model.loss(paddle.to_tensor(ids[:, :-1]),
                             paddle.to_tensor(ids[:, 1:])))
    opt = paddle.optimizer.SGD(0.0, parameters=model.parameters())
    step, params, opt_state = create_train_step(model, opt)
    jit_loss, _, _ = step(params, opt_state, jax.random.key(0),
                          ids[:, :-1], ids[:, 1:], 0.0)
    np.testing.assert_allclose(eager, float(jit_loss), rtol=1e-4)


def test_donated_train_step_preserves_model_weights():
    """donate=True aliases params into the update in place (HBM saver on
    TPU). The returned trees must be copies: the model's own live weight
    buffers must survive the donated step (code-review r3 finding)."""
    paddle.seed(5)
    cfg = gpt2_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step, params, opt_state = create_train_step(model, opt, donate=True)
    ids = RNG.randint(0, cfg.vocab_size, (2, 12))
    x, y = ids[:, :-1], ids[:, 1:]
    loss1, params, opt_state = step(params, opt_state, jax.random.key(0),
                                    x, y, 1e-3)
    # chained steps work (returned trees are the live ones)
    loss2, params, opt_state = step(params, opt_state, jax.random.key(1),
                                    x, y, 1e-3)
    assert np.isfinite(float(loss1)) and np.isfinite(float(loss2))
    # the model's own buffers were NOT donated away: eager forward still runs
    eager = float(model.loss(paddle.to_tensor(x), paddle.to_tensor(y)))
    assert np.isfinite(eager)


def test_consume_donation_skips_copies_and_trains():
    """donate='consume': the returned params ALIAS the model's live
    buffers (no protective copies — the setup-peak saver that fits 0.7B+
    on one v5e). Training through the returned trees works; the stateful
    model is documented-invalid afterwards."""
    paddle.seed(6)
    cfg = gpt2_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step, params, opt_state = create_train_step(model, opt,
                                                donate="consume")
    # no copy was made: the returned arrays ARE the model's buffers
    live = dict(model.named_parameters())
    assert all(params[n] is live[n]._data for n in params)
    ids = RNG.randint(0, cfg.vocab_size, (2, 12))
    x, y = ids[:, :-1], ids[:, 1:]
    losses = []
    for i in range(3):
        loss, params, opt_state = step(params, opt_state,
                                       jax.random.key(i), x, y, 1e-3)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_recompute_engages_jax_checkpoint_under_jit():
    """use_recompute must be REAL on the functional path (code-review r3):
    the traced train step's jaxpr must contain a remat, and the loss/grads
    must match the plain path exactly."""
    from paddle_tpu.models import create_train_step

    paddle.seed(4)
    cfg = llama_tiny()
    cfg.use_recompute = True
    model = LlamaForCausalLM(cfg)
    model.train()
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step, params, opt_state = create_train_step(model, opt)
    ids = RNG.randint(0, cfg.vocab_size, (2, 9)).astype(np.int64)
    x, y = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])
    key = jax.random.key(0)

    jaxpr = str(jax.make_jaxpr(
        lambda p, s: step(p, s, key, x, y, 1e-3))(params, opt_state))
    assert "remat" in jaxpr or "checkpoint" in jaxpr, \
        "use_recompute=True produced no remat in the traced step"

    loss_rc, params_rc, _ = step(params, opt_state, key, x, y, 1e-3)

    paddle.seed(4)
    cfg2 = llama_tiny()
    model2 = LlamaForCausalLM(cfg2)
    model2.train()
    opt2 = paddle.optimizer.AdamW(1e-3, parameters=model2.parameters())
    step2, params2, opt_state2 = create_train_step(model2, opt2)
    jaxpr2 = str(jax.make_jaxpr(
        lambda p, s: step2(p, s, key, x, y, 1e-3))(params2, opt_state2))
    assert "remat" not in jaxpr2 and "checkpoint" not in jaxpr2

    loss_plain, params_plain, _ = step2(params2, opt_state2, key, x, y, 1e-3)
    np.testing.assert_allclose(float(loss_rc), float(loss_plain), rtol=1e-6)
    for k in params_rc:
        np.testing.assert_allclose(np.asarray(params_rc[k]),
                                   np.asarray(params_plain[k]),
                                   rtol=1e-5, atol=1e-6)


def test_multistep_scan_matches_single_step_loop():
    """create_multistep_train_step(K) == K create_train_step calls on the
    same fold sequence — the scan-of-K execute must be the same math as
    the single-step loop, not a different trainer."""
    from paddle_tpu.models import create_multistep_train_step

    K = 4
    data = RNG.randint(0, 256, (2, 9))
    key = jax.random.key(7)

    paddle.seed(3)
    cfg = gpt2_tiny()
    m1 = GPTForCausalLM(cfg)
    m1.eval()
    opt1 = paddle.optimizer.AdamW(1e-2, parameters=m1.parameters())
    step, p, s = create_train_step(m1, opt1)
    losses = []
    for i in range(K):
        loss, p, s = step(p, s, jax.random.fold_in(key, i),
                          data[:, :-1], data[:, 1:], 5e-3)
        losses.append(float(loss))

    paddle.seed(3)
    m2 = GPTForCausalLM(cfg)
    m2.eval()
    opt2 = paddle.optimizer.AdamW(1e-2, parameters=m2.parameters())
    step_k, pk, sk = create_multistep_train_step(m2, opt2, steps=K)
    xs = jnp.tile(jnp.asarray(data[:, :-1])[None], (K, 1, 1))
    ys = jnp.tile(jnp.asarray(data[:, 1:])[None], (K, 1, 1))
    losses_k, pk, sk = step_k(pk, sk, key, xs, ys, 5e-3)

    np.testing.assert_allclose(np.asarray(losses_k), np.asarray(losses),
                               rtol=1e-5, atol=1e-6)
    for name in p:
        np.testing.assert_allclose(np.asarray(pk[name]),
                                   np.asarray(p[name]),
                                   rtol=1e-4, atol=1e-5)


def test_multistep_rejects_mismatched_steps_stack():
    """ISSUE 2 satellite: steps=K with inputs stacked [K', B, S] must fail
    at trace time instead of silently scanning K' optimizer steps."""
    from paddle_tpu.models import create_multistep_train_step

    paddle.seed(5)
    m = GPTForCausalLM(gpt2_tiny())
    m.eval()
    opt = paddle.optimizer.AdamW(1e-2, parameters=m.parameters())
    step_k, p, s = create_multistep_train_step(m, opt, steps=4)
    data = RNG.randint(0, 256, (3, 2, 9))   # 3 != steps=4
    xs = jnp.asarray(data[:, :, :-1])
    ys = jnp.asarray(data[:, :, 1:])
    with pytest.raises(ValueError, match="steps=4"):
        step_k(p, s, jax.random.key(0), xs, ys, 5e-3)


def test_multistep_scan_donate_consume():
    from paddle_tpu.models import create_multistep_train_step

    paddle.seed(4)
    cfg = gpt2_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters())
    step_k, p, s = create_multistep_train_step(model, opt,
                                               donate="consume", steps=3)
    data = RNG.randint(0, 256, (2, 9))
    xs = jnp.tile(jnp.asarray(data[:, :-1])[None], (3, 1, 1))
    ys = jnp.tile(jnp.asarray(data[:, 1:])[None], (3, 1, 1))
    losses, p, s = step_k(p, s, jax.random.key(0), xs, ys, 5e-3)
    losses2, p, s = step_k(p, s, jax.random.key(1), xs, ys, 5e-3)
    assert np.all(np.isfinite(np.asarray(losses2)))
    assert float(losses2[-1]) < float(losses[0])


def test_multistep_scan_with_loss_fn_momentum_batchnorm():
    """The config-bench ResNet path: loss_fn + Momentum + BatchNorm model
    through create_multistep_train_step must match the single-step loop."""
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models import create_multistep_train_step

    def build():
        paddle.seed(9)
        m = nn.Sequential(
            nn.Conv2D(3, 4, 3, padding=1), nn.BatchNorm2D(4), nn.ReLU(),
            nn.Flatten(), nn.Linear(4 * 8 * 8, 5))
        m.train()
        opt = paddle.optimizer.Momentum(0.05, momentum=0.9,
                                        parameters=m.parameters())
        return m, opt

    def loss_fn(m, images, labels):
        return F.cross_entropy(m(images), labels)

    K = 3
    images = RNG.randn(2, 3, 8, 8).astype(np.float32)
    labels = RNG.randint(0, 5, (2,))
    key = jax.random.key(1)

    m1, opt1 = build()
    step, p, s = create_train_step(m1, opt1, loss_fn=loss_fn)
    losses = []
    for i in range(K):
        loss, p, s = step(p, s, jax.random.fold_in(key, i),
                          images, labels, 0.05)
        losses.append(float(loss))

    m2, opt2 = build()
    step_k, pk, sk = create_multistep_train_step(m2, opt2,
                                                 loss_fn=loss_fn, steps=K)
    imk = jnp.tile(jnp.asarray(images)[None], (K, 1, 1, 1, 1))
    lbk = jnp.tile(jnp.asarray(labels)[None], (K, 1))
    losses_k, pk, sk = step_k(pk, sk, key, imk, lbk, 0.05)
    np.testing.assert_allclose(np.asarray(losses_k), np.asarray(losses),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")
def test_sharded_multistep_scan_matches_plain_multistep():
    """create_sharded_train_step(steps=K) over dp=2 x tp=4 must produce
    the same per-step losses as the unsharded scan-of-K trainer (the
    zero3/TP path)."""
    from jax.sharding import Mesh

    from paddle_tpu.models import create_multistep_train_step

    K = 3
    data = RNG.randint(0, 256, (4, 9))
    key = jax.random.key(5)

    paddle.seed(6)
    cfg = llama_tiny()
    m1 = LlamaForCausalLM(cfg)
    m1.eval()
    opt1 = paddle.optimizer.AdamW(1e-3, parameters=m1.parameters())
    step_k, p, s = create_multistep_train_step(m1, opt1, steps=K)
    xs = jnp.tile(jnp.asarray(data[:, :-1])[None], (K, 1, 1))
    ys = jnp.tile(jnp.asarray(data[:, 1:])[None], (K, 1, 1))
    losses_plain, p, s = step_k(p, s, key, xs, ys, 1e-3)

    paddle.seed(6)
    m2 = LlamaForCausalLM(cfg)
    m2.eval()
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))
    opt2 = paddle.optimizer.AdamW(1e-3, parameters=m2.parameters())
    step_sh, ps, ss, shard_batch = create_sharded_train_step(
        m2, opt2, mesh, llama_param_spec, steps=K)
    xk = shard_batch(np.tile(data[:, :-1][None], (K, 1, 1)))
    yk = shard_batch(np.tile(data[:, 1:][None], (K, 1, 1)))
    # per-step batch (dim 1) is sharded over dp, scan axis replicated
    assert xk.sharding.spec[1] == "dp" and xk.sharding.spec[0] is None
    losses_sh, ps, ss = step_sh(ps, ss, key, xk, yk, 1e-3)
    np.testing.assert_allclose(np.asarray(losses_sh),
                               np.asarray(losses_plain),
                               rtol=2e-4, atol=2e-5)


def test_multistep_scan_matches_loop_with_dropout():
    """With dropout active the per-step RNG must still line up: scan's
    fold_in(key, traced_i) has to draw the same masks as the eager
    loop's fold_in(key, i)."""
    import dataclasses

    from paddle_tpu.models import GPTConfig, create_multistep_train_step

    cfg = dataclasses.replace(gpt2_tiny(), dropout=0.3)
    K = 3
    data = RNG.randint(0, 256, (2, 9))
    key = jax.random.key(21)

    def build():
        paddle.seed(17)
        m = GPTForCausalLM(cfg)
        m.train()   # dropout active
        opt = paddle.optimizer.AdamW(1e-2, parameters=m.parameters())
        return m, opt

    m1, opt1 = build()
    step, p, s = create_train_step(m1, opt1)
    losses = []
    for i in range(K):
        loss, p, s = step(p, s, jax.random.fold_in(key, i),
                          data[:, :-1], data[:, 1:], 5e-3)
        losses.append(float(loss))

    m2, opt2 = build()
    step_k, pk, sk = create_multistep_train_step(m2, opt2, steps=K)
    xs = jnp.tile(jnp.asarray(data[:, :-1])[None], (K, 1, 1))
    ys = jnp.tile(jnp.asarray(data[:, 1:])[None], (K, 1, 1))
    losses_k, pk, sk = step_k(pk, sk, key, xs, ys, 5e-3)
    np.testing.assert_allclose(np.asarray(losses_k), np.asarray(losses),
                               rtol=1e-5, atol=1e-6)


def test_multistep_accumulation_matches_concat_batch():
    """accumulate=M: mean-of-microbatch-grads must equal the grad of the
    concatenated batch (token-mean CE with equal microbatch shapes), so
    per-step losses and final params match the no-accumulation trainer
    fed the [M*B] batch."""
    from paddle_tpu.models import create_multistep_train_step

    K, M = 2, 2
    cfg = gpt2_tiny()
    data = RNG.randint(0, 256, (4, 9))   # two microbatches of 2
    key = jax.random.key(8)

    def build():
        paddle.seed(23)
        m = GPTForCausalLM(cfg)
        m.eval()
        # SGD: the update is linear in the gradient, so mean-of-microbatch
        # grads vs concat-batch grad stays within f32 rounding (Adam's
        # rsqrt amplifies reduction-order noise ~20x at early steps)
        opt = paddle.optimizer.SGD(0.05, parameters=m.parameters())
        return m, opt

    # concat path: one optimizer step per [4, 8] batch
    m1, opt1 = build()
    step_k, p, s = create_multistep_train_step(m1, opt1, steps=K)
    xs = jnp.tile(jnp.asarray(data[:, :-1])[None], (K, 1, 1))
    ys = jnp.tile(jnp.asarray(data[:, 1:])[None], (K, 1, 1))
    losses_cat, p, s = step_k(p, s, key, xs, ys, 5e-3)

    # accumulation path: same tokens split into M microbatches per step
    m2, opt2 = build()
    step_a, pa, sa = create_multistep_train_step(m2, opt2, steps=K,
                                                 accumulate=M)
    xm = jnp.asarray(data[:, :-1]).reshape(M, 2, 8)
    ym = jnp.asarray(data[:, 1:]).reshape(M, 2, 8)
    xsm = jnp.tile(xm[None], (K, 1, 1, 1))
    ysm = jnp.tile(ym[None], (K, 1, 1, 1))
    losses_acc, pa, sa = step_a(pa, sa, key, xsm, ysm, 5e-3)

    np.testing.assert_allclose(np.asarray(losses_acc),
                               np.asarray(losses_cat),
                               rtol=1e-5, atol=1e-6)
    for name in p:
        np.testing.assert_allclose(np.asarray(pa[name]),
                                   np.asarray(p[name]),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")
def test_sharded_multistep_with_accumulation():
    """steps=K + accumulate=M on the mesh: batch dim moves to dim 2 and
    shard_batch follows it; losses match the unsharded accumulate run."""
    from jax.sharding import Mesh

    from paddle_tpu.models import create_multistep_train_step

    K, M = 2, 2
    cfg = llama_tiny()
    data = RNG.randint(0, cfg.vocab_size, (4, 9))
    key = jax.random.key(9)
    xm = np.tile(data[:, :-1].reshape(M, 2, 8)[None], (K, 1, 1, 1))
    ym = np.tile(data[:, 1:].reshape(M, 2, 8)[None], (K, 1, 1, 1))

    paddle.seed(31)
    m1 = LlamaForCausalLM(cfg)
    m1.eval()
    opt1 = paddle.optimizer.SGD(0.05, parameters=m1.parameters())
    step_p, p, s = create_multistep_train_step(m1, opt1, steps=K,
                                               accumulate=M)
    losses_plain, p, s = step_p(p, s, key, jnp.asarray(xm),
                                jnp.asarray(ym), 0.05)

    paddle.seed(31)
    m2 = LlamaForCausalLM(cfg)
    m2.eval()
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))
    opt2 = paddle.optimizer.SGD(0.05, parameters=m2.parameters())
    step_sh, ps, ss, shard_batch = create_sharded_train_step(
        m2, opt2, mesh, llama_param_spec, steps=K, accumulate=M)
    xk, yk = shard_batch(xm), shard_batch(ym)
    assert xk.sharding.spec[2] == "dp"
    assert xk.sharding.spec[0] is None and xk.sharding.spec[1] is None
    losses_sh, ps, ss = step_sh(ps, ss, key, xk, yk, 0.05)
    np.testing.assert_allclose(np.asarray(losses_sh),
                               np.asarray(losses_plain),
                               rtol=2e-4, atol=2e-5)


def test_write_back_surfaces_unknown_param_names():
    """ISSUE 3 satellite: write_back used to silently drop params whose
    names aren't on the model — a sharded-rename bug class. Unknown names
    now warn (and raise with strict=True); known names still write."""
    paddle.seed(13)
    model = GPTForCausalLM(gpt2_tiny())
    live = dict(model.named_parameters())
    name = next(iter(live))
    params = {name: jnp.zeros_like(live[name]._data),
              "renamed.by.a.spec_fn": jnp.zeros((3,), jnp.float32)}
    with pytest.warns(RuntimeWarning, match="renamed.by.a.spec_fn"):
        write_back(model, params)
    # the known name was still written through
    assert float(jnp.abs(live[name]._data).sum()) == 0.0
    with pytest.raises(KeyError, match="renamed.by.a.spec_fn"):
        write_back(model, params, strict=True)
    # all-known write stays silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        write_back(model, {name: live[name]._data})


def test_multistep_accumulate_rejects_mis_stacked_input():
    from paddle_tpu.models import create_multistep_train_step

    paddle.seed(12)
    m = GPTForCausalLM(gpt2_tiny())
    m.eval()
    opt = paddle.optimizer.SGD(0.05, parameters=m.parameters())
    step_a, p, s = create_multistep_train_step(m, opt, steps=2,
                                               accumulate=4)
    bad = jnp.zeros((2, 2, 2, 8), jnp.int32)   # microbatch dim 2 != 4
    with pytest.raises(ValueError, match="accumulate=4"):
        step_a(p, s, jax.random.key(0), bad, bad, 0.05)
