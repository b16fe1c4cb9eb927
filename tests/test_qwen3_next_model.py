"""models/qwen3_next.py against the benchmark's plain reference on seeded random
weights at the tests' tiny preset (one period: three DeltaNet layers and a
full one, 4 value heads over 2 key heads, softmax top-4 of 16 experts with 8
held and a gated shared expert): loss and every leaf's gradient, on the XLA
path and through the kernels in interpret mode; the held shares of a layer
add up to the uncut layer; ``DroplessMoE``'s defaults lower as before; the
configuration from the published keys; the decay offsets; the plans."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.autograd import tape_paused
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.moe import DroplessMoE
from paddle_tpu.models import (LlamaConfig, Qwen3NextConfig,
                               create_train_step, run_steps, write_back)
from paddle_tpu.models.llama import LlamaMLP
from paddle_tpu.models.qwen3_next import (FULL, LINEAR, GatedSharedExpert,
                                          deltanet_decay_init)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.families import qwen3_next as family  # noqa: E402
from benchmarks.reference import numerics, train as ref_train  # noqa: E402
from benchmarks.reference import qwen3_next as reference  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "tests", "preset_qwen3_next",
                       "configs", "qwen3-next-tiny.json")) as f:
    TINY = json.load(f)
LR, WD, VOCAB, SEQ = 1e-2, 0.01, 96, 160


@pytest.fixture
def interpret_kernels():
    paddle.set_flags({"pallas_force_interpret": True})
    try:
        yield
    finally:
        paddle.set_flags({"pallas_force_interpret": False})


def _program(values, seed, init_range=0.2):
    paddle.seed(0)
    model = family.build_model(values)
    model.train()
    shapes = reference.param_shapes(values)
    names = {k: family.program_name(k) for k in shapes}
    made = ref_train.make_params(shapes, seed, jnp.float32, init_range)
    write_back(model, {names[k]: v for k, v in made.items()}, strict=True)
    opt = paddle.optimizer.AdamW(learning_rate=LR, weight_decay=WD,
                                 parameters=model.parameters())
    step, params, state = create_train_step(model, opt)
    assert set(params) == set(names.values())
    return model, step, params, state, names, made


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_loss_and_every_gradient_match_the_reference(path, request):
    """160 tokens: two chunks of the kernels' plan (128), the second
    ragged."""
    if path == "kernels":
        request.getfixturevalue("interpret_kernels")
    values = dict(TINY, dtype="float32", sequence_length=SEQ)
    _, step, params, state, names, made = _program(values, seed=5)
    ids, labels = ref_train.make_batch(5, 0, 2, SEQ, VOCAB)
    want_loss, grads = jax.value_and_grad(lambda q: jnp.mean(
        reference.token_losses(q, jnp.asarray(ids), jnp.asarray(labels),
                               values, numerics.Exact())))(made)
    loss, _, st = step(params, state, jax.random.key(0), ids, labels, LR)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)
    for k, n in names.items():
        got = st[n]["moment1"] / 0.1
        scale = float(jnp.abs(grads[k]).max()) + 1e-12
        assert float(jnp.abs(got - grads[k]).max()) / scale < 1e-3, k


def test_every_kind_of_leaf_is_there_and_learns():
    values = dict(TINY, dtype="float32", sequence_length=SEQ)
    _, step, params, state, names, _ = _program(values, seed=7)
    kinds = [k.split(".", 2)[-1] for k in names if k.startswith("layers.")]
    for leaf in ("qkvz.weight", "ba.weight", "conv.weight", "A_log",
                 "dt_bias", "gnorm.weight", "out.weight", "q_norm.weight",
                 "shared_gate.weight", "router.weight"):
        assert leaf in kinds, leaf
    start = dict(params)
    batches = [ref_train.make_batch(7, i, 2, SEQ, VOCAB) for i in range(3)]
    p, st, losses = run_steps(step, params, state, batches,
                              key=jax.random.key(0), lr=LR)
    losses = [float(v) for v in losses]
    assert np.isfinite(losses).all() and len(losses) == 3
    for k in ("layers.0.A_log", "layers.1.dt_bias", "layers.3.q.weight",
              "layers.2.shared_gate.weight"):
        assert float(jnp.abs(p[names[k]] - start[names[k]]).max()) > 0, k


D, F, OF, K = 24, 12, 16, 4


def _moe_weights(seed):
    ks = jax.random.split(jax.random.key(seed), 8)
    n = jax.random.normal
    return {"router.weight": n(ks[0], (D, OF)) * 0.3,
            "experts.gate": n(ks[1], (OF, D, F)) * 0.2,
            "experts.up": n(ks[2], (OF, D, F)) * 0.2,
            "experts.down": n(ks[3], (OF, F, D)) * 0.2,
            "shared.gate.weight": n(ks[4], (D, F)) * 0.2,
            "shared.up.weight": n(ks[5], (D, F)) * 0.2,
            "shared.down.weight": n(ks[6], (F, D)) * 0.2,
            "shared_gate.weight": n(ks[7], (D, 1)) * 0.5}


def _moe_layer(lp, first, held, with_shared, **kw):
    shared = GatedSharedExpert(D, F) if with_shared else None
    layer = DroplessMoE(D, F, OF, K, held=(first, held), shared=shared,
                        row_tile=8, **kw)
    cut = slice(first, first + held)
    layer.router_weight._data = lp["router.weight"]
    layer.gate_proj._data = lp["experts.gate"][cut]
    layer.up_proj._data = lp["experts.up"][cut]
    layer.down_proj._data = lp["experts.down"][cut]
    if with_shared:
        shared.gate_proj.weight._data = lp["shared.gate.weight"]
        shared.up_proj.weight._data = lp["shared.up.weight"]
        shared.down_proj.weight._data = lp["shared.down.weight"]
        shared.expert_gate._data = lp["shared_gate.weight"]
    return layer


def _apply(layer, x):
    with tape_paused():
        return layer(Tensor(x))._data


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of four experts each, softmax routing, the shared expert
    and its gate counted once: their sum is what the uncut reference (held =
    of) gives for the whole layer, forward and the gradient of the input."""
    lp = _moe_weights(3)
    x = jax.random.normal(jax.random.key(4), (2, 16, D))
    do = jax.random.normal(jax.random.key(5), (2, 16, D))
    shares = [_moe_layer(lp, first, 4, first == 0, score="softmax")
              for first in range(0, OF, 4)]
    v = {"num_experts_per_tok": K, "num_experts": OF}

    def run(fn):
        out, vjp = jax.vjp(fn, x)
        return out, vjp(do)[0]
    got, dx = jax.jit(lambda: run(lambda x: sum(_apply(s, x)
                                               for s in shares)))()
    want, dx_ref = run(lambda x: reference._experts(x, lp, v,
                                                    numerics.Exact()))
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(dx, dx_ref, atol=1e-4)
    # every assignment landed on exactly one share
    loads = jax.jit(lambda x: [(_apply(s, x), s.expert_load._data)[1]
                               for s in shares])(x)
    assert sum(int(v.sum()) for v in loads) == 2 * 16 * K


def test_the_defaults_lower_the_layer_as_before():
    """``score`` left at its default gives the same program, text for text,
    as a layer built without it; softmax scores change it, and so does the
    gated shared expert in the place of a plain one."""
    lp = _moe_weights(8)
    x = jax.random.normal(jax.random.key(9), (2, 16, D))

    def text(gated=False, **kw):
        paddle.seed(0)
        shared = GatedSharedExpert(D, F) if gated else \
            LlamaMLP(LlamaConfig(hidden_size=D, intermediate_size=F))
        layer = DroplessMoE(D, F, OF, K, held=(4, 8), shared=shared,
                            row_tile=8, **kw)
        layer.router_weight._data = lp["router.weight"]
        return jax.jit(lambda x: _apply(layer, x)).lower(x).as_text()
    plain = text()
    assert text(score="sigmoid") == plain
    assert text(score="softmax") != plain
    assert text(gated=True) != plain
    with pytest.raises(ValueError):
        DroplessMoE(D, F, OF, K, score="relu")


def test_softmax_scores_choose_and_weigh():
    lp = _moe_weights(10)
    x = jax.random.normal(jax.random.key(11), (32, D))
    layer = _moe_layer(lp, 0, OF, False, score="softmax")
    top, w = layer.route(x, lp["router.weight"])
    p = np.asarray(jax.nn.softmax(x @ lp["router.weight"], axis=-1))
    want = np.sort(np.argsort(-p, axis=-1)[:, :K], -1)
    np.testing.assert_array_equal(np.sort(np.asarray(top), -1), want)
    chosen = np.take_along_axis(p, np.asarray(top), -1)
    np.testing.assert_allclose(w, chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)


def test_moe_plan_records_score_and_shared_gate():
    from paddle_tpu.profiler import tracing
    lp = _moe_weights(12)
    layer = _moe_layer(lp, 0, 8, True, score="softmax")
    x = jax.random.normal(jax.random.key(13), (2, 16, D))
    tracing.reset_tracing()
    tracing.enable_tracing(ring_size=tracing.DEFAULT_RING_SIZE)
    try:
        jax.jit(lambda x: _apply(layer, x))(x)
        events = [e["args"] for e in tracing.snapshot_events()
                  if e["name"] == "moe::plan"]
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    assert events[-1]["score"] == "softmax"
    assert events[-1]["shared"] == "GatedSharedExpert"


def test_from_published_keeps_the_published_keys_and_refuses_others():
    cfg = Qwen3NextConfig.from_published(
        dict(TINY, num_hidden_layers=8), experts_held=(0, 8))
    assert cfg.layer_types == (LINEAR, LINEAR, LINEAR, FULL) * 2
    assert (cfg.linear_num_value_heads, cfg.linear_num_key_heads) == (4, 2)
    assert cfg.linear_decay_init == (tuple(TINY["linear_decay_init"]["A"]),
                                     tuple(TINY["linear_decay_init"]["dt"]))
    full = Qwen3NextConfig()
    assert full.layer_types.count(FULL) == 12 and full.num_layers == 48
    assert (full.num_experts, full.num_experts_per_tok, full.head_dim) == \
        (512, 10, 256)
    for bad in ({"mlp_only_layers": [1]}, {"norm_topk_prob": False},
                {"decoder_sparse_step": 2}, {"tie_word_embeddings": True}):
        with pytest.raises(ValueError):
            Qwen3NextConfig.from_published(dict(TINY, **bad))


def test_the_decay_offsets_span_the_published_range_and_interleave():
    a0, dt0 = deltanet_decay_init(32)
    decay = np.exp(-a0 * dt0)
    assert decay.max() >= 0.999 and decay.min() <= 0.5
    assert 0 < a0.min() and a0.max() == 16.0
    assert dt0.min() == pytest.approx(1e-3) and dt0.max() == \
        pytest.approx(0.1)
    # neighbouring heads remember over very different spans
    assert np.abs(np.diff(np.log(-np.log(decay)))).min() > 1.0
    model_layer = family.build_model(dict(
        TINY, dtype="float32")).model.layers[0].linear_attn
    lo, hi = model_layer.rest_decay()
    a, d = (np.asarray(TINY["linear_decay_init"][k]) for k in ("A", "dt"))
    assert lo == pytest.approx(np.exp(-(a * d).max()))
    assert hi == pytest.approx(np.exp(-(a * d).min()))


def test_the_gdn_plan_of_each_deltanet_layer(interpret_kernels):
    from paddle_tpu.profiler import tracing
    values = dict(TINY, dtype="float32", sequence_length=SEQ)
    model = family.build_model(values)
    ids = jnp.zeros((1, SEQ), jnp.int32)
    tracing.reset_tracing()
    tracing.enable_tracing(ring_size=tracing.DEFAULT_RING_SIZE)
    try:
        with tape_paused():
            jax.jit(lambda i: model(Tensor(i))._data).lower(ids)
        events = [e["args"] for e in tracing.snapshot_events()
                  if e["name"] == "gdn::plan"]
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    assert len(events) == 3
    assert {(e["path"], e["chunk"], e["chunks"], e["heads"],
             e["key_heads"]) for e in events} == {("kernel", 128, 2, 4, 2)}
