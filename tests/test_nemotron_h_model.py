"""models/nemotron_h.py against the benchmark's plain reference on seeded random
weights at the tests' tiny preset (the pattern's first attention period
``MEMEM*EME``: 8 Mamba-2 heads of 8 over 2 groups of state 16, relu^2 top-4
of 16 experts with 8 held, a correction bias and a shared expert, 4 query
heads over 2 kv heads with no rotary) cut to one layer of each kind: loss
and every leaf's gradient through the kernels in interpret mode; the held
shares of an expert layer add up to the uncut layer; ``DroplessMoE``'s
default and the shared convolution lower as before, relu^2 experts make no
gate leaf; the configuration from the published keys; the decay offsets;
the plans."""
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
from paddle_tpu.models import (NemotronHConfig, create_train_step,
                               run_steps, write_back)
from paddle_tpu.models.nemotron_h import Relu2MLP, ssm_decay_init
from paddle_tpu.nn.functional.causal_conv import causal_depthwise_conv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.families import nemotron_h as family  # noqa: E402
from benchmarks.reference import nemotron_h as reference  # noqa: E402
from benchmarks.reference import numerics, train as ref_train  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "tests", "preset_nemotron",
                       "configs", "nemotron-tiny.json")) as f:
    TINY = json.load(f)
LR, WD, VOCAB, SEQ = 1e-2, 0.01, 96, 80
# one layer of each kind (the preset's nine compile for minutes on the CPU)
SHORT = dict(TINY, hybrid_override_pattern="ME*", num_hidden_layers=3,
             dtype="float32", sequence_length=SEQ)


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


def test_loss_and_every_gradient_match_the_reference(interpret_kernels):
    """Through the kernels (interpret mode; ``tests/test_ssd.py`` holds the
    XLA path to the same recurrence). 80 tokens: three chunks of the
    preset's 32, the last ragged; random weights at 0.2 so that every mixer
    moves the loss."""
    values = SHORT
    _, step, params, state, names, made = _program(values, seed=5)
    ids, labels = ref_train.make_batch(5, 0, 2, SEQ, VOCAB)
    want_loss, grads = jax.jit(jax.value_and_grad(lambda q: jnp.mean(
        reference.token_losses(q, jnp.asarray(ids), jnp.asarray(labels),
                               values, numerics.Exact()))))(made)
    loss, _, st = step(params, state, jax.random.key(0), ids, labels, LR)
    # float32 on both sides; the chunked scan and the flash kernels reorder
    # sums: a few parts in a million of the loss
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)
    for k, n in names.items():
        got = st[n]["moment1"] / 0.1
        scale = float(jnp.abs(grads[k]).max()) + 1e-12
        # the worst leaf's largest entry to a part in a thousand: the
        # gradient passes three layers of reordered float32 sums
        assert float(jnp.abs(got - grads[k]).max()) / scale < 1e-3, k


def test_every_kind_of_leaf_is_there_and_learns():
    values = SHORT
    model, step, params, state, names, _ = _program(values, seed=7)
    kinds = {k.split(".", 2)[-1] for k in names if k.startswith("layers.")}
    assert kinds == {"norm.weight", "in.weight", "conv.weight", "conv.bias",
                     "A_log", "dt_bias", "D", "gnorm.weight", "out.weight",
                     "router.weight", "experts.up", "experts.down",
                     "shared.up.weight", "shared.down.weight", "q.weight",
                     "k.weight", "v.weight", "o.weight"}
    # non-gated experts: no gate leaf anywhere
    assert not [n for n in params if "gate_proj" in n]
    start = dict(params)
    batches = [ref_train.make_batch(7, i, 2, SEQ, VOCAB) for i in range(3)]
    p, st, losses = run_steps(step, params, state, batches,
                              key=jax.random.key(0), lr=LR)
    losses = [float(v) for v in losses]
    assert np.isfinite(losses).all() and len(losses) == 3
    for k in ("layers.0.A_log", "layers.0.dt_bias", "layers.0.D",
              "layers.0.conv.bias", "layers.2.q.weight",
              "layers.1.experts.up"):
        assert float(jnp.abs(p[names[k]] - start[names[k]]).max()) > 0, k
    assert [i for i, _ in model.sparse_layers()] == [1]
    assert [i for i, _ in family.build_model(TINY).sparse_layers()] == \
        [1, 3, 6, 8]


D, F, FS, OF, K = 24, 12, 20, 16, 4


def _moe_weights(seed):
    ks = jax.random.split(jax.random.key(seed), 5)
    n = jax.random.normal
    return {"router.weight": n(ks[0], (D, OF)) * 0.3,
            "experts.up": n(ks[1], (OF, D, F)) * 0.2,
            "experts.down": n(ks[2], (OF, F, D)) * 0.2,
            "shared.up.weight": n(ks[3], (D, FS)) * 0.2,
            "shared.down.weight": n(ks[4], (FS, D)) * 0.2}


def _moe_layer(lp, first, held, with_shared, bias):
    shared = Relu2MLP(D, FS) if with_shared else None
    layer = DroplessMoE(D, F, OF, K, held=(first, held), shared=shared,
                        row_tile=8, routed_scale=2.5, score_bias=True,
                        activation="relu2")
    cut = slice(first, first + held)
    layer.router_weight._data = lp["router.weight"]
    layer.up_proj._data = lp["experts.up"][cut]
    layer.down_proj._data = lp["experts.down"][cut]
    layer.e_score_correction_bias._data = jnp.asarray(bias)
    if with_shared:
        shared.up_proj.weight._data = lp["shared.up.weight"]
        shared.down_proj.weight._data = lp["shared.down.weight"]
    return layer


def _apply(layer, x):
    with tape_paused():
        return layer(Tensor(x))._data


def test_the_shares_add_up_to_the_uncut_layer():
    """Two shares of eight relu^2 experts each, sigmoid scores chosen with a
    correction bias and scaled by 2.5, the shared expert counted once: their
    sum is what the uncut reference (held = of) gives for the whole layer,
    forward and the gradient of the input."""
    lp = _moe_weights(3)
    v = {"num_experts_per_tok": K, "n_routed_experts": OF,
         "routed_scaling_factor": 2.5, "dtype": "float32",
         "score_bias": {"seed": 41, "scale": 0.3}}
    bias = reference.score_bias(v, 0)
    x = jax.random.normal(jax.random.key(4), (2, 16, D))
    do = jax.random.normal(jax.random.key(5), (2, 16, D))
    shares = [_moe_layer(lp, first, 8, first == 0, bias)
              for first in range(0, OF, 8)]

    def run(fn):
        out, vjp = jax.vjp(fn, x)
        return out, vjp(do)[0]
    got, dx = jax.jit(lambda: run(lambda x: sum(_apply(s, x)
                                               for s in shares)))()
    want, dx_ref = jax.jit(lambda: run(lambda x: reference._experts(
        x, lp, v, numerics.Exact(), 0)))()
    # float32 on both sides, sums in another order
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(dx, dx_ref, atol=1e-4)


def test_relu2_experts_make_no_gate_leaf_and_another_program():
    """relu^2 makes no gate leaf and lowers otherwise than the default; the
    convolution adds its bias where one is given (``test_the_shared_layers_
    lower_as_they_did_before`` holds the defaults to their old text)."""
    lp = _moe_weights(8)
    x = jax.random.normal(jax.random.key(9), (2, 16, D))

    def text(**kw):
        paddle.seed(0)
        layer = DroplessMoE(D, F, OF, K, held=(4, 8), row_tile=8, **kw)
        layer.router_weight._data = lp["router.weight"]
        return layer, jax.jit(lambda x: _apply(layer, x)).lower(x).as_text()
    relu2, other = text(activation="relu2")
    assert other != text()[1] and relu2.gate_proj is None
    assert not [n for n, _ in relu2.named_parameters() if "gate" in n]
    with pytest.raises(ValueError):
        DroplessMoE(D, F, OF, K, activation="gelu")
    xc = jax.random.normal(jax.random.key(1), (2, 16, 12))
    w = jax.random.normal(jax.random.key(2), (12, 4))
    bias = jnp.arange(12, dtype=jnp.float32)
    np.testing.assert_allclose(causal_depthwise_conv(xc, w, bias),
                               causal_depthwise_conv(xc, w) + bias,
                               rtol=1e-6)


def test_from_published_keeps_the_published_keys_and_refuses_others():
    cfg = NemotronHConfig.from_published(TINY, experts_held=(0, 8),
                                         n_routed_experts=16)
    assert cfg.layer_types == tuple("MEMEM*EME") and cfg.num_layers == 9
    assert cfg.ssm_decay_init == (tuple(TINY["ssm_decay_init"]["A"]),
                                  tuple(TINY["ssm_decay_init"]["dt"]))
    full = NemotronHConfig()
    assert (full.layer_types.count("M"), full.layer_types.count("E"),
            full.layer_types.count("*")) == (23, 23, 6)
    assert (full.n_routed_experts, full.num_experts_per_tok,
            full.mamba_num_heads * full.mamba_head_dim) == (128, 6, 4096)
    for bad in ({"num_hidden_layers": 8}, {"mlp_hidden_act": "silu"},
                {"n_group": 2}, {"use_conv_bias": False},
                {"tie_word_embeddings": True}, {"residual_in_fp32": True}):
        with pytest.raises(ValueError):
            NemotronHConfig.from_published(dict(TINY, **bad))
    with pytest.raises(ValueError):
        NemotronHConfig(hybrid_override_pattern="MEX")


def test_the_decay_offsets_span_the_published_range_and_interleave():
    a0, dt0 = ssm_decay_init(64)
    decay = np.exp(-a0 * dt0)
    assert decay.max() == pytest.approx(np.exp(-1e-3))
    assert decay.min() == pytest.approx(np.exp(-1.6))
    assert (a0.min(), a0.max()) == (1.0, 16.0)
    assert dt0.min() == pytest.approx(1e-3) and dt0.max() == \
        pytest.approx(0.1)
    # neighbouring heads remember over very different spans
    assert np.abs(np.diff(np.log(-np.log(decay)))).min() > 1.0
    mixer = family.build_model(dict(TINY, dtype="float32")).model.layers[
        0].mixer
    lo, hi = mixer.rest_decay()
    a, d = (np.asarray(TINY["ssm_decay_init"][k]) for k in ("A", "dt"))
    assert lo == pytest.approx(np.exp(-(a * d).max()))
    assert hi == pytest.approx(np.exp(-(a * d).min()))


def test_the_plans_of_each_mamba_and_expert_layer(interpret_kernels):
    from paddle_tpu.ops.pallas import ssd as sd
    from paddle_tpu.profiler import tracing
    values = dict(SHORT, hybrid_override_pattern="MEM", num_hidden_layers=3)
    model = family.build_model(values)
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=LR,
                                 parameters=model.parameters())
    step, params, state = create_train_step(model, opt)
    ids = jnp.zeros((1, SEQ), jnp.int32)
    before = dict(sd.SSD_PLAN_TALLY)
    tracing.reset_tracing()
    tracing.enable_tracing(ring_size=tracing.DEFAULT_RING_SIZE)
    try:
        step.lower(params, state, jax.random.key(0), ids, ids, LR)
        events = tracing.snapshot_events()
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    ssd_plans = [e["args"] for e in events if e["name"] == "ssd::plan"]
    moe_plans = [e["args"] for e in events if e["name"] == "moe::plan"]
    # the layer body is recomputed and the family's policy keeps the scan's
    # output and states: the forward runs once, "kept"
    assert len(ssd_plans) == 2
    assert {(e["path"], e["chunk"], e["chunks"], e["heads"], e["groups"],
             e["state"], e["head_dim"], e["forward"]) for e in ssd_plans} \
        == {("kernel", 32, 3, 8, 2, 16, 8, "kept")}
    assert sd.SSD_PLAN_TALLY[(SEQ, 32, 8, 2, 16, 8, "kernel", "kept")] == \
        before.get((SEQ, 32, 8, 2, 16, 8, "kernel", "kept"), 0) + 2
    assert len(moe_plans) == 1
    assert {(e["activation"], e["shared"], e["score_bias"], e["top_k"])
            for e in moe_plans} == {("relu2", "Relu2MLP", True, 4)}
    # under "full" the block replays the scan's forward
    model.cfg.recompute_policy = "full"
    tracing.reset_tracing()
    tracing.enable_tracing(ring_size=tracing.DEFAULT_RING_SIZE)
    try:
        create_train_step(model, opt)[0].lower(
            params, state, jax.random.key(0), ids, ids, LR)
        events = tracing.snapshot_events()
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    assert {e["args"]["forward"] for e in events
            if e["name"] == "ssd::plan"} == {"replayed"}


def _digest(layer, x):
    """A digest of the text a layer's forward and gradient lower to."""
    import hashlib

    from paddle_tpu.nn.layer.layers import _swapped_state, functional_state
    arrays = dict(functional_state(layer))

    def f(arrs, x):
        with _swapped_state(layer, arrs), tape_paused():
            out = layer(Tensor(x))
        return jnp.sum(out._data.astype(jnp.float32))
    text = jax.jit(jax.value_and_grad(f)).lower(arrays, x).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def test_the_shared_layers_lower_as_they_did_before():
    """Laguna's, GLM's and Qwen3-Next's kind of SwiGLU expert layer (under
    the default ``activation``) and a Gated DeltaNet layer (through the
    shared convolution, no bias) lower, forward and gradient, to the text
    they lowered to before relu^2 experts and the shared convolution were
    added: the digests below were taken from that code under this suite's
    settings."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaMLP
    x = jax.random.normal(jax.random.key(1), (2, 16, 24)).astype(
        jnp.bfloat16)
    before = {"laguna": "0400ab1e1aa761a8", "glm": "bcfdf16bb9b5f59f",
              "qwen": "64e6a923e9cb66c8", "gdn": "2379e8cc33cde0ab"}
    got = {}
    for name, kw in {"laguna": dict(routed_scale=2.5),
                     "glm": dict(score_bias=True, routed_scale=1.8),
                     "qwen": dict(score="softmax")}.items():
        paddle.seed(0)
        shared = LlamaMLP(LlamaConfig(hidden_size=24, intermediate_size=12))
        got[name] = _digest(DroplessMoE(24, 12, 16, 4, held=(4, 8),
                                        shared=shared, row_tile=8, **kw), x)
    paddle.seed(0)
    got["gdn"] = _digest(paddle.nn.GatedDeltaNet(
        24, 2, 4, 8, 8, 4, 1e-6, A_offset=[0.1] * 4, dt_offset=[0.2] * 4), x)
    assert got == before
