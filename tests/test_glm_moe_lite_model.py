"""models/glm_moe_lite.py against the benchmark's plain reference
(benchmarks/reference/glm4_moe_lite.py) at a tiny preset of the published
structure: a dense first layer and two sparse ones, latent attention with
nope : rope : value dims 3 : 1 : 4 and both ranks unequal to hidden, top-4 of
16 bias-corrected experts of which this chip holds 4, one MTP module."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmarks.families import glm4_moe_lite as family  # noqa: E402
from benchmarks.reference import glm4_moe_lite as ref  # noqa: E402
from benchmarks.reference import numerics  # noqa: E402
from benchmarks.reference import train as ref_train  # noqa: E402
from paddle_tpu import models  # noqa: E402
from paddle_tpu.core.autograd import tape_paused  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.nn.layer.layers import (_swapped_state,  # noqa: E402
                                        functional_state)

with open(os.path.join(ROOT, "benchmarks", "tests", "preset_glm", "configs",
                       "glm-tiny.json")) as _f:
    TINY = json.load(_f)
EXACT = numerics.Exact()


@pytest.fixture
def interpret_kernels():
    """The flash kernels (at the tiny head of 16) and the grouped matmul
    run, interpreted."""
    paddle.set_flags({"pallas_force_interpret": True})
    try:
        yield
    finally:
        paddle.set_flags({"pallas_force_interpret": False})


def built(dtype, seed=7, **changed):
    """(configuration values, the program's model with weights made from
    ``seed``, those weights by reference leaf name, reference name -> program
    name, a batch of 4 x 32)."""
    v = dict(TINY, dtype=jnp.dtype(dtype).name, recompute_layers=False,
             **changed)
    shapes = ref.param_shapes(v)
    made = ref_train.make_params(shapes, seed, dtype, 0.02)
    # gains off 1, so that their gradients are not those of a symmetric point
    made = {k: (a * (1.0 + 0.1 * jnp.cos(jnp.arange(a.size, dtype=jnp.float32)
                                         )).astype(a.dtype)
                if k.endswith("norm.weight") else a) for k, a in made.items()}
    model = family.build_model(v)
    if dtype == jnp.bfloat16:
        model = model.bfloat16()
    model.train()
    names = {k: family.program_name(k) for k in shapes}
    models.write_back(model, {names[k]: a for k, a in made.items()},
                      strict=True)
    assert set(functional_state(model, trainable_only=True)) == \
        set(names.values())
    ids, labels = ref_train.make_batch(seed, 0, 4, 32, v["vocab_size"])
    return v, model, made, names, (jnp.asarray(ids), jnp.asarray(labels))


def program_and_reference(dtype, seed=7, **changed):
    """(loss, gradients by reference leaf name) of the program and of the
    reference on the same weights and batch."""
    v, model, made, names, (ids, labels) = built(dtype, seed, **changed)
    state = functional_state(model, trainable_only=True)

    def program_loss(params):
        with _swapped_state(model, params), tape_paused():
            return model.loss(Tensor(ids), Tensor(labels))._data.astype(
                jnp.float32)

    def reference_loss(params):
        return jnp.mean(ref.token_losses(params, ids, labels, v, EXACT))

    got = jax.jit(jax.value_and_grad(program_loss))(state)
    want = jax.jit(jax.value_and_grad(reference_loss))(made)
    return got[0], {k: got[1][names[k]] for k in made}, want[0], want[1]


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-12))


def test_float32_loss_and_every_gradient_match_the_reference(
        interpret_kernels):
    """Both terms of the loss and all 67 trained leaves, the MTP module's
    and the shared table and head among them. 2e-6 and 2e-5: float32
    round-off through six blocks in another order of summation (the
    readings are 1e-7 and 5e-7); a wrong target, mask, scale or shared
    leaf moves them by O(0.1)."""
    loss, grads, ref_loss, ref_grads = program_and_reference(jnp.float32)
    assert abs(float(loss) - float(ref_loss)) < 2e-6 * float(ref_loss)
    assert len(ref_grads) == 67
    for k, want in ref_grads.items():
        assert rel(grads[k], want) < 2e-5, k


def test_logits_of_both_heads_match_the_reference(interpret_kernels):
    v, model, made, _, (ids, labels) = built(jnp.float32)
    with tape_paused():
        main, mtp = model.forward_mtp(Tensor(ids), Tensor(labels))
    want_main, want_mtp = ref.logits(made, ids, labels, v, EXACT)
    # float32 round-off; the logits are O(0.1)
    np.testing.assert_allclose(main._data, want_main, atol=2e-6)
    np.testing.assert_allclose(mtp._data, want_mtp, atol=2e-6)
    with tape_paused():
        np.testing.assert_array_equal(model(Tensor(ids))._data, main._data)


def test_the_mtp_targets_are_two_ahead_and_the_last_position_has_none(
        interpret_kernels):
    """loss = CE(main logits, labels) + 0.3 x CE(MTP logits[:, :-1],
    labels[:, 1:]), by brute force from the program's own logits; the label
    at the last position reaches the loss through the main term alone."""
    v, model, _, _, (ids, labels) = built(jnp.float32)

    def ce(logits, targets):
        return jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, targets[..., None], -1)[..., 0])

    with tape_paused():
        main, mtp = model.forward_mtp(Tensor(ids), Tensor(labels))
        loss = float(model.loss(Tensor(ids), Tensor(labels))._data)
    want = ce(main._data, labels) + v["mtp_loss_weight"] * ce(
        mtp._data[:, :-1], labels[:, 1:])
    assert loss == pytest.approx(float(want), rel=2e-6)
    # one ahead, or over all S positions, would not be this loss
    assert abs(float(ce(main._data, labels) + 0.3 * ce(mtp._data, labels))
               - loss) > 1e-4
    # an ignored label drops its position from the main term and from the
    # MTP term both as the embedded token (t) and as the target (t - 1)
    holed = labels.at[:, 10].set(-100)
    keep = jnp.ones(labels.shape, bool).at[:, 10].set(False)
    keep_mtp = keep.at[:, 9].set(False)[:, :-1]
    with tape_paused():
        loss = float(model.loss(Tensor(ids), Tensor(holed))._data)

    def ce_where(logits, targets, where):
        each = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, jnp.maximum(targets, 0)[..., None], -1)[..., 0]
        return jnp.sum(jnp.where(where, each, 0.0)) / jnp.sum(where)

    with tape_paused():
        main, mtp = model.forward_mtp(Tensor(ids),
                                      Tensor(jnp.maximum(holed, 0)))
    want = ce_where(main._data, holed, keep) + 0.3 * ce_where(
        mtp._data[:, :-1], holed[:, 1:], keep_mtp)
    assert loss == pytest.approx(float(want), rel=2e-6)


def test_the_table_and_the_head_are_shared_with_the_mtp_module(
        interpret_kernels):
    """The gradients of the embedding and of the head are the sums of both
    uses: the program's (checked against the reference above) differ from
    the main term's alone by 0.3 x the MTP term's, leaf by leaf, and the
    model holds one table and one head."""
    _, grads, _, _ = program_and_reference(jnp.float32)
    _, main_only, _, _ = program_and_reference(jnp.float32,
                                               mtp_loss_weight=0.0)
    _, heavy, _, _ = program_and_reference(jnp.float32, mtp_loss_weight=0.6)
    for k in ("embed", "head.weight", "layers.0.kv_a.weight"):
        mtp_part = grads[k] - main_only[k]
        assert float(jnp.linalg.norm(mtp_part)) > \
            1e-3 * float(jnp.linalg.norm(grads[k])), k
        assert rel(heavy[k] - main_only[k], 2 * mtp_part) < 1e-3, k
    # with no weight the module's own leaves get no gradient at all
    assert float(jnp.linalg.norm(main_only["mtp.eh.weight"])) == 0.0
    model = models.GlmMoeLiteForCausalLM(models.glm_moe_lite_tiny())
    names = [n for n, _ in model.named_parameters()]
    assert [n for n in names if "embed" in n] == ["model.embed_tokens.weight"]
    assert [n for n in names if "head" in n] == ["lm_head.weight"]


def test_mla_expanded_form_against_explicit_scores(interpret_kernels):
    """One attention layer alone, hidden = heads x value dim so that an
    identity output projection shows every head: q, k, v built by hand from
    the layer's leaves (numpy, float64), softmax over explicit causal scores
    at 1 / sqrt(nope + rope)."""
    cfg = models.glm_moe_lite_tiny(hidden_size=64)
    paddle.seed(11)
    attn = models.MLAttention(cfg)
    heads, dn, dr, dv = 4, 12, 4, 16
    attn.o_proj.weight._data = jnp.eye(64, dtype=jnp.float32)
    w = {n: np.asarray(p._data, np.float64)
         for n, p in attn.named_parameters()}
    rng = np.random.default_rng(3)
    u = rng.standard_normal((2, 32, 64))
    s = 32
    from paddle_tpu.models.laguna import laguna_rope_tables
    tables = laguna_rope_tables(s, dr, {"rope_theta": cfg.rope_theta})

    def run(layer):
        with tape_paused():
            return np.asarray(layer(Tensor(jnp.asarray(u, jnp.float32)),
                                    tables)._data)

    def norm(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * g

    def rope(x):                # [..., S, R] along axis -2
        inv = 1e6 ** (-2.0 * np.arange(dr // 2) / dr)
        ang = np.arange(s)[:, None] * inv[None]
        x1, x2 = x[..., 0::2], x[..., 1::2]
        out = np.empty_like(x)
        out[..., 0::2] = x1 * np.cos(ang) - x2 * np.sin(ang)
        out[..., 1::2] = x2 * np.cos(ang) + x1 * np.sin(ang)
        return out

    q = (norm(u @ w["q_a_proj.weight"], w["q_a_layernorm.weight"])
         @ w["q_b_proj.weight"]).reshape(2, s, heads, dn + dr)
    kva = u @ w["kv_a_proj_with_mqa.weight"]
    kv = (norm(kva[..., :16], w["kv_a_layernorm.weight"])
          @ w["kv_b_proj.weight"]).reshape(2, s, heads, dn + dv)
    k_rope = rope(kva[..., 16:])                      # ONE key a token
    want = np.empty((2, s, heads, dv))
    mask = np.tril(np.ones((s, s), bool))
    for h in range(heads):
        qh = np.concatenate([q[:, :, h, :dn], rope(q[:, :, h, dn:])], -1)
        kh = np.concatenate([kv[:, :, h, :dn], k_rope], -1)
        sc = np.einsum("bqd,bkd->bqk", qh, kh) / np.sqrt(dn + dr)
        sc = np.where(mask, sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want[:, :, h] = np.einsum("bqk,bkd->bqd", p / p.sum(-1, keepdims=True),
                                  kv[:, :, h, dn:])
    got = run(attn).reshape(2, s, heads, dv)
    np.testing.assert_allclose(got, want, atol=3e-6)   # float32 round-off

    # the shared rotary key is ONE key: a change of its 4 columns of Wkva,
    # which no head owns, moves every head's output
    moved = np.array(w["kv_a_proj_with_mqa.weight"], np.float32)
    moved[:, 16:] += 0.05 * rng.standard_normal((64, dr))
    attn.kv_a_proj_with_mqa.weight._data = jnp.asarray(moved)
    change = np.abs(run(attn).reshape(2, s, heads, dv) - got)
    assert (change.reshape(-1, heads, dv).max(axis=(0, 2)) > 1e-4).all()


def test_config_from_the_published_keys():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm47-flash-l5-e8.json")) as f:
        v = json.load(f)
    cfg = models.GlmMoeLiteConfig.from_published(
        v, n_routed_experts=64, experts_held=(0, 8))
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank) == (2048, 20, 768, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (192, 64, 256)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.num_experts_per_tok, cfg.n_routed_experts,
            cfg.routed_scaling_factor) == (10240, 1536, 4, 64, 1.8)
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace,
            cfg.num_nextn_predict_layers, cfg.vocab_size) == (5, 1, 1, 19360)
    assert (cfg.rope_theta, cfg.rms_norm_eps) == (1000000, 1e-5)
    # the published model, whole
    full = models.GlmMoeLiteConfig()
    assert full.num_hidden_layers == 47 and full.experts_held is None
    assert full.vocab_size == 154880
    # what this file does not compute is refused, not ignored
    for key, value in (("n_group", 2), ("rope_scaling", {"factor": 2}),
                       ("num_key_value_heads", 4), ("norm_topk_prob", False)):
        with pytest.raises(ValueError, match=key):
            models.GlmMoeLiteConfig.from_published(dict(v, **{key: value}))


def test_a_value_head_unlike_the_key_head_is_refused():
    with pytest.raises(ValueError, match="v_head_dim"):
        models.glm_moe_lite_tiny(v_head_dim=12)


def test_the_reference_refuses_another_sequence_length():
    made = ref_train.make_params(ref.param_shapes(TINY), 1, jnp.float32, 0.02)
    ids = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError):
        ref.token_losses(made, ids, ids, TINY, EXACT)
