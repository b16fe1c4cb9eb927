"""models/laguna.py against the benchmark's plain reference
(benchmarks/reference/laguna.py) at a tiny preset of the published structure:
5 layers of the pattern (full + dense, window x 3, full), a head size that is
not hidden / heads, 6 and 8 query heads over 2 kv heads, window 8 in
sequences of 32, 16 experts top-4 of which this chip holds 4, an expert width
unequal to hidden."""
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmarks.families import laguna as family  # noqa: E402
from benchmarks.reference import laguna as ref  # noqa: E402
from benchmarks.reference import numerics  # noqa: E402
from benchmarks.reference import train as ref_train  # noqa: E402
from paddle_tpu import models  # noqa: E402
from paddle_tpu.core.autograd import tape_paused  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.models import laguna as lg  # noqa: E402
from paddle_tpu.nn.layer.layers import (_swapped_state,  # noqa: E402
                                        functional_state)

with open(os.path.join(ROOT, "benchmarks", "tests", "preset_laguna",
                       "configs", "laguna-tiny.json")) as _f:
    TINY = json.load(_f)


def program_and_reference(dtype, seed=7, **changed):
    """(loss, gradients by reference leaf name) of the program and of the
    reference, on weights made from ``seed`` and one batch of 4 x 32;
    ``changed`` are keys of the configuration's file."""
    TINY = dict(globals()["TINY"], **changed)
    shapes = ref.param_shapes(TINY)
    made = ref_train.make_params(shapes, seed, dtype, 0.02)
    # gains off 1, so that their gradients are not those of a symmetric point
    made = {k: (a * (1.0 + 0.1 * jnp.cos(jnp.arange(a.size, dtype=jnp.float32)
                                         )).astype(a.dtype)
                if k.endswith("norm.weight") else a) for k, a in made.items()}
    model = family.build_model(dict(TINY, recompute_layers=False))
    if dtype == jnp.bfloat16:
        model = model.bfloat16()
    model.train()
    names = {k: family.program_name(k) for k in shapes}
    models.write_back(model, {names[k]: a for k, a in made.items()},
                      strict=True)
    state = functional_state(model)
    assert set(state) == set(names.values())
    ids, labels = ref_train.make_batch(seed, 0, 4, 32, TINY["vocab_size"])
    ids, labels = jnp.asarray(ids), jnp.asarray(labels)

    def program_loss(params):
        with _swapped_state(model, params), tape_paused():
            return model.loss(Tensor(ids), Tensor(labels))._data.astype(
                jnp.float32)

    def reference_loss(params):
        return jnp.mean(ref.token_losses(params, ids, labels, TINY,
                                         numerics.Exact()))

    got = jax.jit(jax.value_and_grad(program_loss))(state)
    want = jax.jit(jax.value_and_grad(reference_loss))(made)
    return got[0], {k: got[1][names[k]] for k in shapes}, want[0], want[1]


def test_float32_loss_and_every_gradient_match_the_reference():
    """The windowed flash kernels and the grouped matmul run (interpret
    mode); tests/test_flash_window.py covers attention's XLA fallback."""
    paddle.set_flags({"pallas_force_interpret": True})
    try:
        loss, grads, ref_loss, ref_grads = program_and_reference(jnp.float32)
    finally:
        paddle.set_flags({"pallas_force_interpret": False})
    assert abs(float(loss) - float(ref_loss)) < 2e-6 * float(ref_loss)
    for k, want in ref_grads.items():
        err = float(jnp.linalg.norm(grads[k] - want)
                    / jnp.maximum(jnp.linalg.norm(want), 1e-12))
        assert err < 2e-5, (k, err)


def test_yarn_table_at_three_positions_by_hand():
    """Laguna-XS.2's full layers: 64 rotated dims, base 500000, factor 64,
    original length 4096, beta_fast 64, beta_slow 1. c(64) = 5.66 and c(1) =
    15.80, so low = 5, high = 16, ramp_i = clip((i - 5) / 11, 0, 1):
    i = 0: ramp 0, inv = 1; i = 10: ramp 5/11, inv = 500000^(-20/64) (5/11 /
    64 + 6/11) = 0.009150584078844943; i = 31: ramp 1, inv =
    500000^(-62/64) / 64 = 4.709153362717455e-08. cos and sin carry the
    attention factor 1.4158883083359672."""
    p = models.LagunaConfig().rope_parameters["full_attention"]
    cos, sin = models.laguna_rope_tables(8192, 128, p)
    assert cos.shape == sin.shape == (8192, 32)
    for (pos, i), (c, s) in {
            (1, 0): (0.7650077178456628, 1.191428929193453),
            (100, 10): (0.863329800649892, 1.122230527562098),
            (5000, 31): (1.4158882690873216, 0.0003333817563411676)}.items():
        assert cos[pos, i] == pytest.approx(c, rel=2e-6)
        assert sin[pos, i] == pytest.approx(s, rel=2e-6)
    np.testing.assert_allclose(lg.yarn_inv_freq(64, p),
                               ref.yarn_inv_freq(64, p), rtol=1e-12)
    # a window layer: the whole head, plain frequencies, factor 1
    cos, _ = models.laguna_rope_tables(
        64, 128, models.LagunaConfig().rope_parameters["sliding_attention"])
    assert cos.shape == (64, 64)
    assert cos[3, 5] == pytest.approx(math.cos(3 * 10000 ** (-10 / 128)),
                                      rel=1e-6)


def test_config_from_the_published_keys():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "laguna-xs2-l5-e32.json")) as f:
        v = json.load(f)
    cfg = models.LagunaConfig.from_published(
        v, num_experts=256, experts_held=(0, 32))
    assert cfg.num_layers == 5 and cfg.head_dim == 128
    assert cfg.num_heads_per_layer == (48, 64, 64, 64, 48)
    assert cfg.layer_types[1] == "sliding_attention"
    assert cfg.mlp_layer_types == ("dense",) + ("sparse",) * 4
    assert (cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.sliding_window) == (2048, 512, 512)
    # the published model, whole
    full = models.LagunaConfig()
    assert full.num_layers == 40 and full.experts_held is None
    with pytest.raises(ValueError):
        models.LagunaConfig(num_heads_per_layer=(7,) * 40)


def test_the_reference_refuses_another_sequence_length():
    shapes = ref.param_shapes(TINY)
    made = ref_train.make_params(shapes, 1, jnp.float32, 0.02)
    ids = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError):
        ref.token_losses(made, ids, ids, TINY, numerics.Exact())
