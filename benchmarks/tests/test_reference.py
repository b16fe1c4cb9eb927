"""Each plain reference against the repo's model at a tiny size, in float32:
the same weights give the same loss and the same gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec
from conftest import PRESET, ROOT


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(PRESET, root=ROOT)


@pytest.mark.parametrize("cell_name", ["gpt2-tiny-train", "llama-tiny-train"])
def test_reference_matches_the_program_in_float32(bench, cell_name):
    cell = bench.cell(cell_name)
    values = cell.config.values
    fam = values["family"]
    family, reference = bench.module("families", fam), \
        bench.module("reference", fam)
    ref_train = bench.module("reference", "train")
    numerics = bench.module("reference", "numerics")
    shapes = reference.param_shapes(values)
    params = ref_train.make_params(shapes, 7, jnp.float32, 0.05)
    # biases and gains off their defaults, so that each is exercised
    params = {k: v + 0.1 * jax.random.normal(jax.random.key(i), v.shape)
              if shapes[k][1] != "normal" else v
              for i, (k, v) in enumerate(params.items())}
    ids, labels = ref_train.make_batch(7, 0, 3, 32, values["vocab_size"])

    def ref_loss(p):
        return jnp.mean(reference.token_losses(p, ids, labels, values,
                                               numerics.Exact()))
    want, want_g = jax.value_and_grad(ref_loss)(params)

    model = family.build_model(values)
    model.train()
    names = {k: family.program_name(k) for k in shapes}

    from paddle_tpu.models.trainer import _functional_pieces
    import paddle_tpu as paddle
    opt = paddle.optimizer.AdamW(parameters=model.parameters())
    loss_call, trainable0, _, _ = _functional_pieces(model, opt, None)
    assert set(names.values()) == set(trainable0)
    got, got_g = jax.value_and_grad(
        lambda p: loss_call({names[k]: v for k, v in p.items()},
                            jnp.asarray(ids), jnp.asarray(labels),
                            jax.random.key(0)))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    for k in params:
        a, b = np.asarray(got_g[k]), np.asarray(want_g[k])
        scale = max(np.abs(b).max(), 1e-6)
        assert np.abs(a - b).max() / scale < 2e-3, k


def test_adamw_follows_the_published_rule(bench):
    """One leaf, two steps, by hand (Loshchilov & Hutter, algorithm 2)."""
    ref_train = bench.module("reference", "train")
    p, m, v = jnp.array([[1.0, -2.0]]), jnp.zeros((1, 2)), jnp.zeros((1, 2))
    g = jnp.array([[0.5, -0.25]])
    p1, m1, v1 = ref_train._adamw_leaf(p, g, m, v, 1.0, 0.1, 0.01,
                                       decay=True, dtype=jnp.dtype("float32"))
    # mhat = g, vhat = g^2: the step is lr * sign(g); decay first
    want = np.array([[1.0, -2.0]]) * (1 - 0.1 * 0.01) - 0.1 * np.sign(
        [[0.5, -0.25]])
    assert np.allclose(np.asarray(p1), want, atol=1e-6)
    assert np.allclose(np.asarray(m1), 0.1 * np.asarray(g))
    assert np.allclose(np.asarray(v1), 0.001 * np.asarray(g) ** 2)
    # bfloat16 storage: a step under half a unit in the last place is lost
    p2, _, _ = ref_train._adamw_leaf(jnp.array([1.0]), jnp.array([1.0]),
                                     jnp.zeros(1), jnp.zeros(1), 1.0, 3e-4,
                                     0.0, decay=False,
                                     dtype=jnp.dtype("bfloat16"))
    assert float(p2[0]) == 1.0


def test_batches_and_weights_come_from_the_seed(bench):
    ref_train = bench.module("reference", "train")
    a = ref_train.make_batch(2**31 + 11, 3, 4, 16, 500)
    b = ref_train.make_batch(2**31 + 11, 3, 4, 16, 500)
    c = ref_train.make_batch(2**31 + 11, 4, 4, 16, 500)
    assert np.array_equal(a[0], b[0]) and not np.array_equal(a[0], c[0])
    assert a[0].max() < 500 and np.array_equal(a[0][:, 1:], a[1][:, :-1])
    assert len({tuple(r) for r in a[0].tolist()}) == 4     # rows all differ
    shapes = {"w": ((4, 4), "normal"), "g": ((4,), "ones")}
    w1 = ref_train.make_params(shapes, 2**31 + 11, jnp.bfloat16, 0.02)
    w2 = ref_train.make_params(shapes, 2**31 + 11, jnp.bfloat16, 0.02)
    w3 = ref_train.make_params(shapes, 11, jnp.bfloat16, 0.02)
    assert np.array_equal(np.asarray(w1["w"], np.float32),
                          np.asarray(w2["w"], np.float32))
    assert not np.array_equal(np.asarray(w1["w"], np.float32),
                              np.asarray(w3["w"], np.float32))
    assert w1["w"].dtype == jnp.bfloat16
