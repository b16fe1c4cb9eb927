"""ops/pallas/linear_attention.py: the chunked scan against the naive
quadratic form, forward and gradient, for two chunk sizes and a length that is
no multiple of the chunk; the two kernels in interpret mode against the scan;
decays at both ends of the published range."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import linear_attention as la

RATES = jnp.asarray([0.84, 0.06, 0.0039], jnp.float32)   # lambda .43 to .996


def naive(q, k, v, rates, scale):
    """o_t = scale sum_{s <= t} exp(-rate (t - s)) (q_t . k_s) v_s."""
    s = q.shape[1]
    t = jnp.arange(s)
    gap = (t[:, None] - t[None, :]).astype(jnp.float32)
    w = jnp.where(gap >= 0, jnp.exp(-rates[:, None, None]
                                    * jnp.maximum(gap, 0)), 0.0)
    z = jnp.einsum("bthd,bshd->bhts", q, k) * scale * w[None]
    return jnp.einsum("bhts,bshd->bthd", z, v)


def _qkv(s, d=16, h=3, b=2):
    key = jax.random.key(s)
    return tuple(jax.random.normal(jax.random.fold_in(key, i), (b, s, h, d),
                                   jnp.float32) for i in range(3))


def _loss_and_grads(fn, q, k, v):
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)
    return jax.value_and_grad(lambda a, b, c: jnp.sum(fn(a, b, c) * w),
                              (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("s,chunk", [(80, 16), (80, 32), (70, 32),
                                     (48, None)])
def test_the_chunked_scan_is_the_quadratic_form(s, chunk):
    q, k, v = _qkv(s)
    want, g_want = _loss_and_grads(
        lambda a, b, c: naive(a, b, c, RATES, 0.25), q, k, v)
    got, g_got = _loss_and_grads(
        lambda a, b, c: la.linear_attention_xla(a, b, c, RATES, 0.25, chunk),
        q, k, v)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


@pytest.mark.parametrize("s,chunk", [(80, 16), (70, 32), (48, None)])
def test_the_kernels_match_the_scan(s, chunk):
    q, k, v = _qkv(s)
    want, g_want = _loss_and_grads(
        lambda a, b, c: la.linear_attention_xla(a, b, c, RATES, 0.25, chunk),
        q, k, v)
    got, g_got = _loss_and_grads(
        lambda a, b, c: la.linear_attention(a, b, c, RATES, 0.25, chunk,
                                            True), q, k, v)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_a_fast_decay_over_a_long_chunk_stays_finite():
    """Every exponent of the chunked form is non-positive: a rate of 5 over
    a chunk of 256 underflows to zero and nothing overflows."""
    q, k, v = _qkv(512, d=8, h=1, b=1)
    rates = jnp.asarray([5.0], jnp.float32)
    out, grads = _loss_and_grads(
        lambda a, b, c: la.linear_attention(a, b, c, rates, 0.35, 256, True),
        q, k, v)
    assert np.isfinite(float(out))
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)
    np.testing.assert_allclose(
        np.asarray(la.linear_attention(q, k, v, rates, 0.35, 256, True)),
        np.asarray(naive(q, k, v, rates, 0.35)), atol=1e-5)


def test_the_chunk_plan():
    assert la.linear_chunk_plan(12288) == 256
    assert la.linear_chunk_plan(64) == 64 and la.linear_chunk_plan(70) == 72
