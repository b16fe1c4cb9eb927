"""ops/pallas/gated_delta.py: the chunked WY form (the XLA path) and the two
kernels in interpret mode against the delta rule computed token by token,
forward and the gradients of q, k, v, g and beta, at chunks shorter than the
sequence, lengths that are no multiple of the chunk, and decays near 1; a
state dropped at every chunk's start in the forward kernel is seen; the chunk
plan and the ``gdn::plan`` event."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.pallas import gated_delta as gd


def recurrence(q, k, v, g, beta):
    """S_t = exp(g_t) S_(t-1); S_t += k_t (beta_t (v_t - S_t^T k_t))^T;
    o_t = S_t^T q_t, one token at a time, value head h on key head h // r."""
    b, _, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))

    def step(st, x):
        qt, kt, vt, gt, bt = x
        st = jnp.exp(gt)[..., None, None] * st
        mem = jnp.einsum("bhkv,bhk->bhv", st, kt)
        st = st + kt[..., :, None] * (bt[..., None] * (vt - mem))[..., None, :]
        return st, jnp.einsum("bhkv,bhk->bhv", st, qt)
    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv)),
                            tuple(jnp.moveaxis(a, 1, 0)
                                  for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _inputs(s, slow=False, b=2, hk=2, hv=4, dk=16, dv=8, seed=0):
    key = jax.random.key(seed + s)
    ks = [jax.random.fold_in(key, i) for i in range(5)]
    q = jax.random.normal(ks[0], (b, s, hk, dk))
    k = jax.random.normal(ks[1], (b, s, hk, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, hv, dv))
    # per token decays from about 0.75 to 1, or (slow) all above 0.999
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, s, hv))) \
        * (1e-3 if slow else 0.3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hv)))
    return q, k, v, g, beta


def _loss_and_grads(fn, args):
    out = fn(*args)
    w = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(out.shape)
    return jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * w),
                              argnums=(0, 1, 2, 3, 4))(*args)


CASES = [(40, 8, False), (37, 16, True), (64, 16, False), (96, 8, True),
         (24, None, False)]


@pytest.mark.parametrize("path", ["xla", "kernels"])
@pytest.mark.parametrize("s,chunk,slow", CASES)
def test_the_chunked_form_is_the_token_recurrence(path, s, chunk, slow):
    args = _inputs(s, slow)
    want, g_want = _loss_and_grads(recurrence, args)
    if path == "xla":
        fn = lambda *a: gd.gated_delta_xla(*a, chunk=chunk)  # noqa: E731
    else:
        fn = lambda *a: gd.gated_delta(*a, chunk=chunk,  # noqa: E731
                                       interpret=True)
    got, g_got = _loss_and_grads(fn, args)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    for name, a, b in zip("q k v g beta".split(), g_got, g_want):
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) <= 2e-5 * max(scale, 1.0), name


@pytest.mark.parametrize("spread", [1.0, 0.01])
def test_correlated_keys_keep_the_triangular_solve_finite(spread):
    """Keys that share a component (as a trained model's do), beta near 1
    and decays near 1 make ``I + A`` nearly all ones below the diagonal: a
    product of its powers over a chunk of 128 overflows and cancels, the
    blocked solve stays at the token recurrence."""
    key = jax.random.key(3)
    base = jax.random.normal(key, (1, 1, 1, 32))
    k = base + spread * jax.random.normal(jax.random.fold_in(key, 1),
                                          (1, 256, 1, 32))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q = jax.random.normal(jax.random.fold_in(key, 2), (1, 256, 1, 32))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(32)
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, 256, 2, 16))
    g = jnp.full((1, 256, 2), -1e-3)
    beta = jnp.full((1, 256, 2), 0.95)
    want = np.asarray(recurrence(q, k, v, g, beta))
    got = np.asarray(gd.gated_delta(q, k, v, g, beta, chunk=128,
                                    interpret=True))
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_decays_near_one_carry_the_state_across_every_chunk():
    """With decays above 0.999 the last token still reads the first: its
    output moves when the first token's value does, through 12 chunks."""
    q, k, v, g, beta = _inputs(96, slow=True)
    v2 = v.at[:, 0].add(1.0)
    a = gd.gated_delta(q, k, v, g, beta, chunk=8, interpret=True)
    b = gd.gated_delta(q, k, v2, g, beta, chunk=8, interpret=True)
    assert float(jnp.abs(a[:, -1] - b[:, -1]).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(b), np.asarray(recurrence(
        q, k, v2, g, beta)), atol=2e-5)


def test_a_state_dropped_at_each_chunk_start_is_seen(monkeypatch):
    """The fault "no state carried across chunk boundaries", planted in the
    forward kernel: its output leaves the token recurrence."""
    args = _inputs(56, seed=11)
    want = np.asarray(recurrence(*args))
    sound = np.asarray(gd.gated_delta(*args, chunk=8, interpret=True))
    np.testing.assert_allclose(sound, want, atol=2e-5)
    kernel = gd._fwd_kernel

    def dropped(*refs, c):
        refs[-1][...] = jnp.zeros_like(refs[-1])
        kernel(*refs, c=c)
    monkeypatch.setattr(gd, "_fwd_kernel", dropped)
    jax.clear_caches()
    try:
        broken = np.asarray(gd.gated_delta(*args, chunk=8, interpret=True))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert np.abs(broken - want).max() > 1e-2
    # within the first chunk nothing was carried, so nothing differs
    np.testing.assert_allclose(broken[:, :8], want[:, :8], atol=2e-5)


def test_the_chunk_plan():
    assert gd.gated_delta_plan(16384) == 128 == gd.gated_delta_plan(128)
    assert gd.gated_delta_plan(40) == 40 and gd.gated_delta_plan(37) == 40


def test_chunk_cumsum_restarts_at_each_chunk():
    g = -jnp.ones((1, 10, 1))
    got = np.asarray(gd.chunk_cumsum(g, 4))[0, :, 0]
    np.testing.assert_array_equal(
        got, [-1, -2, -3, -4, -1, -2, -3, -4, -1, -2, -2, -2])
    assert got.shape == (12,)


def test_the_plan_event_and_tally():
    from paddle_tpu.nn import functional as F
    from paddle_tpu.profiler import tracing
    q, k, v, g, beta = _inputs(40)
    key = (4, 2, 16, 8, 40, 40, "kernel")
    before = gd.GDN_PLAN_TALLY[key]
    paddle.set_flags({"pallas_force_interpret": True})
    tracing.reset_tracing()
    tracing.enable_tracing(ring_size=tracing.DEFAULT_RING_SIZE)
    try:
        out = F.gated_delta_rule(q, k, v, g, beta, rest_decay=(0.2, 0.999))
        events = [e["args"] for e in tracing.snapshot_events()
                  if e["name"] == "gdn::plan"]
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
        paddle.set_flags({"pallas_force_interpret": False})
    assert gd.GDN_PLAN_TALLY[key] == before + 1 and len(events) == 1
    a = events[0]
    assert (a["heads"], a["key_heads"], a["key_dim"], a["value_dim"]) == \
        (4, 2, 16, 8)
    assert (a["path"], a["chunk"], a["chunks"]) == ("kernel", 40, 1)
    assert (a["smallest_decay"], a["largest_decay"]) == (0.2, 0.999)
    assert a["parked_state_bytes"] == 2 * 4 * 1 * 16 * 8 * 4
    # the functional normalises q and k and scales q itself
    qn = q * np.sqrt(16) * 3.0
    want = recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(out._data), np.asarray(want),
                               atol=2e-5)
    again = F.gated_delta_rule(qn, k * 5.0, v, g, beta)
    np.testing.assert_allclose(np.asarray(again._data), np.asarray(want),
                               atol=2e-5)
