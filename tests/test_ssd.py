"""Mamba-2's SSD scan (``ops/pallas/ssd.py``): the kernels in interpret mode,
the XLA path and the recurrence token by token agree, forward and backward,
over several chunks with a strongly varied step, so that a state carried
wrongly between chunks (or a group's B / C read by the wrong heads) fails;
the chunk plan; the ``ssd::plan`` event and its tally."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import ssd as sd


@jax.jit
def recurrence(x, dt, a, b, c):
    """The scan token by token, float32: ``S_t = exp(dt_t a) S_(t-1) + dt_t
    x_t B_t^T``, ``y_t = S_t C_t``."""
    bt, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    bb, cc = (jnp.repeat(z, h // g, axis=2) for z in (b, c))

    def token(st, z):
        xt, dtt, bt_, ct = z                    # [Bt, H, P] / [Bt, H] / ...
        st = jnp.exp(dtt * a)[..., None, None] * st \
            + (dtt[..., None] * xt)[..., :, None] * bt_[..., None, :]
        return st, jnp.sum(st * ct[..., None, :], axis=-1)
    _, y = jax.lax.scan(token, jnp.zeros((bt, h, p, n), jnp.float32),
                        tuple(jnp.moveaxis(z.astype(jnp.float32), 1, 0)
                              for z in (x, dt, bb, cc)))
    return jnp.moveaxis(y, 0, 1)


def _inputs(s, bt=2, h=4, g=2, p=8, n=16, seed=0):
    """dt spread over three decades a token and head, and rates A from 0.5
    to 8: some heads keep their state across every chunk, others forget it
    within a few tokens."""
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (bt, s, h, p), jnp.float32)
    dt = jnp.exp(jax.random.uniform(ks[1], (bt, s, h), jnp.float32,
                                    np.log(1e-3), np.log(1.0)))
    a = -jnp.asarray(np.geomspace(0.5, 8.0, h), jnp.float32)
    b = jax.random.normal(ks[2], (bt, s, g, n), jnp.float32) * 0.5
    c = jax.random.normal(ks[3], (bt, s, g, n), jnp.float32) * 0.5
    return x, dt, a, b, c


def _loss_and_grads(fn, args):
    w = jax.random.normal(jax.random.key(9), args[0].shape, jnp.float32)
    return jax.jit(jax.value_and_grad(lambda *z: jnp.sum(fn(*z) * w),
                                      argnums=(0, 1, 2, 3, 4)))(*args)


def _gap(got, want):
    return float(jnp.abs(got - want).max()) \
        / (float(jnp.abs(want).max()) + 1e-12)


def _close(got, want, tol):
    assert _gap(got, want) < tol


# 72 tokens: chunks of 16, the last ragged; 40: one chunk of 40
@pytest.mark.parametrize("path", ["xla", "kernels"])
@pytest.mark.parametrize("s,chunk", [(72, 16), (40, 40)])
def test_the_chunked_form_is_the_token_recurrence(path, s, chunk):
    args = _inputs(s)
    if path == "xla":
        def fn(*z):
            return sd.ssd_xla(*z, chunk=chunk)
    else:
        def fn(*z):
            return sd.ssd(*z, chunk=chunk, interpret=True)
    with jax.default_matmul_precision("highest"):
        want, gwant = _loss_and_grads(recurrence, args)
        got, ggot = _loss_and_grads(fn, args)
    # float32 throughout on the CPU: the chunked sums reorder the token
    # sums, a few ulps of the largest entries
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    for name, gg, gw in zip("x dt a b c".split(), ggot, gwant):
        assert _gap(gg, gw) < 2e-4, name


def test_the_kernels_in_bfloat16_stay_near_float32():
    """Storage-dtype inputs: the products of the inputs alone ride in
    bfloat16, the state and its products in float32."""
    x, dt, a, b, c = _inputs(64)
    lo = (x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
          c.astype(jnp.bfloat16))
    with jax.default_matmul_precision("highest"):
        want = recurrence(*lo)
    got = sd.ssd(*lo, chunk=16, interpret=True).astype(jnp.float32)
    # bf16 rounding of the chunk products' operands and of the output:
    # a few parts in a thousand of the largest entry
    _close(got, want, 1e-2)


def test_a_carry_or_a_group_broken_is_seen():
    """The inputs above tell a sound scan from one that drops the state at
    each chunk's start, and from one whose heads read the neighbouring
    group's B and C."""
    x, dt, a, b, c = _inputs(64)
    with jax.default_matmul_precision("highest"):
        want = recurrence(x, dt, a, b, c)
        swapped = recurrence(x, dt, a, jnp.roll(b, 1, axis=2),
                             jnp.roll(c, 1, axis=2))
        pieces = jnp.concatenate([recurrence(*(z[:, i:i + 16] for z in
                                               (x, dt)), a,
                                             b[:, i:i + 16], c[:, i:i + 16])
                                  for i in range(0, 64, 16)], axis=1)
    got = sd.ssd(x, dt, a, b, c, chunk=16, interpret=True)
    _close(got, want, 2e-4)
    for broken in (swapped, pieces):
        assert float(jnp.abs(broken - want).max()) \
            > 0.05 * float(jnp.abs(want).max())


def test_the_chunk_plan():
    assert sd.ssd_plan(16384) == 128
    assert sd.ssd_plan(16384, 256) == 256
    assert sd.ssd_plan(100) == 104


def test_the_plan_event_and_tally():
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn import functional as F
    from paddle_tpu.profiler import tracing
    x, dt, a, b, c = _inputs(48)
    d = jnp.ones((4,), jnp.float32)
    before = dict(sd.SSD_PLAN_TALLY)
    tracing.reset_tracing()
    tracing.enable_tracing(ring_size=tracing.DEFAULT_RING_SIZE)
    try:
        y = F.ssd_scan(*(Tensor(z) for z in (x, dt, a, b, c, d)), chunk=16,
                       rest_decay=(0.5, 0.99))
        events = [e["args"] for e in tracing.snapshot_events()
                  if e["name"] == "ssd::plan"]
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    assert events[-1]["seq"] == 48 and events[-1]["chunk"] == 16
    assert events[-1]["chunks"] == 3 and events[-1]["heads"] == 4
    assert events[-1]["groups"] == 2 and events[-1]["state"] == 16
    assert events[-1]["head_dim"] == 8 and events[-1]["path"] == "xla"
    assert events[-1]["forward"] == "kept"
    assert events[-1]["largest_decay"] == 0.99
    key = (48, 16, 4, 2, 16, 8, "xla", "kept")
    assert sd.SSD_PLAN_TALLY[key] == before.get(key, 0) + 1
    # the skip term: y = scan + D x
    with jax.default_matmul_precision("highest"):
        want = recurrence(x, dt, a, b, c) + x
    _close(y._data, want, 2e-4)
