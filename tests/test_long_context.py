"""Ring attention + Ulysses vs the single-device attention oracle on the
8-virtual-device CPU mesh (SURVEY.md §4 pattern: parallelism correctness ==
numeric parity with the unsharded run)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.nn.functional.flash_attention import _attention_xla


def _mesh(n=4, name="sep"):
    return Mesh(np.array(jax.devices()[:n]), (name,))


def _mk(b, s, h, d, hk=None, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hk or h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hk or h, d)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_local(causal):
    q, k, v = _mk(2, 64, 4, 16)
    scale = 1.0 / math.sqrt(16)
    ref = _attention_xla(q, k, v, None, causal, scale, 0.0, None)
    out = dist.ring_attention(q, k, v, mesh=_mesh(), causal=causal)
    np.testing.assert_allclose(np.asarray(out.numpy()), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_gqa():
    q, k, v = _mk(1, 64, 4, 16, hk=2, seed=1)
    scale = 1.0 / math.sqrt(16)
    ref = _attention_xla(q, k, v, None, True, scale, 0.0, None)
    out = dist.ring_attention(q, k, v, mesh=_mesh(), causal=True)
    np.testing.assert_allclose(np.asarray(out.numpy()), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_grad_matches_local():
    q, k, v = _mk(1, 32, 2, 8, seed=2)
    scale = 1.0 / math.sqrt(8)
    mesh = _mesh()
    rng = np.random.RandomState(3)
    ct = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    from paddle_tpu.distributed.long_context import (ring_attention_local,
                                                     shard_map)
    from jax.sharding import PartitionSpec as P
    spec = P(None, "sep", None, None)
    fn = shard_map(
        lambda a, b, c: ring_attention_local(a, b, c, "sep", 4, True, scale),
        mesh, in_specs=(spec, spec, spec), out_specs=spec)

    g_ring = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * ct),
                      argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(
            _attention_xla(q, k, v, None, True, scale, 0.0, None) * ct),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_local(causal):
    q, k, v = _mk(2, 64, 4, 16, seed=4)
    scale = 1.0 / math.sqrt(16)
    ref = _attention_xla(q, k, v, None, causal, scale, 0.0, None)
    out = dist.ulysses_attention(q, k, v, mesh=_mesh(), causal=causal)
    np.testing.assert_allclose(np.asarray(out.numpy()), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_runs_the_flash_kernel_inside_its_own_shard_map():
    """With the kernel route on (the chip's route), Ulysses' local
    attention is already per-shard: the dispatch must see that the mesh
    axes are manual there and not wrap the kernel in a second shard_map
    (it does that only for axes GSPMD still owns)."""
    q, k, v = _mk(1, 64, 4, 16, seed=4)
    scale = 1.0 / math.sqrt(16)
    ref = _attention_xla(q, k, v, None, True, scale, 0.0, None)
    paddle.set_flags({"pallas_force_interpret": True})
    try:
        out = dist.ulysses_attention(q, k, v, mesh=_mesh(), causal=True)
    finally:
        paddle.set_flags({"pallas_force_interpret": False})
    np.testing.assert_allclose(np.asarray(out.numpy()), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_gqa_expand():
    # 2 kv heads < 4 devices: GQA expansion before the head swap
    q, k, v = _mk(1, 64, 8, 16, hk=2, seed=5)
    scale = 1.0 / math.sqrt(16)
    ref = _attention_xla(q, k, v, None, True, scale, 0.0, None)
    out = dist.ulysses_attention(q, k, v, mesh=_mesh(), causal=True)
    np.testing.assert_allclose(np.asarray(out.numpy()), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_gqa_unexpanded_swap():
    # 4 kv heads over 4 devices: kv rides the all_to_all UN-expanded
    # (Hk/H of the bytes); the GQA-native local kernel closes the gap
    q, k, v = _mk(1, 64, 8, 16, hk=4, seed=12)
    scale = 1.0 / math.sqrt(16)
    ref = _attention_xla(q, k, v, None, True, scale, 0.0, None)
    out = dist.ulysses_attention(q, k, v, mesh=_mesh(), causal=True)
    np.testing.assert_allclose(np.asarray(out.numpy()), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # grads flow through the unexpanded path too
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed.long_context import (
        shard_map, ulysses_attention_local)
    spec = P(None, "sep", None, None)
    fn = shard_map(
        lambda a, b, c: ulysses_attention_local(a, b, c, "sep", 4, True,
                                                scale),
        _mesh(), in_specs=(spec, spec, spec), out_specs=spec)
    g = jax.grad(lambda a, b, c: jnp.sum(fn(a, b, c)),
                 argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda a, b, c: jnp.sum(
            _attention_xla(a, b, c, None, True, scale, 0.0, None)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("shape", [
    # (B, S, Hq, Hk, D, N, causal)
    (2, 256, 4, 4, 32, 4, True),
    (1, 384, 4, 2, 32, 8, True),   # GQA + uneven chunks (sc=48)
    (1, 256, 4, 4, 32, 4, False),
])
def test_ring_pallas_impl_parity(shape):
    """The Pallas-chunk ring (VERDICT r4 #5): per-step flash block kernel
    (interpret mode on CPU) must match the dense oracle in forward AND all
    three input grads, elementwise, at S >= 256 with causal boundaries
    that don't align to the kernel's 128 block."""
    B, S, Hq, Hk, D, N, causal = shape
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32) * 0.3
    k = jnp.asarray(rng.standard_normal((B, S, Hk, D)), jnp.float32) * 0.3
    v = jnp.asarray(rng.standard_normal((B, S, Hk, D)), jnp.float32) * 0.3
    scale = 1.0 / math.sqrt(D)
    ct = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed.long_context import (ring_attention_local,
                                                     shard_map)
    spec = P(None, "sep", None, None)
    fn = shard_map(
        lambda a, b, c: ring_attention_local(a, b, c, "sep", N, causal,
                                             scale, impl="pallas"),
        _mesh(N), in_specs=(spec, spec, spec), out_specs=spec)

    out = jax.jit(fn)(q, k, v)
    ref = _attention_xla(q, k, v, None, causal, scale, 0.0, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g_ring = jax.jit(jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * ct),
                              argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(
            _attention_xla(q, k, v, None, causal, scale, 0.0, None) * ct),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("shape", [
    (2, 256, 4, 4, 32, 4),
    (1, 384, 4, 2, 32, 8),   # GQA + sub-chunks of 24 rows
])
def test_zigzag_ring_parity(shape):
    """Causal load-balanced ring (device d holds (c_d, c_{2N-1-d})):
    forward + all grads must match the dense oracle elementwise through
    the tape API, including the zigzag permutation round-trip."""
    B, S, Hq, Hk, D, N = shape
    rng = np.random.RandomState(17)
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32) * 0.3
    k = jnp.asarray(rng.standard_normal((B, S, Hk, D)), jnp.float32) * 0.3
    v = jnp.asarray(rng.standard_normal((B, S, Hk, D)), jnp.float32) * 0.3
    scale = 1.0 / math.sqrt(D)
    qt, kt, vt = (paddle.to_tensor(np.asarray(x), stop_gradient=False)
                  for x in (q, k, v))
    out = dist.ring_attention(qt, kt, vt, mesh=_mesh(N), causal=True,
                              layout="zigzag")
    ref = _attention_xla(q, k, v, None, True, scale, 0.0, None)
    np.testing.assert_allclose(np.asarray(out.numpy()), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    out.sum().backward()
    g_ref = jax.grad(lambda a, b, c: jnp.sum(_attention_xla(
        a, b, c, None, True, scale, 0.0, None)),
        argnums=(0, 1, 2))(q, k, v)
    for t, r, name in zip((qt, kt, vt), g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(t.grad.numpy()),
                                   np.asarray(r), rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name}")


def test_zigzag_rejects_noncausal_and_indivisible():
    q, k, v = _mk(1, 64, 4, 16, seed=3)
    with pytest.raises(ValueError, match="CAUSAL"):
        dist.ring_attention(q, k, v, mesh=_mesh(), causal=False,
                            layout="zigzag")
    with pytest.raises(ValueError, match="unknown ring layout"):
        dist.ring_attention(q, k, v, mesh=_mesh(), layout="nope")
    from paddle_tpu.distributed.long_context import _zigzag_perm
    with pytest.raises(ValueError, match="divisible"):
        _zigzag_perm(100, 8)
    # the permutation is a bijection with the documented shard layout
    p = _zigzag_perm(32, 4)
    assert sorted(p.tolist()) == list(range(32))
    assert p[:8].tolist() == [0, 1, 2, 3, 28, 29, 30, 31]  # (c_0, c_7)


def test_ring_chunked_single_parity():
    """Single-chip chunked-ring compute (the bench surface) matches the
    dense oracle fwd + grads, causal and full."""
    from paddle_tpu.distributed.long_context import ring_chunked_single
    rng = np.random.RandomState(9)
    B, S, H, D, C = 1, 256, 2, 32, 4
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32) * 0.3
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32) * 0.3
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32) * 0.3
    scale = 1.0 / math.sqrt(D)
    ct = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    for causal in (True, False):
        out = jax.jit(lambda a, b, c: ring_chunked_single(
            a, b, c, C, causal, scale, True))(q, k, v)
        ref = _attention_xla(q, k, v, None, causal, scale, 0.0, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        g1 = jax.grad(lambda a, b, c: jnp.sum(ring_chunked_single(
            a, b, c, C, causal, scale, True) * ct),
            argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda a, b, c: jnp.sum(_attention_xla(
            a, b, c, None, causal, scale, 0.0, None) * ct),
            argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-4,
                                       err_msg=f"d{name} causal={causal}")


def test_sep_attention_strategy_selection():
    """fleet sep-axis API (VERDICT r4 #5): ring/ulysses/gather selectable
    via DistributedStrategy.sep_configs, all matching the local oracle."""
    import paddle_tpu.distributed.fleet as fleet
    from paddle_tpu.distributed.fleet.meta_parallel.segment_parallel import (
        sep_attention)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 4,
                               "order": ["dp", "pp", "sharding", "sep",
                                         "mp"]}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()
    assert hcg.get_sep_parallel_world_size() == 4

    q, k, v = _mk(1, 64, 4, 16, seed=8)
    scale = 1.0 / math.sqrt(16)
    ref = np.asarray(_attention_xla(q, k, v, None, True, scale, 0.0, None))
    for mode in ("ring", "ulysses", "gather"):
        strategy.sep_configs = {"attention": mode}
        out = sep_attention(q, k, v, hcg, strategy=strategy, causal=True)
        np.testing.assert_allclose(np.asarray(out.numpy()), ref,
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"mode {mode}")
    strategy.sep_configs = {"attention": "nope"}
    with pytest.raises(ValueError, match="unknown sep attention"):
        sep_attention(q, k, v, hcg, strategy=strategy)
    # ring_layout is validated up front too (typos must not silently run
    # the unbalanced contiguous ring)
    strategy.sep_configs = {"attention": "ring", "ring_layout": "zig-zag"}
    with pytest.raises(ValueError, match="unknown sep ring_layout"):
        sep_attention(q, k, v, hcg, strategy=strategy)
    # the zigzag layout routes through the balanced ring and still
    # matches the oracle
    strategy.sep_configs = {"attention": "ring", "ring_layout": "zigzag"}
    out = sep_attention(q, k, v, hcg, strategy=strategy, causal=True)
    np.testing.assert_allclose(np.asarray(out.numpy()), ref,
                               rtol=2e-5, atol=2e-5)


def test_ring_through_tape():
    """Tensor-level API: gradients flow through the tape into q/k/v."""
    q, k, v = _mk(1, 32, 2, 8, seed=6)
    qt, kt, vt = (paddle.to_tensor(x, stop_gradient=False)
                  for x in (q, k, v))
    out = dist.ring_attention(qt, kt, vt, mesh=_mesh(), causal=True)
    out.sum().backward()
    assert qt.grad is not None and kt.grad is not None and vt.grad is not None
    assert np.isfinite(np.asarray(qt.grad.numpy())).all()
