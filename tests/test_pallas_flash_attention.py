"""Pallas flash-attention kernel vs the XLA reference implementation
(interpret mode on CPU — SURVEY.md §4: kernels testable without hardware).

Mirrors the reference's flash-attn op tests
(test/legacy_test/test_flash_attention.py): forward parity with a plain
softmax-attention oracle and gradient parity, across causal, GQA,
cross-attention (Sq != Sk), and non-block-aligned sequence lengths.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import flags as _flags
from paddle_tpu.nn.functional.flash_attention import _attention_xla
from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas


def _mk(b, sq, sk, hq, hk, d, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.standard_normal((b, sq, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, sk, hk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, sk, hk, d)), dtype)
    return q, k, v


CASES = [
    # b, sq, sk, hq, hk, d, causal
    (2, 128, 128, 2, 2, 32, False),
    (2, 128, 128, 2, 2, 32, True),
    (1, 256, 256, 4, 1, 16, True),      # GQA + multi k-block
    (1, 192, 192, 2, 2, 32, True),      # non-aligned seq (padding)
    (1, 64, 256, 2, 2, 32, True),       # cross: Sq < Sk, offset diagonal
    (1, 128, 96, 2, 2, 16, False),      # Sk not aligned
]


@pytest.mark.parametrize("b,sq,sk,hq,hk,d,causal", CASES)
def test_forward_matches_xla(b, sq, sk, hq, hk, d, causal):
    q, k, v = _mk(b, sq, sk, hq, hk, d)
    scale = 1.0 / math.sqrt(d)
    ref = _attention_xla(q, k, v, None, causal, scale, 0.0, None)
    out = flash_attention_pallas(q, k, v, causal, scale, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,sq,sk,hq,hk,d,causal", [
    (1, 128, 128, 2, 2, 32, True),
    (1, 256, 256, 2, 1, 16, True),      # GQA grad: dk/dv head-group sum
    (1, 192, 192, 2, 2, 32, False),     # padding in bwd
    (1, 64, 128, 2, 2, 16, True),       # offset diagonal bwd
])
def test_grad_matches_xla(b, sq, sk, hq, hk, d, causal):
    q, k, v = _mk(b, sq, sk, hq, hk, d, seed=1)
    scale = 1.0 / math.sqrt(d)
    rng = np.random.RandomState(2)
    ct = jnp.asarray(rng.standard_normal((b, sq, hq, d)), jnp.float32)

    def loss_ref(q, k, v):
        return jnp.sum(_attention_xla(q, k, v, None, causal, scale, 0.0,
                                      None) * ct)

    def loss_pl(q, k, v):
        return jnp.sum(flash_attention_pallas(q, k, v, causal, scale, True)
                       * ct)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gp = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gp, gr, "q k v".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"grad mismatch for {name}")


def test_bf16_forward_close():
    q, k, v = _mk(1, 128, 128, 2, 2, 32, dtype=jnp.bfloat16)
    scale = 1.0 / math.sqrt(32)
    ref = _attention_xla(q, k, v, None, True, scale, 0.0, None)
    out = flash_attention_pallas(q, k, v, True, scale, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_bf16_grads_close():
    """bf16 inputs now ride the MXU natively (storage-dtype dots with f32
    accumulation); gradients must stay within bf16-class tolerance of the
    f32 XLA oracle."""
    rng = np.random.default_rng(7)
    q, k, v = _mk(1, 128, 128, 2, 2, 32, dtype=jnp.bfloat16)
    scale = 1.0 / math.sqrt(32)
    ct = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))

    def loss_ref(q, k, v):
        return jnp.sum(_attention_xla(q, k, v, None, True, scale, 0.0,
                                      None).astype(jnp.float32) * ct)

    def loss_pl(q, k, v):
        return jnp.sum(flash_attention_pallas(q, k, v, True, scale, True)
                       .astype(jnp.float32) * ct)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(qf, kf, vf)
    gp = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gp, gr, "q k v".split()):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32),
                                   rtol=6e-2, atol=6e-2,
                                   err_msg=f"bf16 grad mismatch for {name}")


def test_dispatch_uses_pallas_under_flag():
    """F.scaled_dot_product_attention routes to the Pallas kernel when the
    interpret flag is forced (CPU), and output still matches the oracle."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    q, k, v = _mk(1, 128, 128, 2, 2, 32)
    scale = 1.0 / math.sqrt(32)
    ref = _attention_xla(q, k, v, None, True, scale, 0.0, None)
    _flags.set_flags({"pallas_force_interpret": True})
    try:
        out = F.scaled_dot_product_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            is_causal=True)
    finally:
        _flags.set_flags({"pallas_force_interpret": False})
    np.testing.assert_allclose(np.asarray(out.numpy()), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# in-kernel dropout + additive bias (reference contract ops.yaml:978-989:
# dropout with deterministic (seed, offset)-style replay; attn_mask bias)
# ---------------------------------------------------------------------------
from paddle_tpu.ops.pallas.flash_attention import (  # noqa: E402
    dropout_keep_mask, flash_attention_ext, seed_from_key)

_SEED0 = jnp.zeros((1,), jnp.int32)


def _dense_oracle(q, k, v, scale, bias=None, keep=None, rate=0.0,
                  causal=True):
    hq, hk = q.shape[2], k.shape[2]
    if hq != hk:
        k = jnp.repeat(k, hq // hk, axis=2)
        v = jnp.repeat(v, hq // hk, axis=2)
    sq, sk = q.shape[1], k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        m = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(m, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if keep is not None:
        p = jnp.where(keep, p / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


@pytest.mark.parametrize("bshape", [
    (2, 4, 256, 256),   # full
    (1, 4, 256, 256),   # broadcast batch
    (2, 1, 1, 256),     # broadcast head + query (additive key mask)
    (256, 256),         # 2-D mask
])
def test_bias_in_kernel(bshape):
    q, k, v = _mk(2, 256, 256, 4, 2, 64, seed=3)
    scale = 1.0 / math.sqrt(64)
    rng = np.random.RandomState(4)
    bias = jnp.asarray(rng.standard_normal(bshape), jnp.float32) * 0.5
    out = flash_attention_ext(q, k, v, bias, _SEED0, None, None, True,
                              scale, 0.0, 128, 128, True)
    ref = _dense_oracle(q, k, v, scale, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    # grads incl. dbias reduced onto the broadcast shape
    g = jax.grad(lambda q, b: flash_attention_ext(
        q, k, v, b, _SEED0, None, None, True, scale, 0.0, 128, 128,
        True).sum(), (0, 1))(q, bias)
    ge = jax.grad(lambda q, b: _dense_oracle(
        q, k, v, scale, bias=b).sum(), (0, 1))(q, bias)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(ge[0]),
                               rtol=3e-4, atol=3e-4)
    assert g[1].shape == bias.shape
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(ge[1]),
                               rtol=3e-4, atol=3e-4)


def test_dropout_exact_mask_replay():
    """The kernel's dropout is a pure function of (seed, position):
    dropout_keep_mask reproduces it exactly, so a dense oracle using that
    mask must match the kernel bit-for-bit in fwd AND bwd (the mask is
    regenerated, not stored, by the backward kernels)."""
    b, s, hq, hk, d = 2, 256, 4, 2, 64
    q, k, v = _mk(b, s, s, hq, hk, d, seed=5)
    scale = 1.0 / math.sqrt(d)
    rate = 0.1
    seed = seed_from_key(jax.random.key(42))
    keep = dropout_keep_mask(seed, b * hq, s, s, rate).reshape(b, hq, s, s)
    # drop fraction matches the rate
    assert abs(float(keep.mean()) - (1.0 - rate)) < 0.01

    out = flash_attention_ext(q, k, v, None, seed, None, None, True,
                              scale, rate, 128, 128, True)
    ref = _dense_oracle(q, k, v, scale, keep=keep, rate=rate)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    g = jax.grad(lambda q, k, v: flash_attention_ext(
        q, k, v, None, seed, None, None, True, scale, rate, 128, 128,
        True).sum(), (0, 1, 2))(q, k, v)
    ge = jax.grad(lambda q, k, v: _dense_oracle(
        q, k, v, scale, keep=keep, rate=rate).sum(), (0, 1, 2))(q, k, v)
    for a, e in zip(g, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=3e-4, atol=3e-4)


def test_dropout_matches_xla_fallback():
    """The XLA fallback shares dropout_keep_mask, so for the same key the
    two impls produce identical outputs — dropout no longer forces a
    strategy change in numerics."""
    q, k, v = _mk(1, 128, 128, 2, 2, 32, seed=6)
    scale = 1.0 / math.sqrt(32)
    key = jax.random.key(7)
    ref = _attention_xla(q, k, v, None, True, scale, 0.1, key)
    out = flash_attention_ext(q, k, v, None, seed_from_key(key), None,
                              None, True, scale, 0.1, 128, 128, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_dropout_bias_jit_and_seed_sensitivity():
    q, k, v = _mk(1, 128, 128, 2, 2, 32, seed=8)
    scale = 1.0 / math.sqrt(32)
    rng = np.random.RandomState(9)
    bias = jnp.asarray(rng.standard_normal((1, 2, 128, 128)),
                       jnp.float32) * 0.5
    f = jax.jit(lambda q, k, v, b, s: flash_attention_ext(
        q, k, v, b, s, None, None, False, scale, 0.2, 128, 128, True))
    s1 = seed_from_key(jax.random.key(1))
    s2 = seed_from_key(jax.random.key(2))
    o1, o1b, o2 = f(q, k, v, bias, s1), f(q, k, v, bias, s1), \
        f(q, k, v, bias, s2)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o1b))
    assert not np.allclose(np.asarray(o1), np.asarray(o2))


def test_dispatch_dropout_keeps_pallas_path():
    """VERDICT r2 #3: dropout_p > 0 must no longer fall back to the XLA
    path — the registry impl routes it into the Pallas kernel."""
    from paddle_tpu.ops.pallas.flash_attention import _attention_pallas
    import paddle_tpu.ops.pallas.flash_attention as fa_mod
    q, k, v = _mk(1, 128, 128, 2, 2, 32, seed=10)
    called = {}
    orig = fa_mod.flash_attention_ext

    def spy(*args, **kw):
        called["ext"] = True
        return orig(*args, **kw)
    fa_mod.flash_attention_ext = spy
    _flags.set_flags({"pallas_force_interpret": True})
    try:
        _attention_pallas(q, k, v, None, True, 1.0 / math.sqrt(32), 0.1,
                          jax.random.key(3))
    finally:
        _flags.set_flags({"pallas_force_interpret": False})
        fa_mod.flash_attention_ext = orig
    assert called.get("ext"), "dropout call fell back off the Pallas path"


class TestVarlenSegments:
    """In-kernel segment-id masking (the TPU form of the reference's
    cu_seqlens varlen contract, flash_attn_kernel.cu:199): packed ragged
    sequences must attend only within themselves, fwd and bwd."""

    LENS = [5, 9, 2]

    def _packed(self, d=64, h=2, seed=11):
        rng = np.random.RandomState(seed)
        total = sum(self.LENS)
        q = jnp.asarray(rng.standard_normal((1, total, h, d)),
                        jnp.float32) * 0.3
        k = jnp.asarray(rng.standard_normal((1, total, h, d)),
                        jnp.float32) * 0.3
        v = jnp.asarray(rng.standard_normal((1, total, h, d)),
                        jnp.float32) * 0.3
        cu = np.concatenate([[0], np.cumsum(self.LENS)]).astype(np.int32)
        seg = np.repeat(np.arange(len(self.LENS), dtype=np.int32),
                        self.LENS)[None, :]
        return q, k, v, cu, jnp.asarray(seg)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_per_sequence_dense(self, causal):
        d = 64
        q, k, v, cu, seg = self._packed(d)
        scale = 1.0 / math.sqrt(d)
        out = flash_attention_ext(q, k, v, None, _SEED0, seg, seg, causal,
                                  scale, 0.0, 128, 128, True)
        for i in range(len(self.LENS)):
            lo, hi = int(cu[i]), int(cu[i + 1])
            ref = _dense_oracle(q[:, lo:hi], k[:, lo:hi], v[:, lo:hi],
                                scale, causal=causal)
            np.testing.assert_allclose(np.asarray(out[:, lo:hi]),
                                       np.asarray(ref), rtol=3e-5,
                                       atol=3e-5)

    @pytest.mark.parametrize("hq,hk", [(2, 2), (4, 2)])
    def test_grads_match_per_sequence(self, hq, hk):
        """Varlen backward, MHA and GQA (the GQA-native dkv path routes
        segment words through qrow-indexed specs — hq != hk covers it)."""
        d = 64
        q, k, v, cu, seg = self._packed(d, h=hq)
        k, v = k[:, :, :hk], v[:, :, :hk]
        rep = hq // hk
        scale = 1.0 / math.sqrt(d)
        g = jax.grad(lambda q, k, v: flash_attention_ext(
            q, k, v, None, _SEED0, seg, seg, True, scale, 0.0, 128, 128,
            True).sum(), (0, 1, 2))(q, k, v)
        for i in range(len(self.LENS)):
            lo, hi = int(cu[i]), int(cu[i + 1])
            kx = jnp.repeat(k[:, lo:hi], rep, axis=2)
            vx = jnp.repeat(v[:, lo:hi], rep, axis=2)
            ge = jax.grad(lambda q, kx, vx: _dense_oracle(
                q, kx, vx, scale, causal=True).sum(), (0, 1, 2))(
                q[:, lo:hi], kx, vx)
            L = hi - lo
            dk_ref = np.asarray(ge[1]).reshape(1, L, hk, rep, d).sum(3)
            dv_ref = np.asarray(ge[2]).reshape(1, L, hk, rep, d).sum(3)
            np.testing.assert_allclose(np.asarray(g[0][:, lo:hi]),
                                       np.asarray(ge[0]), rtol=3e-4,
                                       atol=3e-4)
            np.testing.assert_allclose(np.asarray(g[1][:, lo:hi]), dk_ref,
                                       rtol=3e-4, atol=3e-4)
            np.testing.assert_allclose(np.asarray(g[2][:, lo:hi]), dv_ref,
                                       rtol=3e-4, atol=3e-4)

    def test_flash_attn_unpadded_api(self):
        """The packed public API: [total, H, D] + cu_seqlens."""
        import paddle_tpu as paddle
        from paddle_tpu.nn.functional.flash_attention import \
            flash_attn_unpadded

        d = 64
        q, k, v, cu, seg = self._packed(d)
        scale = 1.0 / math.sqrt(d)
        _flags.set_flags({"pallas_force_interpret": True})
        try:
            out, _ = flash_attn_unpadded(
                paddle.to_tensor(np.asarray(q[0])),
                paddle.to_tensor(np.asarray(k[0])),
                paddle.to_tensor(np.asarray(v[0])),
                paddle.to_tensor(cu), paddle.to_tensor(cu),
                max(self.LENS), max(self.LENS), scale, causal=True)
        finally:
            _flags.set_flags({"pallas_force_interpret": False})
        out = np.asarray(out.numpy())
        for i in range(len(self.LENS)):
            lo, hi = int(cu[i]), int(cu[i + 1])
            ref = _dense_oracle(q[:, lo:hi], k[:, lo:hi], v[:, lo:hi],
                                scale, causal=True)
            np.testing.assert_allclose(out[lo:hi], np.asarray(ref)[0],
                                       rtol=3e-5, atol=3e-5)


def test_varlen_causal_ragged_qk_lengths():
    """Per-segment causal with DIFFERENT q/k lengths per segment (the
    reference's cross-attention varlen case): each segment must use its
    own (Lk - Lq)-offset diagonal, not one global diagonal."""
    # per-segment (Lk - Lq) offsets 2 and 0; the single global diagonal
    # would use offset (8-6)=2 for BOTH segments — visibly wrong for the
    # second one. Lk >= Lq keeps every q row non-empty (rows with no
    # visible key are a separate zero-output contract).
    lens_q = [2, 4]
    lens_k = [4, 4]
    d, h = 64, 2
    rng = np.random.RandomState(13)
    tq, tk = sum(lens_q), sum(lens_k)
    q = jnp.asarray(rng.standard_normal((1, tq, h, d)), jnp.float32) * 0.3
    k = jnp.asarray(rng.standard_normal((1, tk, h, d)), jnp.float32) * 0.3
    v = jnp.asarray(rng.standard_normal((1, tk, h, d)), jnp.float32) * 0.3
    seg_q = jnp.asarray(np.repeat(np.arange(2, dtype=np.int32),
                                  lens_q)[None, :])
    seg_k = jnp.asarray(np.repeat(np.arange(2, dtype=np.int32),
                                  lens_k)[None, :])
    scale = 1.0 / math.sqrt(d)
    out = flash_attention_ext(q, k, v, None, _SEED0, seg_q, seg_k, True,
                              scale, 0.0, 128, 128, True)
    cu_q = np.concatenate([[0], np.cumsum(lens_q)])
    cu_k = np.concatenate([[0], np.cumsum(lens_k)])
    for i in range(2):
        qs, qe = int(cu_q[i]), int(cu_q[i + 1])
        ks, ke = int(cu_k[i]), int(cu_k[i + 1])
        ref = _dense_oracle(q[:, qs:qe], k[:, ks:ke], v[:, ks:ke], scale,
                            causal=True)  # oracle uses the offset diagonal
        np.testing.assert_allclose(np.asarray(out[:, qs:qe]),
                                   np.asarray(ref), rtol=3e-5, atol=3e-5)

    # grads too
    g = jax.grad(lambda q, k, v: flash_attention_ext(
        q, k, v, None, _SEED0, seg_q, seg_k, True, scale, 0.0, 128, 128,
        True).sum(), (0, 1, 2))(q, k, v)
    for i in range(2):
        qs, qe = int(cu_q[i]), int(cu_q[i + 1])
        ks, ke = int(cu_k[i]), int(cu_k[i + 1])
        ge = jax.grad(lambda q_, k_, v_: _dense_oracle(
            q_, k_, v_, scale, causal=True).sum(), (0, 1, 2))(
            q[:, qs:qe], k[:, ks:ke], v[:, ks:ke])
        np.testing.assert_allclose(np.asarray(g[0][:, qs:qe]),
                                   np.asarray(ge[0]), rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(g[1][:, ks:ke]),
                                   np.asarray(ge[1]), rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(g[2][:, ks:ke]),
                                   np.asarray(ge[2]), rtol=3e-4, atol=3e-4)


def test_flash_attn_unpadded_xla_fallback_no_nan():
    """The CPU/XLA fallback must zero dead q rows (no visible key) instead
    of emitting NaN, and must apply per-segment causal."""
    import paddle_tpu as paddle
    from paddle_tpu.nn.functional.flash_attention import flash_attn_unpadded

    rng = np.random.RandomState(14)
    # 6 packed q tokens but only 4 covered by cu: the tail 2 are don't-cares
    q = rng.standard_normal((6, 2, 32)).astype(np.float32)
    k = rng.standard_normal((4, 2, 32)).astype(np.float32)
    v = rng.standard_normal((4, 2, 32)).astype(np.float32)
    cu_q = np.array([0, 2, 4], np.int32)
    cu_k = np.array([0, 2, 4], np.int32)
    out, _ = flash_attn_unpadded(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        paddle.to_tensor(cu_q), paddle.to_tensor(cu_k), 2, 2,
        1.0 / math.sqrt(32), causal=True)
    out = np.asarray(out.numpy())
    assert np.isfinite(out[:4]).all()
    np.testing.assert_array_equal(out[4:], 0.0)   # dead rows zeroed


# ---------------------------------------------------------------------------
# the tile plan: blocks chosen from the shape (ISSUE 26). The parity cases
# run at lengths where the plan leaves 128 x 128, under the plan's tiles
# (block arguments None) and under explicit 128 x 128, against the XLA twin.
# ---------------------------------------------------------------------------
from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402

_TILINGS = [pytest.param(None, id="plan"), pytest.param(128, id="b128")]


def _segment_bias(seg_q, seg_k):
    """Additive form of segment ids for the XLA twin."""
    same = seg_q[0][:, None] == seg_k[0][None, :]
    return jnp.where(same, 0.0, -jnp.inf)[None, None]


# name: (b, sq, sk, hq, hk, d, dtype, extra) -- extra in {None, "bias_full",
# "bias_bcast", "segments"}
_PLAN_CASES = {
    "mha_d64_s1024_bf16": (1, 1024, 1024, 2, 2, 64, jnp.bfloat16, None),
    "mha_d64_s1024_f32": (1, 1024, 1024, 2, 2, 64, jnp.float32, None),
    "gqa_4_2_d128_s1024": (1, 1024, 1024, 4, 2, 128, jnp.float32, None),
    "sq512_sk1024_offset": (1, 512, 1024, 2, 2, 64, jnp.float32, None),
    "s1100_padding": (1, 1100, 1100, 2, 2, 64, jnp.float32, None),
    "bias_full_s512": (1, 512, 512, 2, 2, 64, jnp.float32, "bias_full"),
    "bias_bcast_s512": (2, 512, 512, 2, 2, 64, jnp.float32, "bias_bcast"),
    "segments_s512": (1, 512, 512, 2, 2, 64, jnp.float32, "segments"),
}


@pytest.mark.parametrize("block", _TILINGS)
@pytest.mark.parametrize("case", list(_PLAN_CASES))
def test_plan_tiles_match_xla(case, block):
    b, sq, sk, hq, hk, d, dtype, extra = _PLAN_CASES[case]
    q, k, v = _mk(b, sq, sk, hq, hk, d, dtype=dtype, seed=21)
    scale = 1.0 / math.sqrt(d)
    rng = np.random.RandomState(22)
    ct = jnp.asarray(rng.standard_normal((b, sq, hq, d)), jnp.float32)
    bias = seg = twin_bias = None
    if extra == "bias_full":
        bias = jnp.asarray(rng.standard_normal((b, hq, sq, sk)),
                           jnp.float32) * 0.5
    elif extra == "bias_bcast":
        bias = jnp.asarray(rng.standard_normal((1, 1, 1, sk)),
                           jnp.float32) * 0.5
    elif extra == "segments":
        seg = jnp.asarray(np.repeat(np.arange(4, dtype=np.int32),
                                    [100, 200, 150, 62])[None, :])
        twin_bias = _segment_bias(seg, seg)
    diff = (q, k, v) if bias is None else (q, k, v, bias)

    def loss_pl(q, k, v, bias=None):
        out = flash_attention_ext(q, k, v, bias, _SEED0, seg, seg, True,
                                  scale, 0.0, block, block, True)
        return jnp.sum(out.astype(jnp.float32) * ct), out

    def loss_ref(q, k, v, bias=None):
        out = _attention_xla(q, k, v, twin_bias if bias is None else bias,
                             True, scale, 0.0, None)
        return jnp.sum(out.astype(jnp.float32) * ct), out

    argnums = tuple(range(len(diff)))
    (_, out), gp = jax.value_and_grad(loss_pl, argnums, has_aux=True)(*diff)
    (_, ref), gr = jax.value_and_grad(loss_ref, argnums, has_aux=True)(*diff)
    fwd_tol, bwd_tol = (2e-2, 6e-2) if dtype == jnp.bfloat16 \
        else (3e-5, 3e-4)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=fwd_tol, atol=fwd_tol)
    for a, b_, name in zip(gp, gr, ("q", "k", "v", "bias")):
        assert a.shape == b_.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32),
                                   rtol=bwd_tol, atol=bwd_tol,
                                   err_msg=f"{case}: grad of {name}")


def test_dropout_same_mask_under_two_tilings():
    """The keep-mask is a function of the global element index, so the
    plan's tiles and 128 x 128 drop the same positions: outputs and
    gradients agree to float32 rounding, and both agree with the dense
    oracle under ``dropout_keep_mask``."""
    b, s, h, d, rate = 1, 512, 2, 64, 0.25
    q, k, v = _mk(b, s, s, h, h, d, seed=23)
    scale = 1.0 / math.sqrt(d)
    seed = jnp.asarray([1234], jnp.int32)

    def run(block):
        return jax.value_and_grad(lambda q, k, v: flash_attention_ext(
            q, k, v, None, seed, None, None, True, scale, rate, block,
            block, True).sum(), (0, 1, 2))(q, k, v)

    assert fa._blocks(None, None, q, k, v, None, False, rate,
                      True).fwd[:2] != (128, 128)
    (lp, gp), (l128, g128) = run(None), run(128)
    np.testing.assert_allclose(float(lp), float(l128), rtol=1e-5)
    for a, b_ in zip(gp, g128):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)
    keep = dropout_keep_mask(seed, b * h, s, s, rate).reshape(b, h, s, s)
    ref = _dense_oracle(q, k, v, scale, keep=keep, rate=rate)
    out = flash_attention_ext(q, k, v, None, seed, None, None, True, scale,
                              rate, None, None, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


# (sq, sk, d, q/k bytes, v bytes, bias bytes, dbias, segments, dropout)
_PLAN_TABLE = [
    (1024, 1024, 64, 2, 2, 0, False, False, False),    # the GPT-2 cell
    (4096, 4096, 128, 4, 2, 0, False, False, False),   # the Mistral cell
    (2048, 2048, 128, 2, 2, 0, False, False, False),
    (4096, 4096, 256, 4, 4, 0, False, False, False),   # widest head, float32
    (512, 1024, 64, 4, 4, 0, False, False, False),     # ring chunk, Sq != Sk
    (1100, 1100, 64, 4, 4, 0, False, False, False),    # padding
    (1, 2048, 128, 2, 2, 0, False, False, False),      # decode: Sq = 1
    (100, 100, 64, 4, 4, 0, False, False, False),      # short: whole length
    (192, 192, 32, 4, 4, 0, False, False, False),
    (1024, 1024, 64, 2, 2, 4, True, False, False),     # full bias + dbias
    (1024, 1024, 128, 4, 4, 4, True, True, True),      # everything at once
    (2048, 2048, 64, 2, 2, 0, False, True, False),     # segments
    (1024, 1024, 64, 2, 2, 0, False, False, True),     # dropout
]


@pytest.mark.parametrize("row", _PLAN_TABLE, ids=lambda r: "x".join(
    str(int(x)) for x in r))
def test_tile_plan_is_legal(row):
    sq, sk, d, qb, vb, bias_b, dbias, segs, drop = row
    plan = fa.tile_plan(sq, sk, d, qb, qb, vb, bias_bytes=bias_b,
                        dbias=dbias, segments=segs, dropout=drop)
    assert plan == fa.tile_plan(sq, sk, d, qb, qb, vb, bias_bytes=bias_b,
                                dbias=dbias, segments=segs, dropout=drop)
    for kernel, (bq, bk, sub_q, sub_k, kinds) in zip(("fwd", "dq", "dkv"),
                                                     plan):
        # a sub-tile divides its tile; its key side is whole lanes, its
        # query side whole sublanes, unless the side is taken whole
        assert bq % sub_q == 0 and bk % sub_k == 0
        assert sub_k == bk or sub_k % 128 == 0
        assert sub_q == bq or sub_q % 8 == 0
        if sq < 8:
            assert (sub_q, sub_k) == (bq, bk)    # decode: one piece
        # a body for each kind of tile the grid meets: few, and hashable
        # (the Tile is a static argument of the jitted call)
        assert 1 <= len(kinds) <= fa._MAX_WALKS + 1 and hash(kinds)
        for s, blk in ((sq, bq), (sk, bk)):
            if s <= 128:
                assert blk == s          # min(block, S): the whole length
            else:
                # Mosaic: a block's sublane dim a multiple of 8 (16 for
                # bf16), its lane dim (bk of a bias or segment block) a
                # multiple of 128. It divides the length padded to 128, so
                # it pads no more than 128 x 128 did
                assert blk % 128 == 0 and blk <= fa._MAX_BLOCK
                assert (-(-s // 128) * 128) % blk == 0
        assert fa._vmem_bytes(kernel, bq, bk, d, qb, qb, vb, bias_b,
                              dbias and kernel == "dq", segs, drop) \
            <= fa._VMEM_BUDGET
    # the backward's two kernels share operands padded once: both tiles
    # divide the padded lengths
    for s, a, b_ in ((sq, plan.dq.bq, plan.dkv.bq),
                     (sk, plan.dq.bk, plan.dkv.bk)):
        if s > 128:
            assert (-(-s // 128) * 128) % math.lcm(a, b_) == 0


def test_tile_plan_of_the_benchmark_cells():
    """The tiles and sub-tiles PERF.md ("PR 26" and "PR 31", the sweeps on
    the chip) records as the fastest measured at the cells' attention
    shapes."""
    def sides(plan):
        return [t[:4] for t in plan]

    def want(fwd, dq, dkv):
        return [(1024, 1024, sub, sub) for sub in (fwd, dq, dkv)]
    # gpt2s-train-s1024: s1024, head 64, bf16: one tile a head, on the
    # diagonal
    gpt2 = fa.tile_plan(1024, 1024, 64, 2, 2, 2)
    assert sides(gpt2) == want(128, 128, 256)
    assert {t.kinds for t in gpt2} == {((0, None),)}
    # mistral7b-l2-train-s4096: s4096, head 128, bf16 (q and k float32
    # until PR 29); laguna-xs2-train-s8192's full layers: tiles on the
    # diagonal, walked, and tiles under it, one piece each
    for plan in (fa.tile_plan(4096, 4096, 128, 2, 2, 2),
                 fa.tile_plan(4096, 4096, 128, 4, 4, 2),
                 fa.tile_plan(8192, 8192, 128, 2, 2, 2)):
        assert sides(plan) == want(512, 128, 256)
        assert {t.kinds for t in plan} == {((0, None), (None, None))}
    # what the plan returns is what is lowered: a call that is neither
    # causal nor padded has nothing to walk, a padded one has
    plain = fa.tile_plan(4096, 4096, 128, 2, 2, 2, causal=False)
    assert [t for t in plain] == [(1024, 1024, 1024, 1024, ((None, None),))
                                  ] * 3
    padded = fa.tile_plan(1000, 1000, 64, 2, 2, 2, causal=False)
    assert sides(padded) == want(128, 128, 256)
    assert {t.kinds for t in padded} == {((None, 1000),)}


def test_tile_plan_shrinks_under_a_tight_budget():
    roomy = fa.tile_plan(4096, 4096, 128, 4, 4, 2)
    tight = fa.tile_plan(4096, 4096, 128, 4, 4, 2, vmem_budget=2 << 20)
    for (bq, bk, *_), (tq, tk, *_) in zip(roomy, tight):
        assert tq * tk < bq * bk
    least = (128, 128, 128, 128)
    assert [t[:4] for t in fa.tile_plan(
        4096, 4096, 128, 4, 4, 2, vmem_budget=1)] == [least] * 3


def test_tile_plan_event_and_tally_once_per_lowering():
    """One ``flash::tile_plan`` event and one tally count for each lowered
    pallas_call: trace time only, nothing when the compiled step runs."""
    from paddle_tpu.profiler import tracing

    q, k, v = _mk(1, 512, 512, 2, 2, 64, seed=24)
    scale = 1.0 / math.sqrt(64)
    plan = fa._blocks(None, None, q, k, v, None, False, 0.0, True)
    step = jax.jit(jax.grad(lambda q, k, v: flash_attention_pallas(
        q, k, v, True, scale, True).sum(), (0, 1, 2)))
    tracing.reset_tracing()
    tracing.enable_tracing()
    before = dict(fa.TILE_PLAN_TALLY)
    try:
        jax.block_until_ready(step(q, k, v))
        events = [e for e in tracing.snapshot_events()
                  if e["name"] == "flash::tile_plan"]
        jax.block_until_ready(step(q, k, v))     # compiled: no new event
        again = [e for e in tracing.snapshot_events()
                 if e["name"] == "flash::tile_plan"]
    finally:
        tracing.disable_tracing()
        tracing.reset_tracing()
    want = {"flash_fwd": plan.fwd, "flash_bwd_dq": plan.dq,
            "flash_bwd_dkv": plan.dkv}
    assert len(again) == len(events) == 3
    for ev in events:
        a = ev["args"]
        bq, bk, sub_q, sub_k, _ = want.pop(a["kernel"])
        assert (a["bq"], a["bk"], a["sub_q"], a["sub_k"]) == \
            (bq, bk, sub_q, sub_k)
        every = 2 * (512 // sub_q) * (512 // sub_k)
        assert 0 < a["sub_tiles_masked"] <= a["sub_tiles"] <= every
        assert a["sub_tiles"] + a["sub_tiles_skipped"] == every
        nq, nk = 512 // bq, 512 // bk
        assert a["grid_steps"] == 2 * nq * nk
        assert a["skipped_steps"] == 2 * sum(
            1 for i in range(nq) for j in range(nk)
            if j * bk > i * bq + bq - 1)
        assert 0 < a["vmem_bytes"] <= fa._VMEM_BUDGET
        key = (a["kernel"], bq, bk, sub_q, sub_k)
        assert fa.TILE_PLAN_TALLY[key] == before.get(key, 0) + 1
    assert not want


def test_explicit_blocks_win_over_the_plan():
    q, k, v = _mk(1, 512, 512, 2, 2, 64, seed=25)
    scale = 1.0 / math.sqrt(64)

    def lowered(bq, bk):
        before = dict(fa.TILE_PLAN_TALLY)
        jax.jit(jax.grad(lambda q: flash_attention_pallas(
            q, k, v, True, scale, True, bq, bk).sum())).lower(q)
        return {key for key, n in fa.TILE_PLAN_TALLY.items()
                if n > before.get(key, 0)}

    def keys(bq, bk):
        return {(name, bq, bk) + fa._sub_tile(kernel, bq, bk, 64)
                for kernel, name in fa._KERNEL_NAMES.items()}
    assert lowered(256, 128) == keys(256, 128)
    assert lowered(4096, 4096) == keys(512, 512)        # min(block, S)
    with pytest.raises(ValueError, match="together"):
        flash_attention_pallas(q, k, v, True, scale, True, 256, None)


def test_tuned_blocks_cold_returns_the_plan():
    """The route function says ``kernel``, and the call the dispatch lowers
    carries the plan's tiles."""
    q, k, v = _mk(1, 512, 512, 2, 2, 64, seed=26)
    assert fa.attention_route(
        q, k, None, dropout_rate=0.0, has_key=False, causal=True,
        window=None, meshed=False, on_tpu=False,
        force_interpret=True) == ("kernel", "default")
    plan = fa._blocks(None, None, q, k, v, None, False, 0.0, True)
    assert plan == fa.tile_plan(512, 512, 64, 4, 4, 4)
    _flags.set_flags({"pallas_force_interpret": True})
    before = dict(fa.TILE_PLAN_TALLY)
    try:
        jax.jit(lambda q: fa._attention_pallas(
            q, k, v, None, True, 0.125, 0.0, None)).lower(q)
    finally:
        _flags.set_flags({"pallas_force_interpret": False})
    key = ("flash_fwd",) + plan.fwd[:4]
    assert fa.TILE_PLAN_TALLY[key] == before.get(key, 0) + 1


# ---------------------------------------------------------------------------
# sub-tiles (ISSUE 31): a grid step walks its (bq, bk) tile in (sub_q, sub_k)
# pieces, enters only those that hold a visible score and masks only those an
# edge crosses. Parity with plain masked attention, forward and every
# gradient, whatever the sub-tile (S = 1000 meets a tile with the diagonal, one
# with the padding, one with both and one with neither); the counts the
# ``flash::tile_plan`` event carries against a count made by brute force.
# ---------------------------------------------------------------------------

def _force_sub_tile(monkeypatch, sub):
    """Every kernel walks in ``sub`` (cut to its tile): the plan's own rule
    is what the chip's sweep found fastest, the kernels take any sub-tile."""
    monkeypatch.setattr(
        fa, "_sub_tile",
        lambda kernel, bq, bk, *_: (min(sub[0], bq), min(sub[1], bk)))


_SUBS = [pytest.param((512, 512), id="one_piece"),
         pytest.param((128, 128), id="128x128"),
         pytest.param((64, 256), id="64x256"),
         pytest.param((256, 128), id="256x128")]

# name: (b, sq, sk, hq, hk, d, causal, extra)
_WALK_CASES = {
    "causal": (1, 1024, 1024, 2, 2, 32, True, None),
    # a ring chunk against an earlier, longer stretch of keys: the diagonal
    # lies Sk - Sq to the right
    "causal_offset": (1, 512, 1024, 2, 2, 32, True, None),
    "gqa_4_1": (1, 1024, 1024, 4, 1, 32, True, None),
    "s1000_padded_keys": (1, 1000, 1000, 2, 2, 32, True, None),
    "dropout": (1, 1024, 1024, 2, 2, 32, True, "dropout"),
    "bias_dbias": (1, 1024, 1024, 2, 2, 32, True, "bias"),
    "segments": (1, 1000, 1000, 2, 2, 32, True, "segments"),
    "non_causal": (1, 1024, 1024, 2, 2, 32, False, None),
    "non_causal_padded": (1, 1000, 1000, 2, 2, 32, False, None),
}


@pytest.mark.parametrize("sub", _SUBS)
@pytest.mark.parametrize("case", list(_WALK_CASES))
def test_sub_tiles_match_plain_attention(case, sub, monkeypatch):
    b, sq, sk, hq, hk, d, causal, extra = _WALK_CASES[case]
    _force_sub_tile(monkeypatch, sub)
    q, k, v = _mk(b, sq, sk, hq, hk, d, seed=31)
    scale = 1.0 / math.sqrt(d)
    rng = np.random.RandomState(32)
    ct = jnp.asarray(rng.standard_normal((b, sq, hq, d)), jnp.float32)
    bias = seg = keep = None
    rate, seed = 0.0, _SEED0
    if extra == "bias":
        bias = jnp.asarray(rng.standard_normal((b, hq, sq, sk)),
                           jnp.float32) * 0.5
    elif extra == "segments":
        seg = jnp.asarray(np.repeat(np.arange(4, dtype=np.int32),
                                    [300, 212, 420, 68])[None, :])
    elif extra == "dropout":
        rate, seed = 0.2, jnp.asarray([77], jnp.int32)
        keep = dropout_keep_mask(seed, b * hq, sq, sk, rate).reshape(
            b, hq, sq, sk)
    diff = (q, k, v) if bias is None else (q, k, v, bias)

    def loss_pl(q, k, v, bias=None):
        out = flash_attention_ext(q, k, v, bias, seed, seg, seg, causal,
                                  scale, rate, 512, 512, True)
        return jnp.sum(out * ct), out

    def loss_ref(q, k, v, bias=None):
        if seg is not None:
            bias = _segment_bias(seg, seg)
        out = _dense_oracle(q, k, v, scale, bias=bias, keep=keep, rate=rate,
                            causal=causal)
        return jnp.sum(out * ct), out

    argnums = tuple(range(len(diff)))
    (_, out), gp = jax.jit(jax.value_and_grad(
        loss_pl, argnums, has_aux=True))(*diff)
    (_, ref), gr = jax.jit(jax.value_and_grad(
        loss_ref, argnums, has_aux=True))(*diff)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    for a, b_, name in zip(gp, gr, ("q", "k", "v", "bias")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-4, atol=3e-4,
                                   err_msg=f"{case}: grad of {name}")


@pytest.mark.parametrize("bq,bk,sq,sk,walked", [
    # block sides that differ, on a diagonal 72 to the right: six kinds of
    # tile an edge or the padding touches, more than a kernel holds walked
    # bodies for, so every tile is computed in one piece
    (256, 384, 1024, 1096, False),
    # four such kinds: the most that is walked
    (128, 384, 512, 512, True)], ids=["six_kinds", "four_kinds"])
def test_a_call_of_many_kinds_of_tile_is_not_walked(bq, bk, sq, sk, walked):
    d = 32
    plan = fa._blocks(bq, bk, *_mk(1, sq, sk, 2, 2, d, seed=35), None, False,
                      0.0, True)
    nq, nk = -(-sq // bq), -(-sk // bk)
    edges = dict(causal=True, offset=sk - sq, window=None, sk_real=sk)
    touched = fa._tile_kinds(bq, bk, nq, nk, **edges) - {(None, None)}
    assert (len(touched) <= fa._MAX_WALKS) == walked
    for kernel, tile in zip(("fwd", "dq", "dkv"), plan):
        assert (tile[2:4] != (bq, bk)) == walked
        if not walked:
            assert tile.kinds == ((fa._ANY, None), (None, None))
        # the event's counts follow what is lowered: at the tile's grain
        # where the tile is one piece
        assert fa._sub_tile_counts(kernel, *tile[:4], nq, nk, segments=False,
                                   **edges) == \
            _brute_force(nq * bq, nk * bk, *tile[2:4], **edges)
    q, k, v = _mk(1, sq, sk, 2, 2, d, seed=35)
    scale = 1.0 / math.sqrt(d)
    ct = jnp.asarray(np.random.RandomState(36).standard_normal(
        (1, sq, 2, d)), jnp.float32)

    def loss_pl(q, k, v):
        out = flash_attention_ext(q, k, v, None, _SEED0, None, None, True,
                                  scale, 0.0, bq, bk, True)
        return jnp.sum(out * ct), out

    def loss_ref(q, k, v):
        out = _dense_oracle(q, k, v, scale, causal=True)
        return jnp.sum(out * ct), out
    (_, out), gp = jax.jit(jax.value_and_grad(
        loss_pl, (0, 1, 2), has_aux=True))(q, k, v)
    (_, ref), gr = jax.jit(jax.value_and_grad(
        loss_ref, (0, 1, 2), has_aux=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    for a, b_, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=3e-4,
                                   atol=3e-4, err_msg=f"grad of {name}")


def _brute_force(sq, sk, sub_q, sub_k, *, causal, offset, window, sk_real):
    """(sub-tiles that hold a visible score, those of them that also hold a
    hidden one) of the (sq, sk) score matrix cut into (sub_q, sub_k)."""
    r, c = np.arange(sq)[:, None], np.arange(sk)[None, :]
    vis = np.broadcast_to(c < sk_real, (sq, sk)).copy()
    if causal:
        vis &= c <= r + offset
    if window is not None:
        vis &= c > r + offset - window
    t = vis.reshape(sq // sub_q, sub_q, sk // sub_k, sub_k)
    some, every = t.any(axis=(1, 3)), t.all(axis=(1, 3))
    return int(some.sum()), int((some & ~every).sum())


# name: (sq, sk padded, real sk, d, window, causal, the tiles or None for the
# plan's): the three cells' attention, Laguna's window layer, and shapes with
# an offset, padding and a window no sub-tile divides
_COUNT_CASES = {
    "gpt2s_cell": (1024, 1024, 1024, 64, None, True, None),
    "mistral_cell": (4096, 4096, 4096, 128, None, True, None),
    "laguna_full_layer": (8192, 8192, 8192, 128, None, True, None),
    "laguna_window_layer": (8192, 8192, 8192, 128, 512, True, None),
    "offset_chunk": (512, 1024, 1024, 64, None, True, (512, 512, 128, 256)),
    "padded_s1000": (1024, 1024, 1000, 64, None, True, (512, 512, 64, 128)),
    "window_200_padded": (1536, 1536, 1400, 64, 200, True,
                          (512, 512, 128, 128)),
    "non_causal_padded": (1024, 1024, 1000, 64, None, False,
                          (512, 512, 256, 128)),
}


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("case", list(_COUNT_CASES))
def test_sub_tile_counts_equal_brute_force(case, kernel):
    """The arithmetic that lays out the walks enters exactly the sub-tiles
    that hold a visible score and masks exactly those an edge crosses."""
    sq, sk, sk_real, d, window, causal, tile = _COUNT_CASES[case]
    if tile is None:
        tile = getattr(fa.tile_plan(sq, sk, d, window=window), kernel)
        assert tile[2:4] != tile[:2]       # the cells' tiles are walked
    bq, bk, sub_q, sub_k = tile[:4]
    edges = dict(causal=causal, offset=sk - sq, window=window,
                 sk_real=sk_real)
    got = fa._sub_tile_counts(kernel, bq, bk, sub_q, sub_k, sq // bq,
                              sk // bk, segments=False, **edges)
    assert got == _brute_force(sq, sk, sub_q, sub_k, **edges)
    entered, masked = got
    every = (sq // sub_q) * (sk // sub_k)
    assert 0 < masked <= entered <= every
    if causal:
        assert entered < every           # something is never entered
    # under segment ids every entered sub-tile keeps its mask
    assert fa._sub_tile_counts(kernel, bq, bk, sub_q, sub_k, sq // bq,
                               sk // bk, segments=True, **edges) \
        == (entered, entered)


def test_tile_plan_event_carries_the_sub_tile_counts(monkeypatch):
    """``sub_tiles``, ``sub_tiles_skipped`` and ``sub_tiles_masked`` of a
    lowered call, summed over its (batch, q head) rows, against the brute
    force; a body that computes its tile in one piece enters the tiles that
    run and masks those an edge or the padding touches."""
    from paddle_tpu.profiler import tracing

    b, s, hq, hk, d = 2, 1000, 4, 2, 32
    q, k, v = _mk(b, s, s, hq, hk, d, seed=33)

    def events(sub, causal=True):
        _force_sub_tile(monkeypatch, sub)
        tracing.reset_tracing()
        tracing.enable_tracing()
        try:
            jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention_ext(
                q, k, v, None, _SEED0, None, None, causal, 0.2, 0.0, 512,
                512, True).sum(), (0, 1, 2)))(q, k, v)
            return {e["args"]["kernel"]: e["args"]
                    for e in tracing.snapshot_events()
                    if e["name"] == "flash::tile_plan"}
        finally:
            tracing.disable_tracing()
            tracing.reset_tracing()

    edges = dict(causal=True, offset=0, window=None, sk_real=s)
    for sub in ((128, 256), (512, 512)):
        entered, masked = _brute_force(1024, 1024, *sub, **edges)
        got = events(sub)
        assert set(got) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
        for a in got.values():
            assert (a["sub_q"], a["sub_k"]) == sub
            assert a["sub_tiles"] == b * hq * entered
            assert a["sub_tiles_masked"] == b * hq * masked
            assert a["sub_tiles"] + a["sub_tiles_skipped"] == \
                b * hq * (1024 // sub[0]) * (1024 // sub[1])
    # neither causal nor padded: nothing to skip, no mask to save, so the
    # tile is computed in one piece whatever the plan's sub-tile
    q, k, v = _mk(b, 1024, 1024, hq, hk, d, seed=34)
    for a in events((128, 128), causal=False).values():
        assert (a["sub_q"], a["sub_k"]) == (a["bq"], a["bk"]) == (512, 512)
        assert (a["sub_tiles"], a["sub_tiles_masked"],
                a["sub_tiles_skipped"]) == (b * hq * 4, 0, 0)
