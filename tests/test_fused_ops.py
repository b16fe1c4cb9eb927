"""Fused kernels: Pallas cross-entropy, fused optimizer step, incubate
fused functional ops (reference test models: test/legacy_test/
test_softmax_with_cross_entropy_op.py, fused-op tests)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.incubate.nn import functional as IF
from paddle_tpu.ops.pallas.cross_entropy import softmax_xent_pallas


@pytest.fixture(autouse=True)
def _seed():
    paddle.seed(0)


class TestPallasCrossEntropy:
    def _ref(self, logits, labels):
        lse = jax.nn.logsumexp(jnp.asarray(logits, jnp.float32), axis=-1)
        picked = logits[np.arange(len(labels)), labels]
        return np.asarray(lse) - picked

    def test_forward_matches_reference(self):
        rng = np.random.RandomState(0)
        logits = rng.randn(13, 257).astype(np.float32)  # odd sizes: padding
        labels = rng.randint(0, 257, 13)
        out = softmax_xent_pallas(jnp.asarray(logits), jnp.asarray(labels),
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   self._ref(logits, labels), rtol=1e-5)

    def test_invalid_label_zero_loss(self):
        logits = jnp.asarray(np.random.RandomState(0).randn(3, 8),
                             jnp.float32)
        labels = jnp.asarray(np.array([2, -1, 5]))
        out = np.asarray(softmax_xent_pallas(logits, labels,
                                             interpret=True))
        assert out[1] == 0.0 and out[0] > 0 and out[2] > 0

    def test_gradient_matches_softmax_minus_onehot(self):
        rng = np.random.RandomState(1)
        logits = jnp.asarray(rng.randn(5, 33), jnp.float32)
        labels = jnp.asarray(rng.randint(0, 33, 5))

        g = jax.grad(lambda x: softmax_xent_pallas(
            x, labels, interpret=True).sum())(logits)
        p = jax.nn.softmax(logits, axis=-1)
        onehot = jax.nn.one_hot(labels, 33)
        np.testing.assert_allclose(np.asarray(g), np.asarray(p - onehot),
                                   rtol=1e-5, atol=1e-6)

    def test_cross_entropy_api_uses_core_and_matches_general(self):
        rng = np.random.RandomState(2)
        logits = paddle.to_tensor(rng.randn(4, 7, 50).astype(np.float32))
        labels_np = rng.randint(0, 50, (4, 7)).astype(np.int64)
        labels_np[0, 0] = -100  # ignore_index
        labels = paddle.to_tensor(labels_np)
        fast = F.cross_entropy(logits, labels)
        # general path: force by passing label_smoothing tiny? use weight=None
        # comparison against a hand-rolled reference instead
        mask = labels_np != -100
        lg = logits.numpy().reshape(-1, 50)
        lb = labels_np.reshape(-1)
        lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) + \
            lg.max(-1)
        per = np.where(lb != -100, lse - lg[np.arange(len(lb)),
                                            np.where(lb == -100, 0, lb)], 0)
        ref = per.sum() / mask.sum()
        np.testing.assert_allclose(float(fast), ref, rtol=1e-5)

    def test_ce_grad_through_tape(self):
        logits = paddle.to_tensor(
            np.random.RandomState(0).randn(6, 11).astype(np.float32))
        logits.stop_gradient = False
        labels = paddle.to_tensor(
            np.random.RandomState(1).randint(0, 11, 6).astype(np.int64))
        loss = F.cross_entropy(logits, labels)
        loss.backward()
        g = logits.grad.numpy()
        p = np.asarray(jax.nn.softmax(logits._data, axis=-1))
        onehot = np.eye(11)[labels.numpy()]
        np.testing.assert_allclose(g, (p - onehot) / 6, rtol=1e-4,
                                   atol=1e-6)


class TestFusedOptimizerStep:
    def _train(self, fused: bool, opt_cls, **kw):
        paddle.seed(0)
        paddle.set_flags({"use_fused_optimizer": fused})
        try:
            net = paddle.nn.Sequential(paddle.nn.Linear(8, 16),
                                       paddle.nn.ReLU(),
                                       paddle.nn.Linear(16, 4))
            opt = opt_cls(0.01, parameters=net.parameters(), **kw)
            rng = np.random.RandomState(0)
            x = paddle.to_tensor(rng.randn(4, 8).astype(np.float32))
            y = paddle.to_tensor(rng.randint(0, 4, 4).astype(np.int64))
            lf = paddle.nn.CrossEntropyLoss()
            for _ in range(5):
                loss = lf(net(x), y)
                loss.backward()
                opt.step()
                opt.clear_grad()
            return [p.numpy() for p in net.parameters()], float(loss)
        finally:
            paddle.set_flags({"use_fused_optimizer": True})

    @pytest.mark.parametrize("opt_cls,kw", [
        (paddle.optimizer.AdamW, {"weight_decay": 0.1}),
        (paddle.optimizer.Adam, {}),
        (paddle.optimizer.SGD, {}),
        (paddle.optimizer.Momentum, {"momentum": 0.9}),
    ])
    def test_fused_matches_loop(self, opt_cls, kw):
        fused_params, fused_loss = self._train(True, opt_cls, **kw)
        loop_params, loop_loss = self._train(False, opt_cls, **kw)
        assert fused_loss == pytest.approx(loop_loss, rel=1e-5)
        for a, b in zip(fused_params, loop_params):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_adamw_decay_param_fun_respected(self):
        paddle.seed(0)
        lin = paddle.nn.Linear(4, 4)
        opt = paddle.optimizer.AdamW(
            0.1, parameters=lin.parameters(), weight_decay=0.5,
            apply_decay_param_fun=lambda n: "w_0" in (n or ""))
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        (lin(x).sum()).backward()
        b0 = lin.bias.numpy().copy()
        opt.step()
        # bias excluded from decay: pure adam step, |delta| <= lr bound
        assert np.all(np.abs(lin.bias.numpy() - b0) < 0.11)


class TestIncubateFused:
    def test_fused_rope_matches_model_impl(self):
        from paddle_tpu.models.llama import _rope_tables, apply_rotary_pos_emb
        rng = np.random.RandomState(0)
        q = rng.randn(2, 8, 4, 16).astype(np.float32)
        k = rng.randn(2, 8, 2, 16).astype(np.float32)
        cos, sin = _rope_tables(8, 16, 10000.0)
        qr, kr = apply_rotary_pos_emb(jnp.asarray(q), jnp.asarray(k),
                                      cos, sin)
        q2, k2, _ = IF.fused_rotary_position_embedding(
            paddle.to_tensor(q), paddle.to_tensor(k), sin=sin, cos=cos,
            use_neox_rotary_style=False)
        np.testing.assert_allclose(q2.numpy(), np.asarray(qr), rtol=1e-5)
        np.testing.assert_allclose(k2.numpy(), np.asarray(kr), rtol=1e-5)

    def test_fused_rope_paddle_table_shapes(self):
        # paddle-parity [1, S, 1, D] full-width tables (interleaved dup)
        from paddle_tpu.models.llama import _rope_tables, apply_rotary_pos_emb
        rng = np.random.RandomState(0)
        q = rng.randn(1, 8, 2, 16).astype(np.float32)
        cos, sin = _rope_tables(8, 16, 10000.0)  # [S, D/2]
        full_cos = np.repeat(np.asarray(cos), 2, axis=-1)[None, :, None, :]
        full_sin = np.repeat(np.asarray(sin), 2, axis=-1)[None, :, None, :]
        ref, _ = apply_rotary_pos_emb(jnp.asarray(q), jnp.asarray(q),
                                      cos, sin)
        out, _, _ = IF.fused_rotary_position_embedding(
            paddle.to_tensor(q), sin=full_sin, cos=full_cos,
            use_neox_rotary_style=False)
        np.testing.assert_allclose(out.numpy(), np.asarray(ref), rtol=1e-5)

    @pytest.mark.parametrize("q_dtype,k_dtype", [
        ("bfloat16", "bfloat16"), ("float32", "float32"),
        ("bfloat16", "float32"), ("float32", "bfloat16")])
    def test_rope_returns_the_dtypes_it_was_given(self, q_dtype, k_dtype):
        """Rotated in float32 (the tables are), rounded once to what came
        in: bit for bit the float32 rotation cast to the input's dtype."""
        from paddle_tpu.models.llama import _rope_tables, apply_rotary_pos_emb
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(2, 8, 4, 16), q_dtype)
        k = jnp.asarray(rng.randn(2, 8, 2, 16), k_dtype)
        cos, sin = _rope_tables(8, 16, 10000.0)
        qr, kr = apply_rotary_pos_emb(q, k, cos, sin)
        assert (qr.dtype, kr.dtype) == (q.dtype, k.dtype)
        q32, k32 = apply_rotary_pos_emb(q.astype(jnp.float32),
                                        k.astype(jnp.float32), cos, sin)
        assert (q32.dtype, k32.dtype) == (jnp.float32, jnp.float32)
        np.testing.assert_array_equal(np.asarray(qr),
                                      np.asarray(q32.astype(q.dtype)))
        np.testing.assert_array_equal(np.asarray(kr),
                                      np.asarray(k32.astype(k.dtype)))

    def test_laguna_rotation_is_bitwise_what_it_was(self):
        """``laguna.py::_rope_partial`` hands float32 slices in and casts
        the result itself, so the rule changes nothing there: its q and k
        equal the rotation written out in float32 and rounded once."""
        from paddle_tpu.models.laguna import _rope_partial, laguna_rope_tables
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(2, 8, 6, 16), jnp.bfloat16)
        k = jnp.asarray(rng.randn(2, 8, 2, 16), jnp.bfloat16)
        cos, sin = laguna_rope_tables(
            8, 16, {"rope_theta": 10000.0, "partial_rotary_factor": 0.5})
        qr, kr = _rope_partial(q, k, cos, sin)
        rot = 2 * cos.shape[-1]
        assert rot == 8 and (qr.dtype, kr.dtype) == (q.dtype, k.dtype)

        def plain(x):
            x32 = np.asarray(x[..., :rot].astype(jnp.float32))
            x1, x2 = x32[..., ::2], x32[..., 1::2]
            c, s = cos[None, :, None, :], sin[None, :, None, :]
            out = np.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
            out = jnp.asarray(out.reshape(x32.shape)).astype(x.dtype)
            return np.concatenate([np.asarray(out), np.asarray(x[..., rot:])],
                                  axis=-1)
        np.testing.assert_array_equal(np.asarray(qr), plain(q))
        np.testing.assert_array_equal(np.asarray(kr), plain(k))

    def test_fused_norms(self):
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(4, 32).astype(np.float32))
        w = paddle.to_tensor(np.ones(32, np.float32))
        b = paddle.to_tensor(np.zeros(32, np.float32))
        out, invvar = IF.fused_rms_norm(x, w)
        ref = F.rms_norm(x, weight=w)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-5)
        ref_inv = 1.0 / np.sqrt((x.numpy() ** 2).mean(-1) + 1e-6)
        np.testing.assert_allclose(invvar.numpy(), ref_inv, rtol=1e-5)
        out2 = IF.fused_layer_norm(x, w, b)
        ref2 = F.layer_norm(x, [32], weight=w, bias=b)
        np.testing.assert_allclose(out2.numpy(), ref2.numpy(), rtol=1e-5)

    def test_swiglu_and_bias_act(self):
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(3, 8).astype(np.float32))
        out = IF.swiglu(x)
        a = x.numpy()[:, :4]
        ref = a / (1 + np.exp(-a)) * x.numpy()[:, 4:]
        np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5)
        bias = paddle.to_tensor(np.ones(8, np.float32))
        out2 = IF.fused_bias_act(x, bias, act_method="relu")
        np.testing.assert_allclose(out2.numpy(),
                                   np.maximum(x.numpy() + 1, 0), rtol=1e-6)

    def test_fused_dropout_add_eval(self):
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        y = paddle.to_tensor(np.full((2, 4), 2.0, np.float32))
        out = IF.fused_dropout_add(x, y, p=0.5, training=False)
        np.testing.assert_allclose(out.numpy(), 3.0)

    def test_fused_linear(self):
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(3, 4).astype(np.float32))
        w = paddle.to_tensor(
            np.random.RandomState(1).randn(4, 5).astype(np.float32))
        b = paddle.to_tensor(np.ones(5, np.float32))
        out = IF.fused_linear(x, w, b)
        np.testing.assert_allclose(out.numpy(),
                                   x.numpy() @ w.numpy() + 1, rtol=1e-5)


class TestDecodeAttention:
    """Inference-decode attention kernels (reference fusion/gpu/
    masked_multihead_attention.cu + block_multi_head_attention.cu)."""

    def _oracle(self, q, keys, vals, n_valid):
        # q [H,D], keys/vals [H,S,D] with n_valid live positions
        s = np.einsum("hd,hsd->hs", q, keys[:, :n_valid]) \
            / np.sqrt(q.shape[-1])
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        return np.einsum("hs,hsd->hd", p, vals[:, :n_valid])

    def test_masked_mha_decode_step(self):
        from paddle_tpu.incubate.nn.functional import \
            masked_multihead_attention
        rng = np.random.RandomState(0)
        B, H, D, S = 2, 4, 16, 8
        lens = np.array([3, 5], np.int32)
        cache = rng.randn(2, B, H, S, D).astype(np.float32)
        cache[:, 0, :, 3:] = 0.0
        cache[:, 1, :, 5:] = 0.0
        x = rng.randn(B, 3 * H * D).astype(np.float32)
        out, new_cache = masked_multihead_attention(
            paddle.to_tensor(x), paddle.to_tensor(cache),
            seq_lens=paddle.to_tensor(lens))
        out, new_cache = out.numpy(), new_cache.numpy()
        qkv = x.reshape(B, 3, H, D)
        for b in range(B):
            # the new k/v landed at position lens[b]
            np.testing.assert_allclose(new_cache[0, b, :, lens[b]],
                                       qkv[b, 1], rtol=1e-5)
            np.testing.assert_allclose(new_cache[1, b, :, lens[b]],
                                       qkv[b, 2], rtol=1e-5)
            ref = self._oracle(qkv[b, 0], new_cache[0, b], new_cache[1, b],
                               int(lens[b]) + 1)
            np.testing.assert_allclose(out[b].reshape(H, D), ref,
                                       rtol=2e-4, atol=1e-5)

    def test_block_mha_paged_equals_contiguous(self):
        from paddle_tpu.incubate.nn.functional import \
            block_multihead_attention
        rng = np.random.RandomState(1)
        B, H, D, BS, NBLK, MAXB = 2, 4, 16, 4, 8, 3
        lens = np.array([5, 9], np.int32)
        # physical pool + per-seq tables (deliberately shuffled)
        kc = rng.randn(NBLK, H, BS, D).astype(np.float32)
        vc = rng.randn(NBLK, H, BS, D).astype(np.float32)
        tables = np.array([[6, 1, 4], [0, 3, 7]], np.int32)
        q = rng.randn(B, H, D).astype(np.float32)
        k = rng.randn(B, H, D).astype(np.float32)
        v = rng.randn(B, H, D).astype(np.float32)
        out, nkc, nvc = block_multihead_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            paddle.to_tensor(kc), paddle.to_tensor(vc),
            paddle.to_tensor(tables), paddle.to_tensor(lens))
        out, nkc, nvc = out.numpy(), nkc.numpy(), nvc.numpy()
        for b in range(B):
            # rebuild the contiguous cache from the table
            ks = np.concatenate([nkc[t] for t in tables[b]], axis=1)
            vs = np.concatenate([nvc[t] for t in tables[b]], axis=1)
            # new token written at lens[b]
            np.testing.assert_allclose(ks[:, lens[b]], k[b], rtol=1e-5)
            np.testing.assert_allclose(vs[:, lens[b]], v[b], rtol=1e-5)
            ref = self._oracle(q[b], ks, vs, int(lens[b]) + 1)
            np.testing.assert_allclose(out[b], ref, rtol=2e-4, atol=1e-5)

    def test_block_mha_pool_untouched_elsewhere(self):
        from paddle_tpu.incubate.nn.functional import \
            block_multihead_attention
        rng = np.random.RandomState(2)
        kc = rng.randn(4, 2, 4, 8).astype(np.float32)
        vc = rng.randn(4, 2, 4, 8).astype(np.float32)
        tables = np.array([[2, 0]], np.int32)
        lens = np.array([1], np.int32)
        q = rng.randn(1, 2, 8).astype(np.float32)
        k = rng.randn(1, 2, 8).astype(np.float32)
        v = rng.randn(1, 2, 8).astype(np.float32)
        _, nkc, _ = block_multihead_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            paddle.to_tensor(kc), paddle.to_tensor(vc),
            paddle.to_tensor(tables), paddle.to_tensor(lens))
        nkc = nkc.numpy()
        # only (block 2, slot 1) changed
        mask = np.ones_like(kc, bool)
        mask[2, :, 1, :] = False
        np.testing.assert_array_equal(nkc[mask], kc[mask])
        np.testing.assert_allclose(nkc[2, :, 1], k[0], rtol=1e-6)


class TestFusedLinearCrossEntropy:
    def test_matches_unfused(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy
        rng = np.random.RandomState(0)
        h = jnp.asarray(rng.randn(64, 32), jnp.float32) * 0.1
        w = jnp.asarray(rng.randn(100, 32), jnp.float32) * 0.1
        y = jnp.asarray(rng.randint(0, 100, (64,)), jnp.int32)

        def unfused(h, w):
            logits = (h @ w.T).astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, y[:, None], 1)[:, 0]
            return jnp.mean(lse - tgt)

        l1 = fused_linear_cross_entropy(h, w, y)
        l2 = unfused(h, w)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
        g1 = jax.grad(lambda a, b: fused_linear_cross_entropy(a, b, y),
                      argnums=(0, 1))(h, w)
        g2 = jax.grad(unfused, argnums=(0, 1))(h, w)
        np.testing.assert_allclose(g1[0], g2[0], atol=1e-5)
        np.testing.assert_allclose(g1[1], g2[1], atol=1e-5)

    def test_ignore_index(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy
        rng = np.random.RandomState(0)
        h = jnp.asarray(rng.randn(8, 16), jnp.float32)
        w = jnp.asarray(rng.randn(20, 16), jnp.float32)
        y = jnp.asarray([1, 2, -100, 3, -100, 4, 5, 6], jnp.int32)
        l_masked = fused_linear_cross_entropy(h, w, y, ignore_index=-100)
        keep = np.array([0, 1, 3, 5, 6, 7])
        l_ref = fused_linear_cross_entropy(h[keep], w, y[keep])
        np.testing.assert_allclose(float(l_masked), float(l_ref), rtol=1e-5)

    def test_blockwise_matches_unfused(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.fused_ce import blockwise_linear_cross_entropy
        rng = np.random.RandomState(1)
        h = jnp.asarray(rng.randn(48, 32), jnp.float32) * 0.3
        w = jnp.asarray(rng.randn(96, 32), jnp.float32) * 0.3
        y = jnp.asarray(rng.randint(0, 96, (48,)), jnp.int32)

        def unfused(h, w):
            logits = (h @ w.T).astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, y[:, None], 1)[:, 0]
            return jnp.mean(lse - tgt)

        for nb in (2, 4, 8):
            l1 = blockwise_linear_cross_entropy(h, w, y, num_blocks=nb)
            np.testing.assert_allclose(float(l1), float(unfused(h, w)),
                                       rtol=1e-5)
        g1 = jax.grad(lambda a, b: blockwise_linear_cross_entropy(
            a, b, y, num_blocks=4), argnums=(0, 1))(h, w)
        g2 = jax.grad(unfused, argnums=(0, 1))(h, w)
        np.testing.assert_allclose(g1[0], g2[0], atol=1e-5)
        np.testing.assert_allclose(g1[1], g2[1], atol=1e-5)

    def test_blockwise_bf16_and_ignore_index(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.fused_ce import blockwise_linear_cross_entropy
        rng = np.random.RandomState(2)
        h = jnp.asarray(rng.randn(8, 16), jnp.bfloat16)
        w = jnp.asarray(rng.randn(32, 16), jnp.bfloat16)
        y = jnp.asarray([1, 2, -100, 3, -100, 4, 5, 31], jnp.int32)
        l_masked = blockwise_linear_cross_entropy(h, w, y, num_blocks=4,
                                                  ignore_index=-100)
        keep = np.array([0, 1, 3, 5, 6, 7])
        l_ref = blockwise_linear_cross_entropy(h[keep], w, y[keep],
                                               num_blocks=4)
        np.testing.assert_allclose(float(l_masked), float(l_ref), rtol=2e-2)
        # grads stay finite and flow in storage dtype
        gh, gw = jax.grad(lambda a, b: blockwise_linear_cross_entropy(
            a, b, y, num_blocks=4, ignore_index=-100),
            argnums=(0, 1))(h, w)
        assert gh.dtype == jnp.bfloat16 and gw.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.isfinite(gh.astype(jnp.float32))))
        # ignored rows contribute zero grad to h
        np.testing.assert_array_equal(
            np.asarray(gh.astype(jnp.float32))[[2, 4]], 0.0)

    def test_blockwise_rejects_indivisible(self):
        import jax.numpy as jnp
        import pytest

        from paddle_tpu.ops.fused_ce import blockwise_linear_cross_entropy
        h = jnp.zeros((4, 8)); w = jnp.zeros((30, 8))
        y = jnp.zeros((4,), jnp.int32)
        with pytest.raises(ValueError, match="not divisible"):
            blockwise_linear_cross_entropy(h, w, y, num_blocks=4)




class TestMixedPrecisionAttention:
    def _ref(self, q, k, v, scale):
        import jax
        import jax.numpy as jnp
        qf = q.astype(jnp.float32) * scale
        S = q.shape[1]
        lg = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
        mask = jnp.tril(jnp.ones((S, S), bool))
        lg = jnp.where(mask, lg, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(lg, -1),
                          v.astype(jnp.float32))

    def test_f32_inputs_match_reference(self):
        import importlib
        import jax.numpy as jnp
        FA = importlib.import_module(
            "paddle_tpu.nn.functional.flash_attention")
        rng = np.random.RandomState(1)
        q, k, v = [jnp.asarray(rng.randn(2, 64, 4, 32), jnp.float32) * 0.3
                   for _ in range(3)]
        out = FA._attention_xla(q, k, v, None, True, 0.176, 0.0, None)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(self._ref(q, k, v, 0.176)),
                                   atol=1e-5)

    def test_bf16_mixed_path_close_to_f32(self):
        import importlib
        import jax
        import jax.numpy as jnp
        FA = importlib.import_module(
            "paddle_tpu.nn.functional.flash_attention")
        rng = np.random.RandomState(2)
        qf, kf, vf = [jnp.asarray(rng.randn(2, 64, 4, 32),
                                  jnp.float32) * 0.3 for _ in range(3)]
        q, k, v = (qf.astype(jnp.bfloat16), kf.astype(jnp.bfloat16),
                   vf.astype(jnp.bfloat16))
        out = FA._attention_xla(q, k, v, None, True, 0.176, 0.0, None)
        assert out.dtype == jnp.bfloat16
        ref = self._ref(qf, kf, vf, 0.176)
        # bf16 storage: ~2-3 decimal digits
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=3e-2)

    def test_bf16_grads_finite_and_close(self):
        import importlib
        import jax
        import jax.numpy as jnp
        FA = importlib.import_module(
            "paddle_tpu.nn.functional.flash_attention")
        rng = np.random.RandomState(3)
        qf, kf, vf = [jnp.asarray(rng.randn(1, 32, 2, 16),
                                  jnp.float32) * 0.3 for _ in range(3)]

        def loss_mixed(q, k, v):
            return FA._attention_xla(
                q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                v.astype(jnp.bfloat16), None, True, 0.25, 0.0,
                None).astype(jnp.float32).sum()

        def loss_ref(q, k, v):
            return self._ref(q, k, v, 0.25).sum()
        g1 = jax.grad(loss_mixed, argnums=(0, 1, 2))(qf, kf, vf)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(qf, kf, vf)
        for a, b in zip(g1, g2):
            assert np.isfinite(np.asarray(a, np.float32)).all()
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b), atol=5e-2)


class TestPerDirectionSelection:
    """VERDICT r3 #2: per-direction impl winners — the CE kernel's "xla"
    backward (softmax-minus-onehot from the saved lse) must match the
    Pallas backward kernel bit-for-bit in semantics, and the flash
    dispatch must route GQA-at-moderate-seq to XLA (where the saved-P
    autodiff backward measured faster than the flash recompute)."""

    def test_ce_xla_bwd_matches_pallas_bwd(self):
        from paddle_tpu.ops.pallas.cross_entropy import softmax_xent_pallas
        rng = np.random.RandomState(3)
        logits = jnp.asarray(rng.randn(6, 130), jnp.float32)
        labels = jnp.asarray(np.array([0, 5, 129, -1, 200, 64]))
        ct = jnp.asarray(rng.randn(6), jnp.float32)

        def g(bwd):
            return jax.grad(lambda x: jnp.sum(softmax_xent_pallas(
                x, labels, True, bwd) * ct))(logits)
        np.testing.assert_allclose(np.asarray(g("xla")),
                                   np.asarray(g("pallas")),
                                   rtol=1e-5, atol=1e-6)

    def test_ce_xla_bwd_invalid_labels_zero_grad(self):
        from paddle_tpu.ops.pallas.cross_entropy import softmax_xent_pallas
        logits = jnp.asarray(np.random.RandomState(0).randn(3, 130),
                             jnp.float32)
        labels = jnp.asarray(np.array([2, -1, 500]))
        g = jax.grad(lambda x: softmax_xent_pallas(
            x, labels, True, "xla").sum())(logits)
        assert np.allclose(np.asarray(g)[1], 0.0)
        assert np.allclose(np.asarray(g)[2], 0.0)
        assert not np.allclose(np.asarray(g)[0], 0.0)

    def test_flash_routing_gqa_defaults_to_xla(self):
        """On the chip GQA with a fitting score matrix routes to XLA; MHA
        and over-budget GQA stay on the Pallas kernel."""
        from paddle_tpu.ops.pallas.flash_attention import attention_route

        def probe(b, s, hq, hk, d=64):
            q = jax.ShapeDtypeStruct((b, s, hq, d), jnp.bfloat16)
            k = jax.ShapeDtypeStruct((b, s, hk, d), jnp.bfloat16)
            return attention_route(
                q, k, None, dropout_rate=0.0, has_key=False, causal=True,
                window=None, meshed=False, on_tpu=True,
                force_interpret=False).impl

        assert probe(2, 4096, 32, 8) == "xla"       # r3's losing shape
        assert probe(2, 4096, 16, 16) == "kernel"   # MHA: kernel wins
        # GQA but score matrix over budget -> flash recompute bwd
        assert probe(8, 8192, 32, 8) == "kernel"

    def test_norms_ship_xla_on_tpu_by_default(self):
        """The norm dispatch defaults: pallas under
        interpret/flag, xla otherwise — encoded in the impl wrappers."""
        from paddle_tpu.core import flags as _flags
        from paddle_tpu.ops.pallas.norms import _rms_norm_pallas_impl
        from paddle_tpu.nn.functional.norm import _rms_norm_xla
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(4, 128), jnp.float32)
        w = jnp.asarray(rng.randn(128), jnp.float32)
        # off-TPU without force_interpret: plain XLA fallback, same values
        out = _rms_norm_pallas_impl(x, w, 1e-6)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_rms_norm_xla(x, w, 1e-6)),
                                   rtol=1e-6)
        # force_interpret: kernel path still matches the oracle
        _flags.set_flags({"pallas_force_interpret": True})
        try:
            out2 = _rms_norm_pallas_impl(x, w, 1e-6)
            np.testing.assert_allclose(
                np.asarray(out2), np.asarray(_rms_norm_xla(x, w, 1e-6)),
                rtol=1e-5, atol=1e-5)
        finally:
            _flags.set_flags({"pallas_force_interpret": False})


def test_auto_num_blocks_bounds_chunk_size():
    """The vocab-chunk count adapts to tokens so a streamed block never
    scales past the budget (b128 sweep candidates must not OOM on the
    chunk residual)."""
    from paddle_tpu.models.llama import _auto_num_blocks
    V = 50304  # divisible by 8..128 (= 128 * 393)
    assert _auto_num_blocks(8 * 1024, V) == 8        # b8: unchanged
    assert _auto_num_blocks(64 * 1024, V) == 64      # b64: chunk <= budget
    nb = _auto_num_blocks(128 * 1024, V)
    assert nb == 128
    assert 128 * 1024 * (V // nb) <= 64 * 1024 * 1024
    # an odd vocab that only divides by 8 never over-divides
    assert _auto_num_blocks(10 ** 9, 8 * 9973) == 8
