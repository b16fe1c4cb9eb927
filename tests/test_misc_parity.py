"""Wave-4 parity tests: fused incubate functionals, distribution
transforms (torch oracles), amp.debugging module, nn.quant, dlpack
interop, unique_name, hub, sysconfig, cpp_extension setup surface."""
import os

import numpy as np
import pytest
import torch

import paddle_tpu as paddle

t = paddle.to_tensor
rng = np.random.RandomState(5)


class TestFusedFunctionals:
    F = None

    @classmethod
    def setup_class(cls):
        cls.F = paddle.incubate.nn.functional

    def test_fused_matmul_bias(self):
        x = rng.randn(2, 3).astype(np.float32)
        y = rng.randn(3, 4).astype(np.float32)
        b = rng.randn(4).astype(np.float32)
        out = self.F.fused_matmul_bias(t(x), t(y), t(b))
        np.testing.assert_allclose(out.numpy(), x @ y + b, atol=1e-5)

    def test_fused_linear_activation(self):
        x = rng.randn(2, 3).astype(np.float32)
        y = rng.randn(3, 4).astype(np.float32)
        b = np.zeros(4, np.float32)
        out = self.F.fused_linear_activation(t(x), t(y), t(b),
                                             activation="relu")
        np.testing.assert_allclose(out.numpy(),
                                   np.maximum(x @ y, 0), atol=1e-5)

    def test_fused_mha_shapes_and_grads(self):
        x = t(rng.randn(2, 6, 16).astype(np.float32), stop_gradient=False)
        qkvw = t(rng.randn(3, 4, 4, 16).astype(np.float32) * 0.1,
                 stop_gradient=False)
        lw = t(rng.randn(16, 16).astype(np.float32) * 0.1)
        out = self.F.fused_multi_head_attention(
            x, qkvw, lw, pre_layer_norm=True,
            pre_ln_scale=t(np.ones(16, np.float32)),
            pre_ln_bias=t(np.zeros(16, np.float32)),
            ln_scale=t(np.ones(16, np.float32)),
            ln_bias=t(np.zeros(16, np.float32)),
            dropout_rate=0.0, attn_dropout_rate=0.0, training=False)
        assert out.shape == [2, 6, 16]
        (out ** 2).mean().backward()
        assert np.isfinite(qkvw.grad.numpy()).all()

    def test_fused_mha_transpose_qkv_wb_matches_4d(self):
        """2-D (E, 3HD) qkv layout == the (3, H, D, E) layout it reshapes
        into (r3: transpose_qkv_wb was NotImplementedError)."""
        x = t(rng.randn(2, 6, 16).astype(np.float32))
        w4 = rng.randn(3, 4, 4, 16).astype(np.float32) * 0.1
        b4 = rng.randn(3, 4, 4).astype(np.float32) * 0.1
        lw = t(rng.randn(16, 16).astype(np.float32) * 0.1)
        kw = dict(pre_layer_norm=True,
                  pre_ln_scale=t(np.ones(16, np.float32)),
                  pre_ln_bias=t(np.zeros(16, np.float32)),
                  ln_scale=t(np.ones(16, np.float32)),
                  ln_bias=t(np.zeros(16, np.float32)),
                  dropout_rate=0.0, attn_dropout_rate=0.0, training=False)
        ref = self.F.fused_multi_head_attention(
            x, t(w4), lw, qkv_bias=t(b4), **kw)
        # (3, H, D, E) -> (E, 3HD); bias (3, H, D) -> (3HD,)
        w2d = w4.reshape(3 * 4 * 4, 16).T.copy()
        out = self.F.fused_multi_head_attention(
            x, t(w2d), lw, qkv_bias=t(b4.reshape(-1)), num_heads=4,
            transpose_qkv_wb=True, **kw)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), atol=1e-5)
        with pytest.raises(ValueError, match="num_heads"):
            self.F.fused_multi_head_attention(
                x, t(w2d), lw, transpose_qkv_wb=True, **kw)

    def test_fused_feedforward(self):
        x = t(rng.randn(2, 4, 8).astype(np.float32))
        w1 = t(rng.randn(8, 16).astype(np.float32) * 0.1)
        w2 = t(rng.randn(16, 8).astype(np.float32) * 0.1)
        out = self.F.fused_feedforward(
            x, w1, w2, dropout1_rate=0.0, dropout2_rate=0.0,
            ln2_scale=t(np.ones(8, np.float32)),
            ln2_bias=t(np.zeros(8, np.float32)), training=False)
        assert out.shape == [2, 4, 8]

    def test_varlen_attention_masks(self):
        q = t(rng.randn(2, 2, 6, 8).astype(np.float32))
        out = self.F.variable_length_memory_efficient_attention(
            q, q, q, t(np.array([6, 3], np.int32)),
            t(np.array([6, 3], np.int32)))
        assert np.abs(out.numpy()[1, :, 3:]).max() == 0.0
        assert np.abs(out.numpy()[0]).max() > 0.0

    def test_fused_multi_transformer_cached_decode(self):
        """Per-layer cache_kvs decode == the full causal pass (reference
        fused_transformer decode contract)."""
        import paddle_tpu as paddle
        from paddle_tpu.incubate.nn import FusedMultiTransformer
        paddle.seed(3)
        E, H, S, L = 16, 4, 4, 2
        net = FusedMultiTransformer(E, H, 32, dropout_rate=0.0,
                                    normalize_before=True, num_layers=L)
        net.eval()
        x = t(rng.randn(2, S, E).astype(np.float32))
        mask = np.where(np.tril(np.ones((S, S))), 0.0,
                        -1e9).astype(np.float32)
        full = net(x, attn_mask=t(mask[None, None]))
        caches = [t(np.zeros((2, 2, H, 0, E // H), np.float32))
                  for _ in range(L)]
        outs = []
        for step in range(S):
            o, caches = net(x[:, step:step + 1], caches=caches)
            outs.append(o.numpy())
        np.testing.assert_allclose(np.concatenate(outs, axis=1),
                                   full.numpy(), atol=1e-5)
        assert all(list(c.shape) == [2, 2, H, S, E // H] for c in caches)

    def test_fused_mha_cached_decode_matches_full_pass(self):
        """cache_kv decode (reference fused_transformer.py:592,841):
        feeding tokens one at a time through the growing (2,B,H,T,D)
        cache must reproduce the full causal pass exactly."""
        E, H, D, S = 16, 4, 4, 5
        w4 = t(rng.randn(3, H, D, E).astype(np.float32) * 0.1)
        lw = t(rng.randn(E, E).astype(np.float32) * 0.1)
        kw = dict(pre_layer_norm=True,
                  pre_ln_scale=t(np.ones(E, np.float32)),
                  pre_ln_bias=t(np.zeros(E, np.float32)),
                  dropout_rate=0.0, attn_dropout_rate=0.0, training=False)
        x = t(rng.randn(2, S, E).astype(np.float32))
        mask = np.where(np.tril(np.ones((S, S))), 0.0,
                        -1e9).astype(np.float32)
        full = self.F.fused_multi_head_attention(
            x, w4, lw, attn_mask=t(mask[None, None]), **kw)
        cache = t(np.zeros((2, 2, H, 0, D), np.float32))
        outs = []
        for step in range(S):
            o, cache = self.F.fused_multi_head_attention(
                x[:, step:step + 1], w4, lw, cache_kv=cache, **kw)
            outs.append(o.numpy())
        np.testing.assert_allclose(np.concatenate(outs, axis=1),
                                   full.numpy(), atol=1e-5)
        assert list(cache.shape) == [2, 2, H, S, D]


class TestDistributionTransforms:
    def test_stickbreaking_matches_torch(self):
        x = rng.randn(5).astype(np.float32)
        sb = paddle.distribution.StickBreakingTransform()
        y = sb.forward(t(x))
        ty = torch.distributions.StickBreakingTransform()(torch.tensor(x))
        np.testing.assert_allclose(y.numpy(), ty.numpy(), atol=1e-5)
        back = sb.inverse(y)
        np.testing.assert_allclose(back.numpy(), x, atol=1e-4)

    def test_softmax_and_reshape(self):
        x = rng.randn(4).astype(np.float32)
        st = paddle.distribution.SoftmaxTransform()
        np.testing.assert_allclose(float(st.forward(t(x)).numpy().sum()),
                                   1.0, atol=1e-5)
        rt = paddle.distribution.ReshapeTransform((6,), (2, 3))
        assert rt.forward(t(np.zeros(6, np.float32))).shape == [2, 3]
        assert rt.inverse(
            t(np.zeros((2, 3), np.float32))).shape == [6]
        with pytest.raises(ValueError):
            paddle.distribution.ReshapeTransform((6,), (2, 2))

    def test_stack_and_abs(self):
        stk = paddle.distribution.StackTransform(
            [paddle.distribution.ExpTransform(),
             paddle.distribution.ExpTransform()], axis=0)
        out = stk.forward(t(np.array([0.0, 1.0], np.float32)))
        np.testing.assert_allclose(out.numpy(), np.exp([0.0, 1.0]),
                                   atol=1e-5)
        ab = paddle.distribution.AbsTransform()
        np.testing.assert_allclose(
            ab.forward(t(np.array([-2.0], np.float32))).numpy(), [2.0])


class TestAmpDebugging:
    def test_check_numerics_counts(self):
        n, i, z = paddle.amp.debugging.check_numerics(
            t(np.array([np.nan, np.inf, 0.0, 1.0], np.float32)),
            "op", "v",
            debug_mode=paddle.amp.debugging.DebugMode.CHECK_NAN_INF)
        assert int(n.numpy()) == 1
        assert int(i.numpy()) == 1
        assert int(z.numpy()) == 1

    def test_check_numerics_aborts(self):
        with pytest.raises(FloatingPointError):
            paddle.amp.debugging.check_numerics(
                t(np.array([np.nan], np.float32)), "op", "v")

    def test_collect_operator_stats(self, capsys):
        with paddle.amp.debugging.collect_operator_stats():
            x = t(np.ones((2, 2), np.float32))
            (x @ x).sum()
        out = capsys.readouterr().out
        assert "matmul" in out

    def test_tensor_checker_flags(self):
        cfg = paddle.amp.debugging.TensorCheckerConfig(enable=True)
        paddle.amp.debugging.enable_tensor_checker(cfg)
        assert paddle.get_flags(["check_nan_inf"])["check_nan_inf"]
        paddle.amp.debugging.disable_tensor_checker()
        assert not paddle.get_flags(["check_nan_inf"])["check_nan_inf"]

    def test_compare_accuracy(self, tmp_path):
        a_dir, b_dir = tmp_path / "a", tmp_path / "b"
        os.makedirs(a_dir)
        os.makedirs(b_dir)
        np.save(a_dir / "t0.npy", np.ones(4))
        np.save(b_dir / "t0.npy", np.ones(4) + 1e-6)
        out_csv = str(tmp_path / "cmp.csv")
        rows = paddle.amp.debugging.compare_accuracy(
            str(a_dir), str(b_dir), out_csv)
        assert rows and rows[0][1] == "ok"
        assert os.path.exists(out_csv)


class TestNNQuant:
    def test_weight_only_linear(self):
        x = np.ones((2, 4), np.float32)
        w = (np.ones((3, 4)) * 2).astype(np.int8)
        scale = np.full(3, 0.5, np.float32)
        out = paddle.nn.quant.weight_only_linear(
            t(x), t(w), weight_scale=t(scale))
        np.testing.assert_allclose(out.numpy(), np.full((2, 3), 4.0))

    def test_llm_int8_linear_runs(self):
        x = rng.randn(2, 4).astype(np.float32)
        w = rng.randint(-127, 127, (3, 4)).astype(np.int8)
        scale = np.full(3, 0.01, np.float32)
        out = paddle.nn.quant.llm_int8_linear(t(x), t(w),
                                              weight_scale=t(scale))
        ref = x @ (w.astype(np.float32) * scale[:, None]).T
        np.testing.assert_allclose(out.numpy(), ref, atol=1e-4)

    def test_stub(self):
        s = paddle.nn.quant.Stub()
        x = t(np.ones(3, np.float32))
        assert s(x) is x


class TestInteropUtils:
    def test_dlpack_roundtrip_and_torch(self):
        x = t(np.arange(6.0, dtype=np.float32))
        y = paddle.utils.dlpack.from_dlpack(paddle.utils.dlpack.to_dlpack(x))
        np.testing.assert_allclose(y.numpy(), x.numpy())
        tt = torch.from_dlpack(paddle.utils.dlpack.to_dlpack(x))
        np.testing.assert_allclose(tt.numpy(), x.numpy())
        back = paddle.utils.dlpack.from_dlpack(tt)
        np.testing.assert_allclose(back.numpy(), x.numpy())

    def test_unique_name(self):
        with paddle.utils.unique_name.guard():
            a = paddle.utils.unique_name.generate("fc")
            b = paddle.utils.unique_name.generate("fc")
        assert a != b
        assert a.startswith("fc_")

    def test_hub(self, tmp_path):
        (tmp_path / "hubconf.py").write_text(
            "def tiny(n=4):\n"
            "    'Builds a tiny Linear'\n"
            "    import paddle_tpu as p\n"
            "    return p.nn.Linear(n, n)\n")
        assert paddle.hub.list(str(tmp_path)) == ["tiny"]
        assert "tiny" in paddle.hub.help(str(tmp_path), "tiny")
        net = paddle.hub.load(str(tmp_path), "tiny", 3)
        assert net.weight.shape == [3, 3]
        with pytest.raises(RuntimeError):
            paddle.hub.load("org/repo", "x", source="github")

    def test_sysconfig(self):
        assert os.path.isdir(paddle.sysconfig.get_include())
        assert isinstance(paddle.sysconfig.get_lib(), str)

    def test_cuda_extension_rejects_cu(self):
        with pytest.raises(RuntimeError):
            paddle.utils.cpp_extension.CUDAExtension(["kernel.cu"])

    def test_download_cache_miss_raises(self):
        with pytest.raises(RuntimeError):
            paddle.utils.download.get_weights_path_from_url(
                "https://example.com/nonexistent_weights_xyz.pdparams")


class TestIncubateAutogradASP:
    def test_vjp_jvp(self):
        IA = paddle.incubate.autograd

        def f(x):
            return (x * x).sum()
        out, g = IA.vjp(f, t(np.array([1.0, 2.0], np.float32)))
        assert float(out.numpy()) == 5.0
        np.testing.assert_allclose(g.numpy(), [2.0, 4.0])
        _, tangent = IA.jvp(f, t(np.array([1.0, 2.0], np.float32)))
        assert float(tangent.numpy()) == 6.0

    def test_jacobian_hessian(self):
        IA = paddle.incubate.autograd
        J = IA.Jacobian(lambda x: x * 3,
                        t(np.array([1.0, 2.0], np.float32)))
        np.testing.assert_allclose(np.asarray(J[:].numpy()),
                                   np.eye(2) * 3)
        H = IA.Hessian(lambda x: (x ** 2).sum(),
                       t(np.array([1.0, 2.0], np.float32)))
        np.testing.assert_allclose(np.asarray(H[:].numpy()),
                                   np.eye(2) * 2)

    def test_asp_prune_and_decorate(self):
        paddle.seed(0)
        lin = paddle.nn.Linear(8, 4)
        paddle.incubate.asp.prune_model(lin)
        assert abs(paddle.incubate.asp.calculate_density(lin.weight)
                   - 0.5) < 1e-6
        opt = paddle.incubate.asp.decorate(
            paddle.optimizer.SGD(0.1, parameters=lin.parameters()))
        x = t(np.ones((2, 8), np.float32))
        loss = (lin(x) ** 2).mean()
        loss.backward()
        opt.step()
        # mask survives the optimizer step
        assert abs(paddle.incubate.asp.calculate_density(lin.weight)
                   - 0.5) < 1e-6

    def test_tensor_mp_pickle(self):
        import pickle
        x = t(np.arange(3.0, dtype=np.float32))
        y = pickle.loads(pickle.dumps(x))
        np.testing.assert_allclose(y.numpy(), x.numpy())

    def test_autotune_set_config(self, tmp_path):
        """A parity shim: the config comes back, as a dict or from a JSON
        path, and no flag moves (tiles and route come from the shape)."""
        import json
        from paddle_tpu.core import flags
        before = dict(flags._REGISTRY)
        cfg = {"kernel": {"enable": True, "tuning_range": [1, 3]},
               "layout": {"enable": False}}
        assert paddle.incubate.autotune.set_config(cfg) == cfg
        path = tmp_path / "autotune.json"
        path.write_text(json.dumps(cfg))
        assert paddle.incubate.autotune.set_config(str(path)) == cfg
        assert paddle.incubate.autotune.set_config() == {}
        assert flags._REGISTRY == before


class TestIncubateLayers:
    """paddle.incubate.layers generic subset (reference
    incubate/layers/nn.py — shuffle_batch:447, partial_concat:511,
    partial_sum:589, batch_fc:1028, fused_bn_add_act:1297,
    pow2_decay_with_linear_warmup:1502, fused_embedding_seq_pool:37)."""

    def test_shuffle_batch_permutes_rows(self):
        from paddle_tpu.incubate import layers as L
        x = t(np.arange(8, dtype=np.float32).reshape(4, 2))
        s = L.shuffle_batch(x, seed=7)
        assert sorted(map(tuple, s.numpy().tolist())) == \
            sorted(map(tuple, x.numpy().tolist()))

    def test_partial_concat_and_sum(self):
        from paddle_tpu.incubate import layers as L
        a = t(np.arange(6, dtype=np.float32).reshape(2, 3))
        b = t(np.arange(6, 12, dtype=np.float32).reshape(2, 3))
        pc = L.partial_concat([a, b], start_index=1, length=2)
        np.testing.assert_array_equal(
            pc.numpy(), np.concatenate([a.numpy()[:, 1:3],
                                        b.numpy()[:, 1:3]], 1))
        ps = L.partial_sum([a, b], start_index=0, length=2)
        np.testing.assert_array_equal(
            ps.numpy(), a.numpy()[:, :2] + b.numpy()[:, :2])

    def test_batch_fc_shapes_and_grad(self):
        import paddle_tpu as paddle
        from paddle_tpu.incubate import layers as L
        paddle.seed(0)
        x = t(np.ones((2, 3, 4), np.float32), stop_gradient=False)
        out = L.batch_fc(x, [2, 4, 5], None, [2, 5], None, act="relu")
        assert out.shape == [2, 3, 5]
        (out ** 2).mean().backward()
        assert np.isfinite(x.grad.numpy()).all()

    def test_pow2_decay_with_linear_warmup(self):
        from paddle_tpu.incubate import layers as L
        sched = L.pow2_decay_with_linear_warmup(10, 100, 0.1, 0.001)
        lrs = []
        for _ in range(100):
            lrs.append(sched.get_lr())
            sched.step()
        assert abs(lrs[9] - 0.1) < 1e-9          # warmup tops out at base
        assert lrs[0] < lrs[5] < lrs[9]          # linear ramp
        assert lrs[10] > lrs[50] > lrs[-1] >= 0.001  # pow2 decay to end

    def test_fused_embedding_seq_pool_padding(self):
        import paddle_tpu as paddle
        from paddle_tpu.incubate import layers as L
        paddle.seed(1)
        ids = t(np.array([[1, 2, 0], [3, 0, 0]], np.int64))
        pooled = L.fused_embedding_seq_pool(ids, (10, 4), padding_idx=0)
        assert pooled.shape == [2, 4]
        # named attr -> ONE shared table: padded row [3,0,0] pools to
        # exactly the same vector as [3] alone
        attr = paddle.ParamAttr(name="fesp_shared")
        mixed = L.fused_embedding_seq_pool(
            t(np.array([[3, 0, 0]], np.int64)), (10, 4), padding_idx=0,
            param_attr=attr)
        only3 = L.fused_embedding_seq_pool(
            t(np.array([[3]], np.int64)), (10, 4), param_attr=attr)
        np.testing.assert_allclose(mixed.numpy(), only3.numpy(), rtol=1e-6)
        # all-padding pools to exactly zero; OOB ids raise; negative
        # padding_idx normalizes to size+padding_idx
        allpad = L.fused_embedding_seq_pool(
            t(np.array([[0, 0]], np.int64)), (10, 4), padding_idx=0)
        np.testing.assert_array_equal(allpad.numpy(), 0.0)
        with pytest.raises(ValueError, match="out of range"):
            L.fused_embedding_seq_pool(t(np.array([[10]], np.int64)),
                                       (10, 4))
        neg = L.fused_embedding_seq_pool(
            t(np.array([[9, 9]], np.int64)), (10, 4), padding_idx=-1)
        np.testing.assert_array_equal(neg.numpy(), 0.0)

    def test_fused_bn_add_act(self):
        from paddle_tpu.incubate import layers as L
        x = t(np.random.RandomState(0).randn(4, 8).astype("float32"))
        y = t(np.zeros((4, 8), np.float32))
        out = L.fused_bn_add_act(x, y)
        assert out.shape == [4, 8] and float(out.min()) >= 0

    def test_multiclass_nms2(self):
        from paddle_tpu.incubate import layers as L
        bb = np.array([[[0, 0, 10, 10], [1, 1, 11, 11],
                        [50, 50, 60, 60]]], np.float32)
        sc = np.zeros((1, 2, 3), np.float32)
        sc[0, 1] = [0.9, 0.8, 0.7]
        out, idx, rn = L.multiclass_nms2(
            t(bb), t(sc), score_threshold=0.1, nms_top_k=10,
            keep_top_k=10, nms_threshold=0.5, return_index=True,
            return_rois_num=True)
        o = np.asarray(out._data)
        assert o.shape == (2, 6) and int(rn.numpy()[0]) == 2
        np.testing.assert_allclose(sorted(o[:, 1]), [0.7, 0.9])
        assert set(np.asarray(idx._data).tolist()) == {0, 2}
        # reference arity: bare call returns the tensor alone
        out_only = L.multiclass_nms2(
            t(bb), t(sc), score_threshold=0.1, nms_top_k=10,
            keep_top_k=1, nms_threshold=0.5)
        assert np.asarray(out_only._data).shape == (1, 6)
        assert float(np.asarray(out_only._data)[0, 1]) == np.float32(0.9)
        # nms_top_k=-1 keeps every candidate above threshold
        sc3 = np.zeros((1, 2, 3), np.float32)
        sc3[0, 1] = [0.9, 0.8, 0.7]
        bb3 = np.array([[[0, 0, 1, 1], [10, 10, 11, 11],
                         [20, 20, 21, 21]]], np.float32)
        all3 = L.multiclass_nms2(t(bb3), t(sc3), score_threshold=0.1,
                                 nms_top_k=-1, keep_top_k=-1,
                                 nms_threshold=0.5)
        assert np.asarray(all3._data).shape == (3, 6)
        # adaptive nms_eta: threshold shrinks AFTER the first kept box,
        # so a 0.6-IoU pair is suppressed at eta<1 but kept at eta=1
        bbA = np.array([[[0, 0, 10, 4.0], [0, 0, 10, 6.65],
                         [50, 50, 60, 60]]], np.float32)
        scA = np.zeros((1, 2, 3), np.float32)
        scA[0, 1] = [0.9, 0.8, 0.7]
        keep_eta1 = L.multiclass_nms2(t(bbA), t(scA), 0.1, -1, -1,
                                      nms_threshold=0.7, nms_eta=1.0)
        keep_eta = L.multiclass_nms2(t(bbA), t(scA), 0.1, -1, -1,
                                     nms_threshold=0.7, nms_eta=0.8)
        assert np.asarray(keep_eta1._data).shape[0] == 3
        assert np.asarray(keep_eta._data).shape[0] == 2


class TestTopPSamplingThreshold:
    def test_threshold_floors_low_prob_tokens(self):
        """(x, ps, threshold, seed) contract (reference search.py:1235):
        threshold is an absolute per-row probability floor applied
        simultaneously with ps."""
        import paddle_tpu as paddle
        paddle.seed(0)
        x = t(np.array([[5.0, 3.0, -2.0, -2.0]], np.float32))
        ps = t(np.array([0.99], np.float32))
        thr = t(np.array([0.5], np.float32))
        seen = set()
        for _ in range(20):
            _, idx = paddle.tensor.top_p_sampling(x, ps, threshold=thr)
            seen.add(int(idx.numpy()[0, 0]))
        assert seen == {0}
        seen2 = set()
        for _ in range(50):
            _, idx = paddle.tensor.top_p_sampling(x, ps)
            seen2.add(int(idx.numpy()[0, 0]))
        assert {0, 1} <= seen2

    def test_per_row_topp_seed(self):
        """topp_seed is a [B, 1] PER-ROW seed tensor: same seed -> same
        draw per row; changing one row's seed leaves other rows fixed."""
        import paddle_tpu as paddle
        x = t(np.random.RandomState(2).randn(3, 32).astype(np.float32))
        ps = t(np.full(3, 0.95, np.float32))
        s1 = t(np.array([[1], [2], [3]], np.int64))
        s2 = t(np.array([[1], [999], [3]], np.int64))
        _, a = paddle.tensor.top_p_sampling(x, ps, topp_seed=s1)
        _, b = paddle.tensor.top_p_sampling(x, ps, topp_seed=s1)
        np.testing.assert_array_equal(a.numpy(), b.numpy())
        _, c = paddle.tensor.top_p_sampling(x, ps, topp_seed=s2)
        assert a.numpy()[0, 0] == c.numpy()[0, 0]
        assert a.numpy()[2, 0] == c.numpy()[2, 0]
        diffs = 0
        for v in range(5):
            xs = t(np.random.RandomState(10 + v)
                   .randn(3, 32).astype(np.float32))
            _, d1 = paddle.tensor.top_p_sampling(xs, ps, topp_seed=s1)
            _, d2 = paddle.tensor.top_p_sampling(xs, ps, topp_seed=s2)
            diffs += int(d1.numpy()[1, 0] != d2.numpy()[1, 0])
        assert diffs > 0, "row-1 seed has no effect"
