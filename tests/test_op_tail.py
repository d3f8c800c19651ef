"""Round-4 op-tail: vision.ops, geometric, nn.quant, nn.utils, pooling
tail, loss tail, tensor tail, _C_ops surface, fused softmax-mask.

Reference model: per-op forward parity vs NumPy + grad smoke
(test/legacy_test op tests for the corresponding kernels)."""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.vision import ops as V

rng = np.random.default_rng(42)


class TestVisionOps:
    def test_nms(self):
        boxes = np.array([[0, 0, 10, 10], [1, 1, 11, 11], [50, 50, 60, 60],
                          [0, 0, 5, 5]], np.float32)
        scores = np.array([0.9, 0.8, 0.7, 0.6], np.float32)
        keep = V.nms(paddle.to_tensor(boxes), 0.5, paddle.to_tensor(scores))
        assert list(keep.numpy()) == [0, 2, 3]

    def test_nms_categories(self):
        boxes = np.array([[0, 0, 10, 10], [1, 1, 11, 11]], np.float32)
        scores = np.array([0.9, 0.8], np.float32)
        cats = np.array([0, 1], np.int64)
        keep = V.nms(paddle.to_tensor(boxes), 0.5,
                     paddle.to_tensor(scores),
                     paddle.to_tensor(cats), [0, 1])
        assert len(keep.numpy()) == 2  # different classes: both kept

    def test_roi_align_constant(self):
        x = paddle.to_tensor(np.full((2, 3, 16, 16), 5.0, np.float32))
        rois = paddle.to_tensor(np.array(
            [[0, 0, 8, 8], [4, 4, 12, 12], [0, 0, 16, 16]], np.float32))
        bn = paddle.to_tensor(np.array([2, 1], np.int32))
        out = V.roi_align(x, rois, bn, 4)
        assert out.shape == [3, 3, 4, 4]
        np.testing.assert_allclose(out.numpy(), 5.0, rtol=1e-5)

    def test_roi_align_grad(self):
        x = paddle.to_tensor(rng.standard_normal(
            (1, 2, 8, 8)).astype("float32"), stop_gradient=False)
        rois = paddle.to_tensor(np.array([[1, 1, 6, 6]], np.float32))
        bn = paddle.to_tensor(np.array([1], np.int32))
        V.roi_align(x, rois, bn, 2).sum().backward()
        assert x.grad is not None and np.abs(x.grad.numpy()).sum() > 0

    def test_roi_pool(self):
        x = paddle.to_tensor(np.full((1, 2, 8, 8), 3.0, np.float32))
        rois = paddle.to_tensor(np.array([[0, 0, 7, 7]], np.float32))
        bn = paddle.to_tensor(np.array([1], np.int32))
        out = V.roi_pool(x, rois, bn, 2)
        np.testing.assert_allclose(out.numpy(), 3.0, rtol=1e-6)

    def test_psroi_pool(self):
        x = paddle.to_tensor(rng.random((1, 8, 12, 12)).astype("float32"))
        out = V.psroi_pool(x, paddle.to_tensor(
            np.array([[0, 0, 12, 12]], np.float32)),
            paddle.to_tensor(np.array([1], np.int32)), 2)
        assert out.shape == [1, 2, 2, 2]
        with pytest.raises(ValueError):
            V.psroi_pool(paddle.to_tensor(np.zeros((1, 7, 4, 4), "float32")),
                         paddle.to_tensor(np.array([[0, 0, 4, 4]],
                                                   np.float32)),
                         paddle.to_tensor(np.array([1], np.int32)), 2)

    def test_box_coder_roundtrip(self):
        pb = paddle.to_tensor(np.array([[0, 0, 10, 10], [5, 5, 20, 20]],
                                       np.float32))
        tb = paddle.to_tensor(np.array([[1, 1, 9, 9], [6, 6, 18, 18]],
                                       np.float32))
        var = [0.1, 0.1, 0.2, 0.2]
        enc = V.box_coder(pb, var, tb)
        dec = V.box_coder(pb, var, paddle.to_tensor(enc.numpy()),
                          code_type="decode_center_size")
        d = dec.numpy()
        np.testing.assert_allclose(d[0, 0], [1, 1, 9, 9], atol=1e-4)
        np.testing.assert_allclose(d[1, 1], [6, 6, 18, 18], atol=1e-4)

    def test_deform_conv_zero_offset_is_conv(self):
        x = paddle.to_tensor(rng.standard_normal((2, 4, 8, 8))
                             .astype("float32"))
        w = paddle.to_tensor(
            rng.standard_normal((6, 4, 3, 3)).astype("float32") * 0.1)
        off = paddle.to_tensor(np.zeros((2, 18, 8, 8), np.float32))
        y = V.deform_conv2d(x, off, w, padding=1)
        ref = F.conv2d(x, w, padding=1)
        np.testing.assert_allclose(y.numpy(), ref.numpy(), rtol=1e-4,
                                   atol=1e-4)

    def test_deform_conv_layer_and_grad(self):
        layer = V.DeformConv2D(4, 6, 3, padding=1)
        x = paddle.to_tensor(rng.standard_normal((1, 4, 6, 6))
                             .astype("float32"))
        off = paddle.to_tensor(
            rng.standard_normal((1, 18, 6, 6)).astype("float32") * 0.1)
        out = layer(x, off)
        out.sum().backward()
        assert layer.weight.grad is not None

    def test_yolo_box_shapes(self):
        x = paddle.to_tensor(rng.standard_normal(
            (2, 3 * 7, 4, 4)).astype("float32"))
        img = paddle.to_tensor(np.full((2, 2), 64, np.int32))
        b, s = V.yolo_box(x, img, [10, 13, 16, 30, 33, 23], 2, 0.01, 16)
        assert b.shape == [2, 48, 4] and s.shape == [2, 48, 2]

    def test_yolo_loss_finite_and_grad(self):
        x = paddle.to_tensor(rng.standard_normal(
            (2, 3 * 7, 4, 4)).astype("float32") * 0.1, stop_gradient=False)
        gt = paddle.to_tensor(
            np.array([[[0.5, 0.5, 0.3, 0.4]], [[0.2, 0.3, 0.1, 0.2]]],
                     np.float32))
        gl = paddle.to_tensor(np.zeros((2, 1), np.int64))
        loss = V.yolo_loss(x, gt, gl, [10, 13, 16, 30, 33, 23], [0, 1, 2],
                           2, 0.5, 16)
        assert np.isfinite(loss.numpy()).all()
        loss.sum().backward()
        assert x.grad is not None

    def test_prior_box(self):
        inp = paddle.to_tensor(np.zeros((1, 3, 4, 4), np.float32))
        img = paddle.to_tensor(np.zeros((1, 3, 32, 32), np.float32))
        b, v = V.prior_box(inp, img, min_sizes=[8.0], aspect_ratios=[2.0],
                           flip=True, clip=True)
        assert b.shape == [4, 4, 3, 4]
        assert (b.numpy() >= 0).all() and (b.numpy() <= 1).all()

    def test_matrix_nms(self):
        bb = paddle.to_tensor(np.array(
            [[[0, 0, 10, 10], [1, 1, 11, 11], [50, 50, 60, 60]]],
            np.float32))
        sc = paddle.to_tensor(np.array(
            [[[0.1, 0.1, 0.1], [0.9, 0.8, 0.7]]], np.float32))
        out, num = V.matrix_nms(bb, sc, 0.3, 0.0, 10, 5,
                                background_label=0)
        assert out.shape[1] == 6 and int(num.numpy()[0]) == out.shape[0]

    def test_generate_proposals(self):
        H = W = 4
        A = 3
        scores = paddle.to_tensor(rng.random((1, A, H, W)).astype("float32"))
        deltas = paddle.to_tensor(
            rng.standard_normal((1, A * 4, H, W)).astype("float32") * 0.1)
        img = paddle.to_tensor(np.array([[64, 64]], np.float32))
        a = (rng.random((H * W * A, 4)) * 32).astype("float32")
        a[:, 2:] = a[:, :2] + 8  # well-formed boxes
        anchors = paddle.to_tensor(a)
        var = paddle.to_tensor(np.ones((H * W * A, 4), np.float32))
        rois, probs, n = V.generate_proposals(
            scores, deltas, img, anchors, var, min_size=1.0,
            return_rois_num=True)
        assert rois.shape[1] == 4 and int(n.numpy()[0]) == rois.shape[0]

    def test_distribute_fpn_proposals(self):
        rois = paddle.to_tensor(np.array(
            [[0, 0, 10, 10], [0, 0, 100, 100], [0, 0, 224, 224]],
            np.float32))
        multi, restore = V.distribute_fpn_proposals(rois, 2, 5, 4, 224)
        assert len(multi) == 4
        assert sum(m.shape[0] for m in multi) == 3
        assert sorted(restore.numpy().ravel().tolist()) == [0, 1, 2]


class TestGeometric:
    def test_segment_ops(self):
        G = paddle.geometric
        data = paddle.to_tensor(np.array(
            [[1., 2., 3.], [3., 2., 1.], [4., 5., 6.]], np.float32))
        ids = paddle.to_tensor(np.array([0, 0, 1], np.int32))
        np.testing.assert_allclose(G.segment_sum(data, ids).numpy(),
                                   [[4, 4, 4], [4, 5, 6]])
        np.testing.assert_allclose(G.segment_mean(data, ids).numpy(),
                                   [[2, 2, 2], [4, 5, 6]])
        np.testing.assert_allclose(G.segment_min(data, ids).numpy(),
                                   [[1, 2, 1], [4, 5, 6]])
        np.testing.assert_allclose(G.segment_max(data, ids).numpy(),
                                   [[3, 2, 3], [4, 5, 6]])

    def test_send_u_recv_reference_example(self):
        G = paddle.geometric
        x = paddle.to_tensor(np.array([[0, 2, 3], [1, 4, 5], [2, 6, 7]],
                                      np.float32))
        src = paddle.to_tensor(np.array([0, 1, 2, 0], np.int32))
        dst = paddle.to_tensor(np.array([1, 2, 1, 0], np.int32))
        out = G.send_u_recv(x, src, dst, "sum")
        np.testing.assert_allclose(out.numpy(),
                                   [[0, 2, 3], [2, 8, 10], [1, 4, 5]])

    def test_send_u_recv_grad(self):
        G = paddle.geometric
        x = paddle.to_tensor(np.ones((3, 3), np.float32),
                             stop_gradient=False)
        src = paddle.to_tensor(np.array([0, 1, 2, 0], np.int32))
        dst = paddle.to_tensor(np.array([1, 2, 1, 0], np.int32))
        G.send_u_recv(x, src, dst, "sum").sum().backward()
        np.testing.assert_allclose(x.grad.numpy(),
                                   [[2, 2, 2], [1, 1, 1], [1, 1, 1]])

    def test_send_ue_recv_and_uv(self):
        G = paddle.geometric
        x = paddle.to_tensor(np.array([[0, 2, 3], [1, 4, 5], [2, 6, 7]],
                                      np.float32))
        y = paddle.to_tensor(np.ones((4, 3), np.float32))
        src = paddle.to_tensor(np.array([0, 1, 2, 0], np.int32))
        dst = paddle.to_tensor(np.array([1, 2, 1, 0], np.int32))
        out = G.send_ue_recv(x, y, src, dst, "add", "sum")
        np.testing.assert_allclose(out.numpy(),
                                   [[1, 3, 4], [4, 10, 12], [2, 5, 6]])
        assert G.send_uv(x, x, src, dst, "mul").shape == [4, 3]

    def test_reindex_and_sample(self):
        G = paddle.geometric
        xs = paddle.to_tensor(np.array([0, 5, 8, 9], np.int64))
        nbs = paddle.to_tensor(np.array([8, 9, 0, 4, 7, 6, 7], np.int64))
        cnt = paddle.to_tensor(np.array([2, 3, 1, 1], np.int64))
        rs, rd, mp = G.reindex_graph(xs, nbs, cnt)
        assert list(mp.numpy()[:4]) == [0, 5, 8, 9]
        assert rd.numpy().tolist() == [0, 0, 1, 1, 1, 2, 3]
        row = paddle.to_tensor(np.array([1, 2, 0, 2, 0, 1], np.int64))
        colptr = paddle.to_tensor(np.array([0, 2, 4, 6], np.int64))
        nb, c = G.sample_neighbors(
            row, colptr, paddle.to_tensor(np.array([0, 2], np.int64)),
            sample_size=1)
        assert list(c.numpy()) == [1, 1]


class TestQuantOps:
    def test_int8_roundtrip(self):
        from paddle_tpu.nn.quant import weight_dequantize, weight_quantize

        w = rng.standard_normal((64, 32)).astype("float32")
        q, s = weight_quantize(paddle.to_tensor(w))
        assert q.shape == [32, 64] and s.shape == [32]
        assert str(q.numpy().dtype) == "int8"
        wd = weight_dequantize(q, s, out_dtype="float32")
        assert np.abs(wd.numpy() - w).max() / np.abs(w).max() < 0.02

    def test_weight_only_linear(self):
        from paddle_tpu.nn.quant import weight_only_linear, weight_quantize

        w = rng.standard_normal((64, 32)).astype("float32")
        x = rng.standard_normal((4, 64)).astype("float32")
        ref = x @ w
        q, s = weight_quantize(paddle.to_tensor(w))
        y = weight_only_linear(paddle.to_tensor(x), q, weight_scale=s)
        assert np.abs(y.numpy() - ref).max() / np.abs(ref).max() < 0.03
        q4, s4 = weight_quantize(paddle.to_tensor(w),
                                 algo="weight_only_int4")
        assert q4.shape == [32, 32]  # packed nibbles
        y4 = weight_only_linear(paddle.to_tensor(x), q4, weight_scale=s4,
                                weight_dtype="int4")
        assert np.abs(y4.numpy() - ref).max() / np.abs(ref).max() < 0.2

    def test_llm_int8_outliers(self):
        from paddle_tpu.nn.quant import llm_int8_linear, weight_quantize

        w = rng.standard_normal((64, 32)).astype("float32")
        x = rng.standard_normal((4, 64)).astype("float32")
        x[:, 5] *= 50
        q, s = weight_quantize(paddle.to_tensor(w), algo="llm.int8")
        y = llm_int8_linear(paddle.to_tensor(x), q, weight_scale=s,
                            threshold=6.0)
        ref = x @ w
        assert np.abs(y.numpy() - ref).max() / np.abs(ref).max() < 0.05


class TestNNUtils:
    def test_weight_norm_roundtrip(self):
        from paddle_tpu.nn.utils import remove_weight_norm, weight_norm

        lin = paddle.nn.Linear(8, 6)
        x = paddle.to_tensor(rng.standard_normal((4, 8)).astype("float32"))
        ref = lin(x).numpy()
        weight_norm(lin, dim=0)
        np.testing.assert_allclose(lin(x).numpy(), ref, rtol=1e-5)
        lin(x).sum().backward()
        assert lin.weight_g.grad is not None
        remove_weight_norm(lin)
        np.testing.assert_allclose(lin(x).numpy(), ref, rtol=1e-5)
        assert "weight_g" not in dict(lin.named_parameters())

    def test_spectral_norm_unit_sv(self):
        from paddle_tpu.nn.utils import spectral_norm

        lin = paddle.nn.Linear(8, 6)
        with paddle.no_grad():
            lin.weight.set_value(lin.weight.numpy() * 10)
        spectral_norm(lin, n_power_iterations=5)
        lin.train()
        x = paddle.to_tensor(rng.standard_normal((4, 8)).astype("float32"))
        for _ in range(5):
            lin(x)
        sv = np.linalg.svd(lin.weight.numpy(), compute_uv=False).max()
        assert abs(sv - 1.0) < 0.05

    def test_vector_roundtrip_and_clip(self):
        from paddle_tpu.nn.utils import (clip_grad_norm_, clip_grad_value_,
                                         parameters_to_vector,
                                         vector_to_parameters)

        lin = paddle.nn.Linear(3, 2)
        vec = parameters_to_vector(lin.parameters())
        assert vec.shape == [8]
        vector_to_parameters(paddle.to_tensor(np.zeros(8, np.float32)),
                             lin.parameters())
        assert np.abs(lin.weight.numpy()).sum() == 0
        p = paddle.to_tensor(np.ones(4, np.float32), stop_gradient=False)
        (p * paddle.to_tensor(np.array([3., 4., 0., 0.],
                                       np.float32))).sum().backward()
        total = clip_grad_norm_([p], 1.0)
        np.testing.assert_allclose(float(total.numpy()), 5.0, rtol=1e-4)
        np.testing.assert_allclose(np.linalg.norm(p.grad.numpy()), 1.0,
                                   rtol=1e-3)
        clip_grad_value_([p], 0.1)
        assert np.abs(p.grad.numpy()).max() <= 0.1 + 1e-6


class TestPoolingTail:
    def test_max_pool_mask_and_unpool(self):
        x = rng.standard_normal((2, 3, 8, 8)).astype("float32")
        xt = paddle.to_tensor(x)
        out, mask = F.max_pool2d(xt, 2, 2, return_mask=True)
        flat = x.reshape(2, 3, -1)
        for b in range(2):
            for c in range(3):
                np.testing.assert_allclose(
                    flat[b, c][mask.numpy()[b, c].ravel()],
                    out.numpy()[b, c].ravel(), rtol=1e-6)
        un = F.max_unpool2d(out, mask, 2, 2)
        assert un.shape == [2, 3, 8, 8]

    def test_negative_input_padded_pool(self):
        x = paddle.to_tensor(
            -np.abs(rng.standard_normal((2, 3, 8, 8))).astype("float32")
            - 1.0)
        on, _ = F.max_pool2d(x, 3, 2, padding=1, return_mask=True)
        ref = F.max_pool2d(x, 3, 2, padding=1)
        np.testing.assert_allclose(on.numpy(), ref.numpy(), rtol=1e-6)

    def test_unpool_1d_3d(self):
        x1 = paddle.to_tensor(rng.standard_normal((2, 3, 10))
                              .astype("float32"))
        o1, m1 = F.max_pool1d(x1, 2, 2, return_mask=True)
        assert F.max_unpool1d(o1, m1, 2, 2).shape == [2, 3, 10]
        x3 = paddle.to_tensor(rng.standard_normal((1, 2, 4, 4, 4))
                              .astype("float32"))
        o3, m3 = F.max_pool3d(x3, 2, 2, return_mask=True)
        assert F.max_unpool3d(o3, m3, 2, 2).shape == [1, 2, 4, 4, 4]

    def test_lp_pool(self):
        c = paddle.to_tensor(np.full((1, 1, 4, 4), 2.0, np.float32))
        np.testing.assert_allclose(F.lp_pool2d(c, 2, 2, 2).numpy(), 4.0,
                                   rtol=1e-5)
        c1 = paddle.to_tensor(np.full((1, 1, 4), 2.0, np.float32))
        np.testing.assert_allclose(
            F.lp_pool1d(c1, 1, 2, 2).numpy(), 4.0, rtol=1e-5)

    def test_fractional_pool(self):
        x = rng.standard_normal((2, 3, 8, 8)).astype("float32")
        xt = paddle.to_tensor(x)
        out = F.fractional_max_pool2d(xt, 3, random_u=0.3)
        assert out.shape == [2, 3, 3, 3]
        out2, mask = F.fractional_max_pool2d(xt, 3, random_u=0.3,
                                             return_mask=True)
        flat = x.reshape(2, 3, -1)
        for b in range(2):
            for c in range(3):
                np.testing.assert_allclose(
                    flat[b, c][mask.numpy()[b, c].ravel()],
                    out2.numpy()[b, c].ravel(), rtol=1e-6)


class TestLossTail:
    def test_hsigmoid_default_tree(self):
        inp = paddle.to_tensor(rng.standard_normal((4, 8)).astype("float32"),
                               stop_gradient=False)
        lab = paddle.to_tensor(np.array([0, 1, 2, 3], np.int64))
        w = paddle.to_tensor(
            rng.standard_normal((3, 8)).astype("float32") * 0.1,
            stop_gradient=False)
        loss = F.hsigmoid_loss(inp, lab, 4, w)
        assert loss.shape == [4, 1] and np.isfinite(loss.numpy()).all()
        loss.sum().backward()
        assert inp.grad is not None and w.grad is not None

    def test_margin_ce_degenerates_to_plain_ce(self):
        import jax
        import jax.numpy as jnp

        logits = paddle.to_tensor(
            rng.standard_normal((6, 10)).astype("float32") * 0.1)
        lab = paddle.to_tensor(rng.integers(0, 10, (6,)).astype("int64"))
        mce = F.margin_cross_entropy(logits, lab, margin1=1.0, margin2=0.0,
                                     margin3=0.0, scale=1.0,
                                     reduction="mean")
        ref = float(jnp.mean(-jax.nn.log_softmax(logits.numpy())[
            np.arange(6), lab.numpy()]))
        np.testing.assert_allclose(float(mce.numpy()), ref, rtol=1e-4)
        loss, sm = F.margin_cross_entropy(logits, lab, return_softmax=True)
        assert sm.shape == [6, 10]

    def test_class_center_sample(self):
        lab = paddle.to_tensor(np.array([1, 5, 5, 7], np.int64))
        new_lab, sampled = F.class_center_sample(lab, 10, 6)
        s = sampled.numpy()
        assert {1, 5, 7}.issubset(set(s.tolist())) and len(s) == 6
        for orig, nl in zip([1, 5, 5, 7], new_lab.numpy()):
            assert s[nl] == orig

    def test_rrelu(self):
        xa = paddle.to_tensor(np.full((1000,), -1.0, np.float32))
        ev = F.rrelu(xa, training=False)
        np.testing.assert_allclose(ev.numpy(), -(1 / 8 + 1 / 3) / 2,
                                   rtol=1e-5)
        s = -F.rrelu(xa, training=True).numpy()
        assert (s >= 1 / 8 - 1e-6).all() and (s <= 1 / 3 + 1e-6).all()
        assert s.std() > 0.01


class TestTensorTail:
    def test_indices_and_complex(self):
        assert paddle.tril_indices(4, 4, 0).numpy().shape == (2, 10)
        assert paddle.triu_indices(3, 3, 1).numpy().shape == (2, 3)
        c = paddle.complex(paddle.to_tensor([1.0]), paddle.to_tensor([2.0]))
        assert "complex" in str(c.dtype)

    def test_fill_diagonal(self):
        x = paddle.to_tensor(np.zeros((3, 3), np.float32))
        x.fill_diagonal_(5.0)
        np.testing.assert_allclose(x.numpy(), np.eye(3) * 5)
        y = paddle.to_tensor(np.zeros((4, 4), np.float32))
        o = paddle.fill_diagonal_tensor(
            y, paddle.to_tensor(np.array([1., 2., 3., 4.], np.float32)))
        np.testing.assert_allclose(np.diag(o.numpy()), [1, 2, 3, 4])

    def test_reduce_as(self):
        big = paddle.to_tensor(rng.standard_normal((2, 3, 4))
                               .astype("float32"))
        tgt = paddle.to_tensor(np.zeros((3, 1), np.float32))
        r = paddle.reduce_as(big, tgt)
        np.testing.assert_allclose(
            r.numpy(), big.numpy().sum(0).sum(-1, keepdims=True), rtol=1e-5)

    def test_edit_distance(self):
        ed, n = paddle.edit_distance(
            paddle.to_tensor(np.array([[1, 2, 3]], np.int64)),
            paddle.to_tensor(np.array([[1, 3, 3]], np.int64)),
            normalized=False)
        np.testing.assert_allclose(ed.numpy(), [[1.0]])
        assert int(n.numpy()[0]) == 1

    def test_clip_by_norm_svdvals_gamma(self):
        cb = paddle.clip_by_norm(
            paddle.to_tensor(np.array([3.0, 4.0], np.float32)), 1.0)
        np.testing.assert_allclose(np.linalg.norm(cb.numpy()), 1.0,
                                   rtol=1e-5)
        sv = paddle.linalg.svdvals(paddle.to_tensor(
            np.diag([3., 2., 1.]).astype("float32")))
        np.testing.assert_allclose(sv.numpy(), [3, 2, 1], rtol=1e-5)
        g = paddle.standard_gamma(
            paddle.to_tensor(np.full((2000,), 2.0, np.float32)))
        assert abs(g.numpy().mean() - 2.0) < 0.3


class TestSoftmaxMaskFuse:
    def test_fused_softmax_mask(self):
        import jax

        x = rng.standard_normal((2, 2, 4, 4)).astype("float32")
        m = np.where(rng.random((2, 1, 4, 4)) > 0.5, 0.0,
                     -1e9).astype("float32")
        out = paddle.incubate.softmax_mask_fuse(
            paddle.to_tensor(x), paddle.to_tensor(m))
        ref = np.asarray(jax.nn.softmax(x + m, axis=-1))
        np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-6)

    def test_fused_softmax_mask_upper_triangle(self):
        x = rng.standard_normal((1, 2, 5, 5)).astype("float32")
        out = paddle.incubate.softmax_mask_fuse_upper_triangle(
            paddle.to_tensor(x)).numpy()
        # rows sum to 1; strictly-upper entries are 0
        np.testing.assert_allclose(out.sum(-1), 1.0, rtol=1e-5)
        assert np.abs(np.triu(out[0, 0], 1)).max() < 1e-6


class TestCOpsSurface:
    def test_audit_tool_passes(self):
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        from tools import op_audit

        if not os.path.exists(op_audit.OPS_YAML):
            pytest.skip(f"reference tree not mounted ({op_audit.OPS_YAML})")
        r = subprocess.run(
            [sys.executable, "tools/op_audit.py"], capture_output=True,
            text=True, cwd=repo,
            env={"PYTHONPATH": repo, "JAX_PLATFORMS": "cpu",
                 "PATH": os.environ.get("PATH", "")})
        assert r.returncode == 0, r.stdout + r.stderr
        assert "resolution: 9" in r.stdout  # >= 90%

    def test_optimizer_kernels(self):
        import paddle_tpu._C_ops as C

        p = paddle.to_tensor(np.ones(4, np.float32))
        g = paddle.to_tensor(np.full(4, 0.5, np.float32))
        C.sgd_(p, paddle.to_tensor(np.float32(0.1)), g)
        np.testing.assert_allclose(p.numpy(), 0.95)
        m1 = paddle.to_tensor(np.zeros(4, np.float32))
        m2 = paddle.to_tensor(np.zeros(4, np.float32))
        b1 = paddle.to_tensor(np.float32(1.0))
        b2 = paddle.to_tensor(np.float32(1.0))
        C.adam_(p, g, paddle.to_tensor(np.float32(0.1)), m1, m2, b1, b2)
        assert np.isfinite(p.numpy()).all()
        np.testing.assert_allclose(b1.numpy(), 0.9, rtol=1e-6)

    def test_misc_kernels(self):
        import paddle_tpu._C_ops as C

        out = C.hinge_loss(
            paddle.to_tensor(np.array([0.5, -0.5], np.float32)),
            paddle.to_tensor(np.array([1.0, 1.0], np.float32)))
        np.testing.assert_allclose(out.numpy(), [0.5, 1.5])
        al = C.ctc_align(paddle.to_tensor(
            np.array([[1, 1, 0, 2, 2, 0, 3]], np.int32)))
        np.testing.assert_allclose(al.numpy(), [[1, 2, 3]])
        cnt = C.number_count(
            paddle.to_tensor(np.array([0, 1, 1, 2], np.int64)), 4)
        np.testing.assert_allclose(cnt.numpy(), [1, 2, 1, 0])
        mi, _ = C.bipartite_match(paddle.to_tensor(
            np.array([[0.9, 0.1], [0.2, 0.8]], np.float32)))
        np.testing.assert_allclose(mi.numpy(), [[0, 1]])
        d = C.dirichlet(paddle.to_tensor(
            np.array([1.0, 2.0, 3.0], np.float32)))
        np.testing.assert_allclose(d.numpy().sum(), 1.0, rtol=1e-5)

    def test_warprnnt_lattice(self):
        import paddle_tpu._C_ops as C

        r = C.warprnnt(
            paddle.to_tensor(rng.standard_normal((1, 5, 3, 4))
                             .astype("float32")),
            paddle.to_tensor(np.array([[1, 2]], np.int32)),
            paddle.to_tensor(np.array([5], np.int32)),
            paddle.to_tensor(np.array([2], np.int32)))
        assert np.isfinite(r.numpy()).all() and float(r.numpy()) > 0

    def test_fake_quant_family(self):
        import paddle_tpu._C_ops as C

        x = paddle.to_tensor(rng.standard_normal((4, 4)).astype("float32"))
        q, s = C.fake_quantize_abs_max(x)
        assert np.abs(q.numpy()).max() <= 127
        dq, s2 = C.fake_quantize_dequantize_abs_max(x)
        assert np.abs(dq.numpy() - x.numpy()).max() < 0.05
        qc, sc = C.fake_channel_wise_quantize_abs_max(x)
        assert sc.shape == [4]


class TestTextDatasets:
    def test_uci_housing_local(self, tmp_path):
        import paddle_tpu.text.datasets as TD

        rng2 = np.random.default_rng(0)
        raw = np.concatenate([rng2.random((500, 13)),
                              rng2.random((500, 1)) * 50], axis=1)
        f = tmp_path / "housing.data"
        np.savetxt(f, raw)
        train = TD.UCIHousing(data_file=str(f), mode="train")
        test = TD.UCIHousing(data_file=str(f), mode="test")
        assert len(train) == 406 and len(test) == 94
        x, y = train[0]
        assert x.shape == (13,) and y.shape == (1,)

    def test_wmt14_pairs(self, tmp_path):
        import paddle_tpu.text.datasets as TD

        (tmp_path / "s.en").write_text("hello world\nfoo bar baz\n")
        (tmp_path / "t.fr").write_text("bonjour monde\nfu barre base\n")
        ds = TD.WMT14(src_file=str(tmp_path / "s.en"),
                      trg_file=str(tmp_path / "t.fr"))
        assert len(ds) == 2
        src, trg, nxt = ds[0]
        assert trg[0] == ds.trg_dict["<s>"] and nxt[-1] == ds.trg_dict["<e>"]
        assert len(trg) == len(nxt)

    def test_imikolov_ngram(self, tmp_path):
        import tarfile

        import paddle_tpu.text.datasets as TD

        data = tmp_path / "data"
        data.mkdir()
        (data / "ptb.train.txt").write_text(
            "the cat sat\nthe dog sat\n" * 30)
        (data / "ptb.valid.txt").write_text("the cat sat\n")
        tar = tmp_path / "simple-examples.tgz"
        with tarfile.open(tar, "w:gz") as tf:
            tf.add(data / "ptb.train.txt", "simple-examples/data/ptb.train.txt")
            tf.add(data / "ptb.valid.txt", "simple-examples/data/ptb.valid.txt")
        ds = TD.Imikolov(data_file=str(tar), data_type="NGRAM",
                         window_size=3, mode="train", min_word_freq=10)
        assert len(ds) > 0
        assert all(g.shape == (3,) for g in [ds[0], ds[1]])

    def test_download_refused(self):
        import paddle_tpu.text.datasets as TD

        with pytest.raises(RuntimeError):
            TD.Imdb(download=True)
        with pytest.raises(RuntimeError):
            TD.UCIHousing()


class TestNamespaceBatch:
    def test_regularizer_applies_before_clip(self):
        from paddle_tpu import regularizer

        lin = paddle.nn.Linear(
            4, 4, weight_attr=paddle.ParamAttr(
                regularizer=regularizer.L2Decay(0.5)))
        w0 = lin.weight.numpy().copy()
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=lin.parameters())
        loss = lin(paddle.to_tensor(np.zeros((2, 4), np.float32))).sum()
        loss.backward()
        opt.step()
        np.testing.assert_allclose(lin.weight.numpy(),
                                   w0 * (1 - 0.1 * 0.5), rtol=1e-5)
        g = regularizer.L1Decay(0.3)(
            paddle.to_tensor(np.array([2.0, -3.0], np.float32)))
        np.testing.assert_allclose(g.numpy(), [0.3, -0.3])

    def test_reader_decorators(self):
        r = lambda: iter(range(10))  # noqa: E731
        assert [b for b in paddle.batch(r, 3)()][0] == [0, 1, 2]
        assert len([b for b in paddle.batch(r, 3, drop_last=True)()]) == 3
        assert sorted(x for x in paddle.reader.shuffle(r, 5)()) == \
            list(range(10))
        comp = [x for x in paddle.reader.compose(
            lambda: iter([1, 2]), lambda: iter([(3, 4), (5, 6)]))()]
        assert comp == [(1, 3, 4), (2, 5, 6)]

    def test_version_and_misc(self):
        assert paddle.__version__ == paddle.version.full_version
        assert paddle.in_dynamic_mode() is True
        paddle.disable_signal_handler()
        assert paddle.sysconfig.get_include().endswith("native")

    def test_histogramdd_cauchy_geometric(self):
        h, edges = paddle.histogramdd(
            paddle.to_tensor(rng.standard_normal((100, 2))
                             .astype("float32")), bins=4)
        assert h.shape == [4, 4] and len(edges) == 2
        assert float(h.numpy().sum()) == 100
        t = paddle.to_tensor(np.zeros(1000, np.float32))
        t.geometric_(0.5)
        assert t.numpy().min() >= 1 and 1.5 < t.numpy().mean() < 2.5

    def test_hub_local(self, tmp_path):
        (tmp_path / "hubconf.py").write_text(
            "def tiny(scale=1):\n    'doc'\n    return scale * 2\n")
        assert paddle.hub.list(str(tmp_path)) == ["tiny"]
        assert paddle.hub.load(str(tmp_path), "tiny", scale=3) == 6
        with pytest.raises(RuntimeError):
            paddle.hub.load("org/repo", "m", source="github")
