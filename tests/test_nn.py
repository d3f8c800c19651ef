"""nn.Layer / layers / functional tests with NumPy (and analytic) oracles
(reference test model: test/legacy_test op tests + imperative layer tests)."""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def check(t, ref, rtol=1e-4, atol=1e-5):
    np.testing.assert_allclose(np.asarray(t.numpy(), np.float64), ref, rtol=rtol, atol=atol)


class TestLayerBase:
    def test_registration_and_traversal(self):
        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = nn.Linear(4, 8)
                self.fc2 = nn.Linear(8, 2)

            def forward(self, x):
                return self.fc2(F.relu(self.fc1(x)))

        net = Net()
        names = [n for n, _ in net.named_parameters()]
        assert names == ["fc1.weight", "fc1.bias", "fc2.weight", "fc2.bias"]
        assert len(net.parameters()) == 4
        assert len(net.sublayers()) == 2

    def test_state_dict_roundtrip(self, tmp_path):
        net = nn.Linear(3, 3)
        sd = net.state_dict()
        assert set(sd.keys()) == {"weight", "bias"}
        paddle.save(sd, str(tmp_path / "m.pdparams"))
        net2 = nn.Linear(3, 3)
        missing, unexpected = net2.set_state_dict(paddle.load(str(tmp_path / "m.pdparams")))
        assert missing == [] and unexpected == []
        np.testing.assert_array_equal(net2.weight.numpy(), net.weight.numpy())

    def test_train_eval_mode(self):
        net = nn.Sequential(nn.Linear(2, 2), nn.Dropout(0.5))
        assert net.training
        net.eval()
        assert not net[1].training
        x = paddle.ones([4, 2])
        out1, out2 = net(x), net(x)
        np.testing.assert_array_equal(out1.numpy(), out2.numpy())  # no dropout in eval

    def test_forward_hooks(self):
        net = nn.Linear(2, 2)
        calls = []
        h = net.register_forward_post_hook(lambda l, i, o: calls.append("post"))
        net(paddle.ones([1, 2]))
        assert calls == ["post"]
        h.remove()
        net(paddle.ones([1, 2]))
        assert calls == ["post"]

    def test_layer_to_dtype(self):
        net = nn.Linear(2, 2)
        net.to(dtype="bfloat16")
        assert str(net.weight.dtype) == "bfloat16"


class TestCoreLayers:
    def setup_method(self, _):
        self.rng = np.random.RandomState(0)
        paddle.seed(0)

    def test_linear_matches_numpy(self):
        x = self.rng.rand(5, 3).astype(np.float32)
        layer = nn.Linear(3, 4)
        out = layer(paddle.to_tensor(x))
        ref = x @ layer.weight.numpy() + layer.bias.numpy()
        check(out, ref)

    def test_embedding(self):
        emb = nn.Embedding(10, 4, padding_idx=0)
        ids = paddle.to_tensor([[1, 2], [0, 3]], dtype="int32")
        out = emb(ids)
        assert out.shape == [2, 2, 4]
        np.testing.assert_array_equal(out.numpy()[1, 0], np.zeros(4))  # padding row

    def test_conv2d_matches_torch_formula(self):
        import torch
        import torch.nn.functional as tF

        x = self.rng.rand(2, 3, 8, 8).astype(np.float32)
        conv = nn.Conv2D(3, 5, 3, stride=2, padding=1)
        out = conv(paddle.to_tensor(x))
        ref = tF.conv2d(
            torch.tensor(x), torch.tensor(conv.weight.numpy()),
            torch.tensor(conv.bias.numpy()), stride=2, padding=1,
        ).numpy()
        check(out, ref, rtol=1e-3, atol=1e-4)

    def test_conv2d_transpose(self):
        import torch
        import torch.nn.functional as tF

        x = self.rng.rand(2, 4, 5, 5).astype(np.float32)
        conv = nn.Conv2DTranspose(4, 3, 3, stride=2, padding=1, output_padding=1)
        out = conv(paddle.to_tensor(x))
        ref = tF.conv_transpose2d(
            torch.tensor(x), torch.tensor(conv.weight.numpy()),
            torch.tensor(conv.bias.numpy()), stride=2, padding=1, output_padding=1,
        ).numpy()
        assert out.shape == list(ref.shape)
        check(out, ref, rtol=1e-3, atol=1e-4)

    def test_depthwise_conv(self):
        import torch
        import torch.nn.functional as tF

        x = self.rng.rand(1, 4, 6, 6).astype(np.float32)
        conv = nn.Conv2D(4, 4, 3, groups=4, padding=1)
        out = conv(paddle.to_tensor(x))
        ref = tF.conv2d(torch.tensor(x), torch.tensor(conv.weight.numpy()),
                        torch.tensor(conv.bias.numpy()), padding=1, groups=4).numpy()
        check(out, ref, rtol=1e-3, atol=1e-4)

    def test_batchnorm_train_and_eval(self):
        bn = nn.BatchNorm2D(3)
        x = self.rng.rand(4, 3, 5, 5).astype(np.float32) * 2 + 1
        out = bn(paddle.to_tensor(x))
        # training: normalized by batch stats
        np.testing.assert_allclose(out.numpy().mean(axis=(0, 2, 3)), 0, atol=1e-5)
        np.testing.assert_allclose(out.numpy().std(axis=(0, 2, 3)), 1, atol=1e-2)
        # running stats moved toward batch stats
        assert not np.allclose(bn._mean.numpy(), 0)
        bn.eval()
        out_eval = bn(paddle.to_tensor(x))
        assert out_eval.shape == [4, 3, 5, 5]

    def test_layernorm(self):
        ln = nn.LayerNorm(6)
        x = self.rng.rand(2, 4, 6).astype(np.float32)
        out = ln(paddle.to_tensor(x))
        m = x.mean(-1, keepdims=True)
        v = x.var(-1, keepdims=True)
        ref = (x - m) / np.sqrt(v + 1e-5) * ln.weight.numpy() + ln.bias.numpy()
        check(out, ref, rtol=1e-3, atol=1e-4)

    def test_rmsnorm(self):
        rms = nn.RMSNorm(8)
        x = self.rng.rand(3, 8).astype(np.float32)
        out = rms(paddle.to_tensor(x))
        ref = x / np.sqrt((x**2).mean(-1, keepdims=True) + 1e-6) * rms.weight.numpy()
        check(out, ref, rtol=1e-4)

    def test_groupnorm(self):
        gn = nn.GroupNorm(2, 4)
        x = self.rng.rand(2, 4, 3, 3).astype(np.float32)
        out = gn(paddle.to_tensor(x))
        xr = x.reshape(2, 2, 2, 3, 3)
        m = xr.mean(axis=(2, 3, 4), keepdims=True)
        v = xr.var(axis=(2, 3, 4), keepdims=True)
        ref = ((xr - m) / np.sqrt(v + 1e-5)).reshape(2, 4, 3, 3)
        check(out, ref, rtol=1e-3, atol=1e-4)

    def test_pooling(self):
        x = self.rng.rand(1, 2, 4, 4).astype(np.float32)
        mp = nn.MaxPool2D(2)(paddle.to_tensor(x))
        ap = nn.AvgPool2D(2)(paddle.to_tensor(x))
        ref_max = x.reshape(1, 2, 2, 2, 2, 2).max(axis=(3, 5))
        ref_avg = x.reshape(1, 2, 2, 2, 2, 2).mean(axis=(3, 5))
        check(mp, ref_max)
        check(ap, ref_avg)
        gap = nn.AdaptiveAvgPool2D(1)(paddle.to_tensor(x))
        check(gap, x.mean(axis=(2, 3), keepdims=True))

    def test_activations(self):
        x = self.rng.randn(4, 5).astype(np.float32)
        t = paddle.to_tensor(x)
        check(F.relu(t), np.maximum(x, 0))
        check(F.gelu(t), 0.5 * x * (1 + np.vectorize(np.math.erf if hasattr(np, "math") else __import__("math").erf)(x / np.sqrt(2))), rtol=1e-3, atol=1e-4)
        check(F.silu(t), x / (1 + np.exp(-x)), rtol=1e-4)
        check(F.leaky_relu(t, 0.1), np.where(x > 0, x, 0.1 * x))
        sm = F.softmax(t, axis=-1).numpy()
        np.testing.assert_allclose(sm.sum(-1), 1, rtol=1e-5)

    def test_dropout_train_scales(self):
        paddle.seed(7)
        x = paddle.ones([1000])
        out = F.dropout(x, p=0.5, training=True)
        kept = out.numpy()[out.numpy() != 0]
        np.testing.assert_allclose(kept, 2.0, rtol=1e-6)  # upscale_in_train
        assert 300 < (out.numpy() == 0).sum() < 700


class TestLosses:
    def setup_method(self, _):
        self.rng = np.random.RandomState(1)

    def test_cross_entropy(self):
        logits = self.rng.randn(4, 5).astype(np.float32)
        labels = np.array([0, 2, 4, 1])
        loss = F.cross_entropy(paddle.to_tensor(logits), paddle.to_tensor(labels, dtype="int32"))
        # numpy oracle
        e = np.exp(logits - logits.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        ref = -np.log(p[np.arange(4), labels]).mean()
        check(loss, ref, rtol=1e-4)

    def test_cross_entropy_ignore_index(self):
        logits = self.rng.randn(4, 5).astype(np.float32)
        labels = np.array([0, -100, 4, -100])
        loss = F.cross_entropy(paddle.to_tensor(logits), paddle.to_tensor(labels, dtype="int32"), ignore_index=-100)
        e = np.exp(logits - logits.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        ref = -np.log(p[[0, 2], [0, 4]]).mean()
        check(loss, ref, rtol=1e-4)

    def test_soft_label_and_smoothing(self):
        logits = self.rng.randn(3, 4).astype(np.float32)
        soft = np.full((3, 4), 0.25, np.float32)
        loss = F.cross_entropy(paddle.to_tensor(logits), paddle.to_tensor(soft), soft_label=True)
        e = np.exp(logits - logits.max(-1, keepdims=True))
        logp = np.log(e / e.sum(-1, keepdims=True))
        check(loss, -(soft * logp).sum(-1).mean(), rtol=1e-4)

    def test_mse_l1(self):
        a = self.rng.rand(3, 4).astype(np.float32)
        b = self.rng.rand(3, 4).astype(np.float32)
        check(F.mse_loss(paddle.to_tensor(a), paddle.to_tensor(b)), ((a - b) ** 2).mean(), rtol=1e-5)
        check(F.l1_loss(paddle.to_tensor(a), paddle.to_tensor(b)), np.abs(a - b).mean(), rtol=1e-5)

    def test_bce_with_logits(self):
        z = self.rng.randn(6).astype(np.float32)
        y = (self.rng.rand(6) > 0.5).astype(np.float32)
        loss = F.binary_cross_entropy_with_logits(paddle.to_tensor(z), paddle.to_tensor(y))
        p = 1 / (1 + np.exp(-z))
        ref = -(y * np.log(p) + (1 - y) * np.log(1 - p)).mean()
        check(loss, ref, rtol=1e-4)

    def test_grad_through_loss(self):
        layer = nn.Linear(3, 2)
        x = paddle.to_tensor(self.rng.rand(4, 3).astype(np.float32))
        y = paddle.to_tensor(np.array([0, 1, 0, 1]), dtype="int32")
        loss = F.cross_entropy(layer(x), y)
        loss.backward()
        assert layer.weight.grad is not None
        assert layer.weight.grad.shape == [3, 2]


class TestAttention:
    def test_sdpa_matches_reference(self):
        rng = np.random.RandomState(2)
        q = rng.rand(2, 5, 3, 8).astype(np.float32)  # [B,S,H,D]
        k = rng.rand(2, 5, 3, 8).astype(np.float32)
        v = rng.rand(2, 5, 3, 8).astype(np.float32)
        out = F.scaled_dot_product_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v)
        )
        # numpy oracle
        qh = q.transpose(0, 2, 1, 3)
        kh = k.transpose(0, 2, 1, 3)
        vh = v.transpose(0, 2, 1, 3)
        s = qh @ kh.transpose(0, 1, 3, 2) / np.sqrt(8)
        e = np.exp(s - s.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        ref = (p @ vh).transpose(0, 2, 1, 3)
        check(out, ref, rtol=1e-3, atol=1e-4)

    def test_causal_masking(self):
        rng = np.random.RandomState(3)
        q = rng.rand(1, 4, 1, 4).astype(np.float32)
        k = rng.rand(1, 4, 1, 4).astype(np.float32)
        v = rng.rand(1, 4, 1, 4).astype(np.float32)
        out = F.scaled_dot_product_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v), is_causal=True
        )
        # row 0 attends only to col 0 -> equals v[0]
        np.testing.assert_allclose(out.numpy()[0, 0, 0], v[0, 0, 0], rtol=1e-4)

    def test_multihead_attention_layer(self):
        mha = nn.MultiHeadAttention(16, 4)
        x = paddle.randn([2, 6, 16])
        out = mha(x)
        assert out.shape == [2, 6, 16]

    def test_transformer_encoder(self):
        enc_layer = nn.TransformerEncoderLayer(16, 2, 32, dropout=0.0)
        enc = nn.TransformerEncoder(enc_layer, 2)
        out = enc(paddle.randn([2, 5, 16]))
        assert out.shape == [2, 5, 16]

    def test_flashmask_causal_equiv(self):
        """flashmask with trivial indices == plain causal attention."""
        rng = np.random.RandomState(4)
        B, S, H, D = 1, 6, 2, 4
        q = paddle.to_tensor(rng.rand(B, S, H, D).astype(np.float32))
        k = paddle.to_tensor(rng.rand(B, S, H, D).astype(np.float32))
        v = paddle.to_tensor(rng.rand(B, S, H, D).astype(np.float32))
        # start index S for every column: nothing extra masked beyond causal
        idx = paddle.full([B, 1, S, 1], S, dtype="int32")
        out_fm = F.flashmask_attention(q, k, v, idx, causal=True)
        out_ref = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        check(out_fm, out_ref.numpy(), rtol=1e-4, atol=1e-5)


class TestOptimizers:
    def _train(self, opt_cls, **kw):
        paddle.seed(0)
        rng = np.random.RandomState(0)
        net = nn.Linear(4, 1)
        X = paddle.to_tensor(rng.rand(32, 4).astype(np.float32))
        w_true = np.array([[1.0], [-2.0], [3.0], [0.5]], np.float32)
        y = paddle.to_tensor(rng.rand(32, 4).astype(np.float32) @ w_true)
        X = paddle.to_tensor(rng.rand(32, 4).astype(np.float32))
        y = paddle.matmul(X, paddle.to_tensor(w_true))
        opt = opt_cls(parameters=net.parameters(), **kw)
        first = None
        for i in range(60):
            loss = F.mse_loss(net(X), y)
            if first is None:
                first = float(loss.numpy())
            loss.backward()
            opt.step()
            opt.clear_grad()
        return first, float(loss.numpy())

    @pytest.mark.parametrize("cls,kw", [
        ("SGD", {"learning_rate": 0.1}),
        ("Momentum", {"learning_rate": 0.1, "momentum": 0.9}),
        ("Adam", {"learning_rate": 0.05}),
        ("AdamW", {"learning_rate": 0.05, "weight_decay": 0.01}),
        ("RMSProp", {"learning_rate": 0.01}),
        ("Lamb", {"learning_rate": 0.1}),
        ("NAdam", {"learning_rate": 0.05}),
        ("RAdam", {"learning_rate": 0.05}),
        ("Rprop", {"learning_rate": 0.001}),
        ("ASGD", {"learning_rate": 0.05, "batch_num": 2}),
    ])
    def test_optimizers_reduce_loss(self, cls, kw):
        first, last = self._train(getattr(paddle.optimizer, cls), **kw)
        assert last < first * 0.2, f"{cls}: {first} -> {last}"

    def test_adam_matches_reference_formula(self):
        p0 = np.array([1.0, 2.0], np.float32)
        g = np.array([0.1, -0.2], np.float32)
        p = paddle.to_tensor(p0.copy())
        p.stop_gradient = False
        param = paddle.framework.core.Parameter(p._value)
        param.grad = paddle.to_tensor(g)
        opt = paddle.optimizer.Adam(learning_rate=0.1, parameters=[param])
        opt.step()
        m = 0.1 * g
        v = 0.001 * g * g
        mh = m / (1 - 0.9)
        vh = v / (1 - 0.999)
        ref = p0 - 0.1 * mh / (np.sqrt(vh) + 1e-8)
        np.testing.assert_allclose(param.numpy(), ref, rtol=1e-5)

    def test_grad_clip_global_norm(self):
        net = nn.Linear(2, 2)
        clip = nn.ClipGradByGlobalNorm(0.1)
        opt = paddle.optimizer.SGD(learning_rate=0.0, parameters=net.parameters(), grad_clip=clip)
        loss = (net(paddle.ones([1, 2])) * 100).sum()
        loss.backward()
        # apply clip manually to inspect
        pg = [(p, p.grad) for p in net.parameters() if p.grad is not None]
        clipped = clip(pg)
        total = np.sqrt(sum((g.numpy() ** 2).sum() for _, g in clipped))
        assert total <= 0.1 + 1e-5

    def test_adamw_moment_dtype_matches_f32_compute(self):
        """bf16-stored moments with f32 update compute should track the
        all-f32 AdamW closely on an f32 param."""
        rng = np.random.default_rng(0)
        w0 = rng.normal(size=(32, 32)).astype(np.float32)

        def run(moment_dtype):
            w = paddle.to_tensor(w0.copy())
            w.stop_gradient = False
            o = paddle.optimizer.AdamW(learning_rate=1e-2, parameters=[w],
                                       moment_dtype=moment_dtype)
            for i in range(5):
                ((w * w).sum()).backward()
                o.step()
                o.clear_grad()
            return w.numpy()

        ref = run(None)
        low = run("bfloat16")
        assert np.max(np.abs(ref - low)) < 1e-2, np.max(np.abs(ref - low))

    def test_lr_scheduler(self):
        sched = paddle.optimizer.lr.StepDecay(0.1, step_size=2, gamma=0.5)
        opt = paddle.optimizer.SGD(learning_rate=sched, parameters=[paddle.framework.core.Parameter(paddle.zeros([1])._value)])
        lrs = []
        for _ in range(5):
            lrs.append(opt.get_lr())
            sched.step()
        np.testing.assert_allclose(lrs, [0.1, 0.1, 0.05, 0.05, 0.025], rtol=1e-6)

    def test_cosine_warmup(self):
        cos = paddle.optimizer.lr.CosineAnnealingDecay(0.1, T_max=10)
        warm = paddle.optimizer.lr.LinearWarmup(cos, warmup_steps=5, start_lr=0.0, end_lr=0.1)
        vals = []
        for _ in range(8):
            vals.append(warm())
            warm.step()
        assert vals[0] == 0.0 and abs(vals[4] - 0.08) < 1e-6
        assert vals[6] < 0.1  # cosine decay began

    def test_lbfgs_solves_quadratic(self):
        """LBFGS (closure-based, strong-Wolfe) drives a linear least-squares
        problem to ~0 in a few outer steps (reference optimizer/lbfgs.py)."""
        paddle.seed(0)
        rng = np.random.default_rng(0)
        X = paddle.to_tensor(rng.normal(size=(32, 4)).astype(np.float32))
        W = rng.normal(size=(4, 1)).astype(np.float32)
        Y = paddle.to_tensor((X.numpy() @ W).astype(np.float32))
        m = paddle.nn.Linear(4, 1)
        mse = paddle.nn.MSELoss()
        o = paddle.optimizer.LBFGS(learning_rate=1.0, max_iter=10,
                                   line_search_fn="strong_wolfe",
                                   parameters=m.parameters())

        def closure():
            o.clear_grad()
            loss = mse(m(X), Y)
            loss.backward()
            return loss

        l0 = float(closure().numpy())
        for _ in range(3):
            loss = o.step(closure)
        assert float(loss.numpy()) < l0 * 1e-3

    def test_optimizer_state_dict(self):
        net = nn.Linear(2, 2)
        opt = paddle.optimizer.Adam(learning_rate=0.1, parameters=net.parameters())
        loss = net(paddle.ones([1, 2])).sum()
        loss.backward()
        opt.step()
        sd = opt.state_dict()
        assert sd["_step_count"] == 1
        opt2 = paddle.optimizer.Adam(learning_rate=0.1, parameters=net.parameters())
        opt2.set_state_dict(sd)
        assert opt2._step_count == 1


class TestAmp:
    def test_autocast_casts_matmul(self):
        with paddle.amp.auto_cast(dtype="bfloat16"):
            a = paddle.ones([4, 4])
            out = paddle.matmul(a, a)
        assert str(out.dtype) == "bfloat16"

    def test_autocast_keeps_blacklist_f32(self):
        with paddle.amp.auto_cast(dtype="bfloat16"):
            x = paddle.ones([4], dtype="bfloat16")
            out = paddle.nn.functional.softmax(x)
        assert out.dtype == np.float32

    def test_grad_scaler_noop_path(self):
        net = nn.Linear(2, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
        scaler = paddle.amp.GradScaler(init_loss_scaling=1.0)
        loss = net(paddle.ones([3, 2])).sum()
        scaled = scaler.scale(loss)
        scaled.backward()
        scaler.step(opt)
        scaler.update()
        assert net.weight.grad is None or True  # step ran without error

    def test_grad_scaler_skips_on_inf(self):
        net = nn.Linear(2, 1)
        w0 = net.weight.numpy().copy()
        opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
        scaler = paddle.amp.GradScaler(init_loss_scaling=4.0)
        loss = net(paddle.ones([1, 2])).sum()
        scaler.scale(loss).backward()
        net.weight.grad._value = net.weight.grad._value.at[0, 0].set(np.inf)
        scaler.step(opt)
        np.testing.assert_array_equal(net.weight.numpy(), w0)  # skipped
        assert scaler._scale < 4.0  # backed off

    def test_grad_scaler_no_double_unscale(self):
        # unscale_/clip/step pattern: step() must not divide grads by the
        # scale a second time (reference grad_scaler.py:354-373).
        net = nn.Linear(2, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.0, parameters=net.parameters())
        scaler = paddle.amp.GradScaler(init_loss_scaling=1024.0)
        loss = net(paddle.ones([1, 2])).sum()
        scaler.scale(loss).backward()
        scaler.unscale_(opt)
        g_after_unscale = net.weight.grad.numpy().copy()
        scaler.step(opt)  # must NOT unscale again
        np.testing.assert_allclose(
            net.weight.grad.numpy(), g_after_unscale, rtol=1e-6)
        scaler.update()
        # a second explicit unscale_ before the next update() raises
        scaler.scale(net(paddle.ones([1, 2])).sum()).backward()
        scaler.unscale_(opt)
        with pytest.raises(RuntimeError):
            scaler.unscale_(opt)

    def test_decorate_o2(self):
        net = nn.Sequential(nn.Linear(2, 2), nn.LayerNorm(2))
        paddle.amp.decorate(net, level="O2", dtype="bfloat16")
        assert str(net[0].weight.dtype) == "bfloat16"
        assert net[1].weight.dtype == np.float32  # norm stays f32
