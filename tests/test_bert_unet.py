"""BERT + diffusion UNet model families (north-star configs: "BERT-base /
ERNIE-1.0 pretraining (fleet data-parallel only)" and "Stable Diffusion
UNet: conv + cross-attn")."""

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
import paddle_tpu.distributed as dist
from paddle_tpu.models import (
    BertForPretraining,
    BertForSequenceClassification,
    BertModel,
    BertPretrainingCriterion,
    UNetModel,
    bert_tiny,
    unet_tiny,
)


class TestBert:
    def test_model_shapes_and_mask(self):
        paddle.seed(0)
        cfg = bert_tiny()
        m = BertModel(cfg)
        m.eval()
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (2, 16)))
        tt = paddle.to_tensor((rng.random((2, 16)) > 0.5).astype(np.int32))
        am = np.ones((2, 16), np.int32)
        am[1, 8:] = 0  # padding on lane 1
        seq, pooled = m(ids, tt, paddle.to_tensor(am))
        assert tuple(seq.shape) == (2, 16, cfg.hidden_size)
        assert tuple(pooled.shape) == (2, cfg.hidden_size)
        # masked positions must not influence lane 1's pooled output
        ids2 = ids.numpy().copy()
        ids2[1, 8:] = (ids2[1, 8:] + 7) % cfg.vocab_size
        _, pooled2 = m(paddle.to_tensor(ids2), tt, paddle.to_tensor(am))
        np.testing.assert_allclose(pooled.numpy()[1], pooled2.numpy()[1],
                                   atol=1e-5)

    def test_pretraining_loss_decreases(self):
        paddle.seed(0)
        cfg = bert_tiny()
        model = BertForPretraining(cfg)
        crit = BertPretrainingCriterion(cfg)
        model.train()
        o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
        rng = np.random.default_rng(1)
        ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (4, 32)))
        mpos = paddle.to_tensor(rng.integers(0, 32, (4, 6)))
        mlab = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (4, 6)))
        nsp = paddle.to_tensor(rng.integers(0, 2, (4,)))
        losses = []
        for _ in range(6):
            mlm, nspl = model(ids, masked_positions=mpos)
            loss = crit(mlm, nspl, mlab, nsp)
            loss.backward()
            o.step(); o.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0], losses
        # MLM head gathers masked slots only: [B, M, V], not [B, S, V]
        assert tuple(mlm.shape) == (4, 6, cfg.vocab_size)

    def test_mlm_ignore_index(self):
        cfg = bert_tiny()
        crit = BertPretrainingCriterion(cfg)
        mlm = paddle.to_tensor(np.zeros((1, 3, cfg.vocab_size), np.float32))
        nsp = paddle.to_tensor(np.zeros((1, 2), np.float32))
        lab_all = paddle.to_tensor(np.array([[1, 2, 3]]))
        lab_ign = paddle.to_tensor(np.array([[1, -100, -100]]))
        nl = paddle.to_tensor(np.array([0]))
        l1 = float(crit(mlm, nsp, lab_all, nl).numpy())
        l2 = float(crit(mlm, nsp, lab_ign, nl).numpy())
        np.testing.assert_allclose(l1, l2, rtol=1e-6)  # uniform logits

    def test_sequence_classification_dp_trains(self):
        """BERT fine-tuning through the compiled DP step (the BASELINE
        fleet-data-parallel config)."""
        paddle.seed(0)
        cfg = bert_tiny()
        model = BertForSequenceClassification(cfg, num_classes=2)
        ce = nn.CrossEntropyLoss()
        model.train()
        mesh = dist.build_mesh(dp=4)
        step = dist.DistributedTrainStep(
            model, lambda lg, lb: ce(lg, lb),
            opt.AdamW(learning_rate=5e-4, parameters=model.parameters()),
            mesh=mesh)
        rng = np.random.default_rng(2)
        ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (8, 16)))
        y = paddle.to_tensor(rng.integers(0, 2, (8,)))
        losses = [float(step(ids, y)) for _ in range(5)]
        dist.env.set_global_mesh(None)
        assert losses[-1] < losses[0], losses


class TestUNet:
    def test_forward_shape_and_context(self):
        paddle.seed(0)
        cfg = unet_tiny()
        m = UNetModel(cfg)
        m.eval()
        rng = np.random.default_rng(0)
        x = paddle.to_tensor(rng.normal(size=(2, 3, 16, 16)).astype(np.float32))
        t = paddle.to_tensor(np.array([3, 500]))
        ctx = paddle.to_tensor(rng.normal(size=(2, 5, cfg.context_dim))
                               .astype(np.float32))
        out = m(x, t, ctx)
        assert tuple(out.shape) == (2, 3, 16, 16)
        assert np.isfinite(out.numpy()).all()
        # cross-attention context actually conditions the output
        ctx2 = paddle.to_tensor(rng.normal(size=(2, 5, cfg.context_dim))
                                .astype(np.float32))
        out2 = m(x, t, ctx2)
        assert np.abs(out.numpy() - out2.numpy()).max() > 1e-6

    def test_denoising_trains(self):
        paddle.seed(0)
        cfg = unet_tiny()
        m = UNetModel(cfg)
        m.train()
        mse = nn.MSELoss()
        o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
        rng = np.random.default_rng(1)
        clean = rng.normal(size=(2, 3, 8, 8)).astype(np.float32)
        noise = rng.normal(size=(2, 3, 8, 8)).astype(np.float32)
        noisy = paddle.to_tensor(clean + 0.5 * noise)
        t = paddle.to_tensor(np.array([10, 20]))
        ctx = paddle.to_tensor(np.zeros((2, 4, cfg.context_dim), np.float32))
        losses = []
        for _ in range(5):
            pred = m(noisy, t, ctx)
            loss = mse(pred, paddle.to_tensor(noise))
            loss.backward()
            o.step(); o.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0], losses
