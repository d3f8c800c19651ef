"""Direct tier-1 coverage for paddle_tpu/quantization/ — previously only
touched by the test_quant_audio_text.py smoke. Pins the weight-quantization
error bound, QuantizedLinear forward parity at int8 tolerance, PTQ convert
semantics, and the ptq_convert_for_serving pass the serving engines run
under the engines' serve_w8=True."""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.quantization import (
    PTQ,
    QuantizedLinear,
    fake_quant,
    ptq_convert_for_serving,
    quantize_weight,
)


class TestQuantizeWeight:
    def test_roundtrip_error_bounded_by_half_step(self):
        """Symmetric abs-max: |w - q*scale| <= scale/2 per element, scale =
        per-channel absmax / 127 — the rounding bound, channel by channel."""
        rng = np.random.default_rng(0)
        w = rng.standard_normal((32, 16)).astype(np.float32) * 3.0
        for axis in (0, 1):
            q, s = quantize_weight(w, axis=axis)
            qv, sv = np.asarray(q._value), np.asarray(s._value)
            assert qv.dtype == np.int8
            deq = qv.astype(np.float32) * sv
            assert np.all(np.abs(deq - w) <= sv / 2 + 1e-7)
            # per-channel: each channel's scale reflects ITS absmax
            red = 1 - axis
            np.testing.assert_allclose(
                np.squeeze(sv), np.abs(w).max(axis=red) / 127, rtol=1e-6)

    def test_zero_channel_is_safe(self):
        w = np.zeros((4, 3), np.float32)
        w[0] = [1.0, -2.0, 0.5]
        q, s = quantize_weight(w, axis=0)
        deq = np.asarray(q._value, np.float32) * np.asarray(s._value)
        np.testing.assert_allclose(deq, w, atol=2.0 / 127)
        assert np.all(np.isfinite(deq))

    def test_values_stay_in_int8_range(self):
        w = np.asarray([[-5.0, 5.0, 4.99, -4.99]], np.float32)
        q, _ = quantize_weight(w, axis=0)
        qv = np.asarray(q._value)
        assert qv.min() >= -128 and qv.max() <= 127


class TestQuantizedLinear:
    def test_forward_parity_at_int8_tolerance(self):
        paddle.seed(0)
        lin = nn.Linear(24, 12)
        ql = QuantizedLinear(lin)
        x = paddle.to_tensor(
            np.random.default_rng(1).standard_normal((5, 24)).astype(
                np.float32))
        y, yq = lin(x).numpy(), ql(x).numpy()
        # error budget: per-channel scale/2 rounding per weight, summed over
        # the 24-term contraction
        w = np.asarray(lin.weight._value)
        bound = (np.abs(x.numpy()).sum(-1, keepdims=True)
                 * (np.abs(w).max(0) / 127) / 2) + 1e-6
        assert np.all(np.abs(y - yq) <= bound)

    def test_bias_and_no_bias(self):
        paddle.seed(0)
        x = paddle.to_tensor(np.ones((2, 8), np.float32))
        for bias_attr in (None, False):
            lin = nn.Linear(8, 4, bias_attr=bias_attr)
            ql = QuantizedLinear(lin)
            np.testing.assert_allclose(ql(x).numpy(), lin(x).numpy(),
                                       atol=0.05)

    def test_int8_buffers_registered(self):
        ql = QuantizedLinear(nn.Linear(8, 4))
        bufs = dict(ql.named_buffers())
        assert str(bufs["weight_quant"]._value.dtype) == "int8"
        assert bufs["weight_scale"]._value.dtype == jnp.float32


class TestFakeQuantSTE:
    def test_gradient_is_identity(self):
        x = paddle.to_tensor(
            np.asarray([0.3, -1.2, 2.0], np.float32), stop_gradient=False)
        y = fake_quant(x)
        y.sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), np.ones(3), rtol=0)


class TestConvertPasses:
    def _mlp(self):
        paddle.seed(0)

        class M(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = nn.Linear(8, 16)
                self.fc2 = nn.Linear(16, 4)

            def forward(self, x):
                return self.fc2(nn.functional.relu(self.fc1(x)))

        return M()

    def test_ptq_convert_swaps_observed_linears(self):
        m = self._mlp()
        ptq = PTQ()
        ptq.quantize(m)
        m(paddle.to_tensor(np.ones((2, 8), np.float32)))  # calibrate
        ptq.convert(m)
        assert isinstance(m.fc1, QuantizedLinear)
        assert isinstance(m.fc2, QuantizedLinear)
        assert m.fc1.activation_scale > 0

    def test_serving_convert_is_idempotent(self):
        m = self._mlp()
        assert ptq_convert_for_serving(m) == 2
        first = m.fc1
        assert ptq_convert_for_serving(m) == 0  # second pass: no-op
        assert m.fc1 is first  # not re-wrapped / double-quantized

    def test_serving_convert_covers_gpt_projections_only(self):
        """On a built GPTForCausalLM the pass swaps every decoder projection
        (Column/RowParallelLinear) but leaves the embedding — and therefore
        the tied LM head — full precision."""
        from paddle_tpu.models import GPTForCausalLM, gpt3_tiny

        paddle.seed(0)
        m = GPTForCausalLM(gpt3_tiny())
        n = ptq_convert_for_serving(m)
        # 2 layers x (q, k, v, out, fc1, fc2) = 12 projections
        assert n == 12
        for layer in m.gpt.layers:
            assert isinstance(layer.self_attn.q_proj, QuantizedLinear)
            assert isinstance(layer.mlp.fc2, QuantizedLinear)
        assert m.gpt.embed_tokens.weight._value.dtype == jnp.float32
        # projection weight bytes dropped ~4x (int8 payload + f32 scales)
        qbytes = sum(
            int(np.prod(b._value.shape)) * b._value.dtype.itemsize
            for _, b in m.named_buffers())
        cfg = m.config
        f32_proj_bytes = 4 * cfg.num_layers * (
            4 * cfg.hidden_size * cfg.hidden_size
            + 2 * cfg.hidden_size * cfg.ffn_size)
        assert qbytes < f32_proj_bytes / 3.5
        # the converted model still runs a forward
        out = m(paddle.to_tensor(np.ones((1, 4), np.int64)))
        assert np.all(np.isfinite(out.numpy()))

    def test_serving_convert_skips_untied_lm_head(self):
        """The head is the projection most sensitive to weight rounding; a
        tied head rides the f32 embedding matmul, and the untied `lm_head`
        must be skipped by name so the full-precision-head contract is
        independent of tie_word_embeddings."""
        import dataclasses

        from paddle_tpu.models import GPTForCausalLM, gpt3_tiny

        paddle.seed(0)
        m = GPTForCausalLM(dataclasses.replace(gpt3_tiny(),
                                               tie_word_embeddings=False))
        assert ptq_convert_for_serving(m) == 12  # same 12, head excluded
        assert not isinstance(m.lm_head, QuantizedLinear)
        out = m(paddle.to_tensor(np.ones((1, 4), np.int64)))
        assert np.all(np.isfinite(out.numpy()))
