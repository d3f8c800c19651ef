"""Quantization (reference: python/paddle/quantization/ — config.py
QuantConfig, ptq.py PTQ, qat.py QAT, observers/abs_max.py, and the
quantized layers in nn/quant/; kernel analogs
paddle/phi/kernels/gpu/quantize_linear_kernel.cu).

TPU formulation: weight-only int8 is the quantization that pays on TPU
(int8 MXU runs at 2x bf16 peak; activations stay bf16/f32 and XLA fuses the
dequant scale into the matmul). PTQ calibrates per-channel abs-max scales
by running observer-wrapped forwards, then convert() swaps Linear layers
for QuantizedLinear holding int8 weights + scales. QAT wraps weights in a
straight-through fake-quant so training sees quantization error while
gradients flow unquantized."""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
from ..framework.core import Tensor, run_op, to_tensor

__all__ = [
    "QuantConfig",
    "AbsMaxObserver",
    "PTQ",
    "QAT",
    "QuantizedLinear",
    "quantize_weight",
    "fake_quant",
    "ptq_convert_for_serving",
]


def quantize_weight(w, bits=8, axis=0):
    """Per-channel symmetric abs-max quantization (reference
    observers/abs_max.py). Returns (int8_values, scale)."""
    wv = w._value if isinstance(w, Tensor) else jnp.asarray(w)
    qmax = 2 ** (bits - 1) - 1
    reduce_axes = tuple(i for i in range(wv.ndim) if i != axis)
    scale = jnp.max(jnp.abs(wv), axis=reduce_axes, keepdims=True) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(wv / scale), -qmax - 1, qmax).astype(jnp.int8)
    return Tensor(q), Tensor(scale.astype(jnp.float32))


def fake_quant(x, scale=None, bits=8):
    """Straight-through quant-dequant (reference qat.py FakeQuant): forward
    sees the rounded value, backward is identity."""
    t = x if isinstance(x, Tensor) else to_tensor(x)
    qmax = 2 ** (bits - 1) - 1

    def fn(v):
        s = (jnp.max(jnp.abs(v)) / qmax) if scale is None else scale
        s = jnp.where(s == 0, 1.0, s)
        q = jnp.clip(jnp.round(v / s), -qmax - 1, qmax) * s
        # straight-through estimator: identity gradient
        return v + jax.lax.stop_gradient(q - v)

    return run_op("fake_quant", fn, [t])


class AbsMaxObserver:
    """reference observers/abs_max.py AbsmaxObserver."""

    def __init__(self, quant_bits=8):
        self.quant_bits = quant_bits
        self._absmax = 0.0

    def observe(self, x):
        v = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        self._absmax = max(self._absmax, float(jnp.max(jnp.abs(v))))

    def scale(self):
        qmax = 2 ** (self.quant_bits - 1) - 1
        return (self._absmax / qmax) if self._absmax else 1.0


class QuantConfig:
    """reference config.py QuantConfig — which layer types quantize and
    with what observer."""

    def __init__(self, activation=None, weight=None):
        self.activation = activation
        self.weight = weight or AbsMaxObserver
        self._types = [nn.Linear]

    def add_type_config(self, layer_types, activation=None, weight=None):
        types = layer_types if isinstance(layer_types, (list, tuple)) else [layer_types]
        self._types.extend(t for t in types if t not in self._types)
        if weight is not None:
            self.weight = weight
        if activation is not None:
            self.activation = activation
        return self


class QuantizedLinear(nn.Layer):
    """int8-weight Linear (reference nn/quant/ QuantedLinear): stores the
    quantized weight + per-output-channel scale; the matmul dequantizes via
    the fused scale multiply XLA folds into the dot."""

    def __init__(self, linear: nn.Linear, bits=8):
        super().__init__()
        qw, scale = quantize_weight(linear.weight, bits=bits, axis=1)
        self.register_buffer("weight_quant", qw)
        self.register_buffer("weight_scale", scale)
        self.bias = linear.bias
        self.bits = bits

    def forward(self, x):
        t = x if isinstance(x, Tensor) else to_tensor(x)
        b = self.bias

        def fn(v, qw, sc, *rest):
            out = jnp.matmul(v, qw.astype(v.dtype) * sc.astype(v.dtype))
            if rest:
                out = out + rest[0]
            return out

        ins = [t, self.weight_quant, self.weight_scale]
        if b is not None:
            ins.append(b)
        return run_op("quantized_linear", fn, ins)


def _swap_sublayer(root, name, new_layer):
    """Replace the sublayer at dotted path `name` under `root` — the one
    convert-pass swap shared by PTQ.convert and ptq_convert_for_serving."""
    parts = name.split(".")
    parent = root
    for p in parts[:-1]:
        parent = getattr(parent, p)
    setattr(parent, parts[-1], new_layer)


class PTQ:
    """Post-training quantization driver (reference ptq.py PTQ):
    quantize() hooks an activation observer onto each target layer's
    forward, calibration runs feed them, convert() swaps in QuantizedLinear
    (int8 weights from weight statistics; the calibrated activation scale
    rides along on the layer for int8-activation deployment)."""

    def __init__(self, q_config: QuantConfig | None = None):
        self.config = q_config or QuantConfig()
        self._observed: list[tuple] = []

    def quantize(self, model, inplace=False):
        self._observed = []
        for name, sub in list(model.named_sublayers()):
            if any(isinstance(sub, t) for t in self.config._types) and \
                    not getattr(sub, "_ptq_observed", False):
                obs = (self.config.activation or AbsMaxObserver)()
                orig = sub.forward

                def make_fwd(orig, obs):
                    def fwd(x):
                        obs.observe(x)
                        return orig(x)
                    return fwd

                sub.forward = make_fwd(orig, obs)
                sub._ptq_observed = True
                sub._ptq_orig_forward = orig
                self._observed.append((model, name, sub, obs))
        return model

    def activation_scales(self):
        return {name: obs.scale() for _, name, _, obs in self._observed}

    def convert(self, model, inplace=False, bits=8):
        """Swap each observed Linear for its QuantizedLinear carrying the
        calibrated activation scale. Must be the model that quantize()
        instrumented — converting a different object would silently mutate
        the recorded one."""
        if self._observed and self._observed[0][0] is not model:
            raise ValueError(
                "convert() must receive the same model instance that "
                "quantize() instrumented")
        for owner, name, sub, obs in self._observed:
            sub.forward = sub._ptq_orig_forward  # unhook the observer
            ql = QuantizedLinear(sub, bits=bits)
            ql.activation_scale = obs.scale()
            _swap_sublayer(owner, name, ql)
        return model


def ptq_convert_for_serving(model, bits=8):
    """Weight-only int8 serving convert (the engines' `serve_w8=True` pass):
    swap every Linear-family projection under `model` — `nn.Linear` plus the
    TP-sharded `ColumnParallelLinear`/`RowParallelLinear` the GPT/LLaMA
    decoder stacks are built from — for a `QuantizedLinear` holding int8
    weights + per-output-channel f32 scales. Embedding matrices and the LM
    head stay full precision — the tied head shares the embedding matmul,
    and an untied `lm_head` is skipped by name, so the contract holds for
    both configs.

    In place and idempotent: already-converted layers are skipped, so
    calling it twice (or constructing two engines over the same model with
    the toggle on) never double-quantizes. Weight-only is the quantization
    that pays on TPU — activations stay in the model's compute dtype and
    XLA folds the dequant scale into the matmul — and serving engines run
    single-program, so the TP sharding constraints the parallel Linears
    carry are inert there. Returns the number of layers converted."""
    from ..distributed.fleet.layers.mpu.mp_layers import (
        ColumnParallelLinear,
        RowParallelLinear,
    )

    types = (nn.Linear, ColumnParallelLinear, RowParallelLinear)
    n = 0
    for name, sub in list(model.named_sublayers()):
        if isinstance(sub, QuantizedLinear) or not isinstance(sub, types):
            continue
        # the output head is the projection most sensitive to weight
        # rounding; a tied head rides the f32 embedding matmul and never
        # reaches here, so skip the untied `lm_head` too to keep the
        # "heads stay full precision" contract config-independent
        if name.split(".")[-1] == "lm_head":
            continue
        _swap_sublayer(model, name, QuantizedLinear(sub, bits=bits))
        n += 1
    return n


class QAT:
    """Quantization-aware training (reference qat.py QAT): wraps target
    layers' forward with straight-through fake-quant on the weight."""

    def __init__(self, q_config: QuantConfig | None = None):
        self.config = q_config or QuantConfig()

    def quantize(self, model, inplace=False):
        for _name, sub in model.named_sublayers():
            if any(isinstance(sub, t) for t in self.config._types) and \
                    not getattr(sub, "_qat_wrapped", False):
                orig = sub.forward
                weight = sub.weight

                def make_fwd(orig, weight):
                    def fwd(x):
                        saved = weight._value
                        weight._value = fake_quant(Tensor(saved))._value
                        try:
                            return orig(x)
                        finally:
                            weight._value = saved
                    return fwd

                sub.forward = make_fwd(orig, weight)
                sub._qat_wrapped = True
        return model
